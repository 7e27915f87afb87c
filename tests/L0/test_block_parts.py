"""tools/block_parts.py on a tiny trace: device time by part and phase
from a reduced trace joined to a scope table, leaf operations summed and
a loop counted once."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(ROOT, "tools"))
sys.path.insert(0, ROOT)

import block_parts  # noqa: E402
from benchmark import xplane  # noqa: E402

LAYER = "jit(train_step)/{}/transformer/layer_1/layer_1._one_sub_block/mlp"
FWD = LAYER.format("jvp(GPTModel)")
BWD = LAYER.format("transpose(jvp(GPTModel))")
REMAT = LAYER.format("transpose(jvp(GPTModel))/rematted_computation")
SCOPES = {
    "while.7": FWD + "/routed/moe/dispatch/jit(_gather)/while",
    "fusion.1": FWD + "/routed/moe/dispatch/jit(_gather)/while/body/gather",
    # XLA names some of a body's operations by their place in the loop
    "fusion.2": "while/body/dynamic_update_slice",
    "moe_grouped_matmul_fwd.3": FWD + "/routed/moe/experts/experts/"
                                      "jit(_rows)/pallas_call",
    "fusion.4": BWD + "/routed/moe/combine/jit(_gather_walk)/while/body/mul",
    "fusion.5": REMAT + "/routed/moe/router/router/dot_general",
    "fusion.6": FWD + "/shared_up/moe/shared/dot_general",
    "fusion.8": FWD + "/cumsum",
    "fusion.9": "jit(train_step)/fused_adam/mul",
}


def _trace():
    ms = 1e-3
    ops = [
        # a loop of two trips: the while spans its body's four operations
        xplane.Op(0, "while.7", 0 * ms, 10 * ms, "while"),
        xplane.Op(0, "fusion.1", 1 * ms, 3 * ms, "fusion", "kLoop"),
        xplane.Op(0, "fusion.2", 3 * ms, 4 * ms, "fusion", "kLoop"),
        xplane.Op(0, "fusion.1", 5 * ms, 7 * ms, "fusion", "kLoop"),
        xplane.Op(0, "fusion.2", 7 * ms, 8 * ms, "fusion", "kLoop"),
        xplane.Op(0, "moe_grouped_matmul_fwd.3", 10 * ms, 14 * ms,
                  "custom-call"),
        xplane.Op(0, "fusion.4", 14 * ms, 17 * ms, "fusion", "kLoop"),
        xplane.Op(0, "fusion.5", 17 * ms, 18 * ms, "fusion", "kOutput"),
        xplane.Op(0, "fusion.6", 18 * ms, 20 * ms, "fusion", "kOutput"),
        xplane.Op(0, "fusion.8", 20 * ms, 21 * ms, "fusion", "kLoop"),
        xplane.Op(0, "fusion.9", 21 * ms, 23 * ms, "fusion", "kLoop"),
        xplane.Op(0, "copy-done.1", 23 * ms, 24 * ms, "copy-done"),
    ]
    return xplane.Trace(ops, [])


@pytest.mark.parametrize("scope,want", [
    (SCOPES["fusion.1"], ("moe/dispatch", "forward")),
    (SCOPES["moe_grouped_matmul_fwd.3"], ("moe/experts", "forward")),
    (SCOPES["fusion.4"], ("moe/combine", "backward")),
    (SCOPES["fusion.5"], ("moe/router", "recompute")),
    (SCOPES["fusion.6"], ("moe/shared", "forward")),
    (SCOPES["fusion.8"], ("mlp", "forward")),
    (FWD.replace("/mlp", "/self_attention") + "/indexer/indexer/scores/dot",
     ("indexer/scores", "forward")),
    (FWD + "/routed/moe", ("moe", "forward")),
    (SCOPES["fusion.9"], ("optimizer", "update")),
    ("", (None, "update")),
])
def test_a_scope_s_part_is_its_block_and_the_name_after_it(scope, want):
    assert block_parts.part_of(scope) == want


def test_leaves_are_summed_and_a_loop_is_counted_once():
    out = block_parts.table(_trace(), SCOPES, steps=2.0)
    parts = out["parts"]
    # the loop's body, 6 ms over two steps, the operation with no scope
    # of its own among them; the while itself is no leaf
    assert parts["moe/dispatch"] == {"forward": pytest.approx(3.0)}
    assert parts["moe/experts"] == {"forward": pytest.approx(2.0)}
    assert parts["moe/combine"] == {"backward": pytest.approx(1.5)}
    assert parts["moe/router"] == {"recompute": pytest.approx(0.5)}
    assert parts["moe/shared"] == {"forward": pytest.approx(1.0)}
    assert parts["mlp"] == {"forward": pytest.approx(0.5)}
    assert parts["optimizer"] == {"update": pytest.approx(1.0)}
    assert parts["none"] == {"update": pytest.approx(0.5)}
    assert out["kernels"] == {"moe/experts": {
        "moe_grouped_matmul_fwd": pytest.approx(2.0)}}
    # block moe: 16 ms of leaves in two steps; its union holds the
    # while's 10 ms whole, 20 ms
    assert out["blocks"]["moe"]["leaves"] == pytest.approx(8.0)
    assert out["blocks"]["moe"]["union"] == pytest.approx(10.0)
    total = sum(v["leaves"] for v in out["blocks"].values())
    assert total == pytest.approx((24 - 4) / 2)
    text = block_parts.render(out)
    assert "moe/dispatch" in text and "moe_grouped_matmul_fwd" in text


def test_the_set_up_table_under_the_device_table():
    """The result line's ``setup_*`` readings and the compile path's
    largest rows before the window opened, from the program's record."""
    import time

    import jax
    import jax.numpy as jnp

    from apex_tpu.telemetry import compile_watch

    compile_watch.install_monitoring()
    jax.jit(lambda x: jnp.tanh(x) + 5)(jnp.ones((3, 17)))
    setup_s = time.perf_counter() - block_parts._T0
    jax.jit(lambda x: jnp.tanh(x) + 6)(jnp.ones((3, 19)))   # after it
    result = {"metrics": {"setup_trace_s": {"value": 1.5, "unit": "s"},
                          "setup_cache_misses": {"value": 2.0,
                                                 "unit": "count"},
                          "step_ms_p50": {"value": 9.0, "unit": "ms"}}}
    out = block_parts.setup_table(result, setup_s)
    assert out["readings"] == {"setup_trace_s": 1.5,
                               "setup_cache_misses": 2.0}
    assert 0 < len(out["rows"]) <= 20
    assert out["records_before_window"] < out["record_stats"]["kept"] \
        or out["record_stats"]["dropped"] > 0
    assert out["record_stats"]["listener_seconds"] > 0
    assert [r["self_s"] for r in out["rows"]] == sorted(
        (r["self_s"] for r in out["rows"]), reverse=True)
    starts = [row[0] for row in out["timeline"]]
    assert starts == sorted(starts) and 0 < len(starts) <= 16
    ahead = out["clock_start_after_process_s"]
    assert ahead > 0
    assert all(0 <= b <= e <= ahead + setup_s
               for b, e, *_ in out["timeline"])
    text = block_parts.render_setup(out)
    assert "setup_trace_s 1.500" in text and "records before" in text
    assert out["rows"][0]["fun_name"][:43] in text
