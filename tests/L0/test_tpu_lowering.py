"""Chipless TPU lowering: every registered kernel, the decode kernels and
the serving step bodies must get through the real TPU lowering and the
Mosaic compiler for a ``TPU v5 lite`` — without a chip.

libtpu compiles for a topology it is only told about
(``jax.experimental.topologies``), so block-shape refusals, missing
lowering rules and VMEM overflows surface here on the CPU container
instead of on the first chip run. A kernel that compiles is NOT thereby
shown correct — numerics stay with the interpret-mode parity tests here
and ``chip_smoke.py`` on the chip.

Shapes: GPT-2 345M (16 heads of 64, cache 1024) and one GQA layout
(g=4, rep=4, d=64, cache 2048) — chip_smoke.py's kernel cases.
"""

import os
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from apex_tpu.kernels import registry as kreg

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import chip_smoke  # noqa: E402

BF16, F32, I32 = jnp.bfloat16, jnp.float32, jnp.int32

# the test asks "does it lower and compile", not "how fast is the
# result": skip XLA's expensive optimisation passes (Mosaic kernels
# compile the same either way)
_FAST = {"exec_time_optimization_effort": -1.0}


@pytest.fixture(scope="module")
def tpu():
    """The v5e:2x2 topology's devices, with the registry told it is on
    a TPU (test-local: the package grows no switch for this)."""
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            topology_name="v5e:2x2", platform="tpu")
    except Exception as e:  # noqa: BLE001 — any libtpu refusal is a skip
        pytest.skip(f"no chipless TPU topology in this container: {e!r}")
    mp = pytest.MonkeyPatch()
    mp.setattr(kreg, "_on_tpu", lambda: True)
    yield topo.devices
    mp.undo()


def _compile(fn, devices, *avals):
    """Lower + compile ``fn`` for the first topology device; returns
    the compiled text (holds one ``tpu_custom_call`` per kernel)."""
    sh = SingleDeviceSharding(devices[0])
    args = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh),
        avals)
    return jax.jit(fn).lower(*args).compile(_FAST).as_text()


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


# The kernel cases are chip_smoke.py's own table: what tier-1 keeps
# lowering here is exactly what the smoke runs against its oracles on
# the chip (each registered kernel at a GPT-2 345M shape, the attention
# family also at the GQA layout).
CASES = chip_smoke.kernel_cases()


def test_every_registered_kernel_is_covered():
    import apex_tpu.kernels  # noqa: F401 — registers the kernel families

    covered = {c.name.split()[0] for c in CASES}
    missing = set(kreg.get_kernel_registry().names()) - covered
    assert not missing, f"no TPU lowering case for {sorted(missing)}"
    assert {"gqa_decode", "mla_decode"} <= covered


@pytest.mark.parametrize("case", CASES, ids=lambda c: c.name)
def test_kernel_compiles_for_v5e(tpu, case):
    text = _compile(case.kernel, tpu, *jax.eval_shape(case.make_args))
    assert "tpu_custom_call" in text, (
        f"{case.name}: no Mosaic kernel in the compiled program — did "
        f"the oracle path run instead?")


def test_matmul_collectives_compile_under_tp4(tpu):
    """fused_cc family (a) inside shard_map over the four topology
    devices: tiled GEMM + psum, ring reduce-scatter, ring all-gather."""
    from apex_tpu.kernels import fused_cc

    mesh = Mesh(tpu, ("tp",))

    def body(x, w, xs):
        return (fused_cc.matmul_reduce_from(x, w, "tp"),
                fused_cc.matmul_reduce_scatter(x, w, "tp"),
                fused_cc.all_gather_matmul(xs, w, "tp"))

    fn = jax.shard_map(body, mesh=mesh,
                       in_specs=(P(None, "tp"), P("tp", None),
                                 P("tp", None)),
                       out_specs=(P(), P("tp", None), P()),
                       check_vma=False)

    def arg(shape, spec):
        return jax.ShapeDtypeStruct(shape, BF16,
                                    sharding=NamedSharding(mesh, spec))

    text = jax.jit(fn).lower(
        arg((1024, 4096), P(None, "tp")), arg((4096, 1024), P("tp", None)),
        arg((4096, 1024), P("tp", None))).compile(_FAST).as_text()
    assert text.count("tpu_custom_call") >= 3


@pytest.fixture(scope="module")
def gpt2_two_layers():
    """GPT-2 345M at full width, two layers, decode mode — abstract
    params only (nothing this size is ever materialized here)."""
    from apex_tpu.models import GPTModel, TransformerConfig

    cfg = TransformerConfig(
        hidden_size=1024, num_layers=2, num_attention_heads=16,
        vocab_size=50304, max_position_embeddings=1024,
        compute_dtype=BF16)
    model = GPTModel(cfg, decode=True)
    params = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 8), I32)))["params"]
    return model, params


@pytest.mark.parametrize("cache_mode", ["bf16", "int8"])
def test_serve_engine_bodies_compile_for_v5e(tpu, gpt2_two_layers,
                                             monkeypatch, cache_mode):
    """``ServeEngine._decode_fn`` / ``_prefill_fn`` with default gates:
    the bodies the engine AOT-compiles at startup, lowered for the chip.
    The engine object is built on the CPU backend (its own ladder is
    lowered on the oracle path there and not compiled); only the bodies
    are re-lowered for the topology."""
    from apex_tpu.serving import ServeConfig, ServeEngine

    model, params = gpt2_two_layers
    with monkeypatch.context() as mp:
        mp.setattr(kreg, "_on_tpu", lambda: False)
        mp.setattr(ServeEngine, "_compile", lambda *a, **k: None)
        engine = ServeEngine(model, params, ServeConfig(
            batch_buckets=(2,), prefill_buckets=(16,), num_slots=2,
            cache_mode=cache_mode, preflight=False))
    ids = _sds((2,), I32)
    key = _sds((2,), jnp.uint32)
    decode = _compile(engine._decode_fn, tpu, engine._store, params, ids,
                      ids, key, _sds((), I32))
    prefill = _compile(engine._prefill_fn, tpu, engine._store, params,
                       ids, _sds((2, 16), I32), ids, key)
    # one decode-attention kernel a layer; the window kernel in prefill
    assert decode.count("tpu_custom_call") >= 2
    assert prefill.count("tpu_custom_call") >= 2


def test_generate_compiles_for_v5e(tpu, gpt2_two_layers):
    """``generate()``'s jitted prefill + scan-decode pair (batch 2)."""
    from apex_tpu.models import generation

    model, params = gpt2_two_layers
    prefill, decode_all = generation._compiled(
        model, 16, 4, 0.0, None, None, None, 0)
    cache = jax.eval_shape(lambda: generation.init_cache(model, 2))
    text = _compile(prefill, tpu, params, cache, _sds((2, 16), I32))
    assert text.count("tpu_custom_call") >= 2
    init = (cache, _sds((2, 50304), F32), _sds((), I32),
            _sds((2,), jnp.uint32), _sds((2,), jnp.bool_))
    text = _compile(decode_all, tpu, params, init)
    assert text.count("tpu_custom_call") >= 2
