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
import re
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from apex_tpu.analysis.hlo import instruction_scopes
from apex_tpu.kernels import registry as kreg
from apex_tpu.telemetry.scopes import classify

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


def _compile(fn, devices, *avals, options=_FAST, donate=()):
    """Lower + compile ``fn`` for the first topology device; returns
    the compiled text (holds one ``tpu_custom_call`` per kernel)."""
    sh = SingleDeviceSharding(devices[0])
    args = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh),
        avals)
    return jax.jit(fn, donate_argnums=donate).lower(*args).compile(
        options).as_text()


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


# The names the kernels give themselves (``pl.pallas_call(name=)``), by
# the first words of the case that runs them: a profile and the compiled
# text name each Mosaic call after its kernel, not after whoever called it.
KERNEL_NAMES = {
    "flash_attention selection": ("sparse_attention_flash_fwd",
                                  "sparse_attention_flash_dq",
                                  "sparse_attention_flash_dkv",
                                  "sparse_attention_head_probs"),
    "flash_attention mla": ("mla_attention_flash_fwd",
                            "mla_attention_flash_dq",
                            "mla_attention_flash_dkv"),
    "flash_attention bsnd blockdiff": ("blockdiff_attention_flash_fwd",
                                       "blockdiff_attention_flash_dq",
                                       "blockdiff_attention_flash_dkv"),
    "flash_attention bsnd": ("self_attention_flash_fwd",
                             "self_attention_flash_dq",
                             "self_attention_flash_dkv"),
    "flash_attention": ("self_attention_flash_fwd",
                        "self_attention_flash_dq",
                        "self_attention_flash_dkv"),
    "gqa_decode": ("gqa_decode",),
    "mla_decode": ("mla_decode",),
    "fused_cc window": ("fused_cc_window_attention",),
    "fused_cc int8": ("fused_cc_spec_verify",),
    "fused_cc quantize_pack_int4": ("fused_cc_quantize_pack",),
    "fused_cc unpack_dequantize_int4": ("fused_cc_unpack_dequantize",),
    "quant4 quantize": ("quant4_quantize",),
    "quant4 pack/unpack": ("quant4_pack", "quant4_unpack"),
    "quant4 dequantize": ("quant4_dequantize",),
    "quant quantize_rows_blockwise": ("quant_quantize",),
    "quant dequantize_rows_blockwise": ("quant_dequantize",),
    "softmax": ("softmax_fwd", "softmax_bwd"),
    "adam": ("fused_adam",),
    "lamb": ("fused_lamb",),
    "topk_select": ("indexer_topk_select",),
    "indexer_scores": ("indexer_scores_fwd", "indexer_scores_dq",
                       "indexer_scores_dk"),
    "grouped_matmul": ("moe_grouped_matmul_fwd", "moe_grouped_matmul_dlhs",
                       "moe_grouped_matmul_drhs"),
    "expert_ffn": ("moe_expert_ffn_in", "moe_expert_ffn_dh1",
                   "moe_grouped_matmul_fwd", "moe_grouped_matmul_dlhs",
                   "moe_grouped_matmul_drhs"),
}
_TEXTS = {}


def _case_text(devices, case):
    """The case's compiled text, compiled once for the tests below
    (``--dist loadfile`` keeps a file's tests in one process)."""
    if case.name not in _TEXTS:
        _TEXTS[case.name] = _compile(case.kernel, devices,
                                     *jax.eval_shape(case.make_args))
    return _TEXTS[case.name]


@pytest.mark.parametrize("case", CASES, ids=lambda c: c.name)
def test_kernel_compiles_for_v5e(tpu, case):
    text = _case_text(tpu, case)
    assert "tpu_custom_call" in text, (
        f"{case.name}: no Mosaic kernel in the compiled program — did "
        f"the oracle path run instead?")


@pytest.mark.parametrize("case", CASES, ids=lambda c: c.name)
def test_kernel_is_named_in_the_compiled_program(tpu, case):
    names = next(v for k, v in KERNEL_NAMES.items()
                 if case.name.startswith(k))
    scopes = instruction_scopes(_case_text(tpu, case))
    for name in names:
        # the instruction is named after the kernel (``%softmax_bwd.1``;
        # ``%transpose_jvp_softmax_bwd__.1`` where the kernel's is the
        # outermost scope under the transformation), and the kernel's
        # name is the scope component that holds the call
        called = [s for i, s in scopes.items() if name in i]
        assert called, f"{case.name}: no instruction named {name}"
        assert all(re.search(rf"[/(]{name}\)*/pallas_call$", s)
                   for s in called), called


@pytest.mark.parametrize("shape", chip_smoke.EXPERT_LAYERS,
                         ids=lambda s: "x".join(str(n) for n in s[:3]))
def test_the_rows_walk_compiles_for_v5e_at_the_cells_shapes(tpu, shape):
    """The held-share layer of each MoE cell, forward and backward, the
    rows' passes following the count (``kernels/row_gather.py``): a
    loop each for dispatch, combine and their two transposes, with the
    grouped-matmul kernels between them."""
    import functools

    m, h, f, g, act = shape
    cols = f * (2 if act == "swiglu" else 1)
    text = _compile(
        functools.partial(chip_smoke.held_layer_fwd_bwd, act, True),
        tpu, _sds((chip_smoke.TOKENS, h), BF16), _sds((g, h, cols), BF16),
        _sds((g, f, h), BF16), _sds((m,), I32), _sds((m,), F32),
        _sds((g,), I32), _sds((), I32), _sds((chip_smoke.TOKENS, h), BF16))
    for walk in ("_gather_walk", "_scatter_walk"):
        for phase in ("jvp", "transpose\\(jvp"):
            assert re.search(rf"{phase}\(jit\({walk}\)+/while/body", text), (
                walk, phase)
    for kernel in KERNEL_NAMES["grouped_matmul"]:
        assert kernel in text


def test_the_indexer_s_kernels_are_block_indexer_in_a_train_step(tpu):
    """A sparse-attention model's gradient, two checkpointed layers with
    the Keye cell's indexer (16 heads of 64, one key head): the score
    kernels (each layer's forward, its recomputation's, dq and dk) and the
    selection run under ``indexer/scores`` and ``indexer/select``, so
    ``classify`` gives them to block ``indexer`` in their phase, and none
    bears a name an attention reader takes."""
    from collections import Counter

    from apex_tpu.models import GPTModel, TransformerConfig
    from apex_tpu.models.gpt import gpt_loss_fn
    from apex_tpu.models.transformer_lm import indexer_loss_from_variables

    model = GPTModel(TransformerConfig(
        hidden_size=256, num_layers=2, num_attention_heads=2, head_dim=128,
        num_query_groups=1, vocab_size=512, max_position_embeddings=1024,
        compute_dtype=BF16, normalization="rmsnorm",
        position_embedding_type="rope", indexer_heads=16,
        indexer_head_dim=64, indexer_topk=256, use_flash_attention=True,
        activation_checkpointing=True))
    params = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), I32))["params"])

    def objective(params, tokens):
        logits, sown = model.apply({"params": params}, tokens,
                                   mutable=["moe_losses"])
        return gpt_loss_fn(logits, tokens) + indexer_loss_from_variables(sown)

    text = _compile(jax.grad(objective), tpu, params, _sds((2, 1024), I32))
    seen = Counter()
    for name, scope in instruction_scopes(text).items():
        kernel = re.match(r"(indexer_\w+?)\.\d+$", name)
        if kernel:
            part = "select" if "topk" in kernel.group(1) else "scores"
            assert f"/indexer/{part}/jit(" in scope, scope
            seen[kernel.group(1), classify(scope)] += 1
    assert seen == {
        ("indexer_scores_fwd", ("indexer", "forward")): 2,
        ("indexer_scores_fwd", ("indexer", "recompute")): 2,
        ("indexer_scores_dq", ("indexer", "backward")): 2,
        ("indexer_scores_dk", ("indexer", "backward")): 2,
        ("indexer_topk_select", ("indexer", "forward")): 2,
        ("indexer_topk_select", ("indexer", "recompute")): 2}
    for name, _ in seen:
        assert not re.search(r"_flash_(fwd|dq|dkv)$|^sparse_attention_|"
                             r"^softmax_", name)


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


@pytest.fixture(scope="module")
def gpt2_train_step(tpu):
    """The README quick-start step (amp O2 + ``FusedAdam``, flash
    attention, recomputation) for GPT-2 345M at full width, two layers,
    batch 2: its optimised HLO for one ``TPU v5 lite``, and the counters
    its tracing left."""
    from apex_tpu import amp
    from apex_tpu.models import GPTModel, TransformerConfig
    from apex_tpu.models.gpt import gpt_loss_fn
    from apex_tpu.optimizers import FusedAdam

    model = GPTModel(TransformerConfig(
        hidden_size=1024, num_layers=2, num_attention_heads=16,
        vocab_size=50304, max_position_embeddings=1024,
        compute_dtype=BF16, tie_word_embeddings=True,
        use_flash_attention=True, activation_checkpointing=True))
    tokens = _sds((2, 1024), I32)
    params = jax.eval_shape(lambda: amp.frontend.cast_model(
        model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), I32))["params"],
        BF16, keep_batchnorm_fp32=True))
    _, opt = amp.initialize({}, FusedAdam(lr=1e-4), opt_level="O2",
                            verbosity=0)

    def train_step(params, opt_state, tokens):
        scale = opt_state["scaler"].loss_scale
        value, grads = jax.value_and_grad(lambda p: gpt_loss_fn(
            model.apply({"params": p}, tokens), tokens) * scale)(params)
        params, opt_state = opt.step(grads, opt_state, params)
        return params, opt_state, value / scale

    # XLA's whole pipeline and the donated state, as the chip runs it:
    # what is fused into what decides which instruction carries which scope
    from apex_tpu.telemetry.registry import MetricsRegistry, use_registry

    with use_registry(MetricsRegistry(enabled=True)) as reg:
        text = _compile(train_step, tpu, params,
                        jax.eval_shape(opt.init, params), tokens,
                        options=None, donate=(0, 1))
    return text, reg.snapshot()["counters"]


@pytest.fixture(scope="module")
def gpt2_train_step_text(gpt2_train_step):
    return gpt2_train_step[0]


def test_train_step_names_its_attention_kernels(gpt2_train_step_text):
    """Three Mosaic calls a layer: flash forward, dq and dk/dv, each under
    the name ``attention_roofline`` selects. The checkpointed layer keeps
    the forward's output and log-sum-exp (``ParallelTransformer``), so the
    recomputed layer's forward kernel has no consumer and is gone."""
    kernels = re.findall(
        r"%([\w\-]+?)[.\d]* = .* custom_call_target=\"tpu_custom_call\"",
        gpt2_train_step_text)
    assert sorted(kernels) == sorted(
        2 * ["self_attention_flash_fwd", "self_attention_flash_dq",
             "self_attention_flash_dkv"])


def test_train_step_leaves_no_lane_padded_activation_around_attention(
        gpt2_train_step):
    """Both layers took the kernels' batch-major entry (its counter is
    the forward's, the recomputed layer's and the backward's traces), and
    under ``self_attention`` nothing copies or transposes an array whose
    minor dimension is a head of 64: q, k, v, the context and their
    gradients stay ``[b, s, n * d]`` between the matmuls and the
    kernels."""
    gpt2_train_step_text, counters = gpt2_train_step
    assert counters["kernels/dispatch/flash_attention_bsnd_pallas"] >= 2
    assert counters["kernels/dispatch/flash_attention_bsnd_pallas"] == \
        counters["kernels/dispatch/flash_attention_pallas"]
    scopes = instruction_scopes(gpt2_train_step_text)
    moves = re.compile(
        r"^\s+(?:ROOT\s+)?%([\w\-.]+) = (\S+) (copy|transpose)\((.*)$",
        re.M)
    shapes = dict(re.findall(
        r"^\s+(?:ROOT\s+)?%([\w\-.]+) = (\S+) ", gpt2_train_step_text,
        re.M))
    seen = 0
    for name, shape, _, operands in moves.findall(gpt2_train_step_text):
        if "self_attention" not in scopes.get(name, ""):
            continue
        seen += 1
        operand = re.match(r"%([\w\-.]+)", operands)
        for array in (shape, shapes.get(operand and operand.group(1), "")):
            assert not re.search(r",64\]", array), (
                f"{name} = {shape} {operands[:60]} under {scopes[name]!r} "
                f"moves a lane-padded activation ({array})")
    assert seen, "no copy under self_attention at all: is the scope gone?"


def test_train_step_leaves_nothing_of_the_update_or_loss_bare(
        gpt2_train_step_text):
    """Every fusion of the step belongs to a block of the program, but
    for the scalars the step function computes itself (``loss * scale``,
    ``value / scale``)."""
    scopes = instruction_scopes(gpt2_train_step_text)
    fusion = re.compile(
        r"^\s+(?:ROOT\s+)?%([\w\-.]+) = (.*?) fusion\(", re.M)
    # the entry computation's: the operations the device is handed (a
    # fusion inside a fused computation is part of its caller)
    entry = gpt2_train_step_text[gpt2_train_step_text.index("\nENTRY "):]
    fusions = fusion.findall(entry)
    assert len(fusions) > 100
    blocks = set()
    for name, shape in fusions:
        assert name in scopes, f"fusion {name} has no scope"
        block, _ = classify(scopes[name])
        blocks.add(block and block.split("/")[0])
        if block is None:
            assert not re.search(r"\[\d", shape), (
                f"{name} = {shape} under {scopes[name]!r} is in no block")
    assert blocks >= {"embedding", "layernorm", "attention", "mlp", "head",
                      "loss", "amp", "optimizer"}
