#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the main path once through the entry points users call, at the
full width of GPT-2 345M (hidden 1024, 24 layers, 16 heads, vocab 50304,
sequence 1024), weights random from a seed:

- *train*   ``amp.initialize(..., FusedAdam, "O2")`` + the README
            quick-start step under ``jax.jit`` with donation;
- *serve*   ``ServeEngine.serve`` over a bucket ladder on the trained
            parameters (bf16 cache, then one int8 bucket), the decode
            path checked against the training forward on a small input;
- *kernels* every Pallas kernel, once, against its jnp oracle within
            the on-chip bound docs/kernels.md states;
- *four chips* (only where ``len(jax.devices()) >= 4``) the driver's
            ``dryrun_multichip(4)`` and the train step under
            ``shard_map`` over ``dp=4`` with ``DistributedDataParallel``.

One process, no child that touches JAX, no network. It REQUIRES a TPU:
anything else exits non-zero before a result is printed. Any leg that
raises, or any assertion that fails, ends the run non-zero — nothing is
caught and reported as a line of output. The times it prints are set-up
and sanity figures, never a metric.

Last line of stdout on success:
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}``
"""

import contextlib
import functools
import importlib.metadata
import json
import os
import sys
import time
from typing import Callable, NamedTuple
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np

BATCH, SEQ = 8, 1024
WARMUP_STEPS, TIMED_STEPS = 2, 10

# on-chip parity bounds (docs/kernels.md "On-chip parity bounds"), as
# max |kernel - oracle| over max |oracle|, fixed before the first run:
TOL_MXU = 2e-2        # attention family: bf16 operands on the MXU
TOL_BF16 = 2e-2       # bf16 in/out elementwise (norms, softmax)
TOL_F32 = 1e-4        # fp32 elementwise (Adam/LAMB, dequantize)
CODE_FLIP_FRAC = 1e-3  # quantize codes: off by one on <= 0.1% of lanes


def say(key, value):
    print(f"{key}: {value}", flush=True)


def gpt2_345m():
    from apex_tpu.models import TransformerConfig

    return TransformerConfig(
        hidden_size=1024, num_layers=24, num_attention_heads=16,
        vocab_size=50304, max_position_embeddings=SEQ,
        compute_dtype=jnp.bfloat16)


def seeded_batch(cfg):
    rs = np.random.RandomState(0)
    tokens = rs.randint(0, cfg.vocab_size, size=(BATCH, SEQ + 1))
    return jnp.asarray(tokens[:, :-1]), jnp.asarray(tokens[:, 1:])


def device_bytes(key, device=None):
    return (device or jax.devices()[0]).memory_stats()[key]


def rel_err(got, want):
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    assert got.shape == want.shape, (got.shape, want.shape)
    assert np.isfinite(got).all(), "non-finite kernel output"
    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)),
                                                  1e-30))


@contextlib.contextmanager
def counted_dispatches(expect):
    """Run a leg under a live metrics registry and hold its kernel
    dispatch counters to the smoke's rule: every name in ``expect`` took
    the compiled Pallas path at least once, and no kernel took the
    interpreter or its oracle."""
    from apex_tpu.telemetry.registry import MetricsRegistry, use_registry

    with use_registry(MetricsRegistry(enabled=True)) as reg:
        yield reg
    counters = {k[len("kernels/dispatch/"):]: int(v)
                for k, v in reg.snapshot()["counters"].items()
                if k.startswith("kernels/dispatch/")}
    say("  kernel dispatches", json.dumps(counters, sort_keys=True))
    stray = [k for k in counters if not k.endswith("_pallas")]
    assert not stray, f"dispatch left the compiled Pallas path: {stray}"
    missing = [n for n in expect if not counters.get(f"{n}_pallas")]
    assert not missing, f"no Pallas dispatch recorded for {missing}"


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------

def make_train_step(model, opt, sync=None):
    """The README quick-start step; ``sync`` (four-chip leg) averages the
    gradients over the data axis before the optimizer sees them."""
    from apex_tpu.models.gpt import gpt_loss_fn

    def train_step(params, opt_state, tokens, labels):
        scale = opt_state["scaler"].loss_scale
        loss, grads = jax.value_and_grad(
            lambda p: gpt_loss_fn(model.apply({"params": p}, tokens),
                                  labels) * scale)(params)
        if sync is not None:
            grads = sync(grads)
        params, opt_state = opt.step(grads, opt_state, params)
        return params, opt_state, loss / scale

    return train_step


def init_training(cfg):
    from apex_tpu import amp
    from apex_tpu.models import GPTModel
    from apex_tpu.optimizers import FusedAdam

    model = GPTModel(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    params, opt = amp.initialize(params, FusedAdam(lr=1e-4),
                                 opt_level="O2", verbosity=0)
    return model, params, opt, opt.init(params)


def train_leg(cfg):
    from apex_tpu.telemetry import compile_watch

    model, params, opt, opt_state = init_training(cfg)
    tokens, labels = seeded_batch(cfg)
    with counted_dispatches(["flash_attention", "flash_attention_bsnd"]):
        t0 = time.perf_counter()
        step = jax.jit(make_train_step(model, opt),
                       donate_argnums=(0, 1)).lower(
            params, opt_state, tokens, labels).compile()
        compile_s = time.perf_counter() - t0
    calls = step.as_text().count("tpu_custom_call")
    say("  compile seconds", round(compile_s, 1))
    say("  tpu_custom_calls in the step", calls)
    # flash attention ran as a kernel (fwd, dq, dkv per layer), not as
    # _attention_reference
    assert calls >= 3 * cfg.num_layers, calls

    losses = []
    for _ in range(WARMUP_STEPS):
        params, opt_state, loss = step(params, opt_state, tokens, labels)
        losses.append(loss)
    jax.block_until_ready(loss)
    with compile_watch.assert_no_recompiles():
        t0 = time.perf_counter()
        for _ in range(TIMED_STEPS):
            params, opt_state, loss = step(params, opt_state, tokens,
                                           labels)
            losses.append(loss)
        jax.block_until_ready(loss)
        last = float(loss)          # host fetch: the whole chain is done
        dt = time.perf_counter() - t0
    losses = [float(v) for v in losses]
    say("  losses", " ".join(f"{v:.4f}" for v in losses))
    assert np.isfinite(losses).all(), losses
    # random weights, 50304 classes: the first loss sits near ln(vocab)
    assert abs(losses[0] - np.log(cfg.vocab_size)) < 1.0, losses[0]
    assert last == losses[-1] < losses[0], (losses[0], last)
    say("  steady ms per step (sanity figure)",
        round(dt / TIMED_STEPS * 1e3, 1))
    say("  peak_bytes_in_use", device_bytes("peak_bytes_in_use"))
    return params, losses[0]


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------

def seeded_requests(n, vocab, lens, seed):
    from apex_tpu.serving.scheduler import Request

    rs = np.random.RandomState(seed)
    return [Request(rid=i,
                    prompt=rs.randint(0, vocab, size=int(lens[i % len(lens)])
                                      ).astype(np.int32),
                    max_new_tokens=int(rs.randint(4, 13)),
                    arrival=0.25 * i)
            for i in range(n)]


def check_served(completed, requests, vocab, stats):
    from apex_tpu.serving import robust

    by_rid = {c.rid: c for c in completed}
    assert sorted(by_rid) == [r.rid for r in requests], sorted(by_rid)
    for r in requests:
        c = by_rid[r.rid]
        # a non-finite logit row would have ended the request "poisoned"
        assert c.finish_reason in robust.OK_STATUSES, (r.rid,
                                                       c.finish_reason)
        assert len(c.tokens) == r.max_new_tokens, (r.rid, len(c.tokens))
        assert ((0 <= c.tokens) & (c.tokens < vocab)).all(), c.tokens
    say("  requests completed", len(completed))
    say("  decode steps", stats.get("decode_steps"))


def decode_matches_training_forward(cfg, params):
    """The repo's own reference on a small input: logits of the decode
    path (prefill through the window kernel, then one-token steps through
    the decode kernel) against the training forward over the same 40
    tokens, within bf16 tolerance."""
    from apex_tpu.models import GPTModel, generation

    plen, steps = 32, 8
    toks = jnp.asarray(np.random.RandomState(7).randint(
        0, cfg.vocab_size, size=(1, plen + steps)))
    want = jax.jit(lambda p, t: GPTModel(cfg).apply({"params": p}, t))(
        params, toks)[0].astype(jnp.float32)
    model = GPTModel(cfg, decode=True)
    prefill = jax.jit(lambda p, c, t: generation.prefill(
        model, p, c, t, jnp.arange(plen)[None, :]))
    step = jax.jit(lambda p, c, t, n: generation.decode_step(
        model, p, c, t, jnp.full((1, 1), n, jnp.int32)))
    cache, logits = prefill(params, generation.init_cache(model, 1),
                            toks[:, :plen])
    got = [logits[0]]
    for i in range(plen, plen + steps - 1):
        cache, logits = step(params, cache, toks[:, i:i + 1], i)
        got.append(logits[0])
    got = jnp.stack(got).astype(jnp.float32)
    want = want[plen - 1:plen + steps - 1]
    assert bool(jnp.isfinite(got).all()), "non-finite decode logits"
    err = rel_err(got, want)
    say("  decode vs training-forward logits, max rel err", f"{err:.2e}")
    assert err < 5e-2, err


def serve_leg(cfg, params):
    from apex_tpu.models import GPTModel
    from apex_tpu.serving import ServeConfig, ServeEngine
    from apex_tpu.telemetry import compile_watch

    model = GPTModel(cfg, decode=True)
    ladder = ServeConfig(batch_buckets=(2, 4), prefill_buckets=(32, 128),
                         num_slots=4, cache_mode="bf16")
    # prompts fall in both prefill buckets
    lens = (9, 24, 32, 40, 77, 128, 17, 100)
    with counted_dispatches(["gqa_decode", "fused_cc"]):
        t0 = time.perf_counter()
        engine = ServeEngine(model, params, ladder)
        say("  engine start seconds", round(time.perf_counter() - t0, 1))
        say("  compile_count", engine.compile_count)
        assert engine.compile_count == 2 * 2 + 2, engine.compile_count
        requests = seeded_requests(8, cfg.vocab_size, lens, seed=1)
        with compile_watch.assert_no_recompiles():
            completed, stats = engine.serve(requests)
        check_served(completed, requests, cfg.vocab_size, stats)

        del engine
        decode_matches_training_forward(cfg, params)

    # one bucket of an int8 engine: the cache's quantize-on-write and
    # dequant-on-read kernels
    with counted_dispatches(["gqa_decode", "fused_cc", "quant"]):
        engine = ServeEngine(model, params, ServeConfig(
            batch_buckets=(2,), prefill_buckets=(32,), num_slots=2,
            cache_mode="int8"), name="int8")
        requests = seeded_requests(2, cfg.vocab_size, (20, 31), seed=2)
        with compile_watch.assert_no_recompiles():
            completed, stats = engine.serve(requests)
        check_served(completed, requests, cfg.vocab_size, stats)


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------

class KernelCase(NamedTuple):
    """One kernel at one shape: ``kernel(*make_args())`` against
    ``oracle(*make_args())``. ``bound`` is a max-rel-err tolerance, or
    ``"codes"`` for quantizer output (integer codes that may sit one
    step apart on a few lanes), or ``"exact"``.
    tests/L0/test_tpu_lowering.py compiles every ``kernel`` here for a
    chipless v5e, so the cases the chip runs are the cases tier-1 keeps
    lowering."""

    name: str        # "<registry name> ..." — the prefix names the gate
    kernel: Callable
    oracle: Callable
    make_args: Callable
    bound: object
    timed: bool = False     # also print what a call of each takes


def gates_off(fn):
    """The jnp oracle of a registry kernel IS its gate-off path: trace
    the same entry point under ``APEX_TPU_KERNELS=0``, with the dispatch
    uncounted (an oracle run on purpose is not a stray one)."""
    from apex_tpu.telemetry.registry import MetricsRegistry, use_registry

    def oracle(*args):
        with mock.patch.dict(os.environ, {"APEX_TPU_KERNELS": "0"}), \
                use_registry(MetricsRegistry(enabled=False)):
            return fn(*args)

    return oracle


def randn(key, shape, dtype=jnp.bfloat16):
    return jax.random.normal(jax.random.PRNGKey(key), shape,
                             jnp.float32).astype(dtype)


# (gathered rows, hidden, expert width, held experts, activation) of the
# expert layer in the Nemotron cell and in the Keye cell, and the (m, k, n,
# g) of its two grouped matmuls (swiglu's first has [gate | up] columns)
EXPERT_LAYERS = ((98304, 2688, 1856, 8, "relu2"),
                 (131072, 2048, 768, 16, "swiglu"),
                 (98304, 2048, 1408, 8, "swiglu"))
GROUPED_SHAPES = tuple(
    shape for m, h, f, g, act in EXPERT_LAYERS
    for shape in ((m, h, f * (2 if act == "swiglu" else 1), g),
                  (m, f, h, g)))
GROUPED_SHARES = (0.06, 0.125, 0.28)    # of the rows that hold assignments


def group_sizes(m, g, share):
    """``share`` of ``m`` rows in uneven groups of which the second is
    empty; the rest is the tail, in no group."""
    weights = np.random.default_rng(g).dirichlet(np.ones(g))
    weights[1] = 0.0
    return jnp.asarray(np.floor(weights / weights.sum() * share * m),
                       jnp.int32)


def grouped_args(shape, share):
    m, k, n, g = shape
    return (randn(26, (m, k)), randn(27, (g, k, n)),
            group_sizes(m, g, share), randn(28, (m, n), jnp.float32))


def grouped_fwd_bwd(lhs, rhs, sizes, dout):
    from apex_tpu.kernels.grouped_matmul import grouped_matmul

    out, vjp = jax.vjp(lambda a, b: grouped_matmul(a, b, sizes), lhs, rhs)
    return (out,) + vjp(dout)


def expert_ffn(act, sizes):
    """``ExpertMLP``'s ragged layout: the two grouped matmuls with the
    activation between them, ``(x, w1, w2) -> y``."""
    from apex_tpu.kernels.grouped_matmul import grouped_matmul

    def ffn(x, w1, w2):
        h = grouped_matmul(x, w1, sizes)
        if act == "swiglu":
            gate, up = jnp.split(h, 2, axis=-1)
            h = jax.nn.silu(gate) * up
        else:
            h = jnp.square(jax.nn.relu(h))
        return grouped_matmul(h.astype(x.dtype), w2, sizes)

    return ffn


def expert_ffn_fwd_bwd(act, x, w1, w2, sizes, dout):
    """:func:`expert_ffn`, forward and backward."""
    out, vjp = jax.vjp(expert_ffn(act, sizes), x, w1, w2)
    return (out,) + vjp(dout)


def grouped_matmul_times():
    """The kernel against what it replaced, XLA's ``ragged-dot`` over the
    same unpadded counts: each cell's expert layer (its two matmuls'
    shapes are ``GROUPED_SHAPES``) forward and backward at three real
    shares, device ms kernel / oracle."""
    for m, h, f, g, act in EXPERT_LAYERS:
        fn = functools.partial(expert_ffn_fwd_bwd, act)
        kernel, oracle = jax.jit(fn), jax.jit(gates_off(fn))
        cols = f * (2 if act == "swiglu" else 1)
        for share in GROUPED_SHARES:
            args = (randn(26, (m, h)), randn(27, (g, h, cols)) * 0.02,
                    randn(28, (g, f, h)) * 0.02, group_sizes(m, g, share),
                    randn(29, (m, h), jnp.float32))
            say(f"  grouped_matmul: {g} {act} experts {h} -> {f} -> {h} "
                f"over {m} rows, {share:.1%} real, fwd+bwd device ms, "
                "kernel / ragged-dot",
                f"{ms_a_call(kernel, args)} / {ms_a_call(oracle, args)}")


TOKENS = 16384      # 2 x 8192 packed tokens, what the three cells route


def held_rows(m, h, g, share):
    """A held share's rows as ``SwitchMLP._held_share`` hands them out:
    ``(x [TOKENS, h], token_idx [m], gate [m], sizes [g], kept)``; a
    group's tokens distinct, gate 0 from ``kept`` on."""
    sizes = group_sizes(m, g, share)
    kept = int(sizes.sum())
    rng = np.random.default_rng(m + g)
    idx = np.concatenate(
        [rng.permutation(TOKENS)[:n] for n in np.asarray(sizes)]
        + [rng.integers(0, TOKENS, m - kept)])
    gate = np.where(np.arange(m) < kept, rng.uniform(0.05, 0.5, m), 0.0)
    return (randn(30, (TOKENS, h)), jnp.asarray(idx, jnp.int32),
            jnp.asarray(gate, jnp.float32), sizes, jnp.int32(kept))


def whole_array(fn):
    """``fn`` traced with the rows' walk as one tile: XLA's gather and
    scatter-add over every row under the ``r < kept`` mask, the oracle of
    ``kernels/row_gather.py``."""
    from apex_tpu.kernels import row_gather

    def oracle(*args):
        with mock.patch.object(row_gather, "ROW_TILE", 1 << 30), \
                mock.patch.object(row_gather, "SCATTER_TILE", 1 << 30):
            return fn(*args)

    return oracle


def held_layer_fwd_bwd(act, walk, x, w1, w2, idx, gate, sizes, kept, dout):
    """The held-share branch of ``SwitchMLP``, forward and backward:
    dispatch, the expert FFN, combine. ``walk``: the rows' passes follow
    ``kept`` (``gather_rows`` / ``scatter_add_rows``); else the parent's
    formulation, XLA's gather and scatter-add over every row."""
    from apex_tpu.kernels.row_gather import gather_rows, scatter_add_rows

    ffn = expert_ffn(act, sizes)

    def layer(x, w1, w2, gate):
        if walk:
            y = ffn(gather_rows(x, idx, kept), w1, w2)
            out = scatter_add_rows(y, idx, kept, x.shape[0], weights=gate)
        else:
            y = ffn(x[idx] * (gate > 0)[:, None].astype(x.dtype), w1, w2)
            out = jnp.zeros(x.shape, jnp.float32).at[idx].add(
                y * gate[:, None])
        return out.astype(x.dtype)

    out, vjp = jax.vjp(layer, x, w1, w2, gate)
    return (out,) + vjp(dout)


def row_gather_times():
    """``gather_rows`` and ``scatter_add_rows`` at the three cells' shapes
    and three real shares against their whole-array oracle, and each
    cell's expert layer forward + backward, the rows' passes following the
    count against the parent's formulation: parity, then device ms."""
    from apex_tpu.kernels.row_gather import gather_rows, scatter_add_rows

    def scatter(vals, idx, kept, gate):
        return scatter_add_rows(vals, idx, kept, TOKENS, weights=gate)

    for m, h, f, g, act in EXPERT_LAYERS:
        cols = f * (2 if act == "swiglu" else 1)
        w1, w2 = randn(27, (g, h, cols)) * 0.02, randn(28, (g, f, h)) * 0.02
        vals, dout = randn(31, (m, h), jnp.float32), randn(29, (TOKENS, h))
        for share in GROUPED_SHARES:
            x, idx, gate, sizes, kept = held_rows(m, h, g, share)
            where = f"{m} rows of {h}, {share:.1%} real"
            for name, fn, args, bound in (
                    ("gather_rows", gather_rows, (x, idx, kept), "exact"),
                    ("scatter_add_rows", scatter, (vals, idx, kept, gate),
                     TOL_F32)):
                walk, oracle = jax.jit(fn), jax.jit(whole_array(fn))
                say(f"  {name}: {where}",
                    compare(name, bound, walk(*args), oracle(*args)))
                say(f"  {name}: {where}, device ms, walk / whole array",
                    f"{ms_a_call(walk, args)} / {ms_a_call(oracle, args)}")
            args = (x, w1, w2, idx, gate, sizes, kept, dout)
            change = jax.jit(functools.partial(held_layer_fwd_bwd, act, True))
            parent = jax.jit(functools.partial(held_layer_fwd_bwd, act,
                                               False))
            say(f"  held layer: {g} {act} experts {h} -> {f} -> {h}, {where}",
                compare("held layer", TOL_MXU, change(*args), parent(*args)))
            say(f"  held layer: {where}, fwd+bwd device ms, rows walked to "
                "the count / every row",
                f"{ms_a_call(change, args)} / {ms_a_call(parent, args)}")


def kernel_cases():
    """Every kernel once, at a shape from the legs above (GPT-2 345M:
    16 heads of 64, cache 1024) and the attention family also at a GQA
    layout (g=4, rep=4, d=64, cache 2048)."""
    from apex_tpu.contrib import fmha, gqa_decode, mla_decode
    from apex_tpu.kernels import fused_cc, optim, quant4
    from apex_tpu.models import transformer_lm
    from apex_tpu.parallel import compression
    from apex_tpu.transformer.functional import fused_softmax

    sm = 0.125
    layouts = ((16, 1, SEQ), (4, 4, 2048))   # (g, rep, cache length)
    cases = []

    def add(name, kernel, oracle, make_args, bound, timed=False):
        cases.append(KernelCase(name, kernel, oracle, make_args, bound,
                                timed))

    # -- flash attention, forward and backward ----------------------------
    def fwd_bwd(attn):
        def run(*operands):
            out, vjp = jax.vjp(attn, *operands)
            return out, vjp(out)
        return run

    for g, rep, T in layouts:
        add(f"flash_attention fwd+bwd heads={g * rep} seq={T}",
            fwd_bwd(lambda q, k, v: fmha.flash_attention(q, k, v, True)),
            fwd_bwd(lambda q, k, v: fmha._attention_reference(
                q, k, v, sm, True)),
            lambda g=g, rep=rep, T=T: tuple(
                randn(i, (2, g * rep, T, 64)) for i in range(3)),
            TOL_MXU)

    # -- the same kernels through their batch-major entry (q, k, v and the
    # context [b, s, n*d]): GPT-2 345M's heads, two to a 128-lane column,
    # and one head a column at head size 128
    for heads, d, T in ((16, 64, SEQ), (8, 128, 2048)):
        add(f"flash_attention bsnd fwd+bwd heads={heads} d={d} seq={T}",
            fwd_bwd(lambda q, k, v, heads=heads: fmha.flash_attention_bsnd(
                q, k, v, heads, True)),
            fwd_bwd(lambda q, k, v, heads=heads, d=d: fmha._bsnd_reference(
                q, k, v, heads, d ** -0.5, True, None, None)),
            lambda heads=heads, d=d, T=T: tuple(
                randn(i, (2, T, heads * d)) for i in range(3)),
            TOL_MXU)

    # -- the alibi bias inside the strips of a tile on the diagonal
    slopes = 0.01 * jnp.arange(1, 17, dtype=jnp.float32)
    add("flash_attention bsnd fwd+bwd heads=16 d=64 seq=2048 alibi",
        fwd_bwd(lambda q, k, v: fmha.flash_attention_bsnd(
            q, k, v, 16, True, None, 512, 512, None, slopes)),
        fwd_bwd(lambda q, k, v: fmha._bsnd_reference(
            q, k, v, 16, 0.125, True, None, slopes)),
        lambda: tuple(randn(i, (2, 2048, 1024)) for i in range(3)),
        TOL_MXU)

    # -- the same kernel bodies under the block-diffusion rule (a row of
    # 1024 clean tokens and their 1024 noised copies in blocks of 4; the
    # calls named blockdiff_attention_flash_*), batch-major at head size
    # 128: tiles of all three parts run, and tiles of all three are skipped
    add("flash_attention bsnd blockdiff fwd+bwd heads=8 d=128 seq=2x1024 "
        "block=4",
        fwd_bwd(lambda q, k, v: fmha.flash_attention_bsnd(
            q, k, v, 8, False, block_diffusion=4)),
        fwd_bwd(lambda q, k, v: fmha._bsnd_reference(
            q, k, v, 8, 128 ** -0.5, False, None, None,
            fmha._BlockDiffusion(1024, 4))),
        lambda: tuple(randn(40 + i, (2, 2048, 1024)) for i in range(3)),
        TOL_MXU)

    # -- the same kernel bodies with the latent attention's rotary part
    # (Moonlight: 16 heads of 128 + 64 beside values of 128, one shared
    # rotary key a token) at the cell's shape, 2 x 8192; the oracle a head
    # at a time, since its [2, 16, 8192, 8192] scores do not fit
    def mla_by_head(qn, qr, kn, kr, v, heads=16):
        b, s, _ = qn.shape

        def heads_of(x):    # [b, s, n * d] -> [b * n, s, d]
            return x.reshape(b, s, heads, -1).transpose(0, 2, 1, 3).reshape(
                b * heads, s, -1)

        q = jnp.concatenate([heads_of(qn), heads_of(qr)], -1)
        k = jnp.concatenate([heads_of(kn), heads_of(jnp.tile(
            kr, (1, 1, heads)))], -1)
        one = jax.checkpoint(lambda t: fmha._attention_reference(
            *(x[None, None] for x in t), q.shape[-1] ** -0.5, True)[0, 0])
        out = jax.lax.map(one, (q, k, heads_of(v)))
        return out.reshape(b, heads, s, -1).transpose(0, 2, 1, 3).reshape(
            b, s, -1)

    add("flash_attention mla fwd+bwd heads=16 128+64|128 seq=8192",
        fwd_bwd(lambda *a: fmha.mla_flash_attention(*a, 16, True)),
        fwd_bwd(mla_by_head),
        lambda: tuple(randn(30 + i, (2, 8192, width)) for i, width in
                      enumerate((2048, 1024, 2048, 64, 2048))),
        TOL_MXU, timed=True)

    # -- the same kernels with a selection operand (sparse attention: each
    # query its 512 best of the causal keys by a seeded score, one tile
    # with nothing selected) and the head-summed probabilities, at the
    # head size 128 the sparse configurations run
    def selection_args():
        s = 2048
        score = jax.random.normal(jax.random.PRNGKey(20), (1, s, s))
        causal = jnp.tril(jnp.ones((s, s), bool))
        score = jnp.where(causal, score, -jnp.inf)
        kth = jnp.sort(score, axis=-1)[..., -512][..., None]
        sel = (score >= kth) & causal
        sel = sel.at[:, 1536:, :512].set(False)
        return (*(randn(i, (1, 8, s, 128)) for i in (21, 22, 23)),
                sel.astype(jnp.int8))

    def sparse(attend):
        def run(q, k, v, sel):
            (out, probs), vjp = jax.vjp(
                lambda q, k, v: attend(q, k, v, sel), q, k, v)
            return out, probs, vjp((out, jnp.zeros_like(probs)))
        return run

    def sparse_oracle(q, k, v, sel):
        scale = q.shape[-1] ** -0.5
        p = jax.nn.softmax(fmha._reference_scores(
            q, k, scale, True, selection=sel), axis=-1)
        return (fmha._attention_reference(q, k, v, scale, True,
                                          selection=sel),
                jnp.sum(jnp.where(sel[:, None] != 0, p, 0.0), axis=1))

    add("flash_attention selection fwd+bwd+probs heads=8 seq=2048 d=128",
        sparse(lambda q, k, v, sel: fmha.sparse_attention(q, k, v, sel,
                                                          True)),
        sparse(sparse_oracle), selection_args, TOL_MXU)

    # -- the indexer's top-k selection at the shape the Keye cell runs it
    # (a layer's float32 index scores, 2 x 8192 x 8192, top-2048): the
    # kernel's int8 selection is the oracle's in every element
    def select(scores):
        return transformer_lm.topk_selection(scores, 2048)

    add("topk_select [2, 8192, 8192] top-2048", select, gates_off(select),
        lambda: (randn(25, (2, 8192, 8192), jnp.float32),), "exact",
        timed=True)

    # -- the experts' grouped matmul, forward and both gradients, at the
    # six shapes the three MoE cells run it, an eighth of the rows real (a
    # balanced router's share of Keye's; the rest is the tail)
    for shape in GROUPED_SHAPES:
        m, k, n, g = shape
        add(f"grouped_matmul fwd+dlhs+drhs [{m}, {k}] x [{g}, {k}, {n}]",
            grouped_fwd_bwd, gates_off(grouped_fwd_bwd),
            functools.partial(grouped_args, shape, 0.125), TOL_MXU)

    # -- gqa_decode at three fill levels; GQA with window + soft cap -------
    for (g, rep, T), kw in zip(layouts,
                               ({}, dict(window=1000, softcap=30.0))):
        def at_lengths(fn, kw=kw):
            return lambda q, k, v, lens: jnp.stack(
                [fn(q, k, v, lens[i], sm, **kw) for i in range(3)])

        add(f"gqa_decode g={g} rep={rep} T={T}",
            at_lengths(gqa_decode.gqa_flash_decode),
            at_lengths(gqa_decode.gqa_decode_reference),
            lambda g=g, rep=rep, T=T: (
                randn(3, (2, g, rep, 64)), randn(4, (T, 2, g, 64)),
                randn(5, (T, 2, g, 64)), jnp.asarray([1, 300, T])),
            TOL_MXU)

    # -- mla_decode (DeepSeek-V2 latent row: 512 + 64) ----------------------
    lat, rope = 512, 64
    add("mla_decode heads=16 T=1024",
        lambda q, c, lens: jnp.stack([mla_decode.mla_flash_decode(
            q, c, lens[i], lat, 0.04) for i in range(2)]),
        lambda q, c, lens: jnp.stack([mla_decode.mla_decode_reference(
            q, c, lens[i], lat, 0.04) for i in range(2)]),
        lambda: (randn(6, (1, 16, lat + rope)),
                 randn(7, (SEQ, 1, lat + rope)), jnp.asarray([1, 700])),
        TOL_MXU)

    # -- fused_cc: prefill window, int8 verify, int4 pack -------------------
    for g, rep, T in layouts:
        add(f"fused_cc window w=128 g={g} rep={rep} T={T}",
            lambda q, k, v, n: fused_cc.window_attention(q, k, v, n, sm),
            lambda q, k, v, n: fused_cc.window_attention_reference(
                q, k, v, n, sm),
            lambda g=g, rep=rep, T=T: (
                randn(8, (128, 1, g, rep, 64)), randn(9, (T, 1, g, 64)),
                randn(10, (T, 1, g, 64)), jnp.asarray(200)),
            TOL_MXU)

        def int8_cache(g=g, rep=rep, T=T):
            # quantized on the jnp path: the verify case stands alone
            quant = gates_off(compression.quantize_rows_blockwise)
            kq, ks = quant(randn(9, (T, g * 64)))
            vq, vs = quant(randn(10, (T, g * 64)))
            return (randn(11, (5, g, rep, 64)), kq, ks, vq, vs,
                    jnp.asarray(611))

        add(f"fused_cc int8 verify w=5 g={g} rep={rep} T={T}",
            lambda *a: fused_cc.spec_verify_attention(*a, sm),
            lambda *a: fused_cc.spec_verify_reference(*a, sm),
            int8_cache, TOL_MXU)

    def int4_args():
        x = randn(12, (1024, 256), jnp.float32)
        sq, gmax = quant4.int4_block_scales(
            jnp.max(jnp.abs(x), axis=-1, keepdims=True))
        return x, quant4.effective_scales(sq, gmax)

    add("fused_cc quantize_pack_int4",
        lambda x, s: quant4._unpack_jnp(fused_cc.quantize_pack_int4(x, s)),
        lambda x, s: quant4._quantize_jnp(x, s), int4_args, "codes")
    add("fused_cc unpack_dequantize_int4",
        lambda x, s: fused_cc.unpack_dequantize_int4(
            quant4._pack_jnp(quant4._quantize_jnp(x, s)), s),
        lambda x, s: quant4._dequantize_jnp(quant4._quantize_jnp(x, s), s),
        int4_args, TOL_F32)

    # -- quant4 -------------------------------------------------------------
    add("quant4 quantize", quant4.quantize_int4,
        gates_off(quant4.quantize_int4), int4_args, "codes")

    def int4_pack_unpack(x, s):
        return quant4.unpack_int4(quant4.pack_int4(
            quant4._quantize_jnp(x, s)))

    add("quant4 pack/unpack", int4_pack_unpack,
        gates_off(int4_pack_unpack), int4_args, "exact")

    def int4_dequantize(x, s):
        return quant4.dequantize_int4(quant4._quantize_jnp(x, s), s)

    add("quant4 dequantize", int4_dequantize,
        gates_off(int4_dequantize), int4_args, TOL_F32)

    # -- quant (int8: the KV-cache and gradient grid) -----------------------
    add("quant quantize_rows_blockwise",
        lambda x: compression.quantize_rows_blockwise(x)[0],
        gates_off(lambda x: compression.quantize_rows_blockwise(x)[0]),
        lambda: (randn(13, (SEQ, 1024)),), "codes")

    def int8_args():
        return gates_off(compression.quantize_rows_blockwise)(
            randn(13, (SEQ, 1024)))

    add("quant dequantize_rows_blockwise",
        compression.dequantize_rows_blockwise,
        gates_off(compression.dequantize_rows_blockwise), int8_args, TOL_F32)

    # -- softmax: causal forward + backward, masked forward -----------------
    # The backward works from the SAVED bf16 probabilities (as the
    # reference's kernel does), so its cotangent is independent noise:
    # feeding the output back as its own cotangent correlates the
    # rounding of y with dy and cancels in (dy - sum(dy*y)).
    def softmaxes(x, mask, dy):
        y, vjp = jax.vjp(lambda t: fused_softmax
                         .scaled_upper_triang_masked_softmax(t, sm), x)
        return (y, vjp(dy)[0],
                fused_softmax.scaled_masked_softmax(x[None], mask, sm))

    add("softmax causal fwd+bwd, masked fwd", softmaxes,
        gates_off(softmaxes),
        lambda: (randn(14, (16, SEQ, SEQ)), jax.random.bernoulli(
            jax.random.PRNGKey(15), 0.3, (1, 1, SEQ, SEQ)),
            randn(24, (16, SEQ, SEQ))),
        TOL_BF16)

    # -- fused Adam / LAMB over a flat fp32 shard ---------------------------
    def shard():
        g, p, m, v = (randn(i, (1024 * 4096,), jnp.float32)
                      for i in (16, 17, 18, 19))
        return g, p, m, jnp.abs(v)

    hyper = dict(b1=0.9, b2=0.999, eps=1e-8, weight_decay=0.01, adam_w=True)
    adam = functools.partial(optim.fused_adam_update, lr=1e-3, bc1=0.1,
                             bc2=0.001, **hyper)
    lamb = functools.partial(optim.fused_lamb_mvu, bc1=0.1, bc2=0.001,
                             beta3=0.1, **hyper)
    add("adam", adam, gates_off(adam), shard, TOL_F32)
    add("lamb", lamb, gates_off(lamb), shard, TOL_F32)
    return cases


def compare(name, bound, got, want):
    """Hold ``got`` to ``want`` within ``bound`` (see KernelCase); returns
    the line to print."""
    got = [np.asarray(a) for a in jax.tree_util.tree_leaves(got)]
    want = [np.asarray(a) for a in jax.tree_util.tree_leaves(want)]
    assert len(got) == len(want)
    if bound == "exact":
        assert all((g == w).all() for g, w in zip(got, want)), name
        return "bit-exact"
    if bound == "codes":
        diff = np.abs(got[0].astype(np.int32) - want[0].astype(np.int32))
        frac = float((diff != 0).mean())
        assert diff.max() <= 1 and frac <= CODE_FLIP_FRAC, (
            name, int(diff.max()), frac)
        return (f"codes differing {frac:.1e} (bound {CODE_FLIP_FRAC:.0e},"
                f" by one step at most)")
    err = max(rel_err(g, w) for g, w in zip(got, want))
    assert err <= bound, (name, err)
    return f"max rel err {err:.1e} (bound {bound:.0e})"


def ms_a_call(fn, args, calls=5):
    """Device time of one call of a compiled ``fn``: the host's clock
    around ``calls`` dispatches in flight, waited for at the end (after
    one call that wakes the device from the host's turn before it)."""
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(calls):
        out = fn(*args)
    jax.block_until_ready(out)
    return round((time.perf_counter() - t0) / calls * 1e3, 2)


def kernels_leg():
    cases = kernel_cases()
    with counted_dispatches({c.name.split()[0] for c in cases}):
        for case in cases:
            args = case.make_args()
            kernel, oracle = jax.jit(case.kernel), jax.jit(case.oracle)
            say(f"  {case.name}",
                compare(case.name, case.bound, kernel(*args), oracle(*args)))
            if case.timed:
                say(f"  {case.name}, device ms a call, kernel / oracle",
                    f"{ms_a_call(kernel, args)} / {ms_a_call(oracle, args)}")
    grouped_matmul_times()
    row_gather_times()


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------

def matmul_collectives_check(devices):
    """fused_cc's matmul-collective family on the real interconnect:
    tiled GEMM + psum, ring reduce-scatter and ring all-gather under
    tp=4, each against its unfused compute-then-collective oracle."""
    from jax.sharding import Mesh, PartitionSpec as P

    from apex_tpu.kernels import fused_cc

    mesh = Mesh(np.asarray(devices), ("tp",))

    def body(x, w, xs):
        return (fused_cc.matmul_reduce_from(x, w, "tp"),
                fused_cc.matmul_reduce_scatter(x, w, "tp"),
                fused_cc.all_gather_matmul(xs, w, "tp"))

    def sharded(fn):
        return jax.jit(jax.shard_map(
            fn, mesh=mesh,
            in_specs=(P(None, "tp"), P("tp", None), P("tp", None)),
            out_specs=(P(), P("tp", None), P()), check_vma=False))

    args = (randn(21, (1024, 4096)), randn(22, (4096, 1024)) / 64,
            randn(23, (4096, 1024)))
    with counted_dispatches(["fused_cc"]):
        got = sharded(body)(*args)
    want = sharded(gates_off(body))(*args)
    name = "fused_cc matmul collectives tp=4"
    say(f"  {name}", compare(name, TOL_MXU, got, want))


def four_chip_leg(cfg, one_chip_first_loss):
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    import __graft_entry__
    from apex_tpu.parallel import DistributedDataParallel
    from apex_tpu.transformer import parallel_state

    # the driver's entry on the real devices: pp x tp, ep, cp, vpp,
    # encoder-decoder and ViT planes
    __graft_entry__.dryrun_multichip(4)
    parallel_state.destroy_model_parallel()

    devices = jax.devices()[:4]
    matmul_collectives_check(devices)
    mesh = Mesh(np.asarray(devices), ("dp",))
    model, params, opt, opt_state = init_training(cfg)
    ddp = DistributedDataParallel(axis_name="dp")
    local = make_train_step(model, opt, sync=ddp.sync)

    def spmd(params, opt_state, tokens, labels):
        params, opt_state, loss = local(params, opt_state, tokens, labels)
        return params, opt_state, jax.lax.pmean(loss, "dp")

    step = jax.jit(jax.shard_map(
        spmd, mesh=mesh, in_specs=(P(), P(), P("dp"), P("dp")),
        out_specs=(P(), P(), P()), check_vma=False),
        donate_argnums=(0, 1))
    # global batch 32: every chip sees the one-chip leg's own 8
    # sequences, so the mean loss must reproduce that leg's first loss
    tokens, labels = (jnp.tile(a, (4, 1)) for a in seeded_batch(cfg))
    rep, split = NamedSharding(mesh, P()), NamedSharding(mesh, P("dp"))
    params, opt_state = jax.device_put((params, opt_state), rep)
    tokens, labels = jax.device_put((tokens, labels), split)
    homes = {s.device for s in tokens.addressable_shards}
    assert homes == set(devices), homes
    losses = []
    for _ in range(3):
        params, opt_state, loss = step(params, opt_state, tokens, labels)
        losses.append(float(loss))
    say("  dp=4 losses", " ".join(f"{v:.4f}" for v in losses))
    say("  one-chip first loss", f"{one_chip_first_loss:.4f}")
    assert np.isfinite(losses).all() and losses[-1] < losses[0], losses
    assert abs(losses[0] - one_chip_first_loss) < 2e-2, (
        losses[0], one_chip_first_loss)
    in_use = [device_bytes("bytes_in_use", d) for d in devices]
    say("  bytes_in_use per device", in_use)
    # replicated state: every device holds all of it
    state_bytes = sum(a.nbytes for a in jax.tree_util.tree_leaves(
        (params, opt_state)))
    assert min(in_use) >= state_bytes, (in_use, state_bytes)
    assert max(in_use) < 2 * min(in_use), in_use


# ---------------------------------------------------------------------------

def main():
    device = jax.devices()[0]
    if device.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, jax found platform "
                 f"{device.platform!r} ({device.device_kind})")

    from apex_tpu import _C, _compile_cache

    cache_dir = _compile_cache.enable_compile_cache()
    say("platform", device.platform)
    say("device_kind", device.device_kind)
    say("device count", len(jax.devices()))
    say("versions", " ".join(
        f"{p} {importlib.metadata.version(p)}"
        for p in ("jax", "jaxlib", "libtpu")))
    say("compile cache dir", cache_dir)
    say("apex_tpu._C.HAVE_NATIVE", _C.HAVE_NATIVE)
    assert _C.BUILD_ERROR is None, _C.BUILD_ERROR
    assert _C.HAVE_NATIVE, "native runtime missing (APEX_TPU_NO_EXT set?)"

    cfg = gpt2_345m()
    say("leg", "train — GPT-2 345M, amp O2 + FusedAdam, batch "
        f"{BATCH} x {SEQ}")
    params, first_loss = train_leg(cfg)
    say("leg", "serve — ServeEngine on the trained parameters")
    serve_leg(cfg, params)
    del params
    say("leg", "kernels — each against its jnp oracle")
    kernels_leg()
    if len(jax.devices()) >= 4:
        say("leg", "four chips — dryrun_multichip(4), dp=4 full-width step")
        four_chip_leg(cfg, first_loss)
        say("four chips", "OK")
    else:
        say("four chips", f"did not run ({len(jax.devices())} device)")
    say("compile cache", json.dumps(_compile_cache.cache_stats()))
    print(json.dumps({"ok": True, "device": {
        "platform": device.platform, "kind": device.device_kind,
        "count": len(jax.devices())}}), flush=True)


if __name__ == "__main__":
    main()
