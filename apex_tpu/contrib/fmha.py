"""Fused multi-head attention — Pallas flash attention for TPU.

Parity: reference apex/contrib/fmha (fixed-seq-len fused flash-style
attention, fmha_api.cpp:363 — fp16, seq in {128,256,384,512}, d=64) and
apex/contrib/multihead_attn (CUTLASS-based fused attention). The TPU
version is a general flash-attention: online-softmax over KV blocks, fp32
accumulators, causal or full, any seq multiple of the block size.

Three entries to one set of kernel bodies: ``flash_attention`` over
``[b, n, s, d]``, ``flash_attention_bsnd`` over ``[b, s, n * d]``, the
layout the projections around attention write and read (heads folded into
the lanes; a grid cell takes the heads of one 128-lane column), and
``mla_flash_attention``, batch-major too, for multi-head latent attention
(queries and keys of a positionless and a rotary part beside narrower
values, the rotary key one vector a token shared by the heads).

Forward and backward are Pallas kernels over 3-D grids (batch*heads x
outer-blocks x streamed-blocks, innermost/"arbitrary"): K/V (forward, dq)
or Q/dO (dk/dv) stream through VMEM one tile at a time with fp32 scratch
accumulators, so VMEM use is independent of sequence length (validated to
seq 65536 on-chip; see PERF.md). The forward emits the per-row
log-sum-exp; the backward recomputes p = exp(q k^T scale - lse) per tile
(flash-attention v2 style) instead of materializing the [s, s] matrix.

Causal, a tile above the diagonal (or outside a window's band) is skipped
and never fetched, and every tile that runs is masked by position. One
class of tile does less than that: with square blocks and no window (what
every default call has) the forward and dq run a tile *on the diagonal*
in strips of ``STRIP`` rows, each against the keys its queries can see
and masked on its own square of the diagonal alone, so the three eighths
of the tile that no query of a strip sees are never multiplied,
exponentiated or reduced. The same mathematics: what is left out would
have added exact zeros. dkv runs whole tiles everywhere. Every entry's
dispatch record counts a head's tiles by what runs them
(:func:`_tile_classes`).

Off-TPU both passes take the reference einsum path; on TPU, sequence
lengths that no block fits (not a multiple of any of 512/256/128 and
larger than 512) take it too, while short sequences use the whole
sequence as one block. Gate ``flash_attention`` in the kernel registry:
every call records its dispatch path, so an O(s^2) reference run shows
up as ``kernels/dispatch/flash_attention_oracle``.
"""

import functools
import numbers
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from apex_tpu.kernels.registry import get_kernel_registry, kernel_gate

GATE = kernel_gate("flash_attention")

# 512x512 measured fastest on-chip at seq 8192 (8.0 TFLOP/s vs 3.8 at
# 128x128); both are min()'d down for shorter sequences. Causal, a tile of
# these blocks is skipped (above the diagonal) or runs whole under the
# mask; the forward and dq run a tile on the diagonal in strips that leave
# out the keys no row of the strip sees: at 1024 positions two of the
# three tiles that run, at 8192 16 of 136.
DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_K = 512
NEG_INF = -1e30
# checkpoint_name tags of the kernel path's residuals (out, lse): what a
# jax.checkpoint policy names to keep them across recomputation.
FLASH_RESIDUAL_NAMES = ("flash_out", "flash_lse")


def _takes(bq, bk, seq=None, causal=None, window=None, entry=None,
           rule=None) -> str:
    """This call's path, counted: the kernels run where a block divides
    the sequence (:func:`_fit_block` found ``bq`` and ``bk``). A flash
    entry gives its ``seq``, and its record then says what the bodies do
    over a head's tiles (:func:`_tile_classes`), under the entry's own
    name: ``flash_attention``'s, or the ``entry`` counted beside it (under
    a ``rule``, either layout's calls: ``BLOCKDIFF_ENTRY``)."""
    if rule is not None:
        entry = BLOCKDIFF_ENTRY
    fits = bq is not None and bk is not None
    tiles = _tile_classes(seq, bq, bk, causal, window, rule) \
        if fits and seq is not None else {}
    if entry is None:
        return GATE.path(fits=fits, **tiles)
    path = GATE.path(fits=fits)
    get_kernel_registry().dispatch(entry, path, **tiles)
    return path


def dense_layout(seq, heads, head_dim):
    """What a model asks before it lays out q, k and v: do the dense
    kernels take ``heads`` heads of ``head_dim`` over a sequence of
    ``seq``, and in which layout? ``None`` (they do not: the model keeps
    its own softmax path), ``"bsnd"`` (:func:`flash_attention_bsnd`, the
    projections' own layout) or ``"bnsd"`` (:func:`flash_attention`). Not
    counted; the entry the model then calls counts.

    Narrower than a direct call's rule (:func:`_fit_block`: any sequence
    that a block of 512, 256 or 128, or the sequence itself, divides, at
    any head size): whole 128-row tiles and head sizes 64, 128 and 256
    are where ``ParallelAttention`` has always left its softmax path, and
    what every cell and test of a model pins. Whether a model gains from
    the kernels at the shapes between the two rules (a 96-long sequence,
    heads of 32) is not measured; to widen the rule, widen it here."""
    fits = seq % 128 == 0 and head_dim in (64, 128, 256)
    if GATE.path(fits=fits, record=False) == "oracle":
        return None
    return "bnsd" if _heads_per_cell(heads, head_dim) is None else "bsnd"


def _causal_mask(scores, qi, kj, block_q, block_k, window=None):
    """Mask score entries above the diagonal for a (qi, kj) block pair;
    with ``window`` also below the sliding-window band (key j visible to
    query i iff 0 <= i - j < window)."""
    q_ids = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    k_ids = kj * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    visible = q_ids >= k_ids
    if window is not None:
        visible = visible & (q_ids - k_ids < window)
    return jnp.where(visible, scores, NEG_INF)


def _alibi_bias(slopes_ref, k0, shape):
    """Key-position-only alibi bias for scores of ``shape`` whose first
    column is key ``k0``: row constants cancel in softmax, so slope *
    absolute-key-index is the whole bias (HF build_alibi_tensor form)."""
    k_ids = k0 + jax.lax.broadcasted_iota(
        jnp.int32, shape, 1)                 # Mosaic's iota is integer
    return slopes_ref[0, 0, 0] * k_ids.astype(jnp.float32)


def _stream_kv_run(qi, kj, block_q, block_k, causal, window, rule=None):
    """Does kv block kj contribute to q block qi? (fwd / dq kernels)"""
    if rule is not None:
        return rule.tile_runs(qi, kj, block_q, block_k)
    if not causal:
        return True
    run = kj * block_k <= (qi + 1) * block_q - 1
    if window is not None:
        run = run & ((kj + 1) * block_k - 1 >= qi * block_q - window + 1)
    return run


def _stream_q_run(qi, kj, block_q, block_k, causal, window, rule=None):
    """Does q block qi contribute to kv block kj? (dkv kernel)"""
    if rule is not None:
        return rule.tile_runs(qi, kj, block_q, block_k)
    if not causal:
        return True
    run = (qi + 1) * block_q - 1 >= kj * block_k
    if window is not None:
        run = run & (qi * block_q <= _window_last_q_pos(kj, block_k,
                                                        window))
    return run


def _window_first_kv_block(qi, block_q, block_k, window):
    """First kv block inside the band for q block qi (index-map clamp;
    must stay consistent with _stream_kv_run's lower bound)."""
    return jnp.maximum(qi * block_q - window + 1, 0) // block_k


def _window_last_q_pos(kj, block_k, window):
    """Largest query index that can see any key in kv block kj."""
    return (kj + 1) * block_k - 1 + window - 1


def _fetched_kv_block(qi, kj, block_q, block_k, causal, window, rule=None):
    """The kv block cell (qi, kj) of the forward / dq grid fetches. Causal:
    masked blocks are clamped into the contributing range; Pallas skips
    the DMA when a block index repeats, so fully-above-diagonal (and,
    windowed, fully-below-band) K/V tiles are never fetched. Under a
    ``rule`` (:class:`_BlockDiffusion`) the same, into its two ranges."""
    if rule is not None:
        return rule.fetched_kv_block(qi, kj, block_q, block_k)
    if not causal:
        return kj
    last = ((qi + 1) * block_q - 1) // block_k
    kj = jnp.minimum(kj, last)
    if window is not None:
        kj = jnp.maximum(kj, _window_first_kv_block(
            qi, block_q, block_k, window))
    return kj


def _fetched_q_block(kj, qi, block_q, block_k, causal, window, rule=None):
    """The q block cell (kj, qi) of the dkv grid fetches (as
    :func:`_fetched_kv_block`, for the streamed q side)."""
    if rule is not None:
        return rule.fetched_q_block(kj, qi, block_q, block_k)
    if not causal:
        return qi
    first = (kj * block_k) // block_q
    qi = jnp.maximum(qi, first)
    if window is not None:
        qi = jnp.minimum(
            qi, _window_last_q_pos(kj, block_k, window) // block_q)
    return qi


# ------------------------------------------------- the block-diffusion rule
#
# A third attention rule beside ``causal`` and ``window``: training by
# masked diffusion inside blocks of ``block`` tokens, autoregressive across
# blocks (Block Diffusion, arXiv:2503.09573, "efficient training"; SDAR,
# arXiv:2510.06303). A row of ``2 * length`` holds ``length`` clean tokens
# and then their ``length`` noised copies, token ``i`` of either half in
# block ``(i mod length) // block``. Query ``i`` sees key ``j`` iff
#
#     clean -> clean   and block(j) <= block(i)      (block-causal)
#     noisy -> clean   and block(j) <  block(i)      (every earlier block)
#     noisy -> noisy   and block(j) == block(i)      (its own block, both ways)
#
# and a clean query never sees a noisy key. The kernels work the rule out
# from positions, as they do ``causal``: no ``[2L, 2L]`` operand. Blocks
# divide ``length``, so a tile lies in one half on either side, and
# ``block`` divides them, so the tiles that run are whole ranges: for a
# clean q tile the clean kv tiles up to its own; for a noisy one the clean
# kv tiles that hold an earlier block and the noisy ones that overlap it.
# The index maps clamp a skipped tile into those ranges, so it is neither
# run nor fetched. Every tile that runs runs whole under the mask (no
# strips: the rule's diagonal is not a triangle's). These calls are named
# ``blockdiff_attention_*``.

BLOCKDIFF_ENTRY = "flash_attention_blockdiff"   # the calls' dispatch record


class _BlockDiffusion(NamedTuple):
    """The rule of a call: ``length`` (``L``, half the row) and ``block``
    (the diffusion block). Hashable: a static argument of the jitted
    entries."""

    length: int
    block: int

    def _half(self, i, block):
        """Tile ``i``: (is it in the noisy half, its first position inside
        its half)."""
        n = self.length // block
        noisy = i >= n
        return noisy, (i - n * noisy) * block

    def tile_runs(self, qi, kj, block_q, block_k):
        """Does any query of q tile ``qi`` see a key of kv tile ``kj``?"""
        noisy_q, q0 = self._half(qi, block_q)
        noisy_k, k0 = self._half(kj, block_k)
        q_last = q0 + block_q - 1
        clean_clean = k0 <= q_last
        noisy_clean = k0 + self.block <= q_last
        noisy_noisy = (k0 <= q_last) & (q0 <= k0 + block_k - 1)
        return ((~noisy_q & ~noisy_k & clean_clean)
                | (noisy_q & ~noisy_k & noisy_clean)
                | (noisy_q & noisy_k & noisy_noisy))

    def fetched_kv_block(self, qi, kj, block_q, block_k):
        """The kv tile cell (qi, kj) of the forward / dq grid fetches: a
        tile that runs for ``qi``, the same as its neighbour's wherever
        ``kj`` itself does not run."""
        nk = self.length // block_k
        noisy_q, q0 = self._half(qi, block_q)
        q_last = q0 + block_q - 1
        # clean keys [0, last_clean] (none at -1), then, for a noisy q
        # tile, the noisy keys [first_noisy, last_noisy]
        last_clean = jnp.where(noisy_q, (q_last - self.block) // block_k,
                               q_last // block_k)
        first_noisy = jnp.where(noisy_q, nk + q0 // block_k, last_clean)
        last_noisy = jnp.where(noisy_q, nk + q_last // block_k, last_clean)
        return jnp.where(kj <= last_clean, kj,
                         jnp.clip(kj, first_noisy, last_noisy))

    def fetched_q_block(self, kj, qi, block_q, block_k):
        """The q tile cell (kj, qi) of the dkv grid fetches (as
        :meth:`fetched_kv_block`, for the streamed q side)."""
        nq = self.length // block_q
        noisy_k, k0 = self._half(kj, block_k)
        # a clean kv tile: the clean q tiles from its own on, then the
        # noisy ones that hold a later block (none where first_noisy
        # reaches 2 * nq); a noisy kv tile: the noisy q tiles over it
        first_clean = k0 // block_q
        first_noisy = nq + (k0 + self.block) // block_q
        seen_by_clean = jnp.where(
            qi < nq, jnp.maximum(qi, first_clean),
            jnp.where(first_noisy < 2 * nq, jnp.maximum(qi, first_noisy),
                      nq - 1))
        seen_by_noisy = jnp.clip(qi, nq + k0 // block_q,
                                 nq + (k0 + block_k - 1) // block_q)
        return jnp.where(noisy_k, seen_by_noisy, seen_by_clean)

    def _block_of(self, positions):
        # the kernels take a power of two (:func:`_resolve_sizes`): a shift
        return positions >> (self.block.bit_length() - 1)

    def mask(self, scores, qi, kj, block_q, block_k):
        """The tile's scores under the rule, from positions: the queries'
        blocks down a column, the keys' along a row."""
        noisy_q, q0 = self._half(qi, block_q)
        noisy_k, k0 = self._half(kj, block_k)
        qb = self._block_of(q0 + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, 1), 0))
        kb = self._block_of(k0 + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_k), 1))
        # clean -> clean kb <= qb; noisy -> clean kb + 1 <= qb; noisy ->
        # noisy kb <= qb and kb >= qb. (clean -> noisy tiles never run.)
        earlier = (noisy_q & ~noisy_k).astype(jnp.int32)
        own = noisy_k.astype(jnp.int32)
        visible = (kb + earlier <= qb) & (kb >= qb * own)
        return jnp.where(visible, scores, NEG_INF)


def block_diffusion_mask(length, block):
    """The rule as booleans ``[2 * length, 2 * length]``, ``True`` where a
    query (row) sees a key (column): the oracle's mask, and a model's
    where it runs the rule off the kernels."""
    i = np.arange(2 * length)
    clean, blk = i < length, (i % length) // block
    q_clean, k_clean = clean[:, None], clean[None, :]
    qb, kb = blk[:, None], blk[None, :]
    return ((q_clean & k_clean & (kb <= qb))
            | (~q_clean & k_clean & (kb < qb))
            | (~q_clean & ~k_clean & (kb == qb)))


def _rule_of(block_diffusion, seq, causal=False, window=None,
             alibi_slopes=None, selection=None):
    """The call's :class:`_BlockDiffusion` (``None`` without
    ``block_diffusion``), checked against what it does not compose with."""
    if block_diffusion is None:
        return None
    if (isinstance(block_diffusion, bool)
            or not isinstance(block_diffusion, numbers.Integral)
            or block_diffusion < 1 or seq % 2
            or (seq // 2) % block_diffusion):
        raise ValueError(
            f"flash_attention block_diffusion must be a positive static int "
            f"that divides half the sequence ({seq} / 2), got "
            f"{block_diffusion!r}")
    if (causal or window is not None or alibi_slopes is not None
            or selection is not None):
        raise ValueError(
            "flash_attention block_diffusion is a rule of its own: it "
            "composes with neither causal, window, alibi_slopes nor "
            "selection")
    return _BlockDiffusion(seq // 2, int(block_diffusion))


# ------------------------------------------- a tile on the diagonal in strips
#
# Causal, with square blocks and no window (what every default call has),
# the tiles that run are those under the diagonal and those on it
# (``qi == kj``). The forward and dq run a tile on the diagonal in strips
# of ``STRIP`` rows, each against the keys its queries can see and masked
# on its own square of the diagonal alone: the three eighths of a 512-tile
# that no query of a strip sees are never multiplied, exponentiated or
# reduced, and what they would have added is exact zeros. Every other tile
# that runs, and every tile of dkv, runs whole under the mask.

# Rows of a strip. Measured on the chip at GPT-2's shape (16 x 16 heads of
# 64 at 1024, device ms a call; PERF.md section 6, PR 38): forward 1.698
# whole, 1.722 in strips of 256, 1.650 in strips of 128; dq 1.421, 1.328,
# 1.320. dkv in strips of columns lost (1.855 whole, 1.878 at 256, 2.234 at
# 128: each strip of keys turns its own p and ds), so it has none.
STRIP = 128


def _diagonal_strips(block_q, block_k, causal, window):
    """``[(rows, cols)]``, static slices: the strips in which the forward
    and dq run a tile on the diagonal, strip by strip its queries and the
    keys they can see; ``None`` where the call's tiles all run whole
    (not causal, a window, blocks that are not square or hold no two
    strips)."""
    if (not causal or window is not None or block_q != block_k
            or block_q % STRIP or block_q == STRIP):
        return None
    return [(slice(r, r + STRIP), slice(0, r + STRIP))
            for r in range(0, block_q, STRIP)]


def _run_tile(step, run, qi, kj, block_q, block_k, causal, window):
    """Emit ``step(parts, in_strips)`` under ``run``: for a tile on the
    diagonal over its strips, where the call has them, and for every
    other tile (every tile, where it has none) over the whole of it."""
    from jax.experimental import pallas as pl

    whole = [(slice(0, block_q), slice(0, block_k))]
    strips = _diagonal_strips(block_q, block_k, causal, window)
    if strips is None:
        pl.when(run)(functools.partial(step, whole, False))
        return
    pl.when(run & (qi == kj))(functools.partial(step, strips, True))
    pl.when(run & (qi != kj))(functools.partial(step, whole, False))


def _mask_strip(s):
    """A strip's scores with its square on the diagonal, the last columns,
    masked. The rest of the strip lies under the diagonal."""
    n = s.shape[0]
    square = _causal_mask(s[:, -n:], 0, 0, n, n)
    if s.shape[1] == n:
        return square
    return jnp.concatenate([s[:, :-n], square], axis=1)


def _tile_classes(seq, block_q, block_k, causal, window=None, rule=None):
    """What the bodies do over one head's ``seq x seq`` scores, from
    shapes alone: the tiles the forward and dq run in strips
    (``tiles_diagonal``; dkv runs them whole), the tiles all three run
    whole (``tiles_whole``) and the tiles none runs or fetches
    (``tiles_skipped``), the (query, key) pairs the forward's and dq's
    steps compute and the pairs a query sees (``pairs_computed``,
    ``pairs_visible``). The fields of the entries' dispatch records.
    Under a ``rule`` (block diffusion over ``seq`` = ``2L``) every tile
    that runs runs whole, and a query sees ``L + block`` keys on
    average."""
    nq, nk = seq // block_q, seq // block_k
    qi, kj = np.ogrid[:nq, :nk]
    run = np.broadcast_to(
        _stream_kv_run(qi, kj, block_q, block_k, causal, window, rule),
        (nq, nk))
    strips = _diagonal_strips(block_q, block_k, causal, window)
    diagonal = int((run & (qi == kj)).sum()) if strips else 0
    whole = int(run.sum()) - diagonal
    visible = seq * seq
    if rule is not None:
        visible = rule.length * (rule.length + rule.block)
    elif causal:
        band = min(seq, window or seq)
        visible = band * (band + 1) // 2 + (seq - band) * band
    return {
        "tiles_diagonal": diagonal,
        "tiles_whole": whole,
        "tiles_skipped": nq * nk - diagonal - whole,
        "pairs_computed": whole * block_q * block_k + diagonal * sum(
            STRIP * cols.stop for _, cols in strips or ()),
        "pairs_visible": visible,
    }


def _selected(sel_ref, rows=slice(None), cols=slice(None)):
    """The selection tile ``[block_q, block_k]``, or a strip's ``rows``
    and ``cols`` of it, as booleans."""
    return sel_ref[0, rows, cols].astype(jnp.int32) != 0


def _and_run(run, tile_selected):
    """``run`` (a Python ``True`` off the causal path) and the tile's
    flag, where the call carries a selection."""
    if tile_selected is None:
        return run
    return tile_selected if run is True else run & tile_selected


def _flash_fwd_kernel(q_ref, k_ref, v_ref, slopes_ref, o_ref, lse_ref,
                      acc_ref, m_ref, l_ref, *, scale, causal, block_q,
                      block_k, num_kv, window, alibi, sel_ref=None,
                      tile_selected=None, rope=None, rule=None):
    """One (head, q-block, kv-block) grid cell of online-softmax attention.

    K/V arrive as [1, block_k, d] VMEM tiles streamed by the grid — VMEM
    use is independent of sequence length (the previous design staged the
    FULL [seq, d] K/V per program, which Mosaic refuses to compile beyond
    seq ~8k). The kv axis is the innermost, "arbitrary" grid dimension;
    running (acc, m, l) state lives in scratch across its iterations.
    """
    from jax.experimental import pallas as pl

    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # Causal: kv blocks entirely above the diagonal (or, windowed, fully
    # below the band) contribute nothing.
    run = _and_run(_stream_kv_run(qi, kj, block_q, block_k, causal, window,
                                  rule), tile_selected)

    def _step(parts, in_strips):
        q = q_ref[0].astype(jnp.float32) * scale
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        for rows, cols in parts:
            s = jnp.dot(q[rows], k[cols].T,
                        preferred_element_type=jnp.float32)
            if rope is not None:
                s = s + rope.scores(scale, rows, cols)
            if alibi:
                s = s + _alibi_bias(
                    slopes_ref, kj * block_k + cols.start, s.shape)
            if in_strips:
                s = _mask_strip(s)
            elif rule is not None:
                s = rule.mask(s, qi, kj, block_q, block_k)
            elif causal:
                s = _causal_mask(s, qi, kj, block_q, block_k, window)
            if sel_ref is not None:
                sel = _selected(sel_ref, rows, cols)
                s = jnp.where(sel, s, NEG_INF)
            m_prev = m_ref[rows]
            l_prev = l_ref[rows]
            m_cur = jnp.max(s, axis=-1)[:, None]
            m_new = jnp.maximum(m_prev, m_cur)
            p = jnp.exp(s - m_new)
            if sel_ref is not None:
                # a row with nothing selected so far has m_new == NEG_INF,
                # where exp(s - m_new) is 1 on its masked entries
                p = jnp.where(sel, p, 0.0)
            alpha = jnp.exp(m_prev - m_new)
            l_ref[rows] = alpha * l_prev + jnp.sum(p, axis=-1)[:, None]
            m_ref[rows] = m_new
            acc_ref[rows] = acc_ref[rows] * alpha + jnp.dot(
                p, v[cols], preferred_element_type=jnp.float32)

    _run_tile(_step, run, qi, kj, block_q, block_k, causal, window)

    @pl.when(kj == num_kv - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)
        # log-sum-exp of the scaled scores, for the backward kernels
        lse_ref[0] = m_ref[...] + jnp.log(l)


def _slopes_input(alibi_slopes, b, n):
    """[n] per-head slopes -> [b*n, 1, 1] grid input (zeros when alibi
    is off — the kernel branch is static, the input just needs a shape)."""
    if alibi_slopes is None:
        return jnp.zeros((b * n, 1, 1), jnp.float32)
    return jnp.broadcast_to(
        alibi_slopes.astype(jnp.float32)[None, :], (b, n)
    ).reshape(b * n, 1, 1)


def _kernel_name(rule, which):
    """``self_attention_flash_<which>``, or the rule's own name."""
    return ("blockdiff_attention" if rule is not None
            else "self_attention") + "_flash_" + which


def _flash_fwd_pallas(q, k, v, scale, causal, block_q, block_k,
                      window=None, alibi_slopes=None, rule=None):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, n, s, d = q.shape
    q3 = q.reshape(b * n, s, d)
    k3 = k.reshape(b * n, s, d)
    v3 = v.reshape(b * n, s, d)
    block_q = min(block_q, s)
    block_k = min(block_k, s)
    num_kv = s // block_k
    grid = (b * n, s // block_q, num_kv)
    slopes3 = _slopes_input(alibi_slopes, b, n)
    kernel = functools.partial(
        _flash_fwd_kernel, scale=scale, causal=causal, block_q=block_q,
        block_k=block_k, num_kv=num_kv, window=window,
        alibi=alibi_slopes is not None, rule=rule)

    def kv_index(h, i, j):
        return (h, _fetched_kv_block(i, j, block_q, block_k, causal,
                                     window, rule), 0)

    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda h, i, j: (h, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, d), kv_index,
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, d), kv_index,
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, 1), lambda h, i, j: (h, 0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda h, i, j: (h, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_q, 1), lambda h, i, j: (h, i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * n, s, d), q.dtype),
            jax.ShapeDtypeStruct((b * n, s, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),   # acc
            pltpu.VMEM((block_q, 1), jnp.float32),   # running max
            pltpu.VMEM((block_q, 1), jnp.float32),   # running sum
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=GATE.interpret,
        name=_kernel_name(rule, "fwd"),
    )(q3, k3, v3, slopes3)
    return out.reshape(b, n, s, d), lse.reshape(b, n, s)


def _flash_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                     slopes_ref, dq_ref, dq_acc, *, scale, causal,
                     block_q, block_k, num_kv, window, alibi, sel_ref=None,
                     tile_selected=None, rope=None, rule=None):
    """dq for one q block, streaming kv blocks (innermost grid dim):
    p = exp(q k^T scale - lse); ds = p * (do v^T - delta); dq += ds k scale.
    """
    from jax.experimental import pallas as pl

    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    run = _and_run(_stream_kv_run(qi, kj, block_q, block_k, causal, window,
                                  rule), tile_selected)

    def _step(parts, in_strips):
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0]
        delta = delta_ref[0]
        for rows, cols in parts:
            s = jnp.dot(q[rows], k[cols].T,
                        preferred_element_type=jnp.float32)
            if rope is not None:
                s = s + rope.scores(None, rows, cols)
            s = s * scale
            if alibi:
                s = s + _alibi_bias(
                    slopes_ref, kj * block_k + cols.start, s.shape)
            if in_strips:
                s = _mask_strip(s)
            elif rule is not None:
                s = rule.mask(s, qi, kj, block_q, block_k)
            elif causal:
                s = _causal_mask(s, qi, kj, block_q, block_k, window)
            if sel_ref is not None:
                s = jnp.where(_selected(sel_ref, rows, cols), s, NEG_INF)
            p = jnp.exp(s - lse[rows])
            dp = jnp.dot(do[rows], v[cols].T,
                         preferred_element_type=jnp.float32)
            ds = p * (dp - delta[rows])
            dq_acc[rows] += jnp.dot(
                ds, k[cols], preferred_element_type=jnp.float32) * scale
            if rope is not None:
                rope.add_dq(ds, scale, rows, cols)

    _run_tile(_step, run, qi, kj, block_q, block_k, causal, window)

    @pl.when(kj == num_kv - 1)
    def _finish():
        dq_ref[0] = dq_acc[...].astype(dq_ref.dtype)
        if rope is not None:
            rope.write_dq()


def _flash_dkv_kernel(k_ref, v_ref, q_ref, do_ref, lse_ref, delta_ref,
                      slopes_ref, dk_ref, dv_ref, dk_acc, dv_acc, *,
                      scale, causal, block_q, block_k, num_q, window,
                      alibi, sel_ref=None, tile_selected=None, rope=None,
                      rule=None):
    """dk/dv for one kv block, streaming q blocks (innermost grid dim):
    dv += p^T do;  dk += ds^T q scale."""
    from jax.experimental import pallas as pl

    kj = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    # Causal: q blocks entirely above this kv block (or, windowed, beyond
    # the band) contribute nothing.
    run = _and_run(_stream_q_run(qi, kj, block_q, block_k, causal, window,
                                 rule), tile_selected)

    @pl.when(run)
    def _step():
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        q = q_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0]
        delta = delta_ref[0]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)
        if rope is not None:
            s = s + rope.scores()
        s = s * scale
        if alibi:
            s = s + _alibi_bias(slopes_ref, kj * block_k, s.shape)
        if rule is not None:
            s = rule.mask(s, qi, kj, block_q, block_k)
        elif causal:
            s = _causal_mask(s, qi, kj, block_q, block_k, window)
        if sel_ref is not None:
            s = jnp.where(_selected(sel_ref), s, NEG_INF)
        p = jnp.exp(s - lse)
        dv_acc[...] += jnp.dot(p.T, do,
                               preferred_element_type=jnp.float32)
        dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        dk_acc[...] += jnp.dot(ds.T, q,
                               preferred_element_type=jnp.float32) * scale
        if rope is not None:
            rope.add_dk(ds, scale)

    @pl.when(qi == num_q - 1)
    def _finish():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _flash_bwd_pallas(q, k, v, o, lse, do, scale, causal, block_q,
                      block_k, window=None, alibi_slopes=None, rule=None):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, n, s, d = q.shape
    q3, k3, v3 = (x.reshape(b * n, s, d) for x in (q, k, v))
    o3, do3 = (x.reshape(b * n, s, d) for x in (o, do))
    lse3 = lse.reshape(b * n, s, 1)
    # delta_i = rowsum(do_i * o_i) — cheap elementwise+reduce, XLA-fused
    delta = jnp.sum(do3.astype(jnp.float32) * o3.astype(jnp.float32),
                    axis=-1, keepdims=True)
    num_q = s // block_q
    num_kv = s // block_k
    slopes3 = _slopes_input(alibi_slopes, b, n)
    alibi = alibi_slopes is not None

    def kv_index(h, i, j):
        return (h, _fetched_kv_block(i, j, block_q, block_k, causal,
                                     window, rule), 0)

    def q_index_for_kv(h, j, i):
        return (h, _fetched_q_block(j, i, block_q, block_k, causal,
                                    window, rule), 0)

    dq = pl.pallas_call(
        functools.partial(_flash_dq_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, num_kv=num_kv,
                          window=window, alibi=alibi, rule=rule),
        grid=(b * n, num_q, num_kv),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda h, i, j: (h, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, d), kv_index,
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, d), kv_index,
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_q, d), lambda h, i, j: (h, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_q, 1), lambda h, i, j: (h, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_q, 1), lambda h, i, j: (h, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, 1), lambda h, i, j: (h, 0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda h, i, j: (h, i, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((b * n, s, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=GATE.interpret,
        name=_kernel_name(rule, "dq"),
    )(q3, k3, v3, do3, lse3, delta, slopes3)

    dk, dv = pl.pallas_call(
        functools.partial(_flash_dkv_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, num_q=num_q,
                          window=window, alibi=alibi, rule=rule),
        grid=(b * n, num_kv, num_q),
        in_specs=[
            pl.BlockSpec((1, block_k, d), lambda h, j, i: (h, j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, d), lambda h, j, i: (h, j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_q, d), q_index_for_kv,
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_q, d), q_index_for_kv,
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_q, 1), q_index_for_kv,
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_q, 1), q_index_for_kv,
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, 1), lambda h, j, i: (h, 0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda h, j, i: (h, j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, d), lambda h, j, i: (h, j, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * n, s, d), k.dtype),
            jax.ShapeDtypeStruct((b * n, s, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=GATE.interpret,
        name=_kernel_name(rule, "dkv"),
    )(k3, v3, q3, do3, lse3, delta, slopes3)

    rs = lambda x: x.reshape(b, n, s, d)  # noqa: E731
    return rs(dq), rs(dk), rs(dv)


# ------------------------------------------- batch-major, heads in lanes
#
# The same three kernel bodies over q, k, v, ``out`` and their gradients
# as ``[b, s, n * d]``: the layout the qkv projection writes and the
# output projection reads, heads folded into the lane dimension, so that
# no transposed copy stands around the kernels and a head of 64 pads
# nothing to 128 lanes. A grid cell takes the heads of one 128-lane
# column block (two at ``d`` = 64, one at 128 or 256) and runs the body
# once a head on lane slices of its blocks. ``lse`` and delta are
# ``[b, n / c, c, s]`` (``c`` heads a cell), the sequence in lanes: a
# ``[.., s, 1]`` operand pads every number to 128 lanes in HBM. The
# bodies work on columns, so the cells turn a block's rows into columns
# in VMEM and back. Delta is formed in the dq kernel, which holds dO and
# is handed ``out``, and goes to the dkv kernel as rows: reduced by XLA
# over 64 of 128 lanes it costs two passes over a float32 product.

def _heads_per_cell(heads, d):
    """Heads a grid cell of the batch-major kernels takes so that its
    blocks are whole 128-lane columns; ``None`` where ``[b, s, n * d]``
    cannot be cut so."""
    if d % 128 == 0:
        return 1
    if 128 % d == 0 and heads % (128 // d) == 0:
        return 128 // d
    return None


class _HeadLanes:
    """Head ``h`` of a cell's ``[1, block, c * d]`` block, read and
    written as the kernel bodies do it (``ref[0]``, ``ref[0] = x``,
    ``ref.dtype``). Not a ``ref.at[...]`` view: Mosaic takes a static
    lane slice of 64 in a load or a store, not in a view of the ref."""

    def __init__(self, ref, h, d):
        self.ref, self.lanes, self.dtype = ref, slice(h * d, (h + 1) * d), \
            ref.dtype

    def __getitem__(self, row):
        return self.ref[row, :, self.lanes]

    def __setitem__(self, row, value):
        self.ref[row, :, self.lanes] = value


def _turned(x):
    """A ``[n, 1]`` column as a ``[1, n]`` row, or the row as the column
    (in VMEM; the bodies hold ``lse`` and delta as columns)."""
    return x.T


class _HeadSlope:
    """Head ``h``'s slope of a cell's ``[c, 1, 1]`` block, as the bodies
    read it (``ref[0, 0, 0]``)."""

    def __init__(self, ref, h):
        self.ref, self.h = ref, h

    def __getitem__(self, _):
        return self.ref[self.h, 0, 0]


def _bsnd_fwd_kernel(q_ref, k_ref, v_ref, slopes_ref, o_ref, lse_ref,
                     *scratch, heads, d, num_kv, **kw):
    """A cell's heads through :func:`_flash_fwd_kernel`, one after the
    other; ``scratch`` holds (acc, m, l, lse column) a head."""
    from jax.experimental import pallas as pl

    for h in range(heads):
        acc_ref, m_ref, l_ref, lse_col = scratch[4 * h:4 * h + 4]
        _flash_fwd_kernel(
            _HeadLanes(q_ref, h, d), _HeadLanes(k_ref, h, d),
            _HeadLanes(v_ref, h, d), _HeadSlope(slopes_ref, h),
            _HeadLanes(o_ref, h, d), lse_col, acc_ref, m_ref, l_ref,
            num_kv=num_kv, **kw)

    @pl.when(pl.program_id(2) == num_kv - 1)
    def _lse_rows():
        for h in range(heads):
            lse_ref[0, 0, h:h + 1, :] = _turned(scratch[4 * h + 3][0])


def _bsnd_dq_kernel(q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref, slopes_ref,
                    dq_ref, delta_ref, *scratch, heads, d, **kw):
    """As :func:`_bsnd_fwd_kernel` for :func:`_flash_dq_kernel`;
    ``scratch`` holds (dq acc, lse column, delta column) a head. The q
    block stays over the streamed kv blocks, so its first cell turns the
    ``lse`` rows and forms delta_i = rowsum(do_i * o_i), which it also
    writes out, as rows, for the dkv kernel."""
    from jax.experimental import pallas as pl

    for h in range(heads):
        dq_acc, lse_col, delta_col = scratch[3 * h:3 * h + 3]
        do_h = _HeadLanes(do_ref, h, d)

        @pl.when(pl.program_id(2) == 0)
        def _columns():
            lse_col[0] = _turned(lse_ref[0, 0, h:h + 1, :])
            delta = jnp.sum(
                do_h[0].astype(jnp.float32)
                * _HeadLanes(o_ref, h, d)[0].astype(jnp.float32),
                axis=-1, keepdims=True)
            delta_col[0] = delta
            delta_ref[0, 0, h:h + 1, :] = _turned(delta)

        _flash_dq_kernel(
            _HeadLanes(q_ref, h, d), _HeadLanes(k_ref, h, d),
            _HeadLanes(v_ref, h, d), do_h, lse_col, delta_col,
            _HeadSlope(slopes_ref, h), _HeadLanes(dq_ref, h, d), dq_acc,
            **kw)


def _bsnd_dkv_kernel(k_ref, v_ref, q_ref, do_ref, lse_ref, delta_ref,
                     slopes_ref, dk_ref, dv_ref, *scratch, heads, d, **kw):
    """As :func:`_bsnd_fwd_kernel` for :func:`_flash_dkv_kernel`;
    ``scratch`` holds (dk acc, dv acc, lse column, delta column) a head.
    The q side streams, so every cell that runs turns its rows."""
    from jax.experimental import pallas as pl

    run = _stream_q_run(pl.program_id(2), pl.program_id(1), kw["block_q"],
                        kw["block_k"], kw["causal"], kw["window"],
                        kw.get("rule"))
    for h in range(heads):
        dk_acc, dv_acc, lse_col, delta_col = scratch[4 * h:4 * h + 4]

        @pl.when(run)
        def _columns():
            lse_col[0] = _turned(lse_ref[0, 0, h:h + 1, :])
            delta_col[0] = _turned(delta_ref[0, 0, h:h + 1, :])

        _flash_dkv_kernel(
            _HeadLanes(k_ref, h, d), _HeadLanes(v_ref, h, d),
            _HeadLanes(q_ref, h, d), _HeadLanes(do_ref, h, d), lse_col,
            delta_col, _HeadSlope(slopes_ref, h), _HeadLanes(dk_ref, h, d),
            _HeadLanes(dv_ref, h, d), dk_acc, dv_acc, **kw)


def _bsnd_specs(heads, d, block_q, block_k, q_of, kv_of):
    """BlockSpecs over a ``(b * n / c, x, y)`` grid; ``q_of(x, y)`` and
    ``kv_of(x, y)`` give the q and the kv block a cell fetches."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    per_cell = _heads_per_cell(heads, d)
    cells = heads // per_cell

    def spec(shape, index):
        return pl.BlockSpec(shape, index, memory_space=pltpu.VMEM)

    return {
        "q": spec((1, block_q, per_cell * d),
                  lambda g, x, y: (g // cells, q_of(x, y), g % cells)),
        "kv": spec((1, block_k, per_cell * d),
                   lambda g, x, y: (g // cells, kv_of(x, y), g % cells)),
        "row": spec((1, 1, per_cell, block_q),
                    lambda g, x, y: (g // cells, g % cells, 0, q_of(x, y))),
        "slopes": spec((per_cell, 1, 1), lambda g, x, y: (g % cells, 0, 0)),
    }


def _bsnd_slopes(alibi_slopes, heads):
    """``[n]`` per-head slopes -> the ``[n, 1, 1]`` grid input."""
    if alibi_slopes is None:
        return jnp.zeros((heads, 1, 1), jnp.float32)
    return alibi_slopes.astype(jnp.float32).reshape(heads, 1, 1)


# Under ``jax.jit``: a model's layers then share one trace and one
# lowering of each kernel (every static argument is in the key, the
# gate's interpreter switch among them), where each bare ``pallas_call``
# is traced and lowered to Mosaic again, two heads' worth a cell here.
_bsnd_jit = functools.partial(jax.jit, static_argnames=(
    "heads", "scale", "causal", "block_q", "block_k", "window", "interpret",
    "rule"))


@_bsnd_jit
def _bsnd_fwd_pallas(q, k, v, alibi_slopes, *, heads, scale, causal,
                     block_q, block_k, window, interpret, rule=None):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, s, width = q.shape
    d = width // heads
    per_cell = _heads_per_cell(heads, d)
    cells = heads // per_cell
    num_kv = s // block_k
    sp = _bsnd_specs(
        heads, d, block_q, block_k, lambda i, j: i,
        lambda i, j: _fetched_kv_block(i, j, block_q, block_k, causal,
                                       window, rule))
    return pl.pallas_call(
        functools.partial(
            _bsnd_fwd_kernel, heads=per_cell, d=d, scale=scale,
            causal=causal, block_q=block_q, block_k=block_k, num_kv=num_kv,
            window=window, alibi=alibi_slopes is not None, rule=rule),
        grid=(b * cells, s // block_q, num_kv),
        in_specs=[sp["q"], sp["kv"], sp["kv"], sp["slopes"]],
        out_specs=[sp["q"], sp["row"]],
        out_shape=[
            jax.ShapeDtypeStruct((b, s, width), q.dtype),
            jax.ShapeDtypeStruct((b, cells, per_cell, s), jnp.float32),
        ],
        scratch_shapes=per_cell * [
            pltpu.VMEM((block_q, d), jnp.float32),      # acc
            pltpu.VMEM((block_q, 1), jnp.float32),      # running max
            pltpu.VMEM((block_q, 1), jnp.float32),      # running sum
            pltpu.VMEM((1, block_q, 1), jnp.float32),   # lse column
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name=_kernel_name(rule, "fwd"),
    )(q, k, v, _bsnd_slopes(alibi_slopes, heads))


@_bsnd_jit
def _bsnd_bwd_pallas(q, k, v, o, lse, do, alibi_slopes, *, heads, scale,
                     causal, block_q, block_k, window, interpret, rule=None):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, s, width = q.shape
    d = width // heads
    per_cell = _heads_per_cell(heads, d)
    cells = heads // per_cell
    num_q, num_kv = s // block_q, s // block_k
    slopes = _bsnd_slopes(alibi_slopes, heads)
    params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))
    static = dict(heads=per_cell, d=d, scale=scale, causal=causal,
                  block_q=block_q, block_k=block_k, window=window,
                  alibi=alibi_slopes is not None, rule=rule)
    column = pltpu.VMEM((1, block_q, 1), jnp.float32)

    sp = _bsnd_specs(
        heads, d, block_q, block_k, lambda i, j: i,
        lambda i, j: _fetched_kv_block(i, j, block_q, block_k, causal,
                                       window, rule))
    dq, delta = pl.pallas_call(
        functools.partial(_bsnd_dq_kernel, num_kv=num_kv, **static),
        grid=(b * cells, num_q, num_kv),
        in_specs=[sp["q"], sp["kv"], sp["kv"], sp["q"], sp["q"], sp["row"],
                  sp["slopes"]],
        out_specs=[sp["q"], sp["row"]],
        out_shape=[jax.ShapeDtypeStruct((b, s, width), q.dtype),
                   jax.ShapeDtypeStruct(lse.shape, jnp.float32)],
        scratch_shapes=per_cell * [
            pltpu.VMEM((block_q, d), jnp.float32), column, column],
        compiler_params=params, interpret=interpret,
        name=_kernel_name(rule, "dq"),
    )(q, k, v, do, o, lse, slopes)

    sp = _bsnd_specs(
        heads, d, block_q, block_k,
        lambda j, i: _fetched_q_block(j, i, block_q, block_k, causal,
                                      window, rule),
        lambda j, i: j)
    dk, dv = pl.pallas_call(
        functools.partial(_bsnd_dkv_kernel, num_q=num_q, **static),
        grid=(b * cells, num_kv, num_q),
        in_specs=[sp["kv"], sp["kv"], sp["q"], sp["q"], sp["row"],
                  sp["row"], sp["slopes"]],
        out_specs=[sp["kv"], sp["kv"]],
        out_shape=[jax.ShapeDtypeStruct((b, s, width), k.dtype),
                   jax.ShapeDtypeStruct((b, s, width), v.dtype)],
        scratch_shapes=per_cell * [
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32), column, column],
        compiler_params=params, interpret=interpret,
        name=_kernel_name(rule, "dkv"),
    )(k, v, q, do, lse, delta, slopes)
    return dq, dk, dv


# ------------------------------------------------ with a selection operand
#
# ``selection`` ``[b, s, s]`` int8 says, for every query, which keys it
# may see (DeepSeek Sparse Attention: a learned indexer chooses them;
# models/transformer_lm.py ``SparseIndexer``). It is one more operand of
# the same three kernel bodies, streamed by tile and shared by a batch
# row's heads; ``_tile_flags`` tells each grid cell, through scalar
# prefetch, whether its tile selects anything, and cells whose tile does
# not are skipped like those above the diagonal. These calls are named
# ``sparse_attention_*``.

def _tile_flags(selection, block_q, block_k):
    """``[b * nq * nk]`` int32: does tile (i, j) of row ``b`` select any
    (query, key) pair?"""
    b, s, _ = selection.shape
    nq, nk = s // block_q, s // block_k
    tiles = selection.reshape(b, nq, block_q, nk, block_k)
    return (jnp.max(tiles, axis=(2, 4)) != 0).astype(jnp.int32).reshape(-1)


def _flag(flags_ref, row, qi, kj, num_q, num_kv):
    return flags_ref[(row * num_q + qi) * num_kv + kj] != 0


def _sparse_fwd_kernel(flags_ref, q_ref, k_ref, v_ref, sel_ref, o_ref,
                       lse_ref, acc_ref, m_ref, l_ref, *, heads, num_q,
                       **kw):
    from jax.experimental import pallas as pl

    tile = _flag(flags_ref, pl.program_id(0) // heads, pl.program_id(1),
                 pl.program_id(2), num_q, kw["num_kv"])
    _flash_fwd_kernel(q_ref, k_ref, v_ref, None, o_ref, lse_ref, acc_ref,
                      m_ref, l_ref, sel_ref=sel_ref, tile_selected=tile,
                      window=None, alibi=False, **kw)


def _sparse_dq_kernel(flags_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                      delta_ref, sel_ref, dq_ref, dq_acc, *, heads, num_q,
                      **kw):
    from jax.experimental import pallas as pl

    tile = _flag(flags_ref, pl.program_id(0) // heads, pl.program_id(1),
                 pl.program_id(2), num_q, kw["num_kv"])
    _flash_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, None,
                     dq_ref, dq_acc, sel_ref=sel_ref, tile_selected=tile,
                     window=None, alibi=False, **kw)


def _sparse_dkv_kernel(flags_ref, k_ref, v_ref, q_ref, do_ref, lse_ref,
                       delta_ref, sel_ref, dk_ref, dv_ref, dk_acc, dv_acc,
                       *, heads, num_kv, **kw):
    from jax.experimental import pallas as pl

    tile = _flag(flags_ref, pl.program_id(0) // heads, pl.program_id(2),
                 pl.program_id(1), kw["num_q"], num_kv)
    _flash_dkv_kernel(k_ref, v_ref, q_ref, do_ref, lse_ref, delta_ref, None,
                      dk_ref, dv_ref, dk_acc, dv_acc, sel_ref=sel_ref,
                      tile_selected=tile, window=None, alibi=False, **kw)


def _head_probs_kernel(flags_ref, q_ref, k_ref, lse_ref, sel_ref, p_ref, *,
                       scale, causal, block_q, block_k, num_q, num_kv):
    """One (row, q-block, kv-block, head) cell: add this head's
    ``exp(q k^T scale - lse)`` on the selected pairs to the tile's sum.
    The head axis is innermost, so the output tile stays in VMEM over
    it."""
    from jax.experimental import pallas as pl

    qi, kj, h = pl.program_id(1), pl.program_id(2), pl.program_id(3)

    @pl.when(h == 0)
    def _init():
        p_ref[...] = jnp.zeros_like(p_ref)

    run = _and_run(_stream_kv_run(qi, kj, block_q, block_k, causal, None),
                   _flag(flags_ref, pl.program_id(0), qi, kj, num_q, num_kv))

    @pl.when(run)
    def _step():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        if causal:
            s = _causal_mask(s, qi, kj, block_q, block_k)
        s = jnp.where(_selected(sel_ref), s, NEG_INF)
        p_ref[0] += jnp.exp(s - lse_ref[0])


def _sparse_specs(d, block_q, block_k, heads):
    """BlockSpecs over a ``(b * n, q-block, kv-block)`` grid with one
    scalar-prefetch operand (index maps take it last)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def spec(shape, index):
        return pl.BlockSpec(shape, index, memory_space=pltpu.VMEM)

    return {
        "q": spec((1, block_q, d), lambda h, i, j, f: (h, i, 0)),
        "kv": spec((1, block_k, d), lambda h, i, j, f: (h, j, 0)),
        "row": spec((1, block_q, 1), lambda h, i, j, f: (h, i, 0)),
        "sel": spec((1, block_q, block_k),
                    lambda h, i, j, f: (h // heads, i, j)),
    }


def _sparse_fwd_pallas(q, k, v, selection, scale, causal, block_q, block_k):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, n, s, d = q.shape
    q3, k3, v3 = (x.reshape(b * n, s, d) for x in (q, k, v))
    num_q, num_kv = s // block_q, s // block_k
    sp = _sparse_specs(d, block_q, block_k, n)
    out, lse = pl.pallas_call(
        functools.partial(_sparse_fwd_kernel, heads=n, num_q=num_q,
                          scale=scale, causal=causal, block_q=block_q,
                          block_k=block_k, num_kv=num_kv),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(b * n, num_q, num_kv),
            in_specs=[sp["q"], sp["kv"], sp["kv"], sp["sel"]],
            out_specs=[sp["q"], sp["row"]],
            scratch_shapes=[
                pltpu.VMEM((block_q, d), jnp.float32),
                pltpu.VMEM((block_q, 1), jnp.float32),
                pltpu.VMEM((block_q, 1), jnp.float32),
            ]),
        out_shape=[
            jax.ShapeDtypeStruct((b * n, s, d), q.dtype),
            jax.ShapeDtypeStruct((b * n, s, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=GATE.interpret,
        name="sparse_attention_flash_fwd",
    )(_tile_flags(selection, block_q, block_k), q3, k3, v3, selection)
    return out.reshape(b, n, s, d), lse.reshape(b, n, s)


def _sparse_bwd_pallas(q, k, v, o, lse, do, selection, scale, causal,
                       block_q, block_k):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, n, s, d = q.shape
    q3, k3, v3 = (x.reshape(b * n, s, d) for x in (q, k, v))
    o3, do3 = (x.reshape(b * n, s, d) for x in (o, do))
    lse3 = lse.reshape(b * n, s, 1)
    delta = jnp.sum(do3.astype(jnp.float32) * o3.astype(jnp.float32),
                    axis=-1, keepdims=True)
    num_q, num_kv = s // block_q, s // block_k
    flags = _tile_flags(selection, block_q, block_k)
    sp = _sparse_specs(d, block_q, block_k, n)
    params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))
    static = dict(scale=scale, causal=causal, block_q=block_q,
                  block_k=block_k, heads=n)

    dq = pl.pallas_call(
        functools.partial(_sparse_dq_kernel, num_q=num_q, num_kv=num_kv,
                          **static),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(b * n, num_q, num_kv),
            in_specs=[sp["q"], sp["kv"], sp["kv"], sp["q"], sp["row"],
                      sp["row"], sp["sel"]],
            out_specs=sp["q"],
            scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((b * n, s, d), q.dtype),
        compiler_params=params, interpret=GATE.interpret,
        name="sparse_attention_flash_dq",
    )(flags, q3, k3, v3, do3, lse3, delta, selection)

    # the dkv grid is (b * n, kv-block, q-block): swap the roles of the
    # two block indices in every spec
    def swapped(spec):
        return pl.BlockSpec(spec.block_shape,
                            lambda h, j, i, f: spec.index_map(h, i, j, f),
                            memory_space=pltpu.VMEM)

    sw = {name: swapped(spec) for name, spec in sp.items()}
    dk, dv = pl.pallas_call(
        functools.partial(_sparse_dkv_kernel, num_q=num_q, num_kv=num_kv,
                          **static),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(b * n, num_kv, num_q),
            in_specs=[sw["kv"], sw["kv"], sw["q"], sw["q"], sw["row"],
                      sw["row"], sw["sel"]],
            out_specs=[sw["kv"], sw["kv"]],
            scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                            pltpu.VMEM((block_k, d), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct((b * n, s, d), k.dtype),
                   jax.ShapeDtypeStruct((b * n, s, d), v.dtype)],
        compiler_params=params, interpret=GATE.interpret,
        name="sparse_attention_flash_dkv",
    )(flags, k3, v3, q3, do3, lse3, delta, selection)

    rs = lambda x: x.reshape(b, n, s, d)  # noqa: E731
    return rs(dq), rs(dk), rs(dv)


def _head_probs_pallas(q, k, lse, selection, scale, causal, block_q,
                       block_k):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, n, s, d = q.shape
    num_q, num_kv = s // block_q, s // block_k

    def spec(shape, index):
        return pl.BlockSpec(shape, index, memory_space=pltpu.VMEM)

    return pl.pallas_call(
        functools.partial(_head_probs_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, num_q=num_q,
                          num_kv=num_kv),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(b, num_q, num_kv, n),
            in_specs=[
                spec((1, block_q, d), lambda r, i, j, h, f: (r * n + h, i, 0)),
                spec((1, block_k, d), lambda r, i, j, h, f: (r * n + h, j, 0)),
                spec((1, block_q, 1), lambda r, i, j, h, f: (r * n + h, i, 0)),
                spec((1, block_q, block_k), lambda r, i, j, h, f: (r, i, j)),
            ],
            out_specs=spec((1, block_q, block_k),
                           lambda r, i, j, h, f: (r, i, j))),
        out_shape=jax.ShapeDtypeStruct((b, s, s), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=GATE.interpret,
        name="sparse_attention_head_probs",
    )(_tile_flags(selection, block_q, block_k), q.reshape(b * n, s, d),
      k.reshape(b * n, s, d), lse.reshape(b * n, s, 1), selection)


def _reference_scores(q, k, scale, causal, window=None, alibi_slopes=None,
                      selection=None, rule=None):
    """Masked float32 scores ``[b, n, s, s]`` of the reference path."""
    s = jnp.einsum("bnqd,bnkd->bnqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if alibi_slopes is not None:
        s = s + (alibi_slopes.astype(jnp.float32)[None, :, None, None]
                 * jnp.arange(s.shape[-1], dtype=jnp.float32
                              )[None, None, None, :])
    if causal:
        sq, sk = s.shape[-2], s.shape[-1]
        mask = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        if window is not None:
            mask = mask & jnp.triu(jnp.ones((sq, sk), bool),
                                   k=sk - sq - window + 1)
        s = jnp.where(mask, s, NEG_INF)
    if selection is not None:
        s = jnp.where(selection[:, None] != 0, s, NEG_INF)
    if rule is not None:
        s = jnp.where(block_diffusion_mask(*rule), s, NEG_INF)
    return s


def _attention_reference(q, k, v, scale, causal, window=None,
                         alibi_slopes=None, selection=None, rule=None):
    """Reference einsum attention (fp32 softmax), used for the backward
    rematerialization and the non-TPU fallback."""
    p = jax.nn.softmax(_reference_scores(q, k, scale, causal, window,
                                         alibi_slopes, selection, rule),
                       axis=-1)
    return jnp.einsum("bnqk,bnkd->bnqd", p, v.astype(jnp.float32)).astype(q.dtype)


def _fit_block(block, s):
    """Largest of (block, 256, 128, s) that divides s, so seq lengths that
    are 128-multiples but not block-multiples stay on the kernel instead
    of silently falling back to the O(s^2) reference path."""
    for cand in (block, 256, 128):
        b = min(cand, s)
        if s % b == 0:
            return b
    return None


def _resolve(q, scale, block_q, block_k, rule=None):
    """(scale, block_q, block_k) for ``[.., seq, head_dim]`` operands."""
    return _resolve_sizes(q.shape[-1], q.shape[-2], scale, block_q, block_k,
                          rule)


def _resolve_sizes(head_dim, s, scale, block_q, block_k, rule=None):
    if scale is None:
        scale = 1.0 / (head_dim ** 0.5)
    elif not isinstance(scale, numbers.Number):
        # scale sits in custom_vjp nondiff_argnums: a traced value (e.g.
        # 1/jnp.sqrt(d)) surfaces as a cryptic UnexpectedTracerError deep
        # inside autodiff — fail fast with the actual contract instead.
        raise TypeError(
            "flash_attention scale must be a python number (it is a "
            f"static argument of the custom_vjp), got {type(scale)}; "
            "pass scale=None for the 1/sqrt(head_dim) default")
    if rule is not None:
        # tiles lie in one half of the row and hold whole diffusion blocks
        # of a power of two (every block that divides a 128-tile is one)
        bq, bk = _fit_block(block_q, rule.length), _fit_block(block_k,
                                                              rule.length)
        if (bq is None or bk is None or bq % rule.block or bk % rule.block
                or rule.block & (rule.block - 1)):
            bq = bk = None
        return scale, bq, bk
    return scale, _fit_block(block_q, s), _fit_block(block_k, s)


def _check_window(window, causal):
    if window is None:
        return
    if not causal:
        raise ValueError("flash_attention window requires causal=True")
    # numbers.Integral admits numpy scalars from parsed configs; bool is
    # an int subclass and must not silently mean window=1.
    if (isinstance(window, bool) or not isinstance(window, numbers.Integral)
            or window < 1):
        raise ValueError(f"flash_attention window must be a positive "
                         f"static int, got {window!r}")


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 10))
def flash_attention(q, k, v, causal=True, scale=None,
                    block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K,
                    window=None, alibi_slopes=None, selection=None,
                    block_diffusion=None):
    """Flash attention over [batch, heads, seq, head_dim] inputs (head
    major: for callers that hold their heads so; on a TPU a head_dim
    under 128 pads to 128 lanes in every operand. A caller that holds
    ``[batch, seq, heads * head_dim]``, as a projection writes it, calls
    :func:`flash_attention_bsnd`: the same kernels, nothing transposed
    or padded).

    ``selection``: int8 ``[batch, seq, seq]``, non-zero where a query
    (second axis) may see a key (third axis), shared by the heads; the
    softmax runs over the selected keys alone (with ``causal`` also
    below the diagonal only). Every query has to select a key. Tiles
    that select nothing are skipped. Not differentiable; composes with
    neither ``window`` nor ``alibi_slopes``. Without it the kernels are
    the ones they are without this argument.

    ``window``: sliding-window band (key j visible to query i iff
    0 <= i - j < window); blocks fully outside the band are skipped, so
    compute scales with seq * window instead of seq^2.
    ``alibi_slopes``: per-head [heads] slopes adding the key-position
    alibi bias inside the kernel. Treated as NON-DIFFERENTIABLE (the
    returned cotangent is zero, matching the CUDA flash-attention
    convention) — trained-ALiBi variants must not route slope gradients
    through this op.
    ``block_diffusion``: the diffusion block's length, a static int: the
    row holds ``seq / 2`` clean tokens and then their noised copies, and
    the rule is block diffusion's (:class:`_BlockDiffusion`), worked out
    from positions inside the kernels (named ``blockdiff_attention_*``;
    tiles no query of which sees a key are neither run nor fetched).
    A rule of its own: ``causal`` has to be ``False``, and it composes
    with neither ``window``, ``alibi_slopes`` nor ``selection``. Counted
    as ``kernels/dispatch/flash_attention_blockdiff_<path>`` beside
    ``flash_attention``'s own counter; where no block fits both halves
    and whole diffusion blocks of a power of two, the oracle under
    :func:`block_diffusion_mask`."""
    _check_window(window, causal)
    _check_selection(selection, q, window, alibi_slopes)
    rule = _rule_of(block_diffusion, q.shape[2], causal, window,
                    alibi_slopes, selection)
    scale, bq, bk = _resolve(q, scale, block_q, block_k, rule)
    if _takes(bq, bk, q.shape[2], causal, window, rule=rule) != "oracle":
        if selection is not None:
            return _sparse_fwd_pallas(q, k, v, selection, scale, causal,
                                      bq, bk)[0]
        return _flash_fwd_pallas(q, k, v, scale, causal, bq, bk,
                                 window, alibi_slopes, rule)[0]
    return _attention_reference(q, k, v, scale, causal, window,
                                alibi_slopes, selection, rule)


def _check_selection(selection, q, window, alibi_slopes):
    if selection is None:
        return
    if window is not None or alibi_slopes is not None:
        raise ValueError("flash_attention selection composes with neither "
                         "window nor alibi_slopes")
    want = (q.shape[0], q.shape[2], q.shape[2])
    if selection.shape != want or selection.dtype != jnp.int8:
        raise ValueError(f"flash_attention selection must be int8 {want}, "
                         f"got {selection.dtype} {selection.shape}")


def _flash_fwd_rule(q, k, v, causal, scale, block_q, block_k,
                    window=None, alibi_slopes=None, selection=None,
                    block_diffusion=None):
    _check_window(window, causal)
    _check_selection(selection, q, window, alibi_slopes)
    rule = _rule_of(block_diffusion, q.shape[2], causal, window,
                    alibi_slopes, selection)
    scale_, bq, bk = _resolve(q, scale, block_q, block_k, rule)
    if _takes(bq, bk, q.shape[2], causal, window, rule=rule) != "oracle":
        if selection is not None:
            out, lse = _sparse_fwd_pallas(q, k, v, selection, scale_, causal,
                                          bq, bk)
        else:
            out, lse = _flash_fwd_pallas(q, k, v, scale_, causal, bq, bk,
                                         window, alibi_slopes, rule)
        # The two residuals only another run of the kernel can rebuild,
        # named so that a checkpointed layer can keep them (``lse`` in
        # its [b, n, s] form: the kernel's [b*n, s, 1] pads its last
        # dimension to 128 lanes in HBM). Metadata outside jax.checkpoint.
        # The forward has to go on from the named ``out``: named for the
        # residuals alone, the recomputed layer would still need the
        # kernel's own ``out`` for what follows it, and run the kernel.
        out = checkpoint_name(out, FLASH_RESIDUAL_NAMES[0])
        lse = checkpoint_name(lse, FLASH_RESIDUAL_NAMES[1])
        return out, (q, k, v, out, lse, alibi_slopes, selection)
    return (_attention_reference(q, k, v, scale_, causal, window,
                                 alibi_slopes, selection, rule),
            (q, k, v, None, None, alibi_slopes, selection))


def _flash_bwd_rule(causal, scale, block_q, block_k, window,
                    block_diffusion, res, g):
    q, k, v, out, lse, alibi_slopes, selection = res
    rule = _rule_of(block_diffusion, q.shape[2])
    scale_, bq, bk = _resolve(q, scale, block_q, block_k, rule)
    none_slope_grad = (None if alibi_slopes is None
                       else jnp.zeros_like(alibi_slopes))
    if lse is not None and selection is not None:
        dq, dk, dv = _sparse_bwd_pallas(q, k, v, out, lse, g, selection,
                                        scale_, causal, bq, bk)
        return dq, dk, dv, none_slope_grad, None
    if lse is not None:
        dq, dk, dv = _flash_bwd_pallas(q, k, v, out, lse, g, scale_,
                                       causal, bq, bk, window,
                                       alibi_slopes, rule)
        return dq, dk, dv, none_slope_grad, None
    _, vjp = jax.vjp(
        lambda q_, k_, v_: _attention_reference(q_, k_, v_, scale_,
                                                causal, window,
                                                alibi_slopes, selection,
                                                rule),
        q, k, v)
    return (*vjp(g), none_slope_grad, None)


flash_attention.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def _to_head_major(x, heads):
    """``[b, s, n * d]`` -> ``[b, n, s, d]``."""
    b, s, width = x.shape
    return x.reshape(b, s, heads, width // heads).transpose(0, 2, 1, 3)


def _to_batch_major(x):
    """``[b, n, s, d]`` -> ``[b, s, n * d]``."""
    b, n, s, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b, s, n * d)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 10))
def flash_attention_bsnd(q, k, v, heads, causal=True, scale=None,
                         block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K,
                         window=None, alibi_slopes=None,
                         block_diffusion=None):
    """:func:`flash_attention` over ``[batch, seq, heads * head_dim]``
    inputs: the layout a qkv projection writes and an output projection
    reads, so that nothing is transposed around the kernels and a head
    narrower than 128 lanes pads nothing in HBM. The same kernel bodies
    under other index maps; a grid cell takes the heads of one 128-lane
    column, so ``head_dim`` has to be a multiple of 128, or divide 128
    with ``heads`` a multiple of ``128 // head_dim``. ``causal``,
    ``scale``, ``window``, ``alibi_slopes`` and ``block_diffusion`` as
    :func:`flash_attention` has them. The residuals a checkpointed layer can keep
    (``FLASH_RESIDUAL_NAMES``) are the kernel's own: ``out`` ``[b, s,
    n * d]`` in the operands' dtype and the log-sum-exp ``[b, n / c, c,
    s]`` float32, ``c`` heads a cell; the backward kernels read both as
    they are. Counted as ``kernels/dispatch/flash_attention_bsnd_<path>``
    (under ``block_diffusion`` as ``flash_attention_blockdiff_<path>``)
    beside ``flash_attention``'s own counter."""
    return _bsnd_fwd_rule(q, k, v, heads, causal, scale, block_q, block_k,
                          window, alibi_slopes, block_diffusion)[0]


def _bsnd_resolve(q, heads, scale, block_q, block_k, causal, window,
                  rule=None):
    """(scale, block_q, block_k, does the kernel run), recording the
    call under both counters."""
    width = q.shape[-1]
    if width % heads or _heads_per_cell(heads, width // heads) is None:
        raise ValueError(
            f"flash_attention_bsnd cannot cut {heads} heads over {width} "
            "lanes into 128-lane columns; use flash_attention")
    scale, bq, bk = _resolve_sizes(width // heads, q.shape[1], scale,
                                   block_q, block_k, rule)
    path = _takes(bq, bk, q.shape[1], causal, window,
                  entry="flash_attention_bsnd", rule=rule)
    return scale, bq, bk, path != "oracle"


def _bsnd_reference(q, k, v, heads, scale, causal, window, alibi_slopes,
                    rule=None):
    return _to_batch_major(_attention_reference(
        *(_to_head_major(x, heads) for x in (q, k, v)), scale, causal,
        window, alibi_slopes, rule=rule))


def _bsnd_fwd_rule(q, k, v, heads, causal, scale, block_q, block_k,
                   window=None, alibi_slopes=None, block_diffusion=None):
    _check_window(window, causal)
    rule = _rule_of(block_diffusion, q.shape[1], causal, window,
                    alibi_slopes)
    scale_, bq, bk, kernel = _bsnd_resolve(q, heads, scale, block_q,
                                           block_k, causal, window, rule)
    if kernel:
        out, lse = _bsnd_fwd_pallas(
            q, k, v, alibi_slopes, heads=heads, scale=scale_, causal=causal,
            block_q=bq, block_k=bk, window=window, interpret=GATE.interpret,
            rule=rule)
        # as _flash_fwd_rule; both are kept as the kernel wrote them
        out = checkpoint_name(out, FLASH_RESIDUAL_NAMES[0])
        lse = checkpoint_name(lse, FLASH_RESIDUAL_NAMES[1])
        return out, (q, k, v, out, lse, alibi_slopes)
    return (_bsnd_reference(q, k, v, heads, scale_, causal, window,
                            alibi_slopes, rule),
            (q, k, v, None, None, alibi_slopes))


def _bsnd_bwd_rule(heads, causal, scale, block_q, block_k, window,
                   block_diffusion, res, g):
    q, k, v, out, lse, alibi_slopes = res
    rule = _rule_of(block_diffusion, q.shape[1])
    scale_, bq, bk = _resolve_sizes(q.shape[-1] // heads, q.shape[1], scale,
                                    block_q, block_k, rule)
    none_slope_grad = (None if alibi_slopes is None
                       else jnp.zeros_like(alibi_slopes))
    if lse is not None:
        return (*_bsnd_bwd_pallas(
            q, k, v, out, lse, g, alibi_slopes, heads=heads, scale=scale_,
            causal=causal, block_q=bq, block_k=bk, window=window,
            interpret=GATE.interpret, rule=rule), none_slope_grad)
    _, vjp = jax.vjp(
        lambda q_, k_, v_: _bsnd_reference(q_, k_, v_, heads, scale_,
                                           causal, window, alibi_slopes,
                                           rule),
        q, k, v)
    return (*vjp(g), none_slope_grad)


flash_attention_bsnd.defvjp(_bsnd_fwd_rule, _bsnd_bwd_rule)


# ----------------------------------------------------- latent attention
#
# Multi-head latent attention (DeepSeek-V2/V3, Moonlight) scores a pair as
# ``q^C . k^C + q^R . k^R``: a positionless part a head (``d_nope`` wide)
# and a rotary part (``d_rope`` wide) whose key is ONE vector a token,
# shared by the heads; values are ``d_v`` wide. The same three kernel
# bodies take the rotary part as a second product into the same scores
# (``rope=``: :class:`_RopePart`), batch-major as the ``bsnd`` entry, a
# grid cell over the heads of whole 128-lane columns of every operand
# (two heads at 128 + 64 beside 128). The shared rotary key is read as it
# is, ``[b, s, d_rope]``, once a tile: no ``[b, s, n, d_rope]`` broadcast
# and no concatenated key is written, and its gradient is summed over a
# cell's heads in the kernel's scratch (over the cells by XLA, from
# float32). These calls are named ``mla_attention_*``.

class _RopePart:
    """A head's rotary operands beside the body's own q and k: ``q``
    (``[1, block_q, d_rope]`` as a body reads it) and the shared ``k``
    block, and where the gradient that the body forms goes."""

    def __init__(self, q, k, dq_ref=None, acc=None):
        self.q, self.k, self.dq_ref, self.acc = q, k, dq_ref, acc

    def scores(self, scale=None, rows=slice(None), cols=slice(None)):
        """The rotary product of the tile's ``rows`` against its ``cols``
        (a strip's; dkv takes the whole tile)."""
        q = self.q[0].astype(jnp.float32)[rows]
        if scale is not None:       # the forward scales q, not the scores
            q = q * scale
        return jnp.dot(q, self.k[0].astype(jnp.float32)[cols].T,
                       preferred_element_type=jnp.float32)

    def add_dq(self, ds, scale, rows, cols):
        self.acc[rows] += jnp.dot(
            ds, self.k[0].astype(jnp.float32)[cols],
            preferred_element_type=jnp.float32) * scale

    def write_dq(self):
        self.dq_ref[0] = self.acc[...].astype(self.dq_ref.dtype)

    def add_dk(self, ds, scale):
        # every head of the cell adds to the one shared key's gradient
        self.acc[...] += jnp.dot(ds.T, self.q[0].astype(jnp.float32),
                                 preferred_element_type=jnp.float32) * scale


def _mla_heads_per_cell(heads, widths):
    """Heads a latent-attention cell takes so that its block of every
    per-head operand is whole 128-lane columns; ``None`` where there is
    no such cut."""
    for c in (1, 2, 4, 8):
        if heads % c == 0 and all(c * w % 128 == 0 for w in widths):
            return c
    return None


def _mla_fwd_kernel(qn_ref, qr_ref, kn_ref, kr_ref, v_ref, o_ref, lse_ref,
                    *scratch, heads, dims, num_kv, **kw):
    """As :func:`_bsnd_fwd_kernel`, with the rotary part."""
    from jax.experimental import pallas as pl

    dn, dr, dv = dims
    for h in range(heads):
        acc_ref, m_ref, l_ref, lse_col = scratch[4 * h:4 * h + 4]
        _flash_fwd_kernel(
            _HeadLanes(qn_ref, h, dn), _HeadLanes(kn_ref, h, dn),
            _HeadLanes(v_ref, h, dv), None, _HeadLanes(o_ref, h, dv),
            lse_col, acc_ref, m_ref, l_ref, num_kv=num_kv, window=None,
            alibi=False, rope=_RopePart(_HeadLanes(qr_ref, h, dr), kr_ref),
            **kw)

    @pl.when(pl.program_id(2) == num_kv - 1)
    def _lse_rows():
        for h in range(heads):
            lse_ref[0, 0, h:h + 1, :] = _turned(scratch[4 * h + 3][0])


def _mla_dq_kernel(qn_ref, qr_ref, kn_ref, kr_ref, v_ref, do_ref, o_ref,
                   lse_ref, dqn_ref, dqr_ref, delta_ref, *scratch, heads,
                   dims, **kw):
    """As :func:`_bsnd_dq_kernel`; ``scratch`` holds (dq acc, rotary dq
    acc, lse column, delta column) a head."""
    from jax.experimental import pallas as pl

    dn, dr, dv = dims
    for h in range(heads):
        dq_acc, dqr_acc, lse_col, delta_col = scratch[4 * h:4 * h + 4]
        do_h = _HeadLanes(do_ref, h, dv)

        @pl.when(pl.program_id(2) == 0)
        def _columns():
            lse_col[0] = _turned(lse_ref[0, 0, h:h + 1, :])
            delta = jnp.sum(
                do_h[0].astype(jnp.float32)
                * _HeadLanes(o_ref, h, dv)[0].astype(jnp.float32),
                axis=-1, keepdims=True)
            delta_col[0] = delta
            delta_ref[0, 0, h:h + 1, :] = _turned(delta)
            dqr_acc[...] = jnp.zeros_like(dqr_acc)

        _flash_dq_kernel(
            _HeadLanes(qn_ref, h, dn), _HeadLanes(kn_ref, h, dn),
            _HeadLanes(v_ref, h, dv), do_h, lse_col, delta_col, None,
            _HeadLanes(dqn_ref, h, dn), dq_acc, window=None, alibi=False,
            rope=_RopePart(_HeadLanes(qr_ref, h, dr), kr_ref,
                           _HeadLanes(dqr_ref, h, dr), dqr_acc), **kw)


def _mla_dkv_kernel(kn_ref, kr_ref, v_ref, qn_ref, qr_ref, do_ref, lse_ref,
                    delta_ref, dkn_ref, dkr_ref, dv_ref, *scratch, heads,
                    dims, num_q, **kw):
    """As :func:`_bsnd_dkv_kernel`; ``scratch`` holds (dk acc, dv acc,
    lse column, delta column) a head and, last, the shared rotary key's
    gradient, which all the cell's heads add to."""
    from jax.experimental import pallas as pl

    dn, dr, dv = dims
    dkr_acc = scratch[-1]
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dkr_acc[...] = jnp.zeros_like(dkr_acc)

    run = _stream_q_run(qi, pl.program_id(1), kw["block_q"], kw["block_k"],
                        kw["causal"], None)
    for h in range(heads):
        dk_acc, dv_acc, lse_col, delta_col = scratch[4 * h:4 * h + 4]

        @pl.when(run)
        def _columns():
            lse_col[0] = _turned(lse_ref[0, 0, h:h + 1, :])
            delta_col[0] = _turned(delta_ref[0, 0, h:h + 1, :])

        _flash_dkv_kernel(
            _HeadLanes(kn_ref, h, dn), _HeadLanes(v_ref, h, dv),
            _HeadLanes(qn_ref, h, dn), _HeadLanes(do_ref, h, dv), lse_col,
            delta_col, None, _HeadLanes(dkn_ref, h, dn),
            _HeadLanes(dv_ref, h, dv), dk_acc, dv_acc, num_q=num_q,
            window=None, alibi=False,
            rope=_RopePart(_HeadLanes(qr_ref, h, dr), kr_ref, acc=dkr_acc),
            **kw)

    @pl.when(qi == num_q - 1)
    def _finish():
        dkr_ref[0, 0] = dkr_acc[...]


def _mla_specs(per_cell, cells, dims, block_q, block_k, q_of, kv_of):
    """BlockSpecs over a ``(b * cells, x, y)`` grid, as
    :func:`_bsnd_specs`: ``qn``/``qr``/``o`` follow the q block, ``kn``/
    ``kr``/``v`` the kv block; ``dkr`` is the cell's part of the shared
    rotary key's gradient, ``[b, cells, s, d_rope]`` float32."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    dn, dr, dv = dims

    def spec(shape, index):
        return pl.BlockSpec(shape, index, memory_space=pltpu.VMEM)

    def q_side(width):
        return spec((1, block_q, per_cell * width),
                    lambda g, x, y: (g // cells, q_of(x, y), g % cells))

    def kv_side(width):
        return spec((1, block_k, per_cell * width),
                    lambda g, x, y: (g // cells, kv_of(x, y), g % cells))

    return {
        "qn": q_side(dn), "qr": q_side(dr), "o": q_side(dv),
        "kn": kv_side(dn), "v": kv_side(dv),
        "kr": spec((1, block_k, dr),
                   lambda g, x, y: (g // cells, kv_of(x, y), 0)),
        "row": spec((1, 1, per_cell, block_q),
                    lambda g, x, y: (g // cells, g % cells, 0, q_of(x, y))),
        "dkr": spec((1, 1, block_k, dr),
                    lambda g, x, y: (g // cells, g % cells, kv_of(x, y), 0)),
    }


def _mla_dims(q_nope, q_rope, v, heads):
    return tuple(x.shape[-1] // heads for x in (q_nope, q_rope, v))


_mla_jit = functools.partial(jax.jit, static_argnames=(
    "heads", "scale", "causal", "block_q", "block_k", "interpret"))


@_mla_jit
def _mla_fwd_pallas(q_nope, q_rope, k_nope, k_rope, v, *, heads, scale,
                    causal, block_q, block_k, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, s, _ = q_nope.shape
    dims = _mla_dims(q_nope, q_rope, v, heads)
    per_cell = _mla_heads_per_cell(heads, dims)
    cells = heads // per_cell
    num_kv = s // block_k
    sp = _mla_specs(
        per_cell, cells, dims, block_q, block_k, lambda i, j: i,
        lambda i, j: _fetched_kv_block(i, j, block_q, block_k, causal,
                                       None))
    return pl.pallas_call(
        functools.partial(
            _mla_fwd_kernel, heads=per_cell, dims=dims, scale=scale,
            causal=causal, block_q=block_q, block_k=block_k, num_kv=num_kv),
        grid=(b * cells, s // block_q, num_kv),
        in_specs=[sp["qn"], sp["qr"], sp["kn"], sp["kr"], sp["v"]],
        out_specs=[sp["o"], sp["row"]],
        out_shape=[
            jax.ShapeDtypeStruct(v.shape, q_nope.dtype),
            jax.ShapeDtypeStruct((b, cells, per_cell, s), jnp.float32),
        ],
        scratch_shapes=per_cell * [
            pltpu.VMEM((block_q, dims[2]), jnp.float32),    # acc
            pltpu.VMEM((block_q, 1), jnp.float32),          # running max
            pltpu.VMEM((block_q, 1), jnp.float32),          # running sum
            pltpu.VMEM((1, block_q, 1), jnp.float32),       # lse column
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="mla_attention_flash_fwd",
    )(q_nope, q_rope, k_nope, k_rope, v)


@_mla_jit
def _mla_bwd_pallas(q_nope, q_rope, k_nope, k_rope, v, o, lse, do, *, heads,
                    scale, causal, block_q, block_k, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, s, _ = q_nope.shape
    dims = dn, dr, dv = _mla_dims(q_nope, q_rope, v, heads)
    per_cell = _mla_heads_per_cell(heads, dims)
    cells = heads // per_cell
    num_q, num_kv = s // block_q, s // block_k
    params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))
    static = dict(heads=per_cell, dims=dims, scale=scale, causal=causal,
                  block_q=block_q, block_k=block_k)
    column = pltpu.VMEM((1, block_q, 1), jnp.float32)

    sp = _mla_specs(
        per_cell, cells, dims, block_q, block_k, lambda i, j: i,
        lambda i, j: _fetched_kv_block(i, j, block_q, block_k, causal,
                                       None))
    dq_nope, dq_rope, delta = pl.pallas_call(
        functools.partial(_mla_dq_kernel, num_kv=num_kv, **static),
        grid=(b * cells, num_q, num_kv),
        in_specs=[sp["qn"], sp["qr"], sp["kn"], sp["kr"], sp["v"], sp["o"],
                  sp["o"], sp["row"]],
        out_specs=[sp["qn"], sp["qr"], sp["row"]],
        out_shape=[jax.ShapeDtypeStruct(q_nope.shape, q_nope.dtype),
                   jax.ShapeDtypeStruct(q_rope.shape, q_rope.dtype),
                   jax.ShapeDtypeStruct(lse.shape, jnp.float32)],
        scratch_shapes=per_cell * [
            pltpu.VMEM((block_q, dn), jnp.float32),
            pltpu.VMEM((block_q, dr), jnp.float32), column, column],
        compiler_params=params, interpret=interpret,
        name="mla_attention_flash_dq",
    )(q_nope, q_rope, k_nope, k_rope, v, do, o, lse)

    sp = _mla_specs(
        per_cell, cells, dims, block_q, block_k,
        lambda j, i: _fetched_q_block(j, i, block_q, block_k, causal, None),
        lambda j, i: j)
    dk_nope, dk_rope, dv_ = pl.pallas_call(
        functools.partial(_mla_dkv_kernel, num_q=num_q, **static),
        grid=(b * cells, num_kv, num_q),
        in_specs=[sp["kn"], sp["kr"], sp["v"], sp["qn"], sp["qr"], sp["o"],
                  sp["row"], sp["row"]],
        out_specs=[sp["kn"], sp["dkr"], sp["v"]],
        out_shape=[jax.ShapeDtypeStruct(k_nope.shape, k_nope.dtype),
                   jax.ShapeDtypeStruct((b, cells, s, dr), jnp.float32),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=per_cell * [
            pltpu.VMEM((block_k, dn), jnp.float32),
            pltpu.VMEM((block_k, dv), jnp.float32), column, column]
        + [pltpu.VMEM((block_k, dr), jnp.float32)],
        compiler_params=params, interpret=interpret,
        name="mla_attention_flash_dkv",
    )(k_nope, k_rope, v, q_nope, q_rope, do, lse, delta)
    dk_rope = jnp.sum(dk_rope, axis=1).astype(k_rope.dtype)
    return dq_nope, dq_rope, dk_nope, dk_rope, dv_


def mla_attention_reference(q_nope, q_rope, k_nope, k_rope, v, heads,
                            causal=True):
    """The latent attention's oracle: the rotary key broadcast to the
    heads and concatenated to each head's positionless key, then
    :func:`_attention_reference` (float32 softmax over ``[b, n, s, s]``
    scores). Operands and result as :func:`mla_flash_attention`."""
    b, s, _ = q_nope.shape
    dn, dr, _ = _mla_dims(q_nope, q_rope, v, heads)
    scale = 1.0 / ((dn + dr) ** 0.5)

    def per_head(x):
        return x.reshape(b, s, heads, -1)

    q = jnp.concatenate([per_head(q_nope), per_head(q_rope)], axis=-1)
    k = jnp.concatenate(
        [per_head(k_nope),
         jnp.broadcast_to(k_rope[:, :, None, :], (b, s, heads, dr))],
        axis=-1)
    out = _attention_reference(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        per_head(v).transpose(0, 2, 1, 3), scale, causal)
    return _to_batch_major(out)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def mla_flash_attention(q_nope, q_rope, k_nope, k_rope, v, heads,
                        causal=True, block_q=DEFAULT_BLOCK_Q,
                        block_k=DEFAULT_BLOCK_K):
    """Flash attention for multi-head latent attention, batch-major:
    ``q_nope``, ``k_nope`` ``[batch, seq, heads * d_nope]``, ``q_rope``
    ``[batch, seq, heads * d_rope]`` (rotated), ``k_rope`` ``[batch, seq,
    d_rope]`` (rotated; one key a token, shared by the heads), ``v``
    ``[batch, seq, heads * d_v]`` -> ``[batch, seq, heads * d_v]``.
    Scores are ``(q_nope . k_nope + q_rope . k_rope) * (d_nope + d_rope)
    ** -0.5``. The kernels run where a block divides the sequence and a
    cell's heads fill whole 128-lane columns of every operand
    (:func:`_mla_heads_per_cell`); their oracle
    (:func:`mla_attention_reference`) elsewhere. Residuals as
    :func:`flash_attention_bsnd` keeps them (``FLASH_RESIDUAL_NAMES``).
    Counted as ``kernels/dispatch/flash_attention_mla_<path>`` beside
    ``flash_attention``'s own counter."""
    return _mla_fwd_rule(q_nope, q_rope, k_nope, k_rope, v, heads, causal,
                         block_q, block_k)[0]


def _mla_resolve(q_nope, q_rope, v, heads, block_q, block_k):
    dims = _mla_dims(q_nope, q_rope, v, heads)
    scale, bq, bk = _resolve_sizes(dims[0] + dims[1], q_nope.shape[1],
                                   None, block_q, block_k)
    if _mla_heads_per_cell(heads, dims) is None:
        bq = bk = None      # no cut of the heads into 128-lane columns
    return scale, bq, bk


def _mla_fwd_rule(q_nope, q_rope, k_nope, k_rope, v, heads, causal,
                  block_q, block_k):
    scale, bq, bk = _mla_resolve(q_nope, q_rope, v, heads, block_q, block_k)
    path = _takes(bq, bk, q_nope.shape[1], causal,
                  entry="flash_attention_mla")
    operands = (q_nope, q_rope, k_nope, k_rope, v)
    if path != "oracle":
        out, lse = _mla_fwd_pallas(
            *operands, heads=heads, scale=scale, causal=causal,
            block_q=bq, block_k=bk, interpret=GATE.interpret)
        out = checkpoint_name(out, FLASH_RESIDUAL_NAMES[0])
        lse = checkpoint_name(lse, FLASH_RESIDUAL_NAMES[1])
        return out, operands + (out, lse)
    return (mla_attention_reference(*operands, heads, causal),
            operands + (None, None))


def _mla_bwd_rule(heads, causal, block_q, block_k, res, g):
    *operands, out, lse = res
    scale, bq, bk = _mla_resolve(operands[0], operands[1], operands[4],
                                 heads, block_q, block_k)
    if lse is not None:
        return _mla_bwd_pallas(
            *operands, out, lse, g, heads=heads, scale=scale,
            causal=causal, block_q=bq, block_k=bk,
            interpret=GATE.interpret)
    _, vjp = jax.vjp(
        lambda *xs: mla_attention_reference(*xs, heads, causal), *operands)
    return vjp(g)


mla_flash_attention.defvjp(_mla_fwd_rule, _mla_bwd_rule)


def head_summed_probs(q, k, selection, causal=True, scale=None,
                      block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K,
                      lse=None):
    """``sum_n softmax_{selected u}(q[n, t] . k[n, u] * scale)``, float32
    ``[batch, seq, seq]``, zero off the selection: what a sparse-attention
    indexer is trained towards (DeepSeek-V3.2-Exp, the target of its KL
    loss). ``q``, ``k`` ``[batch, heads, seq, head_dim]``, ``selection``
    as :func:`flash_attention` takes it, ``lse`` ``[batch, heads, seq]``
    the forward kernel's log-sum-exp where the caller holds it (without
    it the forward kernel runs once more for its statistics). No gradient
    flows through it. On the kernel path
    (``sparse_attention_head_probs``) the sum is built tile by tile,
    never ``[heads, seq, seq]`` at once."""
    q, k = jax.lax.stop_gradient((q, k))
    _check_selection(selection, q, None, None)
    scale, bq, bk = _resolve(q, scale, block_q, block_k)
    if lse is not None or _takes(bq, bk) != "oracle":
        if lse is None:
            # v plays no part in the statistics; k stands in for it
            lse = _sparse_fwd_pallas(q, k, k, selection, scale, causal, bq,
                                     bk)[1]
        return _head_probs_pallas(q, k, jax.lax.stop_gradient(lse),
                                  selection, scale, causal, bq, bk)
    p = jax.nn.softmax(_reference_scores(q, k, scale, causal,
                                         selection=selection), axis=-1)
    return jnp.sum(jnp.where(selection[:, None] != 0, p, 0.0), axis=1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def sparse_attention(q, k, v, selection, causal=True, scale=None,
                     block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K):
    """``flash_attention(..., selection=selection)`` and, from the same
    log-sum-exp, :func:`head_summed_probs`: ``(out, probs)``. The
    gradient is ``flash_attention``'s; ``probs`` carries none."""
    return _sparse_fwd_rule(q, k, v, selection, causal, scale, block_q,
                            block_k)[0]


def _sparse_fwd_rule(q, k, v, selection, causal, scale, block_q, block_k):
    out, res = _flash_fwd_rule(q, k, v, causal, scale, block_q, block_k,
                               None, None, selection)
    probs = head_summed_probs(q, k, selection, causal, scale, block_q,
                              block_k, lse=res[4])
    return (out, probs), res


def _sparse_bwd_rule(causal, scale, block_q, block_k, res, g):
    return _flash_bwd_rule(causal, scale, block_q, block_k, None, None, res,
                           g[0])[:3] + (None,)


sparse_attention.defvjp(_sparse_fwd_rule, _sparse_bwd_rule)


class FMHA:
    """Class-style entry point (parity: apex/contrib/fmha/fmha.py FMHAFun).
    The reference restricts to seq in {128,256,384,512}, d=64; the TPU
    kernel is general but the same restriction check is exposed."""

    supported_seq_lens = (128, 256, 384, 512)

    def __init__(self, causal=False):
        self.causal = causal

    def __call__(self, qkv, cu_seqlens=None, seqlen=None):
        # qkv: [total, 3, heads, d] packed like the reference; here assume
        # dense [b, s, 3, n, d]
        q, k, v = (qkv[..., i, :, :] for i in range(3))
        q = q.transpose(0, 2, 1, 3)
        k = k.transpose(0, 2, 1, 3)
        v = v.transpose(0, 2, 1, 3)
        out = flash_attention(q, k, v, self.causal)
        return out.transpose(0, 2, 1, 3)
