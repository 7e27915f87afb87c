"""Multi-head Latent Attention (DeepSeek-V2) language model.

The KV-cache-compression attention innovation for the zoo: K/V are
projected through a small shared LATENT (``kv_lora_rank`` wide, plus a
decoupled rope sub-vector shared across heads) and re-expanded per head,
shrinking the cache by an order of magnitude — directly relevant on TPU
where HBM capacity bounds batch at decode. Queries optionally compress
through their own latent (``q_lora_rank``; deepseek-v2-lite skips it).

Layout (DeepSeek-V2 conventions, validated against HF by the converter
oracle): per head, queries/keys carry ``qk_nope_head_dim`` positionless
channels plus ``qk_rope_head_dim`` rotary channels (the key's rope
sub-vector comes from the latent projection and is SHARED by all heads);
values carry ``v_head_dim``. Scores scale by (nope+rope)**-0.5. The
rotary uses the interleaved-pair convention (HF's internal de-interleave
permute cancels in the q·k contraction). RMSNorm everywhere, SwiGLU MLP,
untied head.

TP design: the latent projections (q_a, kv_a) are small and REPLICATED;
the per-head expansions (q_b, kv_b) are column-parallel over heads and
the output projection is row-parallel — so the latent rides every rank
while heads shard, the same geometry the cache savings want.
"""

import dataclasses
import functools
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from apex_tpu.models.transformer_lm import _rope_core, latent_attention
from apex_tpu.normalization import FusedRMSNorm
from apex_tpu.transformer.parallel_state import (
    get_tensor_model_parallel_world_size,
)
from apex_tpu.transformer.tensor_parallel import (
    ColumnParallelLinear,
    RowParallelLinear,
    VocabParallelEmbedding,
    copy_to_tensor_model_parallel_region,
)
from apex_tpu.transformer.tensor_parallel.utils import divide


@dataclasses.dataclass(frozen=True)
class MLAConfig:
    vocab_size: int = 102400
    hidden_size: int = 2048
    num_layers: int = 12
    num_heads: int = 16
    q_lora_rank: Optional[int] = None   # None -> direct q projection
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    ffn_hidden_size: int = 8192
    rms_eps: float = 1e-6
    rotary_base: float = 10000.0
    max_decode_length: int = 512   # latent-cache window for decoding
    # DeepSeek MoE layers (None -> dense everywhere). Layers >=
    # first_k_dense_replace route top-k over n_routed_experts small
    # experts (greedy gate, raw softmax mass unless norm_topk_prob,
    # output scaled by routed_scaling_factor) PLUS an always-on shared
    # expert of n_shared_experts * moe_intermediate_size width.
    n_routed_experts: Optional[int] = None
    moe_intermediate_size: Optional[int] = None
    n_shared_experts: Optional[int] = None
    moe_top_k: int = 2
    routed_scaling_factor: float = 1.0
    norm_topk_prob: bool = False
    first_k_dense_replace: int = 0
    # None -> dropless (E/k, the HF-parity semantics: every token reaches
    # its routed experts). Training users can cap it (e.g. 1.25) without
    # forking the block; dropped tokens then ride the residual.
    moe_capacity_factor: Optional[float] = None
    # auto -> ragged grouped-matmul when dropless on one ep rank,
    # scatter otherwise (see transformer/moe/layer.py SwitchMLP).
    moe_dispatch_mode: str = "auto"
    params_dtype: Any = jnp.float32
    compute_dtype: Any = jnp.bfloat16

    @property
    def qk_head_dim(self):
        return self.qk_nope_head_dim + self.qk_rope_head_dim


def _norm(cfg, name, width=None):
    return FusedRMSNorm(normalized_shape=width or cfg.hidden_size,
                        eps=cfg.rms_eps, param_dtype=jnp.float32,
                        name=name)


class MLAAttention(nn.Module):
    """Latent-compressed attention (module doc). ``mode`` (static):
    'train' — full attention; 'prefill'/'step' — the ABSORBED-projection
    latent-cache decode: the cache holds ONLY the per-token latent row
    [kv_lora_rank + qk_rope_head_dim] (normed latent | rotated shared
    k_pe), shared across heads, and ``kv_b``'s halves fold into the
    attention contractions

      scores_nope[i,j] = q_nope_i . (W_nope c_j) = (W_nope^T q_nope_i) . c_j
      ctx_i            = sum_j p_ij (W_v c_j)   = W_v (sum_j p_ij c_j)

    so per-layer cache bytes shrink from 2*heads*(nope+rope) to
    (kv_rank+rope) floats/token (8-28x on the published configs) and
    per-step FLOPs over the prefix stop scaling with heads."""

    config: MLAConfig

    @nn.compact
    def __call__(self, x, position_ids=None, mode="train"):
        cfg = self.config
        tp = get_tensor_model_parallel_world_size()
        n_local = divide(cfg.num_heads, tp)
        nope, rope = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
        vd, lat = cfg.v_head_dim, cfg.kv_lora_rank
        s, b, _ = x.shape
        x = x.astype(cfg.compute_dtype)

        # -- queries: optional latent compression, then per-head expand
        if cfg.q_lora_rank:
            qa = nn.Dense(cfg.q_lora_rank, use_bias=False,
                          dtype=cfg.compute_dtype,
                          param_dtype=cfg.params_dtype, name="q_a")(x)
            qa = _norm(cfg, "q_a_norm", cfg.q_lora_rank)(
                qa.astype(jnp.float32)).astype(cfg.compute_dtype)
            qa = copy_to_tensor_model_parallel_region(qa)
            q = ColumnParallelLinear(
                input_size=cfg.q_lora_rank,
                output_size=cfg.num_heads * cfg.qk_head_dim,
                gather_output=False, bias=False,
                params_dtype=cfg.params_dtype, name="q_b")(qa)
        else:
            q = ColumnParallelLinear(
                input_size=cfg.hidden_size,
                output_size=cfg.num_heads * cfg.qk_head_dim,
                gather_output=False, bias=False,
                params_dtype=cfg.params_dtype, name="q_b")(x)
        q = q.reshape(s, b, n_local, cfg.qk_head_dim)
        q_nope, q_pe = q[..., :nope], q[..., nope:]

        # -- the shared latent projection (keys/values live inside it)
        ckv = nn.Dense(lat + rope, use_bias=False,
                       dtype=cfg.compute_dtype,
                       param_dtype=cfg.params_dtype, name="kv_a")(x)

        if mode != "train":
            return self._decode_tail(cfg, x, ckv, q_nope, q_pe, n_local,
                                     nope, rope, vd, lat, s, b, mode)

        compressed, k_pe = ckv[..., :lat], ckv[..., lat:]
        compressed = _norm(cfg, "kv_a_norm", lat)(
            compressed.astype(jnp.float32)).astype(cfg.compute_dtype)
        compressed = copy_to_tensor_model_parallel_region(compressed)
        kv = ColumnParallelLinear(
            input_size=lat,
            output_size=cfg.num_heads * (nope + vd),
            gather_output=False, bias=False,
            params_dtype=cfg.params_dtype, name="kv_b")(compressed)
        kv = kv.reshape(s, b, n_local, nope + vd)
        k_nope, value = kv[..., :nope], kv[..., nope:]

        # rotary on the decoupled sub-vectors (interleaved convention; the
        # key's rotary part is one shared "head") and causal attention:
        # the package's one spelling of it, the flash kernels where they
        # run (models/transformer_lm.py latent_attention)
        ctx = latent_attention(
            q_nope.astype(cfg.compute_dtype), q_pe.astype(cfg.compute_dtype),
            k_nope.astype(cfg.compute_dtype), k_pe.astype(cfg.compute_dtype),
            value.astype(cfg.compute_dtype), rotary_base=cfg.rotary_base,
            position_ids=position_ids)
        ctx = ctx.reshape(s, b, n_local * vd).astype(cfg.compute_dtype)
        return RowParallelLinear(
            input_size=cfg.num_heads * vd, output_size=cfg.hidden_size,
            input_is_parallel=True, bias=False,
            params_dtype=cfg.params_dtype, name="o")(ctx)

    def _decode_tail(self, cfg, x, ckv, q_nope, q_pe, n_local, nope,
                     rope, vd, lat, s, b, mode):
            compressed = _norm(cfg, "kv_a_norm", lat)(
                ckv[..., :lat].astype(jnp.float32)).astype(cfg.compute_dtype)

            # the kv_b weight READ AS A TENSOR (same param path/shape the
            # train-mode ColumnParallelLinear creates), split into its
            # absorbed halves: [lat, n*(nope+vd)] -> W_nope, W_v
            w_full = _RawWeight((lat, n_local * (nope + vd)),
                                cfg.params_dtype, name="kv_b")()
            w_full = w_full.astype(cfg.compute_dtype).reshape(
                lat, n_local, nope + vd)
            w_nope, w_v = w_full[..., :nope], w_full[..., nope:]

            pos_ctr = self.variable("cache", "pos",
                                    lambda: jnp.zeros((), jnp.int32))
            pos = jnp.zeros((), jnp.int32) if mode == "prefill" \
                else pos_ctr.value
            pos_ctr.value = pos + s
            positions = pos + jnp.arange(s)

            q_pe = _rope_core(q_pe, cfg.rotary_base, positions, rope,
                              interleaved=True)
            k_pe = _rope_core(ckv[..., None, lat:], cfg.rotary_base,
                              positions, rope, interleaved=True)[:, :, 0]

            # latent cache rows: [max_len, b, lat + rope]
            max_len = cfg.max_decode_length
            row = jnp.concatenate([compressed, k_pe], axis=-1)
            cache = self.variable("cache", "latent", jnp.zeros,
                                  (max_len, b, lat + rope), cfg.compute_dtype)
            cache.value = jax.lax.dynamic_update_slice(
                cache.value, row.astype(cfg.compute_dtype), (pos, 0, 0))
            c_lat = cache.value[..., :lat]      # [t, b, lat]
            c_pe = cache.value[..., lat:]       # [t, b, rope]

            # absorb: queries into latent space (per step, per head)
            q_lat = jnp.einsum("sbnd,lnd->sbnl", q_nope.astype(
                cfg.compute_dtype), w_nope,
                preferred_element_type=jnp.float32).astype(cfg.compute_dtype)
            scale = float(cfg.qk_head_dim ** -0.5)
            from apex_tpu.contrib import mla_decode as _mla_decode

            if (mode == "step" and s == 1
                    and _mla_decode.use_flash(
                        max_len, cache_shape=cache.value.shape)):
                # Single-token hot loop: the streaming Pallas kernel —
                # cache read once for all heads, no [b, n, 1, T] score
                # round-trip through HBM, dead prefix tiles never
                # fetched (contrib/mla_decode.py). Gated on use_flash so
                # every non-kernel configuration runs the einsum path
                # below, not the kernel module's fp32 fallback.
                q_full = jnp.concatenate(
                    [q_lat[0], q_pe[0].astype(cfg.compute_dtype)], -1)
                ctx_lat = _mla_decode.mla_flash_decode(
                    q_full, cache.value, pos + 1, lat, scale)[None].astype(
                    cfg.compute_dtype)
            else:
                scores = (jnp.einsum("sbnl,tbl->bnst", q_lat, c_lat,
                                     preferred_element_type=jnp.float32)
                          + jnp.einsum("sbnd,tbd->bnst",
                                       q_pe.astype(cfg.compute_dtype), c_pe,
                                       preferred_element_type=jnp.float32)
                          ) * scale
                jpos = jnp.arange(max_len)[None, :]
                ipos = pos + jnp.arange(s)[:, None]
                scores = jnp.where(jpos > ipos, -1e9, scores)
                probs = jax.nn.softmax(scores, axis=-1)
                # weighted latent out, THEN expand through W_v (absorbed)
                ctx_lat = jnp.einsum("bnst,tbl->sbnl",
                                     probs.astype(cfg.compute_dtype), c_lat,
                                     preferred_element_type=jnp.float32
                                     ).astype(cfg.compute_dtype)
            ctx = jnp.einsum("sbnl,lnd->sbnd", ctx_lat, w_v,
                             preferred_element_type=jnp.float32)
            ctx = ctx.reshape(s, b, n_local * vd).astype(cfg.compute_dtype)
            return RowParallelLinear(
                input_size=cfg.num_heads * vd, output_size=cfg.hidden_size,
                input_is_parallel=True, bias=False,
                params_dtype=cfg.params_dtype, name="o")(ctx)


class _SwiGLU(nn.Module):
    config: MLAConfig
    ffn: Optional[int] = None  # None -> config.ffn_hidden_size

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        ffn = self.ffn or cfg.ffn_hidden_size
        x = x.astype(cfg.compute_dtype)
        gate_up = ColumnParallelLinear(
            input_size=cfg.hidden_size, output_size=2 * ffn,
            gather_output=False, bias=False,
            params_dtype=cfg.params_dtype, name="gate_up")(x)
        gate, up = jnp.split(gate_up.astype(jnp.float32), 2, axis=-1)
        h = (jax.nn.silu(gate) * up).astype(cfg.compute_dtype)
        return RowParallelLinear(
            input_size=ffn, output_size=cfg.hidden_size,
            input_is_parallel=True, bias=False,
            params_dtype=cfg.params_dtype, name="down")(h)


class DeepseekBlock(nn.Module):
    config: MLAConfig
    layer_idx: int = 0

    def _is_moe(self):
        cfg = self.config
        return (cfg.n_routed_experts is not None
                and self.layer_idx >= cfg.first_k_dense_replace)

    @nn.compact
    def __call__(self, h, position_ids=None, mode="train"):
        cfg = self.config
        x = _norm(cfg, "input_norm")(h.astype(jnp.float32)).astype(
            cfg.compute_dtype)
        h = h + MLAAttention(cfg, name="self_attn")(
            x, position_ids, mode=mode).astype(h.dtype)
        x = _norm(cfg, "post_attn_norm")(h.astype(jnp.float32)).astype(
            cfg.compute_dtype)
        if not self._is_moe():
            return h + _SwiGLU(cfg, name="mlp")(x).astype(h.dtype)
        from apex_tpu.transformer.moe import SwitchMLP

        E, k = cfg.n_routed_experts, cfg.moe_top_k
        routed = SwitchMLP(
            hidden_size=cfg.hidden_size,
            ffn_hidden_size=cfg.moe_intermediate_size,
            num_experts=E, top_k=k,
            # default: dropless (E/k), the HF-parity semantics
            capacity_factor=(cfg.moe_capacity_factor
                             if cfg.moe_capacity_factor is not None
                             else float(E) / k),
            dispatch_mode=cfg.moe_dispatch_mode,
            router_type="top_k", activation="swiglu",
            normalize_topk=cfg.norm_topk_prob,
            params_dtype=cfg.params_dtype,
            compute_dtype=cfg.compute_dtype,
            warn_on_dropped_losses=False, name="mlp")(x)
        # scaling the combined routed output == scaling every gate
        out = routed * jnp.asarray(cfg.routed_scaling_factor, routed.dtype)
        if cfg.n_shared_experts:
            out = out + _SwiGLU(
                cfg, ffn=cfg.n_shared_experts * cfg.moe_intermediate_size,
                name="shared_mlp")(x)
        return h + out.astype(h.dtype)


class DeepseekModel(nn.Module):
    """DeepSeek-V2-style causal LM on MLA. Token ids [b, s] ->
    [b, s, vocab/tp] logits. Configs with ``n_routed_experts`` run
    greedy-gate MoE layers (fine-grained experts on SwitchMLP + shared
    expert) from ``first_k_dense_replace`` onward. Dropless serving
    (the default) uses the ragged grouped-matmul dispatch — linear in
    tokens, zero capacity padding; ``moe_capacity_factor`` caps it for
    training (scatter dispatch, dropped tokens ride the residual)."""

    config: MLAConfig

    @nn.compact
    def __call__(self, tokens, position_ids=None, mode="train"):
        cfg = self.config
        h = VocabParallelEmbedding(
            num_embeddings=cfg.vocab_size, embedding_dim=cfg.hidden_size,
            params_dtype=cfg.params_dtype, name="embed_tokens")(tokens)
        h = h.astype(cfg.compute_dtype).transpose(1, 0, 2)  # [s, b, h]
        pos = (position_ids.transpose(1, 0)
               if position_ids is not None else None)
        for i in range(cfg.num_layers):
            h = DeepseekBlock(cfg, layer_idx=i, name=f"layer_{i}")(
                h, pos, mode=mode)
        h = _norm(cfg, "final_norm")(h.astype(jnp.float32))
        h = copy_to_tensor_model_parallel_region(
            h.astype(cfg.compute_dtype))
        tp = get_tensor_model_parallel_world_size()
        head = self.param("lm_head", nn.initializers.normal(0.02),
                          (cfg.hidden_size, divide(cfg.vocab_size, tp)),
                          cfg.params_dtype)
        logits = jnp.einsum("sbh,hv->sbv", h,
                            head.astype(cfg.compute_dtype),
                            preferred_element_type=jnp.float32)
        return logits.transpose(1, 0, 2)

    def decode_prefill(self, tokens):
        """Latent-cache decode, phase 1 (apply with mutable=["cache"])."""
        return self(tokens, mode="prefill")

    def decode_step(self, tokens):
        """Latent-cache decode, phase 2 (single-token extension)."""
        return self(tokens, mode="step")


class _RawWeight(nn.Module):
    """Parameter-only scope: creates/looks up ``<name>/weight`` with the
    same shape the train-mode parallel linear uses, so decode and train
    modes share one param tree."""

    shape: tuple
    dtype: Any

    @nn.compact
    def __call__(self):
        return self.param("weight", nn.initializers.normal(0.02),
                          self.shape, self.dtype)


def mla_greedy_generate(model, params, prompt_tokens, max_new_tokens):
    """Greedy decode (full re-run per token — oracle path)."""
    from apex_tpu.transformer.tensor_parallel import (
        gather_from_tensor_model_parallel_region,
    )

    toks = jnp.asarray(prompt_tokens, jnp.int32)
    for _ in range(max_new_tokens):
        logits = model.apply({"params": params}, toks)
        full = gather_from_tensor_model_parallel_region(logits[:, -1, :])
        nxt = jnp.argmax(full, -1).astype(jnp.int32)
        toks = jnp.concatenate([toks, nxt[:, None]], axis=1)
    return toks


@functools.lru_cache(maxsize=16)
def _mla_compiled_decode(model, max_new_tokens):
    from apex_tpu.transformer.tensor_parallel import (
        gather_from_tensor_model_parallel_region,
    )

    @jax.jit
    def prefill(params, prompt):
        logits, mut = model.apply(
            {"params": params}, prompt, mutable=["cache"],
            method=DeepseekModel.decode_prefill)
        full = gather_from_tensor_model_parallel_region(logits[:, -1, :])
        return mut["cache"], jnp.argmax(full, -1).astype(jnp.int32)

    @jax.jit
    def decode_all(params, cache, first):
        def step(carry, _):
            cache, tok = carry
            logits, mut = model.apply(
                {"params": params, "cache": cache}, tok[:, None],
                mutable=["cache"], method=DeepseekModel.decode_step)
            full = gather_from_tensor_model_parallel_region(
                logits[:, -1, :])
            nxt = jnp.argmax(full, -1).astype(jnp.int32)
            return (mut["cache"], nxt), nxt
        (_, _), toks = jax.lax.scan(step, (cache, first), None,
                                    length=max_new_tokens - 1)
        return toks

    return prefill, decode_all


def mla_cached_generate(model, params, prompt_tokens, max_new_tokens):
    """Greedy decode on the LATENT cache (absorbed projections): the
    cache stores kv_lora_rank + qk_rope_head_dim floats per token per
    layer — shared across heads — instead of the 2*heads*(nope+rope)
    a conventional KV cache would. Token-exact vs
    :func:`mla_greedy_generate`, its oracle."""
    cfg = model.config
    plen = prompt_tokens.shape[1]
    if plen + max_new_tokens > cfg.max_decode_length:
        raise ValueError(
            f"prompt + max_new_tokens ({plen + max_new_tokens}) exceeds "
            f"max_decode_length ({cfg.max_decode_length})")
    toks = jnp.asarray(prompt_tokens, jnp.int32)
    if max_new_tokens == 0:
        return toks
    prefill, decode_all = _mla_compiled_decode(model, max_new_tokens)
    cache, first = prefill(params, toks)
    if max_new_tokens == 1:
        return jnp.concatenate([toks, first[:, None]], axis=1)
    rest = decode_all(params, cache, first)
    return jnp.concatenate([toks, first[:, None], rest.T], axis=1)
