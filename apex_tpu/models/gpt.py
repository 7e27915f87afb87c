"""GPT language model on the parallel transformer stack.

Parity: reference apex/transformer/testing/standalone_gpt.py (111 LoC) +
standalone_transformer_lm.py GPTModel: vocab-parallel embedding + learned
positions -> causal ParallelTransformer -> output logits through the tied
embedding (parallel_lm_logits) -> vocab_parallel_cross_entropy.
"""

from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from apex_tpu.models.transformer_lm import (
    ParallelTransformer,
    TransformerConfig,
    _make_norm,
)
from apex_tpu.telemetry.registry import get_registry
from apex_tpu.transformer.parallel_state import (
    get_tensor_model_parallel_world_size,
)
from apex_tpu.transformer.tensor_parallel import (
    VocabParallelEmbedding,
    copy_to_tensor_model_parallel_region,
    vocab_parallel_cross_entropy,
)
from apex_tpu.transformer.tensor_parallel.utils import divide


class GPTModel(nn.Module):
    """Causal LM. Input token ids [b, s] -> vocab-parallel logits
    [b, s, vocab/tp] (pre-loss; use ``gpt_loss_fn``).

    With ``config.diffusion_block_length`` (training by diffusion over
    blocks) a row is ``[x0 ; xt]``, ``L`` clean tokens and their ``L``
    noised copies: both copies of a token take its position (``position_ids``
    default to ``0..L-1`` twice), and the final norm and the head run on the
    noisy half alone: logits ``[b, L, vocab/tp]``, for
    ``block_diffusion_loss_fn``."""

    config: TransformerConfig
    num_layers: Optional[int] = None
    pre_process: bool = True   # embed on entry (first pipeline stage)
    post_process: bool = True  # logits+loss on exit (last pipeline stage)
    # KV-cache incremental decoding (apply with mutable=["cache"]). With
    # learned positions, pass explicit position_ids on decode steps (the
    # embed's arange default only suits the prefill chunk); rope offsets
    # come from the cache index automatically.
    decode: bool = False

    @nn.compact
    def __call__(self, tokens, position_ids=None, attention_mask=None,
                 hidden_input=None):
        cfg = self.config
        tp = get_tensor_model_parallel_world_size()

        if cfg.diffusion_block_length is not None and position_ids is None:
            half = tokens.shape[-1] // 2
            position_ids = jnp.tile(jnp.arange(half), 2)[None, :]
        if self.pre_process:
            with jax.named_scope("embedding"):
                emb = VocabParallelEmbedding(
                    num_embeddings=cfg.vocab_size,
                    embedding_dim=cfg.hidden_size,
                    params_dtype=cfg.params_dtype, name="word_embeddings")
                h = emb(tokens)
                if cfg.position_embedding_type == "learned":
                    if position_ids is None:
                        position_ids = jnp.arange(tokens.shape[-1])[None, :]
                    pos = self.param(
                        "position_embeddings", nn.initializers.normal(0.02),
                        (cfg.max_position_embeddings, cfg.hidden_size),
                        cfg.params_dtype)
                    h = h + pos[position_ids]
                h = h.astype(cfg.compute_dtype)
                if cfg.embedding_multiplier is not None:
                    h = h * jnp.asarray(cfg.embedding_multiplier,
                                        cfg.compute_dtype)
                if cfg.embedding_layernorm:  # BLOOM: LN right after embed
                    h = _make_norm(cfg, "embedding_layernorm")(
                        h.astype(jnp.float32)).astype(cfg.compute_dtype)
                # [b, s, h] -> [s, b, h] (Megatron layout: seq-major for SP)
                h = h.transpose(1, 0, 2)
        else:
            h = hidden_input

        # rope consumes positions inside attention (seq-major [s, b]);
        # packed-sequence callers pass per-document position_ids [b, s]
        # (multi-component positions [c, b, s] go in as [c, s, b])
        rope_positions = (jnp.swapaxes(position_ids, -1, -2)
                          if (cfg.position_embedding_type == "rope"
                              and position_ids is not None) else None)
        h = ParallelTransformer(cfg, num_layers=self.num_layers,
                                decode=self.decode,
                                name="transformer")(h, attention_mask,
                                                    rope_positions)

        if not self.post_process:
            return h

        if cfg.diffusion_block_length is not None:
            # the clean half was context: it reaches neither the final
            # norm, the head nor the loss
            with jax.named_scope("diffusion/select_noisy"):
                h = h[h.shape[0] // 2:]
            reg = get_registry()
            reg.gauge("diffusion/block_length").set(
                cfg.diffusion_block_length)
            reg.gauge("diffusion/head_rows").set(h.shape[0] * h.shape[1])
        h = _make_norm(cfg, "final_layernorm")(h.astype(jnp.float32))
        with jax.named_scope("head" if cfg.diffusion_block_length is None
                             else "diffusion/head"):
            h = copy_to_tensor_model_parallel_region(
                h.astype(cfg.compute_dtype))
            if cfg.tie_word_embeddings:
                # Tied head (reference parallel_lm_logits): logits through the
                # embedding table. Requires embed and head on the same program
                # (pre_process and post_process both true — pipeline stages
                # must use the untied head instead).
                if not self.pre_process:
                    raise ValueError(
                        "tie_word_embeddings needs the embedding on this "
                        "stage; pipeline-split models must untie")
                logits = emb.attend(h)  # [s, b, vocab/tp]
            else:
                vocab_per_rank = divide(cfg.vocab_size, tp)
                head = self.param(
                    "lm_head",
                    lambda key, shape, dtype: nn.initializers.normal(0.02)(
                        _fold_tp(key), shape, dtype),
                    (cfg.hidden_size, vocab_per_rank), cfg.params_dtype)
                logits = jnp.einsum("sbh,hv->sbv", h,
                                    head.astype(cfg.compute_dtype),
                                    preferred_element_type=jnp.float32)
                if cfg.lm_head_bias:
                    logits = logits + self.param(
                        "lm_head_bias", nn.initializers.zeros,
                        (vocab_per_rank,), cfg.params_dtype).astype(
                            logits.dtype)
            if cfg.logits_scaling != 1.0:
                # Granite: logits are DIVIDED by the scaling (elementwise,
                # shard-safe)
                logits = logits / jnp.asarray(cfg.logits_scaling,
                                              logits.dtype)
            if cfg.final_logit_softcapping is not None:
                # Gemma-2: logits -> cap * tanh(logits / cap), fp32 (HF
                # modeling_gemma2 Gemma2ForCausalLM.forward). Elementwise, so
                # valid on each vocab-parallel shard independently.
                cap = jnp.float32(cfg.final_logit_softcapping)
                logits = (cap * jnp.tanh(logits.astype(jnp.float32) / cap)
                          ).astype(logits.dtype)
            return logits.transpose(1, 0, 2)  # [b, s, vocab/tp]


def _fold_tp(key):
    try:
        rank = jax.lax.axis_index("tp")
    except Exception:
        rank = 0
    return jax.random.fold_in(key, rank)


def block_diffusion_loss_fn(vocab_parallel_logits, labels, weights):
    """The block-diffusion training loss: cross-entropy of the noisy
    half's logits ``[b, L, vocab/tp]`` against the data tokens ``labels``
    ``[b, L]`` at the masked positions, each weighted by its block's
    ``1 / t`` (``weights`` ``[b, L]``: ``m / t``, zero where the token was
    not masked), over all ``L`` data tokens of every sequence (not over
    the masked ones: the weights carry the schedule's normalisation). No
    shift: the logit at a masked position predicts that position's
    token."""
    # two scopes, not one name with a slash: a transformation wraps the
    # outermost name whole (``jvp(diffusion)/loss``)
    with jax.named_scope("diffusion"), jax.named_scope("loss"):
        losses = vocab_parallel_cross_entropy(vocab_parallel_logits, labels)
        return jnp.sum(losses * weights) / losses.size


@jax.named_scope("loss")
def gpt_loss_fn(vocab_parallel_logits, labels, loss_mask=None):
    """Mean per-token vocab-parallel CE loss (reference
    standalone_transformer_lm.py post_language_model_processing)."""
    losses = vocab_parallel_cross_entropy(vocab_parallel_logits, labels)
    if loss_mask is not None:
        return jnp.sum(losses * loss_mask) / jnp.maximum(
            jnp.sum(loss_mask), 1.0)
    return jnp.mean(losses)
