"""BERT model on the parallel transformer stack.

Parity: reference apex/transformer/testing/standalone_bert.py (255 LoC):
bidirectional (padding-mask) transformer with token-type embeddings, MLM
head (dense + gelu + LN + tied-vocab projection) and binary NSP head.
"""


import flax.linen as nn
import jax
import jax.numpy as jnp

from apex_tpu.models.gpt import _fold_tp
from apex_tpu.models.transformer_lm import (
    ParallelTransformer,
    TransformerConfig,
)
from apex_tpu.normalization import FusedLayerNorm
from apex_tpu.transformer.enums import AttnMaskType
from apex_tpu.transformer.parallel_state import (
    get_tensor_model_parallel_world_size,
)
from apex_tpu.transformer.tensor_parallel import (
    VocabParallelEmbedding,
    copy_to_tensor_model_parallel_region,
    vocab_parallel_cross_entropy,
)
from apex_tpu.transformer.tensor_parallel.utils import divide


class BertModel(nn.Module):
    """Returns (mlm_logits [b, s, vocab/tp], nsp_logits [b, 2])."""

    config: TransformerConfig
    num_tokentypes: int = 2
    add_binary_head: bool = True

    @nn.compact
    def __call__(self, tokens, padding_mask=None, tokentype_ids=None,
                 position_ids=None):
        cfg = self.config
        assert cfg.attn_mask_type == AttnMaskType.padding, (
            "BERT is bidirectional: config.attn_mask_type must be "
            "AttnMaskType.padding (got causal; the transformer stack would "
            "silently apply a causal mask)")
        with jax.named_scope("embedding"):
            emb = VocabParallelEmbedding(
                num_embeddings=cfg.vocab_size, embedding_dim=cfg.hidden_size,
                params_dtype=cfg.params_dtype, name="word_embeddings")
            h = emb(tokens)
            if position_ids is None:
                position_ids = jnp.arange(tokens.shape[-1])[None, :]
            pos = self.param(
                "position_embeddings", nn.initializers.normal(0.02),
                (cfg.max_position_embeddings, cfg.hidden_size),
                cfg.params_dtype)
            h = h + pos[position_ids]
            if tokentype_ids is not None:
                tt = self.param("tokentype_embeddings",
                                nn.initializers.normal(0.02),
                                (self.num_tokentypes, cfg.hidden_size),
                                cfg.params_dtype)
                h = h + tt[tokentype_ids]
            h = h.astype(cfg.compute_dtype).transpose(1, 0, 2)  # [s, b, h]

        # padding mask: [b, s] 1=keep -> attention mask [b, 1, s, s]
        attention_mask = None
        if padding_mask is not None:
            with jax.named_scope("attention_mask"):
                keep = padding_mask.astype(bool)
                attention_mask = ~(keep[:, None, None, :]
                                   & keep[:, None, :, None])

        h = ParallelTransformer(cfg, name="transformer")(h, attention_mask)
        h = FusedLayerNorm(normalized_shape=cfg.hidden_size,
                           eps=cfg.layernorm_epsilon, param_dtype=jnp.float32,
                           name="final_layernorm")(h.astype(jnp.float32))

        with jax.named_scope("head"):
            # MLM head (reference BertLMHead): dense+gelu+LN then vocab proj
            x = nn.Dense(cfg.hidden_size, param_dtype=cfg.params_dtype,
                         name="lm_dense")(h.astype(cfg.compute_dtype))
            x = jnp.asarray(nn.gelu(x.astype(jnp.float32)), cfg.compute_dtype)
            x = FusedLayerNorm(
                normalized_shape=cfg.hidden_size, eps=cfg.layernorm_epsilon,
                param_dtype=jnp.float32,
                name="lm_layernorm")(x.astype(jnp.float32))
            tp = get_tensor_model_parallel_world_size()
            vocab_per_rank = divide(cfg.vocab_size, tp)
            head = self.param(
                "lm_head",
                lambda key, shape, dtype: nn.initializers.normal(0.02)(
                    _fold_tp(key), shape, dtype),
                (cfg.hidden_size, vocab_per_rank), cfg.params_dtype)
            x = copy_to_tensor_model_parallel_region(
                x.astype(cfg.compute_dtype))
            mlm_logits = jnp.einsum("sbh,hv->sbv", x,
                                    head.astype(cfg.compute_dtype),
                                    preferred_element_type=jnp.float32)
            mlm_logits = mlm_logits.transpose(1, 0, 2)

            nsp_logits = None
            if self.add_binary_head:
                # pooled [CLS] (first token) -> tanh dense -> binary head
                pooled = nn.Dense(
                    cfg.hidden_size, param_dtype=cfg.params_dtype,
                    name="pooler")(h[0].astype(cfg.compute_dtype))
                pooled = jnp.tanh(pooled.astype(jnp.float32))
                nsp_logits = nn.Dense(2, param_dtype=cfg.params_dtype,
                                      name="binary_head")(
                    pooled.astype(cfg.compute_dtype)).astype(jnp.float32)
        return mlm_logits, nsp_logits


@jax.named_scope("loss")
def bert_loss_fn(mlm_logits, nsp_logits, labels, loss_mask,
                 nsp_labels=None):
    """MLM CE (vocab-parallel) + optional NSP CE
    (reference standalone_bert loss)."""
    mlm_losses = vocab_parallel_cross_entropy(mlm_logits, labels)
    lm_loss = jnp.sum(mlm_losses * loss_mask) / jnp.maximum(
        jnp.sum(loss_mask), 1.0)
    if nsp_logits is not None and nsp_labels is not None:
        nsp_logp = nsp_logits - jnp.log(
            jnp.sum(jnp.exp(nsp_logits), axis=-1, keepdims=True))
        nsp_loss = -jnp.mean(
            jnp.take_along_axis(nsp_logp, nsp_labels[:, None], axis=-1))
        return lm_loss + nsp_loss
    return lm_loss
