"""Pipeline-stage view of the GPT model.

Under SPMD pipelining every pp rank runs the *same program* over its own
stage weights, so a "stage" bundles: the embedding (used when
``is_first_stage``), a slice of transformer layers, and the final
LN + LM head + loss (evaluated by the schedule's ``loss_func`` on the last
stage). This mirrors the reference's ``build_model`` with
pre_process/post_process flags (apex/transformer/pipeline_parallel/schedules/
common.py:30-151) re-expressed as masked SPMD branches.
"""


import flax.linen as nn
import jax.numpy as jnp

from apex_tpu.models.gpt import _fold_tp
from apex_tpu.models.transformer_lm import (
    ParallelTransformer,
    TransformerConfig,
    _make_norm,
)
from apex_tpu.transformer.parallel_state import (
    get_tensor_model_parallel_world_size,
)
from apex_tpu.transformer.tensor_parallel import (
    VocabParallelEmbedding,
    copy_to_tensor_model_parallel_region,
    gather_from_sequence_parallel_region,
    scatter_to_sequence_parallel_region,
    vocab_parallel_cross_entropy,
)
from apex_tpu.transformer.tensor_parallel.utils import divide


class GPTStage(nn.Module):
    config: TransformerConfig
    layers_per_stage: int

    def setup(self):
        cfg = self.config
        # every stage runs the same program and numbers its layers from 0
        cfg.check_uniform("a pipeline stage")
        self.word_embeddings = VocabParallelEmbedding(
            num_embeddings=cfg.vocab_size, embedding_dim=cfg.hidden_size,
            params_dtype=cfg.params_dtype, name="word_embeddings")
        if cfg.position_embedding_type == "learned":
            self.position_embeddings = self.param(
                "position_embeddings", nn.initializers.normal(0.02),
                (cfg.max_position_embeddings, cfg.hidden_size),
                cfg.params_dtype)
        self.transformer = ParallelTransformer(
            cfg, num_layers=self.layers_per_stage, name="transformer")
        self.final_layernorm = _make_norm(cfg, "final_layernorm")
        self.embedding_layernorm = (
            _make_norm(cfg, "embedding_layernorm")
            if cfg.embedding_layernorm else None)
        tp = get_tensor_model_parallel_world_size()
        self.lm_head = self.param(
            "lm_head",
            lambda key, shape, dtype: nn.initializers.normal(0.02)(
                _fold_tp(key), shape, dtype),
            (cfg.hidden_size, divide(cfg.vocab_size, tp)), cfg.params_dtype)
        self.lm_head_bias = (self.param(
            "lm_head_bias", nn.initializers.zeros,
            (divide(cfg.vocab_size, tp),), cfg.params_dtype)
            if cfg.lm_head_bias else None)

    def embed(self, tokens):
        cfg = self.config
        s = tokens.shape[-1]
        h = self.word_embeddings(tokens)
        if cfg.position_embedding_type == "learned":
            h = h + self.position_embeddings[:s][None, :, :]
        h = h.astype(cfg.compute_dtype)
        if cfg.embedding_multiplier is not None:
            h = h * jnp.asarray(cfg.embedding_multiplier, cfg.compute_dtype)
        if cfg.embedding_layernorm:  # BLOOM: LN right after embed
            h = self.embedding_layernorm(
                h.astype(jnp.float32)).astype(cfg.compute_dtype)
        h = h.transpose(1, 0, 2)  # [s, b, h]
        if cfg.sequence_parallel:
            h = scatter_to_sequence_parallel_region(h)
        return h

    def __call__(self, tokens, h_in, is_first):
        """Stage forward: embed on the first stage, then this stage's
        layers. ``h_in`` is the activation arriving from the previous
        stage (seq-sharded under SP)."""
        e = self.embed(tokens)
        h = jnp.where(is_first, e, h_in.astype(e.dtype))
        return self.transformer(h, None)

    def loss(self, h, labels, loss_mask=None):
        """Last-stage head: final LN -> LM head -> vocab-parallel CE."""
        cfg = self.config
        h = self.final_layernorm(h.astype(jnp.float32))
        if cfg.sequence_parallel:
            h = gather_from_sequence_parallel_region(h.astype(cfg.compute_dtype), True)
        h = copy_to_tensor_model_parallel_region(h.astype(cfg.compute_dtype))
        logits = jnp.einsum("sbh,hv->sbv", h,
                            self.lm_head.astype(cfg.compute_dtype),
                            preferred_element_type=jnp.float32)
        if self.lm_head_bias is not None:
            logits = logits + self.lm_head_bias.astype(logits.dtype)
        if cfg.logits_scaling != 1.0:  # Granite divisor — as in GPTModel
            logits = logits / jnp.asarray(cfg.logits_scaling,
                                          logits.dtype)
        if cfg.final_logit_softcapping is not None:
            # same cap as GPTModel's head — a pipelined softcap model
            # must not silently train on uncapped logits
            cap = jnp.float32(cfg.final_logit_softcapping)
            logits = (cap * jnp.tanh(logits.astype(jnp.float32) / cap)
                      ).astype(logits.dtype)
        logits = logits.transpose(1, 0, 2)  # [b, s, vocab/tp]
        losses = vocab_parallel_cross_entropy(logits, labels)
        if loss_mask is not None:
            return jnp.sum(losses * loss_mask) / jnp.maximum(
                jnp.sum(loss_mask), 1.0)
        return jnp.mean(losses)

    def full(self, tokens, h_in, is_first, labels):
        """Init-path helper touching every parameter."""
        h = self(tokens, h_in, is_first)
        return self.loss(h, labels)
