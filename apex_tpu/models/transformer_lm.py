"""Megatron-style parallel transformer blocks (TPU-native).

Parity: reference apex/transformer/testing/standalone_transformer_lm.py —
``ParallelMLP`` (h -> 4h column-parallel -> gelu -> 4h -> h row-parallel),
``ParallelAttention`` (column-parallel QKV, core attention with
FusedScaleMaskSoftmax, row-parallel output projection),
``ParallelTransformerLayer`` (pre-LN residual blocks). Re-designed for TPU:
bf16 matmuls on the MXU with fp32 layernorm/softmax, sequence-parallel
collectives on the seq dim, flash attention (Pallas) for the core when
enabled.
"""

import dataclasses
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from apex_tpu.normalization import FusedLayerNorm
from apex_tpu.transformer.enums import AttnMaskType
from apex_tpu.transformer.parallel_state import (
    get_tensor_model_parallel_world_size,
)
from apex_tpu.transformer.tensor_parallel import (
    ColumnParallelLinear,
    RowParallelLinear,
)


@dataclasses.dataclass(frozen=True)
class RopeScaling:
    """Frequency-rescaled RoPE (HF modeling_rope_utils semantics).

    ``rope_type="linear"`` divides every inverse frequency by ``factor``
    (position interpolation). ``rope_type="llama3"`` (Llama-3.1) keeps
    wavelengths shorter than ``original_max/high_freq_factor``, divides
    those longer than ``original_max/low_freq_factor`` by ``factor``,
    and smoothly interpolates in between
    (_compute_llama3_parameters). All-scalar and frozen, so
    TransformerConfig remains hashable for static jit arguments."""

    rope_type: str = "llama3"
    factor: float = 8.0
    low_freq_factor: float = 1.0
    high_freq_factor: float = 4.0
    original_max_position_embeddings: int = 8192


def _scale_rope_freqs(inv, scaling: RopeScaling):
    import math

    if scaling.rope_type == "linear":
        return inv / scaling.factor
    if scaling.rope_type != "llama3":
        raise ValueError(f"unknown rope_type {scaling.rope_type!r}")
    old_len = scaling.original_max_position_embeddings
    low_wavelen = old_len / scaling.low_freq_factor
    high_wavelen = old_len / scaling.high_freq_factor
    wavelen = 2 * math.pi / inv
    scaled = jnp.where(wavelen > low_wavelen, inv / scaling.factor, inv)
    smooth = ((old_len / wavelen - scaling.low_freq_factor)
              / (scaling.high_freq_factor - scaling.low_freq_factor))
    smoothed = ((1 - smooth) * scaled / scaling.factor + smooth * scaled)
    medium = (wavelen >= high_wavelen) & (wavelen <= low_wavelen)
    return jnp.where(medium, smoothed, scaled)


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    hidden_size: int = 1024
    num_layers: int = 24
    num_attention_heads: int = 16
    ffn_hidden_size: Optional[int] = None
    vocab_size: int = 50257
    max_position_embeddings: int = 1024
    attention_dropout: float = 0.0
    hidden_dropout: float = 0.0
    layernorm_epsilon: float = 1e-5
    params_dtype: Any = jnp.float32
    compute_dtype: Any = jnp.bfloat16
    sequence_parallel: bool = False
    use_flash_attention: bool = True
    attn_mask_type: AttnMaskType = AttnMaskType.causal
    # Context parallelism: run the WHOLE model on sequence shards over
    # the 'cp' mesh axis (attention communicates; everything else is
    # per-token). Callers shard tokens/labels over cp and pass global
    # position_ids; see transformer/context_parallel. Algorithms:
    # "ring" (K/V ppermute around the ring — any head count) or
    # "ulysses" (two all_to_alls, full attention on heads/cp heads —
    # needs heads divisible by cp; cheaper when heads >= cp).
    context_parallel: bool = False
    context_parallel_algo: str = "ring"
    # Compile the layer stack as ONE lax.scan over stacked params instead
    # of unrolling n layers (compile time O(1) in depth — the unrolled
    # 24-layer GPT costs minutes of XLA time per bench variant). Params
    # get a leading [num_layers] axis under 'layers'; requires a uniform
    # stack (with MoE: moe_layer_freq == 1).
    scan_layers: bool = False
    # Per-layer activation recompute (reference tensor_parallel/random.py
    # checkpoint). ON by default for the reference's memory profile; turn
    # OFF when the model fits HBM without it — backward then reuses the
    # forward's activations instead of re-running every layer (~25-30%
    # fewer executed FLOPs per train step, the single biggest single-chip
    # MFU lever at GPT-2-345M scale). Kept across the recomputation: the
    # layer's input and, where the flash kernel ran, its output and
    # log-sum-exp (b*s*h*2 + b*n*s*4 bytes a layer, about one more layer
    # input; twice that on a TPU at head dimension 64, see
    # ParallelTransformer), so the backward re-runs everything of a layer
    # but the attention kernel. Nothing else is kept.
    activation_checkpointing: bool = True
    # Mixture-of-experts (no reference equivalent; SURVEY.md §2.3 note).
    # None -> dense ParallelMLP everywhere. Every ``moe_layer_freq``-th
    # layer (starting at layer 0) becomes a SwitchMLP with this many
    # global experts, sharded over the 'ep' mesh axis.
    num_moe_experts: Optional[int] = None
    moe_top_k: int = 1
    moe_capacity_factor: float = 1.25
    moe_layer_freq: int = 1
    # The first ``moe_first_dense_layers`` layers keep the dense MLP
    # (DeepSeek's ``first_k_dense_replace``); ``moe_layer_freq`` counts
    # from the first layer after them.
    moe_first_dense_layers: int = 0
    # An expert's width where it is not the dense MLP's (DeepSeek's
    # ``moe_intermediate_size``); None -> ``ffn_size``.
    moe_ffn_hidden_size: Optional[int] = None
    moe_jitter_eps: float = 0.0
    moe_router_type: str = "top_k"  # or "expert_choice"
    moe_aux_loss_coeff: float = 1e-2
    moe_z_loss_coeff: float = 0.0
    # auto -> ragged grouped matmuls when dropless on one ep rank (the
    # converted-Mixtral serving shape), scatter otherwise; "einsum" keeps
    # the dense [T,E,C] one-hot formulation (see moe/layer.py SwitchMLP).
    moe_dispatch_mode: str = "auto"
    # renormalize the selected top-k gates to sum to 1 (Mixtral); False
    # keeps raw softmax mass (Qwen2-MoE norm_topk_prob=false)
    moe_normalize_topk: bool = True
    # Always-on shared expert beside the routed set (Qwen2-MoE block:
    # out = routed + sigmoid(gate(x)) * shared(x)); None -> none.
    moe_shared_expert_size: Optional[int] = None
    moe_shared_expert_gated: bool = True
    # Modern-LLM (Llama-family) knobs — beyond the reference, which is
    # GPT-2/BERT-era: grouped-query attention (fewer K/V head groups),
    # rotary position embeddings, SwiGLU MLPs, RMSNorm blocks.
    num_query_groups: Optional[int] = None  # None -> MHA (groups == heads)
    # "learned", "rope", "alibi", or "none": no positional encoding at
    # all (Nemotron-H's attention layers, which sit between state-space
    # layers that see the order)
    position_embedding_type: str = "learned"
    rotary_base: float = 10000.0
    # Long-context RoPE frequency rescaling (Llama-3.1 "llama3" or
    # position-interpolation "linear"); None -> unscaled frequencies.
    rope_scaling: Optional[RopeScaling] = None
    # Gemma-3: layers whose sliding window applies use THIS rope base
    # and skip rope_scaling (local 10k vs global 1M + linear scaling);
    # None -> every layer uses rotary_base/rope_scaling.
    rotary_base_local: Optional[float] = None
    # SmolLM3 NoPE alternation: every interval-th layer ((i+1) % N == 0)
    # applies NO rotary embedding at all. 0 -> rope on every layer.
    no_rope_layer_interval: int = 0
    # Query/key RMSNorm before rope: "projection" (OLMoE — one norm over
    # the full flattened q / k projection output) or "head" (Qwen3 —
    # per-head over head_dim, tensor-parallel-safe). None -> off.
    qk_norm: Optional[str] = None
    # DBRX: clamp the QKV projection outputs to [-clip, clip]
    # (elementwise, applied after the fused projection — identical to
    # HF's clamp of the fused Wqkv output). None -> no clamp.
    qkv_clip: Optional[float] = None
    # "gelu" is the tanh approximation (GPT-2 gelu_new); "gelu_exact"
    # the erf form (HF "gelu" — Falcon/NeoX default); "relu" (OPT);
    # "relu2" squared ReLU (Nemotron); "swiglu"/"geglu" are the gated
    # fused forms.
    activation: str = "gelu"
    # Scale token embeddings by this factor on entry (Gemma family uses
    # sqrt(hidden_size); the tied head contracts with the UNSCALED table).
    embedding_multiplier: Optional[float] = None
    # Per-head attention dim decoupled from hidden_size/num_heads (e.g.
    # gemma-7b: 256 vs 3072/16=192). None -> hidden_size // num_heads.
    head_dim: Optional[int] = None
    # GPT-NeoX/Pythia-family knobs: sum attention and MLP branches into
    # ONE residual (both read the pre-attn stream), and rotate only the
    # leading fraction of each head's dims (rotary_pct).
    parallel_residual: bool = False
    rotary_percent: float = 1.0
    # GPT-J rope convention: rotate interleaved even/odd pairs instead
    # of the rotate-half block form.
    rotary_interleaved: bool = False
    # Phi/Falcon-7b form of the parallel residual: ONE layernorm feeds
    # both branches (no post_attention_layernorm params).
    parallel_residual_shared_ln: bool = False
    # Phi ties a bias to the LM head projection (vocab-parallel sliced
    # with the head columns).
    lm_head_bias: bool = False
    # Mistral-style sliding-window attention: query i sees key j iff
    # 0 <= i - j < sliding_window (on top of causal). None -> full causal.
    sliding_window: Optional[int] = None
    # Alternating local/global attention (Gemma-2/3): the window applies
    # to layer i iff (i + 1) % pattern != 0 — every pattern-th layer runs
    # full causal attention (Gemma-2: pattern 2 -> even layers local;
    # Gemma-3: pattern 6). 1 -> every layer windowed (Mistral).
    sliding_window_pattern: int = 1
    # Gemma-2 tanh soft-capping: scores -> cap * tanh(scores / cap)
    # after the softmax scale, before masking (HF modeling_gemma2
    # eager_attention_forward). Takes the masked-softmax path — the
    # flash kernel has no softcap epilogue.
    attn_logit_softcapping: Optional[float] = None
    # Gemma-2: LM-head logits -> cap * tanh(logits / cap) (fp32),
    # applied per vocab-parallel shard (elementwise).
    final_logit_softcapping: Optional[float] = None
    # Decoupled softmax scale (Gemma-2 query_pre_attn_scalar): scores
    # are scaled by this value**-0.5 instead of kv_channels**-0.5
    # (gemma-2-27b: 144 vs head_dim 128). None -> kv_channels.
    query_pre_attn_scalar: Optional[float] = None
    # Gemma-2 "sandwich" residual form: each branch output is normed
    # BEFORE its residual add (x + post_norm(branch(pre_norm(x)))) —
    # adds post_self_attn_norm / post_mlp_norm params per layer.
    sandwich_norm: bool = False
    # False -> no input/pre-MLP norms: branches read the RAW residual
    # stream (OLMo-2 post-norm blocks: x + post_norm(branch(x))).
    # Requires sandwich_norm (a block with no norms at all is refused).
    pre_norm: bool = True
    # Granite muP-style scalars: each branch output is scaled before
    # its residual add (x + m * branch(...)), and LM logits are DIVIDED
    # by logits_scaling (HF modeling_granite "main diff with Llama").
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0
    normalization: str = "layernorm"  # or "rmsnorm"
    # BLOOM applies a layernorm directly after the token embeddings.
    embedding_layernorm: bool = False
    # False -> no bias on the attention projections (query_key_value and
    # dense), as the Llama/Qwen families publish them.
    attention_bias: bool = True
    # Multi-component rotary positions (Qwen2-VL "mrope_section"): the
    # rotary frequencies are split into consecutive sections and section
    # c takes its position from component c of ``position_ids``
    # ``[len(sections), b, s]``. None -> one component.
    rope_sections: Optional[tuple] = None
    # DeepSeek Sparse Attention (DeepSeek-V3.2-Exp): a learned indexer of
    # ``indexer_heads`` heads of ``indexer_head_dim`` scores every causal
    # (query, key) pair and each query attends over its ``indexer_topk``
    # best keys alone (SparseIndexer). None -> dense attention.
    indexer_heads: Optional[int] = None
    indexer_head_dim: int = 64
    indexer_topk: int = 2048
    # An expert layer that holds ``moe_local_experts`` of the
    # ``num_moe_experts`` it routes over, those from ``moe_expert_offset``
    # on, with no 'ep' mesh axis: one rank's share of an expert-parallel
    # layer, without the exchange (SwitchMLP). None -> all of them.
    moe_local_experts: Optional[int] = None
    moe_expert_offset: int = 0
    # How the router scores: "softmax" (Switch / Mixtral), or
    # "sigmoid_bias" (DeepSeek-V3, Nemotron-H): sigmoid scores, the top k
    # chosen by score + a per-expert bias that no gradient reaches, the
    # gates the unbiased scores of the chosen (normalised if
    # moe_normalize_topk) times moe_routed_scaling_factor, no auxiliary
    # loss. Sorted routing only (moe/router.py).
    moe_router_score: str = "softmax"
    moe_routed_scaling_factor: float = 1.0
    # DeepSeek-V3's complementary sequence-wise balance loss on the
    # sigmoid_bias router (``seq_aux``): each expert layer sows
    # ``seq_aux_loss`` (moe/router.py ``sequence_balance_loss``) and the
    # training loss adds this coefficient times their sum
    # (``moe.seq_aux_loss_from_variables``). 0.0 -> nothing is traced.
    moe_seq_aux_loss_coeff: float = 0.0
    # One sub-block a layer (Nemotron-H "hybrid_override_pattern"): a
    # string of num_layers letters, each layer x + f(norm(x)) with f a
    # Mamba-2 mixer ("M", transformer/ssm.py), attention ("*") or the
    # expert layer ("E"). None -> every layer is attention then MLP, as
    # everywhere else in this file.
    layer_pattern: Optional[str] = None
    # Multi-head latent attention (DeepSeek-V2/V3, Moonlight): keys and
    # values come from a ``kv_lora_rank``-wide latent of the token, RMS
    # normed and projected up to ``qk_nope_head_dim`` positionless key
    # channels and ``v_head_dim`` value channels a head; ``qk_rope_head_dim``
    # rotary channels ride beside them, the key's as ONE vector a token
    # that all heads share; queries come straight from the hidden state
    # (``q_lora_rank`` None: a query latent is models/mla.py's alone so
    # far, and any other value is refused here).
    # Scores scale by (nope + rope) ** -0.5; ``rotary_base`` and
    # ``rotary_interleaved`` say how the rotary channels turn.
    # ``kv_lora_rank`` None -> attention as everywhere else in this file.
    kv_lora_rank: Optional[int] = None
    q_lora_rank: Optional[int] = None
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    # Mamba-2 ("M" layers): heads of mamba_head_dim channels in
    # mamba_n_groups groups that share B and C, mamba_state_size states a
    # channel, a causal depthwise conv of mamba_conv_kernel, the scan in
    # chunks of mamba_chunk_size; dt_min/max/floor shape dt_bias's init.
    mamba_num_heads: int = 64
    mamba_head_dim: int = 64
    mamba_n_groups: int = 8
    mamba_state_size: int = 128
    mamba_conv_kernel: int = 4
    mamba_chunk_size: int = 128
    mamba_dt_min: float = 0.001
    mamba_dt_max: float = 0.1
    mamba_dt_floor: float = 1e-4
    # Training by diffusion over blocks (Block Diffusion, arXiv:2503.09573;
    # SDAR): with ``attn_mask_type`` ``AttnMaskType.block_diffusion``, a row
    # holds ``L`` clean tokens and then their ``L`` noised copies, cut into
    # blocks of this many tokens; attention follows the block-diffusion
    # rule (the flash kernels work it out from positions; off them the
    # softmax path takes it as a boolean mask), both copies of a token
    # share its position, and the final norm and the head run on the noisy
    # half alone (models/gpt.py; the loss is ``block_diffusion_loss_fn``).
    # Training only. None -> rows are what they are everywhere else.
    diffusion_block_length: Optional[int] = None
    # Tie the LM head to the word-embedding table (reference
    # parallel_lm_logits ties by default). Off here because the SPMD
    # pipeline harness needs untied heads (first/last stages run the same
    # program but hold different params); single-program models (dp/tp/ep)
    # can and should tie.
    tie_word_embeddings: bool = False

    def __post_init__(self):
        ruled = self.attn_mask_type == AttnMaskType.block_diffusion
        if ruled != (self.diffusion_block_length is not None) or (
                ruled and self.diffusion_block_length < 1):
            raise ValueError(
                f"AttnMaskType.block_diffusion and diffusion_block_length "
                f"({self.diffusion_block_length}, >= 1) come together")
        if ruled and (self.context_parallel or self.sequence_parallel
                      or self.position_embedding_type == "alibi"):
            raise ValueError(
                "block diffusion runs on one sequence shard, without alibi: "
                "no context or sequence parallelism")
        if self.sliding_window is not None:
            if self.sliding_window < 1:
                raise ValueError(
                    f"sliding_window ({self.sliding_window}) must be >= 1")
            if self.attn_mask_type != AttnMaskType.causal:
                raise ValueError("sliding_window requires causal attention")
            if self.context_parallel:
                raise ValueError(
                    "sliding_window does not compose with context "
                    "parallelism (the ring/ulysses kernels run full "
                    "causal attention)")
        if self.sliding_window_pattern < 1:
            raise ValueError(
                f"sliding_window_pattern ({self.sliding_window_pattern}) "
                f"must be >= 1")
        if self.sliding_window_pattern > 1:
            if self.sliding_window is None:
                raise ValueError(
                    "sliding_window_pattern > 1 needs sliding_window set")
            if self.scan_layers:
                raise ValueError(
                    "scan_layers needs a uniform stack: alternating "
                    "local/global attention (sliding_window_pattern > 1) "
                    "cannot be scanned")
        if self.query_pre_attn_scalar is not None and self.context_parallel:
            raise ValueError(
                "query_pre_attn_scalar does not compose with context "
                "parallelism (the ring/ulysses kernels use the default "
                "1/sqrt(head_dim) softmax scale)")
        if self.attn_logit_softcapping is not None:
            if self.attn_logit_softcapping <= 0:
                raise ValueError(
                    f"attn_logit_softcapping "
                    f"({self.attn_logit_softcapping}) must be > 0")
            if self.context_parallel:
                raise ValueError(
                    "attn_logit_softcapping does not compose with context "
                    "parallelism (the ring/ulysses kernels carry no "
                    "softcap epilogue)")
        if self.qkv_clip is not None and self.qkv_clip <= 0:
            raise ValueError(f"qkv_clip ({self.qkv_clip}) must be > 0")
        if self.qk_norm not in (None, "projection", "head"):
            raise ValueError(
                f"unknown qk_norm {self.qk_norm!r}; expected "
                f"'projection' (OLMoE) or 'head' (Qwen3)")
        if self.no_rope_layer_interval:
            if self.no_rope_layer_interval < 2:
                raise ValueError(
                    f"no_rope_layer_interval "
                    f"({self.no_rope_layer_interval}) must be >= 2 (1 "
                    f"would disable rope everywhere — use "
                    f"position_embedding_type='learned'/'alibi' instead)")
            if self.position_embedding_type != "rope":
                raise ValueError("no_rope_layer_interval requires "
                                 "position_embedding_type='rope'")
            if self.scan_layers:
                raise ValueError(
                    "scan_layers needs a uniform stack: NoPE alternation "
                    "(no_rope_layer_interval) cannot be scanned")
        if self.rotary_base_local is not None and self.sliding_window is None:
            raise ValueError(
                "rotary_base_local needs sliding_window set (it applies "
                "to the windowed layers only)")
        if self.rope_scaling is not None:
            if self.position_embedding_type != "rope":
                raise ValueError("rope_scaling requires "
                                 "position_embedding_type='rope'")
            if self.rope_scaling.rope_type not in ("linear", "llama3"):
                raise ValueError(
                    f"unknown rope_type "
                    f"{self.rope_scaling.rope_type!r}; expected 'linear' "
                    f"or 'llama3'")
            if self.rope_scaling.factor < 1.0:
                raise ValueError(
                    f"rope_scaling.factor ({self.rope_scaling.factor}) "
                    f"must be >= 1")
        if (self.final_logit_softcapping is not None
                and self.final_logit_softcapping <= 0):
            raise ValueError(
                f"final_logit_softcapping "
                f"({self.final_logit_softcapping}) must be > 0")
        if self.sandwich_norm and self.parallel_residual:
            raise ValueError(
                "sandwich_norm and parallel_residual are mutually "
                "exclusive residual forms")
        if self.logits_scaling <= 0:
            raise ValueError(
                f"logits_scaling ({self.logits_scaling}) must be > 0 "
                f"(it divides the LM logits)")
        if not self.pre_norm and not self.sandwich_norm:
            # (parallel_residual is already excluded transitively: it is
            # mutually exclusive with the sandwich_norm required here)
            raise ValueError(
                "pre_norm=False (OLMo-2 post-norm blocks) requires "
                "sandwich_norm=True — a block with no norms at all "
                "is almost certainly a config mistake")
        if self.parallel_residual_shared_ln and not self.parallel_residual:
            raise ValueError(
                "parallel_residual_shared_ln requires parallel_residual")
        if self.lm_head_bias and self.tie_word_embeddings:
            raise ValueError(
                "lm_head_bias requires an untied head (the tied path "
                "contracts with the embedding table and has no bias)")
        if not 0.0 < self.rotary_percent <= 1.0:
            raise ValueError(
                f"rotary_percent ({self.rotary_percent}) must be in (0, 1]")
        if self.head_dim is not None:
            if self.head_dim < 1:
                raise ValueError(f"head_dim ({self.head_dim}) must be >= 1")
            if self.head_dim * self.num_attention_heads == self.hidden_size:
                # normalize the derived value to None so numerically
                # identical configs compare/serialize identically and
                # producers can pass head_dim through unconditionally
                object.__setattr__(self, "head_dim", None)
        if self.position_embedding_type not in ("learned", "rope",
                                                "alibi", "none"):
            raise ValueError(
                f"unknown position_embedding_type "
                f"{self.position_embedding_type!r}; expected 'learned', "
                f"'rope', 'alibi' or 'none'")
        if self.position_embedding_type == "alibi" and self.context_parallel:
            raise ValueError("alibi does not compose with context "
                             "parallelism (ring/ulysses kernels carry no "
                             "position bias)")
        if self.activation not in ("gelu", "gelu_exact", "relu",
                                   "relu2", "swiglu", "geglu"):
            raise ValueError(f"unknown activation {self.activation!r}")
        if self.normalization not in ("layernorm", "rmsnorm"):
            raise ValueError(f"unknown normalization {self.normalization!r}")
        if self.context_parallel_algo not in ("ring", "ulysses"):
            raise ValueError(f"unknown context_parallel_algo "
                             f"{self.context_parallel_algo!r}")
        if self.rope_sections is not None:
            object.__setattr__(self, "rope_sections",
                               tuple(int(n) for n in self.rope_sections))
            if self.position_embedding_type != "rope":
                raise ValueError("rope_sections requires "
                                 "position_embedding_type='rope'")
            rotary = int(self.kv_channels * self.rotary_percent + 1e-6)
            if 2 * sum(self.rope_sections) != rotary:
                raise ValueError(
                    f"rope_sections {self.rope_sections} must add up to "
                    f"half the rotary width ({rotary})")
        if self.indexer_heads is not None:
            if self.indexer_heads < 1 or self.indexer_topk < 1:
                raise ValueError("indexer_heads and indexer_topk must be "
                                 ">= 1")
            if (self.attn_mask_type != AttnMaskType.causal
                    or self.sliding_window is not None
                    or self.attn_logit_softcapping is not None
                    or self.query_pre_attn_scalar is not None
                    or self.position_embedding_type != "rope"
                    or self.context_parallel or self.sequence_parallel):
                raise ValueError(
                    "the sparse-attention indexer needs causal rope "
                    "attention without a window, a soft cap, a custom "
                    "softmax scale or context / sequence parallelism")
        if self.moe_local_experts is not None:
            if self.num_moe_experts is None or not (
                    0 <= self.moe_expert_offset
                    and self.moe_local_experts >= 1
                    and self.moe_expert_offset + self.moe_local_experts
                    <= self.num_moe_experts):
                raise ValueError(
                    f"moe_local_experts ({self.moe_local_experts}) from "
                    f"moe_expert_offset ({self.moe_expert_offset}) must lie "
                    f"within num_moe_experts ({self.num_moe_experts})")
        if self.moe_first_dense_layers < 0 or (
                self.moe_first_dense_layers and (
                    self.num_moe_experts is None or self.scan_layers
                    or self.layer_pattern is not None)):
            raise ValueError(
                f"moe_first_dense_layers ({self.moe_first_dense_layers}) "
                f"needs num_moe_experts and an unrolled stack without a "
                f"layer_pattern")
        if self.moe_ffn_hidden_size is not None and (
                self.moe_ffn_hidden_size < 1 or self.num_moe_experts is None):
            raise ValueError(
                f"moe_ffn_hidden_size ({self.moe_ffn_hidden_size}) must be "
                f">= 1 and needs num_moe_experts")
        if self.moe_seq_aux_loss_coeff < 0 or (
                self.moe_seq_aux_loss_coeff
                and self.moe_router_score != "sigmoid_bias"):
            raise ValueError(
                f"moe_seq_aux_loss_coeff ({self.moe_seq_aux_loss_coeff}) "
                f"must be >= 0 and belongs to moe_router_score "
                f"'sigmoid_bias'")
        if self.kv_lora_rank is not None:
            widths = (self.kv_lora_rank, self.qk_nope_head_dim,
                      self.qk_rope_head_dim, self.v_head_dim)
            if min(widths) < 1 or self.qk_rope_head_dim % 2:
                raise ValueError(
                    f"latent attention needs kv_lora_rank, qk_nope_head_dim,"
                    f" qk_rope_head_dim (even) and v_head_dim >= 1, got "
                    f"{widths}")
            if self.q_lora_rank is not None:
                raise ValueError(
                    f"q_lora_rank ({self.q_lora_rank}): this path projects "
                    f"queries straight from the hidden state (None); "
                    f"models/mla.py has the query latent")
            if (self.attn_mask_type != AttnMaskType.causal
                    or self.sliding_window is not None
                    or self.attn_logit_softcapping is not None
                    or self.query_pre_attn_scalar is not None
                    or self.qk_norm is not None
                    or self.qkv_clip is not None
                    or self.indexer_heads is not None
                    or self.num_query_groups not in (
                        None, self.num_attention_heads)
                    or self.attention_bias
                    or self.position_embedding_type != "rope"
                    or self.rope_scaling is not None
                    or self.rope_sections is not None
                    or self.rotary_percent != 1.0
                    or self.context_parallel or self.sequence_parallel):
                raise ValueError(
                    "latent attention (kv_lora_rank) is causal rope "
                    "attention over all the heads, without biases, a "
                    "window, a soft cap, a custom softmax scale, QK norm, "
                    "a clip, the sparse indexer, grouped queries, rope "
                    "scaling / sections / percent or context / sequence "
                    "parallelism")
        elif self.q_lora_rank is not None:
            raise ValueError("q_lora_rank needs kv_lora_rank (latent "
                             "attention)")
        if self.moe_router_score not in ("softmax", "sigmoid_bias"):
            raise ValueError(
                f"unknown moe_router_score {self.moe_router_score!r}; "
                f"expected 'softmax' or 'sigmoid_bias'")
        if self.layer_pattern is not None:
            if (len(self.layer_pattern) != self.num_layers
                    or set(self.layer_pattern) - set("ME*")):
                raise ValueError(
                    f"layer_pattern {self.layer_pattern!r} must be "
                    f"num_layers ({self.num_layers}) letters of 'M' "
                    f"(Mamba-2), 'E' (experts), '*' (attention)")
            if (self.scan_layers or self.parallel_residual
                    or self.sandwich_norm or not self.pre_norm):
                raise ValueError(
                    "layer_pattern layers are x + f(norm(x)), unrolled: "
                    "no scan_layers, parallel_residual, sandwich_norm or "
                    "pre_norm=False")
            if "E" in self.layer_pattern and self.num_moe_experts is None:
                raise ValueError("an 'E' layer needs num_moe_experts")
            if "M" in self.layer_pattern and (
                    self.mamba_num_heads % self.mamba_n_groups
                    or (self.mamba_num_heads * self.mamba_head_dim)
                    % self.mamba_n_groups):
                raise ValueError(
                    f"mamba_num_heads ({self.mamba_num_heads}) must be a "
                    f"multiple of mamba_n_groups ({self.mamba_n_groups})")
        if self.num_query_groups is not None:
            if (self.num_query_groups < 1
                    or self.num_attention_heads % self.num_query_groups):
                raise ValueError(
                    f"num_attention_heads ({self.num_attention_heads}) must "
                    f"be a positive multiple of num_query_groups "
                    f"({self.num_query_groups})")

    @property
    def ffn_size(self):
        return self.ffn_hidden_size or 4 * self.hidden_size

    @property
    def kv_channels(self):
        return self.head_dim or self.hidden_size // self.num_attention_heads

    @property
    def query_groups(self):
        return self.num_query_groups or self.num_attention_heads


def _attn_mask_fn(scores, mask):
    return jnp.where(mask.astype(bool), -10000.0, scores)


_SWA_FLASH_WARNED = set()


def _warn_sliding_window_flash_once(window, seq):
    """Flash supports the window band natively (fmha kernel block-skip),
    but it was unavailable at this call site (non-TPU backend, an
    explicit attention_mask, or seq not a block multiple) — the
    masked-softmax path materializes full [s, s] scores. Trace-time,
    warn once per distinct window so a later, different model that also
    falls back still gets a signal, while variable-length workloads
    (length-bucketed batches retracing many seq values) don't spam one
    warning per length."""
    key = int(window)
    if key in _SWA_FLASH_WARNED:
        return
    _SWA_FLASH_WARNED.add(key)
    import warnings

    warnings.warn(
        f"sliding_window={window} < seq={seq}: flash attention was "
        f"requested but unavailable here (non-TPU backend, explicit "
        f"attention_mask, or seq/head_dim outside the kernel's blocks); "
        f"falling back to masked softmax with O(s^2) score "
        f"materialization.")


def apply_rotary_emb(x, base: float = 10000.0, positions=None,
                     percent: float = 1.0, interleaved: bool = False,
                     scaling: Optional[RopeScaling] = None,
                     sections: Optional[tuple] = None):
    """Rotary position embedding (rotate-half convention) on [s, b, n, d].

    ``positions`` is [s] (shared across the batch) or [s, b] (per-sequence
    indices, e.g. packed documents); defaults to global indices 0..s-1 —
    correct under sequence parallelism too, because the QKV projections
    gather the full sequence before heads are formed. fp32 trig, cast
    back to x.dtype. ``percent`` < 1 (GPT-NeoX rotary_pct) rotates only
    the leading dims of each head: rotary_ndims = int(d * percent) sets
    the frequency normalization, and 2*ceil(rotary_ndims/2) dims rotate
    (the HF convention — an odd rotary_ndims still pairs up).
    ``sections`` (Qwen2-VL mrope_section) splits the frequencies into
    consecutive runs; with it ``positions`` is [len(sections), s] or
    [len(sections), s, b] and run c is rotated by component c.
    """
    d_full = x.shape[-1]
    if percent < 1.0:
        # +eps: keep HF's trunc semantics while absorbing fp error when
        # percent was derived as rotary_dim / head_dim
        rot_n = int(d_full * percent + 1e-6)  # HF rotary_ndims (may be odd)
        width = 2 * ((rot_n + 1) // 2)  # dims actually rotated
        out = _rope_core(x[..., :width], base, positions, rot_n,
                         interleaved, scaling, sections)
        return jnp.concatenate([out, x[..., width:]], axis=-1)
    return _rope_core(x, base, positions, d_full, interleaved, scaling,
                      sections)


def _has_components(positions, sections, s):
    """Are ``positions`` multi-component (``[len(sections), s(, b)]``)
    rather than ``[s]`` or ``[s, b]``?"""
    return (sections is not None and positions is not None
            and positions.ndim > 1
            and positions.shape[0] == len(sections) != s)


def _rope_core(x, base, positions, freq_dim, interleaved=False,
               scaling=None, sections=None):
    s, _, _, d = x.shape
    if positions is None:
        positions = jnp.arange(s)
    inv = 1.0 / (base ** (jnp.arange(0, freq_dim, 2, dtype=jnp.float32)
                          / freq_dim))
    if scaling is not None:
        inv = _scale_rope_freqs(inv, scaling)
    freqs = positions[..., None].astype(jnp.float32) * inv  # [s(,b), d/2]
    if _has_components(positions, sections, s):
        # [c, s(,b), d/2]: frequency i keeps the component of its section
        import numpy as np

        component = np.repeat(np.arange(len(sections)), sections)
        freqs = sum(jnp.where(component == c, freqs[c], 0.0)
                    for c in range(len(sections)))
    if freqs.ndim == 2:  # [s, d/2] -> broadcast over batch and heads
        freqs = freqs[:, None, :]
    cos = jnp.cos(freqs)[:, :, None, :]
    sin = jnp.sin(freqs)[:, :, None, :]
    xf = x.astype(jnp.float32)
    if interleaved:  # GPT-J: pairs are (even, odd) lanes
        x1, x2 = xf[..., 0::2], xf[..., 1::2]
        out = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                        axis=-1).reshape(x.shape)
    else:  # rotate-half: pairs are (i, i + d/2)
        x1, x2 = jnp.split(xf, 2, axis=-1)
        out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                              -1)
    return out.astype(x.dtype)


def alibi_slopes(num_heads):
    """Per-head alibi slopes (ALiBi paper / HF build_alibi_tensor):
    geometric in 2^(-8/n) for the nearest power-of-two head count,
    interpolated for the remainder."""
    import math

    pow2 = 2 ** math.floor(math.log2(num_heads))
    base = 2.0 ** (-(2.0 ** -(math.log2(pow2) - 3)))
    slopes = [base ** (i + 1) for i in range(pow2)]
    if pow2 < num_heads:
        extra_base = 2.0 ** (-(2.0 ** -(math.log2(2 * pow2) - 3)))
        slopes += [extra_base ** (2 * i + 1)
                   for i in range(num_heads - pow2)]
    return jnp.asarray(slopes, jnp.float32)


def _local_alibi_slopes(cfg, np_local):
    """This tp rank's slice of the global slope vector (heads are
    contiguously sharded over tp; the canonical rank helper also honors
    the eager set_tensor_model_parallel_rank override)."""
    from apex_tpu.transformer.parallel_state import (
        get_tensor_model_parallel_rank,
    )

    slopes = alibi_slopes(cfg.num_attention_heads)
    rank = get_tensor_model_parallel_rank()
    return jax.lax.dynamic_slice_in_dim(slopes, rank * np_local, np_local)


def _make_norm(cfg, name):
    if cfg.normalization == "rmsnorm":
        from apex_tpu.normalization import FusedRMSNorm

        return FusedRMSNorm(normalized_shape=cfg.hidden_size,
                            eps=cfg.layernorm_epsilon,
                            param_dtype=jnp.float32, name=name)
    if cfg.normalization != "layernorm":
        raise ValueError(f"unknown normalization {cfg.normalization!r}")
    return FusedLayerNorm(normalized_shape=cfg.hidden_size,
                          eps=cfg.layernorm_epsilon,
                          param_dtype=jnp.float32, name=name)


INDEXER_CHUNK = 512   # queries scored at a time (DSA's q_chunk_size)


def topk_selection(scores, topk: int):
    """``[b, s, s]`` int8: for each query ``t`` the ``min(t + 1, topk)``
    keys ``u <= t`` of largest float32 ``scores[b, t, u]``. By a threshold
    a row, the ``topk``-th largest score found by bisection over the
    float32's bits (32 counting passes, no sort, no gather); scores that
    tie with the threshold are all kept.

    The kernel registry's one rule picks who computes it (counted as
    ``kernels/dispatch/topk_select_<path>``): the Pallas kernel
    ``indexer_topk_select`` (``kernels/topk_select.py``), which holds a
    block of query rows in VMEM through all 32 passes and reads the
    scores from HBM once, or the jnp oracle below, one sequence at a time
    so that its temporaries stay a sequence's, where the shape does not
    fit (``topk_select.fits``), off the TPU and under
    ``APEX_TPU_KERNELS=0``. The two agree in every element."""
    from apex_tpu.kernels import topk_select

    if topk_select.GATE.path(topk_select.fits(scores.shape)) != "oracle":
        return topk_select.topk_select(scores, topk)
    return jax.lax.map(
        lambda row: _topk_selection_oracle(row[None], topk)[0], scores)


def _topk_selection_oracle(scores, topk: int):
    """:func:`topk_selection` in jnp: the kernel's oracle. Every pass
    builds the keys from ``scores`` again, so it reads them 33 times."""
    b, s, _ = scores.shape
    t = jnp.arange(s)
    causal = t[None, :] <= t[:, None]
    want = jnp.minimum(t + 1, topk)

    def keys():
        # float32 order as uint32 order; 0 stands for "not causal"
        bits = jax.lax.bitcast_convert_type(scores, jnp.uint32)
        flipped = jnp.where(bits >> 31 == 1, ~bits,
                            bits | jnp.uint32(0x80000000))
        return jnp.where(causal, flipped, jnp.uint32(0))

    def body(i, found):
        cand = found | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        count = jnp.sum(keys() >= cand[..., None], axis=-1, dtype=jnp.int32)
        return jnp.where(count >= want, cand, found)

    threshold = jax.lax.fori_loop(0, 32, body,
                                  jnp.zeros((b, s), jnp.uint32))
    return ((keys() >= threshold[..., None]) & causal).astype(jnp.int8)


class SparseIndexer(nn.Module):
    """DeepSeek Sparse Attention's lightning indexer (DeepSeek-V3.2-Exp
    technical report, eq. 1-2, and its ``inference/model.py`` ``Indexer``).

    From the layer's normed input, detached: ``qI = x WqI`` (``heads``
    of ``head_dim``), one key head ``kI = LayerNorm(x WkI)``, head
    weights ``w = x Ww``; rotary on the whole of ``qI`` and ``kI`` from
    the first position component;
    ``I[t, u] = sum_j w[t, j] * heads^-1/2 * head_dim^-1/2 *
    relu(qI[t, j] . kI[u])``. ``__call__`` -> ``(I [b, s, s] float32,
    selection [b, s, s] int8)``, the selection being each query's
    ``indexer_topk`` best causal keys (:func:`topk_selection`, under the
    scope ``indexer/select``: one ``indexer_topk_select`` kernel over the
    whole ``[b, s, s]`` where the kernel registry gives it, the jnp
    bisection a sequence at a time elsewhere). Scores are computed one
    sequence and ``INDEXER_CHUNK`` queries at a time, so ``[heads, s, s]``
    is never live; the loss runs a sequence at a time too.

    ``loss`` sows ``indexer_loss`` into ``moe_losses``:
    ``mean_t KL(p_t || softmax_{selected u} I[t, u])`` with ``p_t`` the
    attention's head-summed probabilities over the selection,
    L1-normalised and detached (V3.2's sparse training stage). The
    indexer's parameters get gradient from it alone, and nothing else
    does: its input and its target are detached, and the selection
    carries no gradient.
    """

    config: TransformerConfig

    @nn.compact
    def __call__(self, hidden_states, position_ids=None):
        from apex_tpu.telemetry.registry import get_registry

        cfg = self.config
        heads, dim = cfg.indexer_heads, cfg.indexer_head_dim
        s, b, h = hidden_states.shape
        x = jax.lax.stop_gradient(hidden_states).astype(cfg.compute_dtype)
        init = nn.initializers.normal(0.02)

        def weight(name, width):
            return self.param(name, init, (h, width),
                              cfg.params_dtype).astype(cfg.compute_dtype)

        with jax.named_scope("indexer/project"):
            q = jnp.dot(x, weight("wq", heads * dim)).reshape(s, b, heads,
                                                               dim)
            k = jnp.dot(x, weight("wk", dim),
                        preferred_element_type=jnp.float32)
            k = FusedLayerNorm(normalized_shape=dim,
                               eps=cfg.layernorm_epsilon,
                               param_dtype=jnp.float32, name="k_norm")(k)
            w = jnp.dot(x, weight("weights_proj", heads),
                        preferred_element_type=jnp.float32)
            w = w * (heads ** -0.5 * dim ** -0.5)
            if _has_components(position_ids, cfg.rope_sections, s):
                position_ids = position_ids[0]
            q = apply_rotary_emb(q, cfg.rotary_base, position_ids)
            k = apply_rotary_emb(
                k.astype(cfg.compute_dtype)[:, :, None, :], cfg.rotary_base,
                position_ids)[:, :, 0, :]

        chunk = INDEXER_CHUNK if s % INDEXER_CHUNK == 0 else s

        @jax.checkpoint
        def chunk_scores(qc, wc, kr):
            # [c, heads, dim] x [s, dim] -> [heads, c, s]
            logits = jnp.einsum("chd,ud->hcu", qc, kr,
                                preferred_element_type=jnp.float32)
            return jnp.einsum("hcu,ch->cu", jax.nn.relu(logits), wc)

        def row_scores(qwk):
            qr, wr, kr = qwk        # one sequence: [s, heads, dim], ...
            return jax.lax.map(
                lambda qw: chunk_scores(*qw, kr),
                (qr.reshape(s // chunk, chunk, heads, dim),
                 wr.reshape(s // chunk, chunk, heads))).reshape(s, s)

        # a sequence, and within it a chunk of queries, at a time
        with jax.named_scope("indexer/scores"):
            scores = jax.lax.map(row_scores, (q.transpose(1, 0, 2, 3),
                                              w.transpose(1, 0, 2),
                                              k.transpose(1, 0, 2)))
        with jax.named_scope("indexer/select"):
            selection = topk_selection(jax.lax.stop_gradient(scores),
                                       cfg.indexer_topk)
        get_registry().gauge("attention/selected_keys_max").set(
            min(cfg.indexer_topk, s))
        return scores, selection

    def loss(self, scores, selection, probs):
        """Sow ``KL(target || softmax over the selection of scores)``,
        mean over queries and batch; ``probs`` ``[b, s, s]`` are the
        attention's head-summed probabilities (zero off the selection)."""
        @jax.checkpoint
        def row_loss(row):
            score, chosen, prob = row           # one sequence, [s, s] each
            chosen = chosen != 0
            target = prob / jnp.maximum(
                jnp.sum(prob, axis=-1, keepdims=True), 1e-30)
            logq = jax.nn.log_softmax(
                jnp.where(chosen, score, -1e30), axis=-1)
            live = chosen & (target > 0)
            kl = jnp.where(
                live,
                target * (jnp.log(jnp.where(live, target, 1.0)) - logq), 0.0)
            return jnp.mean(jnp.sum(kl, axis=-1))

        with jax.named_scope("indexer/loss"):
            value = jnp.mean(jax.lax.map(
                row_loss, (scores, selection, jax.lax.stop_gradient(probs))))
        self.sow("moe_losses", "indexer_loss", value)
        return value


def indexer_loss_from_variables(variables):
    """The sum over layers of the sparse-attention indexers' losses, from
    the ``moe_losses`` collection of ``model.apply(...,
    mutable=["moe_losses"])`` (beside
    ``transformer.moe.moe_loss_from_variables``)."""
    from apex_tpu.transformer.moe import sown_total

    return sown_total(variables, "indexer_loss")


def latent_attention(q_nope, q_rope, k_nope, k_rope, v, *, rotary_base,
                     position_ids=None, interleaved=True, flash=True):
    """Multi-head latent attention from the projected operands to the
    context: the one spelling of it for training in the package
    (``ParallelAttention``'s latent path and ``models/mla.py``).

    ``q_nope``, ``k_nope`` ``[s, b, n, d_nope]``, ``q_rope`` ``[s, b, n,
    d_rope]`` and ``k_rope`` ``[s, b, d_rope]`` (the one rotary key a
    token, shared by the heads) not yet rotated, ``v`` ``[s, b, n, d_v]``
    -> ``[s, b, n * d_v]``. Rotary on ``q_rope`` of every head and on
    ``k_rope`` (scope ``mla/rope``), then causal attention over
    ``(q_nope . k_nope + q_rope . k_rope) * (d_nope + d_rope) ** -0.5``
    (scope ``mla/kernel``): ``contrib.fmha.mla_flash_attention``, whose
    one rule runs the kernels or their oracle; with ``flash`` off, the
    oracle. Nothing is broadcast or concatenated on the kernel path."""
    from apex_tpu.contrib import fmha

    s, b, n, _ = q_nope.shape

    def batch_major(t):     # [s, b, ...] -> [b, s, the rest folded]
        return t.reshape(s, b, -1).transpose(1, 0, 2)

    with jax.named_scope("mla"):    # the layout changes between the parts
        with jax.named_scope("rope"):
            rope = q_rope.shape[-1]
            q_rope = _rope_core(q_rope, rotary_base, position_ids, rope,
                                interleaved)
            k_rope = _rope_core(k_rope[:, :, None, :], rotary_base,
                                position_ids, rope, interleaved)[:, :, 0, :]
        operands = tuple(map(batch_major,
                             (q_nope, q_rope, k_nope, k_rope, v)))
        with jax.named_scope("kernel"):
            if flash:
                ctx = fmha.mla_flash_attention(*operands, n, True)
            else:
                ctx = fmha.mla_attention_reference(*operands, n, True)
        return ctx.transpose(1, 0, 2)


class ParallelAttention(nn.Module):
    """Self-attention with column-parallel QKV + row-parallel projection
    (reference standalone_transformer_lm.py ParallelAttention).

    ``decode=True`` enables KV-cache incremental decoding: 'cache'
    variables hold rotated K/V (group heads, pre-GQA-broadcast) for
    ``max_position_embeddings`` positions; each call appends its ``s``
    tokens at ``cache_index`` and attends over the filled prefix. Apply
    with ``mutable=["cache"]``; works for the prefill chunk (s = prompt
    length) and single-token steps alike.
    """

    config: TransformerConfig
    decode: bool = False
    # which layer this is — selects local vs global attention under
    # sliding_window_pattern (Gemma-2/3 alternation)
    layer_number: int = 0

    def _layer_window(self):
        """This layer's sliding window, or None when it runs full causal
        attention (every sliding_window_pattern-th layer)."""
        cfg = self.config
        if cfg.sliding_window is None:
            return None
        if (cfg.sliding_window_pattern > 1
                and (self.layer_number + 1) % cfg.sliding_window_pattern
                == 0):
            return None
        return cfg.sliding_window

    def _layer_uses_rope(self):
        """False on SmolLM3-style NoPE layers (every interval-th)."""
        cfg = self.config
        if not cfg.no_rope_layer_interval:
            return True
        return (self.layer_number + 1) % cfg.no_rope_layer_interval != 0

    def _layer_rope(self):
        """(rotary_base, rope_scaling) for THIS layer: Gemma-3 gives the
        windowed (local) layers their own base with no frequency
        rescaling, while global layers keep rotary_base/rope_scaling."""
        cfg = self.config
        if (cfg.rotary_base_local is not None
                and self._layer_window() is not None):
            return cfg.rotary_base_local, None
        return cfg.rotary_base, cfg.rope_scaling

    @nn.compact
    def __call__(self, hidden_states, attention_mask=None, position_ids=None):
        cfg = self.config
        tp = get_tensor_model_parallel_world_size()
        np_local = cfg.num_attention_heads // tp
        if cfg.kv_lora_rank is not None:
            return self._latent_attention(cfg, hidden_states,
                                          attention_mask, position_ids,
                                          np_local)
        kv = cfg.kv_channels
        s, b, h = hidden_states.shape[-3:]
        x = hidden_states.astype(cfg.compute_dtype)
        if self.decode and cfg.sequence_parallel:
            raise ValueError("decode mode does not compose with "
                             "sequence parallelism")
        block_length = cfg.diffusion_block_length
        if block_length is not None:
            if self.decode:
                raise ValueError(
                    "block diffusion (diffusion_block_length) is a training "
                    "rule over [clean; noisy] rows; there is no decode path")
            if s % (2 * block_length):
                raise ValueError(
                    f"a block-diffusion row holds L clean tokens and their L "
                    f"noised copies in blocks of {block_length}; got {s}")
            from apex_tpu.telemetry.registry import get_registry

            get_registry().counter("diffusion/layers").inc()
        # the rule of this call: the block length where attention follows
        # block diffusion (a planted ``causal`` over the same rows has none)
        rule = (block_length
                if cfg.attn_mask_type == AttnMaskType.block_diffusion
                else None)
        if cfg.indexer_heads is not None and (
                self.decode or attention_mask is not None or tp > 1):
            raise ValueError(
                "sparse attention (indexer_heads) supports training "
                "without an explicit attention_mask on one "
                "tensor-parallel rank; there is no decode path")

        # flash handles the built-in causal/full patterns and the
        # sliding-window band (kernel block-skip); an explicit
        # attention_mask (e.g. padding), a softcap, or a non-default
        # softmax scale must take the masked softmax path or they would
        # be silently ignored.
        from apex_tpu.contrib import fmha

        seq_full = s * tp if cfg.sequence_parallel else s
        layout = fmha.dense_layout(seq_full, np_local, kv)
        flash = (cfg.use_flash_attention and attention_mask is None
                 and cfg.attn_logit_softcapping is None
                 and cfg.query_pre_attn_scalar in (None, kv)
                 and layout is not None)
        # Where the heads fill whole 128-lane columns the kernels take
        # q, k, v and give the context as [b, s, n*d], the projections'
        # own layout: each of q, k, v then comes from a matmul of its
        # own over its columns of the stored weight, so that no
        # activation is sliced, transposed or lane-padded on the way.
        batch_major = (flash and not self.decode
                       and not cfg.context_parallel
                       and cfg.indexer_heads is None
                       and layout == "bsnd")

        def heads_of(t):   # [s, b, n * kv] -> [s, b, n, kv]
            return t.reshape(*t.shape[:-1], -1, kv)

        if cfg.query_groups == cfg.num_attention_heads:
            qkv = ColumnParallelLinear(
                input_size=cfg.hidden_size,
                output_size=3 * cfg.num_attention_heads * kv,
                gather_output=False, bias=cfg.attention_bias,
                params_dtype=cfg.params_dtype,
                sequence_parallel_enabled=cfg.sequence_parallel,
                name="query_key_value")
            if batch_major:
                def q_k_v(w):   # the columns are [np_local, 3, kv]
                    w = w.reshape(*w.shape[:-1], np_local, 3, kv)
                    return tuple(t.reshape(*w.shape[:-3], -1)
                                 for t in jnp.split(w, 3, axis=-2))

                q, k, v = map(heads_of, qkv(x, column_groups=q_k_v))
            else:
                # [s, b, 3*h/tp] -> [s, b, np_local, 3*kv]
                qkv = qkv(x).reshape(seq_full, b, np_local, 3 * kv)
                q, k, v = jnp.split(qkv, 3, axis=-1)
        else:
            # Grouped-query attention: fewer K/V head groups; ONE fused
            # projection (a single SP all-gather / matmul dispatch) whose
            # per-rank columns lay out as [q heads | kv groups] — each tp
            # rank holds whole groups, and per-rank pairing is
            # self-consistent because shards are initialized per rank.
            from apex_tpu.transformer.tensor_parallel.utils import divide

            g_local = divide(cfg.query_groups, tp)
            proj = ColumnParallelLinear(
                input_size=cfg.hidden_size,
                output_size=(cfg.num_attention_heads
                             + 2 * cfg.query_groups) * kv,
                gather_output=False, bias=cfg.attention_bias,
                params_dtype=cfg.params_dtype,
                sequence_parallel_enabled=cfg.sequence_parallel,
                name="query_key_value")

            if batch_major:
                def q_k_v(w):   # the columns are [q heads | g_local, 2, kv]
                    kvp = w[..., np_local * kv:].reshape(
                        *w.shape[:-1], g_local, 2, kv)
                    return (w[..., :np_local * kv],
                            *(kvp[..., i, :].reshape(*w.shape[:-1], -1)
                              for i in range(2)))

                q, k, v = map(heads_of, proj(x, column_groups=q_k_v))
            else:
                proj = proj(x)
                q = proj[..., :np_local * kv].reshape(seq_full, b, np_local,
                                                      kv)
                kvp = proj[..., np_local * kv:].reshape(seq_full, b, g_local,
                                                        2 * kv)
                k, v = jnp.split(kvp, 2, axis=-1)

        if cfg.qkv_clip is not None:  # DBRX: clamp projection outputs
            clip = jnp.asarray(cfg.qkv_clip, q.dtype)
            q = jnp.clip(q, -clip, clip)
            k = jnp.clip(k, -clip, clip)
            v = jnp.clip(v, -clip, clip)

        if cfg.qk_norm is not None:
            q, k = self._apply_qk_norm(cfg, q, k, tp)

        if self.decode:
            if attention_mask is not None:
                raise ValueError(
                    "decode mode does not support attention_mask: batch "
                    "unpadded prompts (left-trim or group by length)")
            if cfg.context_parallel:
                raise ValueError("decode mode does not compose with "
                                 "context parallelism")
            return self._decode_attention(cfg, q, k, v, position_ids,
                                          np_local, kv, b)

        if cfg.context_parallel:
            if attention_mask is not None:
                raise ValueError("context parallelism supports only the "
                                 "built-in causal/full patterns, not an "
                                 "explicit attention_mask")
            return self._ring_attention(cfg, q, k, v, position_ids,
                                        np_local, kv, b)

        if (cfg.position_embedding_type == "rope"
                and self._layer_uses_rope()):
            rope_base, rope_scale = self._layer_rope()
            q = apply_rotary_emb(q, rope_base, position_ids,
                                 cfg.rotary_percent,
                                 cfg.rotary_interleaved,
                                 rope_scale, cfg.rope_sections)
            k = apply_rotary_emb(k, rope_base, position_ids,
                                 cfg.rotary_percent,
                                 cfg.rotary_interleaved,
                                 rope_scale, cfg.rope_sections)
        if k.shape[2] != np_local:
            # broadcast each K/V group to its query heads
            rep = np_local // k.shape[2]
            k = jnp.repeat(k, rep, axis=2)
            v = jnp.repeat(v, rep, axis=2)

        # a window covering the whole sequence is plain causal
        layer_win = self._layer_window()
        win = (layer_win
               if (layer_win is not None and layer_win < seq_full)
               else None)

        if cfg.indexer_heads is not None:
            from apex_tpu.contrib.fmha import sparse_attention

            indexer = SparseIndexer(cfg, name="indexer")
            scores, selection = indexer(hidden_states, position_ids)
            # one path: the kernels where they run, their oracle elsewhere
            ctx, probs = sparse_attention(
                q.transpose(1, 2, 0, 3).astype(cfg.compute_dtype),
                k.transpose(1, 2, 0, 3).astype(cfg.compute_dtype),
                v.transpose(1, 2, 0, 3).astype(cfg.compute_dtype),
                selection, True)
            indexer.loss(scores, selection, probs)
            ctx = ctx.transpose(2, 0, 1, 3).reshape(seq_full, b,
                                                    np_local * kv)
            return self._output_proj(cfg, ctx)

        if flash:
            slopes = (_local_alibi_slopes(cfg, np_local)
                      if cfg.position_embedding_type == "alibi" else None)
            causal = cfg.attn_mask_type == AttnMaskType.causal
            if batch_major:
                # [s, b, n, d] -> [b, s, n*d]: XLA folds the transpose
                # into whatever wrote q, k, v (the matmul, or rotary /
                # QK-norm / clip), and the one back into the dense
                # matmul. Heads first, then the transpose: transposed as
                # [s, b, n, d] each array is copied three times a layer.
                q, k, v = (t.reshape(seq_full, b, np_local * kv)
                           .transpose(1, 0, 2) for t in (q, k, v))
                ctx = fmha.flash_attention_bsnd(
                    q, k, v, np_local, causal, window=win,
                    alibi_slopes=slopes, block_diffusion=rule)
                ctx = ctx.transpose(1, 0, 2)  # [s, b, n*d]
            else:
                # [s, b, n, d] -> [b, n, s, d]
                qt = q.transpose(1, 2, 0, 3)
                kt = k.transpose(1, 2, 0, 3)
                vt = v.transpose(1, 2, 0, 3)
                ctx = fmha.flash_attention(qt, kt, vt, causal=causal,
                                           window=win, alibi_slopes=slopes,
                                           block_diffusion=rule)
                ctx = ctx.transpose(2, 0, 1, 3)  # [s, b, n, d]
        else:
            if win is not None:
                # fold the window band into the mask (masked-softmax path
                # materializes full [s, s] scores — warn when the caller
                # asked for flash but it was unavailable here)
                if cfg.use_flash_attention:
                    _warn_sliding_window_flash_once(win, seq_full)
                i = jnp.arange(seq_full)[:, None]
                j = jnp.arange(seq_full)[None, :]
                band = (j > i) | (i - j >= win)
                attention_mask = (band if attention_mask is None
                                  else band | attention_mask.astype(bool))
            if rule is not None:
                # the kernels' oracle: the rule as a boolean mask (True
                # where a query does not see a key), counted as theirs
                from apex_tpu.kernels.registry import get_kernel_registry

                get_kernel_registry().dispatch(fmha.BLOCKDIFF_ENTRY, "oracle")
                unseen = jnp.asarray(~fmha.block_diffusion_mask(
                    seq_full // 2, rule))
                attention_mask = (unseen if attention_mask is None
                                  else unseen | attention_mask.astype(bool))
            # core attention (reference CoreAttention): [b, n, s, s] scores
            qt = q.transpose(1, 2, 0, 3).astype(cfg.compute_dtype)
            kt = k.transpose(1, 2, 0, 3).astype(cfg.compute_dtype)
            vt = v.transpose(1, 2, 0, 3).astype(cfg.compute_dtype)
            scores = jnp.einsum("bnsd,bntd->bnst", qt, kt,
                                preferred_element_type=jnp.float32)
            scores = scores / jnp.sqrt(
                cfg.query_pre_attn_scalar or kv).astype(jnp.float32)
            if cfg.attn_logit_softcapping is not None:
                # Gemma-2: scale, then cap * tanh(s / cap), then mask
                cap = jnp.float32(cfg.attn_logit_softcapping)
                scores = cap * jnp.tanh(scores / cap)
            if cfg.position_embedding_type == "alibi":
                # key-position-only form (HF build_alibi_tensor): each
                # row differs from slope*(j - i) by a constant, which
                # softmax cancels
                slopes = _local_alibi_slopes(cfg, np_local)
                scores = scores + (slopes[None, :, None, None]
                                   * jnp.arange(seq_full, dtype=jnp.float32
                                                )[None, None, None, :])
            from apex_tpu.transformer.functional.fused_softmax import (
                scaled_masked_softmax,
                scaled_upper_triang_masked_softmax,
            )

            if (cfg.attn_mask_type == AttnMaskType.causal
                    and attention_mask is None):
                bsz, nh, sq, sk = scores.shape
                probs = scaled_upper_triang_masked_softmax(
                    scores.reshape(bsz * nh, sq, sk), 1.0
                ).reshape(bsz, nh, sq, sk)
            else:
                probs = scaled_masked_softmax(scores, attention_mask, 1.0)
            ctx = jnp.einsum("bnst,bntd->bnsd", probs.astype(cfg.compute_dtype), vt,
                             preferred_element_type=jnp.float32)
            ctx = ctx.transpose(2, 0, 1, 3)  # [s, b, n, d]

        ctx = ctx.reshape(ctx.shape[0], b, np_local * kv)
        return self._output_proj(cfg, ctx)

    def _latent_attention(self, cfg, hidden_states, attention_mask,
                          position_ids, n):
        """Multi-head latent attention (``kv_lora_rank`` set), training:
        the projections, each under its scope ``mla/{q_proj,kv_down,
        kv_up,out_proj}``, around :func:`latent_attention`. The q and
        kv_up weights' columns are ``[every head's positionless part |
        every head's rotary part]`` and ``[every head's key part | every
        head's value]``: each part comes from a matmul of its own over its
        columns, in the kernels' layout, so that no activation is sliced
        per head. Counted at trace time as ``mla/layers``."""
        from apex_tpu.normalization import FusedRMSNorm
        from apex_tpu.telemetry.registry import get_registry

        if self.decode or attention_mask is not None:
            raise ValueError(
                "latent attention trains without an explicit "
                "attention_mask; its cache row does not exist in this "
                "path (models/mla.py decodes)")
        get_registry().counter("mla/layers").inc()
        dn, dr, dv = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                      cfg.v_head_dim)
        lat = cfg.kv_lora_rank
        s, b, _ = hidden_states.shape
        x = hidden_states.astype(cfg.compute_dtype)

        def linear(name, fan_in, fan_out):
            # the latents ride every rank whole; the heads shard
            return ColumnParallelLinear(
                input_size=fan_in, output_size=fan_out, gather_output=False,
                bias=False, params_dtype=cfg.params_dtype, name=name)

        def norm(t, name):
            return FusedRMSNorm(
                normalized_shape=t.shape[-1], eps=cfg.layernorm_epsilon,
                param_dtype=jnp.float32, name=name)(
                    t.astype(jnp.float32)).astype(cfg.compute_dtype)

        def cut_at(width):
            return lambda w: (w[..., :width], w[..., width:])

        with jax.named_scope("mla/q_proj"):
            q_nope, q_rope = linear(
                "q_proj", cfg.hidden_size,
                cfg.num_attention_heads * (dn + dr))(
                    x, column_groups=cut_at(n * dn))
        with jax.named_scope("mla/kv_down"):
            down = nn.Dense(lat + dr, use_bias=False,
                            dtype=cfg.compute_dtype,
                            param_dtype=cfg.params_dtype, name="kv_down")(x)
            latent = norm(down[..., :lat], "kv_norm")
            k_rope = down[..., lat:]
        with jax.named_scope("mla/kv_up"):
            k_nope, v = linear(
                "kv_up", lat, cfg.num_attention_heads * (dn + dv))(
                    latent, column_groups=cut_at(n * dn))
        ctx = latent_attention(
            q_nope.reshape(s, b, n, dn), q_rope.reshape(s, b, n, dr),
            k_nope.reshape(s, b, n, dn), k_rope, v.reshape(s, b, n, dv),
            rotary_base=cfg.rotary_base, position_ids=position_ids,
            interleaved=cfg.rotary_interleaved,
            flash=cfg.use_flash_attention)
        with jax.named_scope("mla/out_proj"):
            return RowParallelLinear(
                input_size=cfg.num_attention_heads * dv,
                output_size=cfg.hidden_size, input_is_parallel=True,
                bias=False, params_dtype=cfg.params_dtype,
                name="dense")(ctx.astype(cfg.compute_dtype))

    def _apply_qk_norm(self, cfg, q, k, tp):
        """Query/key RMSNorm before rope (fp32, cast back).

        "projection" (HF modeling_olmoe OlmoeAttention: q_norm/k_norm
        over the FULL projected vector before the head reshape) —
        normalizes across all heads jointly, so a tp-sharded projection
        would need a cross-rank psum of squares; refused for tp > 1.
        "head" (Qwen3 convention): per-head over head_dim — tp-safe."""
        from apex_tpu.normalization import FusedRMSNorm

        def norm(x, shape, name):
            return FusedRMSNorm(
                normalized_shape=shape, eps=cfg.layernorm_epsilon,
                param_dtype=jnp.float32, name=name)(
                x.astype(jnp.float32)).astype(cfg.compute_dtype)

        if cfg.qk_norm == "head":
            return (norm(q, q.shape[-1], "q_norm"),
                    norm(k, k.shape[-1], "k_norm"))
        if tp > 1:
            raise ValueError(
                "qk_norm='projection' normalizes the full projection "
                "width and is not tensor-parallel (would need a psum of "
                "squares across ranks); use tp=1 or qk_norm='head'")
        s, b = q.shape[:2]
        qn = norm(q.reshape(s, b, -1), q.shape[-2] * q.shape[-1], "q_norm")
        kn = norm(k.reshape(s, b, -1), k.shape[-2] * k.shape[-1], "k_norm")
        return qn.reshape(q.shape), kn.reshape(k.shape)

    def _output_proj(self, cfg, ctx):
        """Shared row-parallel output projection (both attention paths —
        keep them on ONE 'dense' module so numerics can't diverge)."""
        return RowParallelLinear(
            input_size=cfg.num_attention_heads * cfg.kv_channels,
            output_size=cfg.hidden_size,
            input_is_parallel=True, bias=cfg.attention_bias,
            params_dtype=cfg.params_dtype,
            sequence_parallel_enabled=(cfg.sequence_parallel
                                       and not self.decode),
            name="dense")(ctx.astype(cfg.compute_dtype))

    def _ring_attention(self, cfg, q, k, v, position_ids, np_local, kv, b):
        """Context-parallel core: hidden states are sequence shards over
        the 'cp' axis and activations never materialize the full
        sequence — K/V rotate around the ring (ppermute) or, with
        ``context_parallel_algo="ulysses"``, two all_to_alls trade seq
        sharding for head sharding around a local full attention. RoPE
        uses global positions (cp_rank * s_local + i) so shards agree
        with the unsharded model."""
        from jax import lax

        from apex_tpu.transformer.context_parallel import (
            ring_self_attention,
            ulysses_self_attention,
        )
        from apex_tpu.transformer.parallel_state import CONTEXT_PARALLEL_AXIS

        s = q.shape[0]
        if (cfg.position_embedding_type == "rope"
                and self._layer_uses_rope()):
            if position_ids is None:
                try:
                    rank = lax.axis_index(CONTEXT_PARALLEL_AXIS)
                except Exception:
                    rank = 0
                position_ids = rank * s + jnp.arange(s)
            rope_base, rope_scale = self._layer_rope()
            q = apply_rotary_emb(q, rope_base, position_ids,
                                 cfg.rotary_percent,
                                 cfg.rotary_interleaved,
                                 rope_scale)
            k = apply_rotary_emb(k, rope_base, position_ids,
                                 cfg.rotary_percent,
                                 cfg.rotary_interleaved,
                                 rope_scale)
        if k.shape[2] != np_local:
            rep = np_local // k.shape[2]
            k = jnp.repeat(k, rep, axis=2)
            v = jnp.repeat(v, rep, axis=2)
        attn = (ulysses_self_attention
                if cfg.context_parallel_algo == "ulysses"
                else ring_self_attention)
        # [s, b, n, d] -> [b, s, n, d]
        ctx = attn(
            q.transpose(1, 0, 2, 3).astype(cfg.compute_dtype),
            k.transpose(1, 0, 2, 3).astype(cfg.compute_dtype),
            v.transpose(1, 0, 2, 3).astype(cfg.compute_dtype),
            causal=(cfg.attn_mask_type == AttnMaskType.causal))
        ctx = ctx.transpose(1, 0, 2, 3).reshape(s, b, np_local * kv)
        return self._output_proj(cfg, ctx)

    def _decode_attention(self, cfg, q, k, v, position_ids, np_local, kv, b):
        """KV-cache path: rotate at absolute positions, append to the
        cache, attend over the filled prefix. The cache keeps K/V at
        group granularity and the attention einsums are grouped
        ([b, g, rep, s, t]) — no head-broadcast copy of the full cache
        per step (the GQA memory saving survives decode)."""
        s = q.shape[0]
        n_kv = k.shape[2]
        rep = np_local // n_kv
        max_len = cfg.max_position_embeddings
        initialized = self.has_variable("cache", "cached_key")
        ck = self.variable("cache", "cached_key", jnp.zeros,
                           (max_len, b, n_kv, kv), cfg.compute_dtype)
        cv = self.variable("cache", "cached_value", jnp.zeros,
                           (max_len, b, n_kv, kv), cfg.compute_dtype)
        ci = self.variable("cache", "cache_index",
                           lambda: jnp.zeros((), jnp.int32))
        idx = ci.value
        if (cfg.position_embedding_type == "rope"
                and self._layer_uses_rope()):
            pos = (position_ids if position_ids is not None
                   else idx + jnp.arange(s))
            rope_base, rope_scale = self._layer_rope()
            q = apply_rotary_emb(q, rope_base, pos,
                                 cfg.rotary_percent,
                                 cfg.rotary_interleaved,
                                 rope_scale)
            k = apply_rotary_emb(k, rope_base, pos,
                                 cfg.rotary_percent,
                                 cfg.rotary_interleaved,
                                 rope_scale)
        if not initialized:
            # init pass: create the variables, plain causal attention over
            # the given tokens (shapes/params identical to the real path)
            k_full, v_full, kv_len, offset = k, v, s, jnp.zeros((), jnp.int32)
        else:
            ck.value = jax.lax.dynamic_update_slice(
                ck.value, k.astype(cfg.compute_dtype), (idx, 0, 0, 0))
            cv.value = jax.lax.dynamic_update_slice(
                cv.value, v.astype(cfg.compute_dtype), (idx, 0, 0, 0))
            ci.value = idx + s
            k_full, v_full, kv_len, offset = ck.value, cv.value, max_len, idx
        qg = q.reshape(s, b, n_kv, rep, kv).astype(cfg.compute_dtype)
        kt = k_full.astype(cfg.compute_dtype)
        vt = v_full.astype(cfg.compute_dtype)
        if (s == 1 and initialized
                and cfg.position_embedding_type != "alibi"):
            # serving hot loop: stream the cache through VMEM once per
            # (batch, group) with tile skipping beyond the prefix and,
            # for windowed layers, before the window (contrib/gqa_decode)
            from apex_tpu.contrib import gqa_decode

            if gqa_decode.use_flash(kv_len, kv_shape=kt.shape):
                import math

                sm = 1.0 / math.sqrt(cfg.query_pre_attn_scalar or kv)
                ctx = gqa_decode.gqa_flash_decode(
                    qg[0], kt, vt, idx + s, sm,
                    window=self._layer_window(),
                    softcap=cfg.attn_logit_softcapping)
                ctx = ctx.reshape(1, b, np_local * kv)
                return self._output_proj(cfg, ctx)
        if (s > 1 and initialized
                and cfg.position_embedding_type != "alibi"):
            # speculative verify window (and any multi-token decode
            # chunk): one flash kernel over the s-position window
            # instead of materializing [b, g, rep, s, T] scores
            # (kernels/fused_cc, family b)
            from apex_tpu.kernels import fused_cc

            if fused_cc.use_window(kv_len, q_shape=qg.shape):
                import math

                sm = 1.0 / math.sqrt(cfg.query_pre_attn_scalar or kv)
                ctx = fused_cc.window_attention(
                    qg, kt, vt, offset, sm,
                    window=self._layer_window(),
                    softcap=cfg.attn_logit_softcapping)
                ctx = ctx.reshape(s, b, np_local * kv)
                return self._output_proj(cfg, ctx)
        scores = jnp.einsum("sbgrd,tbgd->bgrst", qg, kt,
                            preferred_element_type=jnp.float32)
        scores = scores / jnp.sqrt(
            cfg.query_pre_attn_scalar or kv).astype(jnp.float32)
        if cfg.attn_logit_softcapping is not None:
            cap = jnp.float32(cfg.attn_logit_softcapping)
            scores = cap * jnp.tanh(scores / cap)
        # causal over absolute positions: query i (at offset+i) sees keys
        # j <= offset+i; unfilled cache tail is masked the same way
        if cfg.position_embedding_type == "alibi":
            slopes = _local_alibi_slopes(cfg, n_kv * rep).reshape(
                n_kv, rep)
            scores = scores + (slopes[None, :, :, None, None]
                               * jnp.arange(kv_len, dtype=jnp.float32
                                            )[None, None, None, None, :])
        jpos = jnp.arange(kv_len)[None, :]
        ipos = offset + jnp.arange(s)[:, None]
        masked = jpos > ipos
        decode_win = self._layer_window()
        if decode_win is not None:
            # stale cache entries beyond the window stay resident but
            # invisible (Mistral semantics: 0 <= i - j < window)
            masked = masked | (ipos - jpos >= decode_win)
        scores = jnp.where(masked, -1e30, scores)
        probs = jax.nn.softmax(scores, axis=-1)
        ctx = jnp.einsum("bgrst,tbgd->sbgrd",
                         probs.astype(cfg.compute_dtype), vt,
                         preferred_element_type=jnp.float32)
        ctx = ctx.reshape(s, b, np_local * kv)
        return self._output_proj(cfg, ctx)


class ParallelMLP(nn.Module):
    """h -> 4h (column) -> gelu -> 4h -> h (row)
    (reference standalone_transformer_lm.py ParallelMLP)."""

    config: TransformerConfig

    @nn.compact
    def __call__(self, hidden_states):
        cfg = self.config
        if cfg.activation in ("swiglu", "geglu"):
            # Fused [gate | up] projection: each tp rank's local columns
            # split into its own gate/up halves (per-rank pairing is
            # self-consistent because shards are initialized per rank,
            # not sliced from a global matrix). geglu (Gemma family)
            # gates with tanh-approx gelu instead of silu.
            gate_up = ColumnParallelLinear(
                input_size=cfg.hidden_size, output_size=2 * cfg.ffn_size,
                gather_output=False, bias=False,
                params_dtype=cfg.params_dtype,
                sequence_parallel_enabled=cfg.sequence_parallel,
                name="dense_h_to_4h")(hidden_states.astype(cfg.compute_dtype))
            gate, up = jnp.split(gate_up.astype(jnp.float32), 2, axis=-1)
            act = jax.nn.silu if cfg.activation == "swiglu" else jax.nn.gelu
            x = (act(gate) * up).astype(cfg.compute_dtype)
        elif cfg.activation in ("gelu", "gelu_exact", "relu", "relu2"):
            x = ColumnParallelLinear(
                input_size=cfg.hidden_size, output_size=cfg.ffn_size,
                gather_output=False, bias=True, params_dtype=cfg.params_dtype,
                sequence_parallel_enabled=cfg.sequence_parallel,
                name="dense_h_to_4h")(hidden_states.astype(cfg.compute_dtype))
            xf = x.astype(jnp.float32)
            if cfg.activation in ("relu", "relu2"):
                xf = jax.nn.relu(xf)
                if cfg.activation == "relu2":  # Nemotron squared ReLU
                    xf = xf * xf
            else:
                xf = jax.nn.gelu(xf, approximate=(cfg.activation == "gelu"))
            x = xf.astype(cfg.compute_dtype)
        else:
            raise ValueError(f"unknown activation {cfg.activation!r}")
        x = RowParallelLinear(
            input_size=cfg.ffn_size, output_size=cfg.hidden_size,
            input_is_parallel=True,
            bias=(cfg.activation in ("gelu", "gelu_exact", "relu",
                                     "relu2")),
            params_dtype=cfg.params_dtype,
            sequence_parallel_enabled=cfg.sequence_parallel,
            name="dense_4h_to_h")(x)
        return x


def _make_mlp(cfg, moe: bool):
    """The feed-forward module of a layer, named ``mlp``: the expert layer
    (with its shared expert where ``moe_shared_expert_size`` is set) or
    the dense MLP."""
    if not moe:
        return ParallelMLP(cfg, name="mlp")
    routed = dict(
        hidden_size=cfg.hidden_size,
        ffn_hidden_size=cfg.moe_ffn_hidden_size or cfg.ffn_size,
        num_experts=cfg.num_moe_experts, top_k=cfg.moe_top_k,
        capacity_factor=cfg.moe_capacity_factor,
        jitter_eps=cfg.moe_jitter_eps, router_type=cfg.moe_router_type,
        dispatch_mode=cfg.moe_dispatch_mode,
        normalize_topk=cfg.moe_normalize_topk, activation=cfg.activation,
        params_dtype=cfg.params_dtype, compute_dtype=cfg.compute_dtype,
        local_experts=cfg.moe_local_experts,
        expert_offset=cfg.moe_expert_offset,
        router_score=cfg.moe_router_score,
        routed_scaling_factor=cfg.moe_routed_scaling_factor,
        seq_aux_loss=cfg.moe_seq_aux_loss_coeff > 0,
        sequence_parallel_enabled=cfg.sequence_parallel, name="mlp")
    if cfg.moe_shared_expert_size:
        from apex_tpu.transformer.moe.layer import SharedExpertMoE

        return SharedExpertMoE(
            shared_expert_size=cfg.moe_shared_expert_size,
            shared_expert_gated=cfg.moe_shared_expert_gated, **routed)
    from apex_tpu.transformer.moe import SwitchMLP

    return SwitchMLP(**routed)


class ParallelTransformerLayer(nn.Module):
    """Pre-LN transformer block (reference ParallelTransformerLayer)."""

    config: TransformerConfig
    layer_number: int = 0
    decode: bool = False

    def _is_moe_layer(self) -> bool:
        cfg = self.config
        after = self.layer_number - cfg.moe_first_dense_layers
        return (cfg.num_moe_experts is not None and after >= 0
                and after % cfg.moe_layer_freq == 0)

    def _one_sub_block(self, hidden_states, attention_mask, position_ids):
        """A ``layer_pattern`` layer: ``x + f(norm(x))``, ``f`` by this
        layer's letter."""
        cfg = self.config
        kind = cfg.layer_pattern[self.layer_number]
        if self.decode and kind == "M":
            raise ValueError("the Mamba-2 mixer has no decode path")
        x = _make_norm(cfg, "input_layernorm")(
            hidden_states.astype(jnp.float32)).astype(cfg.compute_dtype)
        if kind == "M":
            from apex_tpu.transformer.ssm import Mamba2Mixer

            out = Mamba2Mixer(cfg, name="mixer")(x)
        elif kind == "*":
            out = ParallelAttention(cfg, decode=self.decode,
                                    layer_number=self.layer_number,
                                    name="self_attention")(
                x, attention_mask, position_ids)
        else:
            out = _make_mlp(cfg, True)(x)
        return hidden_states + out.astype(hidden_states.dtype)

    @nn.compact
    def __call__(self, hidden_states, attention_mask=None, position_ids=None):
        cfg = self.config
        if cfg.layer_pattern is not None:
            return self._one_sub_block(hidden_states, attention_mask,
                                       position_ids)
        if cfg.pre_norm:
            ln1 = _make_norm(cfg, "input_layernorm")
            ln1_out = ln1(hidden_states.astype(jnp.float32)).astype(
                cfg.compute_dtype)
        else:  # OLMo-2: the attention branch reads the raw stream
            ln1_out = hidden_states.astype(cfg.compute_dtype)
        attn_out = ParallelAttention(cfg, decode=self.decode,
                                     layer_number=self.layer_number,
                                     name="self_attention")(
            ln1_out, attention_mask, position_ids)
        if cfg.sandwich_norm:
            # Gemma-2: norm each branch's OUTPUT before its residual add
            attn_out = _make_norm(cfg, "post_self_attn_norm")(
                attn_out.astype(jnp.float32)).astype(cfg.compute_dtype)
        rm = cfg.residual_multiplier
        if rm != 1.0:  # Granite: x + m * branch(...)
            attn_out = attn_out * jnp.asarray(rm, attn_out.dtype)
        residual = hidden_states  # pre-attn input (parallel-residual form)
        if not cfg.parallel_residual:
            hidden_states = hidden_states + attn_out.astype(
                hidden_states.dtype)
        # Phi/Falcon-7b: no second norm — both branches read ln1's
        # output. OLMo-2 (pre_norm=False): no pre-MLP norm either — the
        # MLP reads the post-attention residual stream raw.
        ln2 = (None if (cfg.parallel_residual_shared_ln
                        or not cfg.pre_norm)
               else _make_norm(cfg, "post_attention_layernorm"))
        mlp = _make_mlp(cfg, self._is_moe_layer())
        if ln2 is not None:
            mlp_in = ln2(hidden_states.astype(jnp.float32)).astype(
                cfg.compute_dtype)
        elif not cfg.pre_norm:
            # OLMo-2: the MLP reads the post-attention residual raw
            mlp_in = hidden_states.astype(cfg.compute_dtype)
        else:  # Phi/Falcon-7b shared-LN: both branches read ln1's output
            mlp_in = ln1_out
        mlp_out = mlp(mlp_in)
        if cfg.sandwich_norm:
            mlp_out = _make_norm(cfg, "post_mlp_norm")(
                mlp_out.astype(jnp.float32)).astype(cfg.compute_dtype)
        if rm != 1.0:
            mlp_out = mlp_out * jnp.asarray(rm, mlp_out.dtype)
        if cfg.parallel_residual:
            # GPT-NeoX form: both branches read the SAME input (ln2 is
            # applied to the pre-attn stream) and sum into one residual
            return (residual + attn_out.astype(residual.dtype)
                    + mlp_out.astype(residual.dtype))
        return hidden_states + mlp_out.astype(hidden_states.dtype)


class _ScanBlock(nn.Module):
    """lax.scan body for ParallelTransformer(scan_layers=True): one
    uniform layer, (carry, out) signature; params carry a leading
    [num_layers] axis under 'layers/layer'."""

    config: TransformerConfig
    decode: bool = False

    @nn.compact
    def __call__(self, hidden_states, attention_mask, position_ids):
        h = ParallelTransformerLayer(self.config, layer_number=0,
                                     decode=self.decode,
                                     name="layer")(hidden_states,
                                                   attention_mask,
                                                   position_ids)
        return h, None


def _remat_keeping_flash_residuals(block, wrapped, **kwargs):
    """``nn.remat(block)`` under the policy that keeps the flash forward's
    output and log-sum-exp (``contrib.fmha.FLASH_RESIDUAL_NAMES``) and
    nothing else: q, k, v come back from the recomputed qkv matmul, these
    two only from another run of the kernel. Where the kernel did not run
    in the block (a mask, a soft cap, the oracle path, flash off) no such
    name exists and nothing is kept. Counts the ``wrapped`` layers (one
    for a scanned block) as ``remat/save_flash_residuals`` at trace time;
    ``kernels/dispatch/flash_attention_pallas`` beside it says the kernel
    ran in them, ``kernels/dispatch/flash_attention_bsnd_pallas`` that it
    ran through its batch-major entry."""
    from apex_tpu.contrib.fmha import FLASH_RESIDUAL_NAMES
    from apex_tpu.telemetry.registry import get_registry

    get_registry().counter("remat/save_flash_residuals").inc(wrapped)
    return nn.remat(
        block, static_argnums=(),
        policy=jax.checkpoint_policies.save_only_these_names(
            *FLASH_RESIDUAL_NAMES),
        **kwargs)


class ParallelTransformer(nn.Module):
    """A stack of layers, optionally rematerialized per layer
    (reference ParallelTransformer with activation checkpointing -> here
    ``jax.checkpoint`` over each layer, or over the scanned block).

    A checkpointed layer keeps its input and, where flash attention's
    kernel ran in it, the kernel's output in the compute dtype and
    log-sum-exp in float32, both as the kernel wrote them: ``b*s*h*2 +
    b*n*s*4`` bytes a layer, for one kernel run a layer less in the
    backward. Where the heads fill whole 128-lane columns
    (``contrib.fmha.flash_attention_bsnd``) that is ``[b, s, n*d]`` and
    ``[b, n/c, c, s]``, nothing padded: GPT-2 345M at 16 x 1024 keeps
    33.5 + 1 MB a layer, 0.83 GB over 24 layers. Through the head-major
    call the output is ``[b, n, s, d]``, where a head of 64 pads to 128
    lanes on a TPU (67 MB a layer there; PERF.md section 6, PRs 27 and
    29). The rest of the layer is recomputed."""

    config: TransformerConfig
    num_layers: Optional[int] = None
    # None -> follow config.activation_checkpointing
    activation_checkpointing: Optional[bool] = None
    decode: bool = False

    @nn.compact
    def __call__(self, hidden_states, attention_mask=None, position_ids=None):
        cfg = self.config
        n = self.num_layers if self.num_layers is not None else cfg.num_layers
        remat_on = (cfg.activation_checkpointing
                    if self.activation_checkpointing is None
                    else self.activation_checkpointing)
        if cfg.scan_layers:
            if cfg.num_moe_experts is not None and cfg.moe_layer_freq != 1:
                raise ValueError(
                    "scan_layers needs a uniform stack: moe_layer_freq "
                    "must be 1 (every layer MoE) or num_moe_experts None")
            block = _ScanBlock
            if remat_on and not self.decode:
                block = _remat_keeping_flash_residuals(block, 1,
                                                       prevent_cse=False)
            scanned = nn.scan(
                block,
                variable_axes={"params": 0, "moe_losses": 0, "cache": 0},
                # split 'jitter' too: un-listed rng streams are DROPPED by
                # nn.scan, which would silently disable router jitter
                split_rngs={"params": True, "jitter": True},
                in_axes=(nn.broadcast, nn.broadcast), length=n,
                metadata_params={nn.PARTITION_NAME: None})
            h, _ = scanned(cfg, decode=self.decode, name="layers")(
                hidden_states, attention_mask, position_ids)
            return h
        layer = ParallelTransformerLayer
        if remat_on and not self.decode:
            layer = _remat_keeping_flash_residuals(layer, n)
        for i in range(n):
            hidden_states = layer(cfg, layer_number=i, decode=self.decode,
                                  name=f"layer_{i}")(
                hidden_states, attention_mask, position_ids)
        return hidden_states


def is_sequence_parallel_param(path: str) -> bool:
    """Path predicate for ``allreduce_sequence_parallel_grads`` on this
    model family: layernorm scales/biases, position embeddings, and the
    replicated biases of the row-parallel linears ('dense', 'dense_4h_to_h')
    are seq-partial under sequence parallelism. Column-parallel biases
    ('query_key_value', 'dense_h_to_4h') are per-rank shards with complete
    grads and must NOT be reduced."""
    if "layernorm" in path or "position_embeddings" in path:
        return True
    if path.endswith("bias"):
        parent = path.rsplit("/", 1)[0].rsplit("/", 1)[-1]
        return parent in ("dense", "dense_4h_to_h")
    return False
