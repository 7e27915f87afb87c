"""Keye-VL-2.0-30B-A3B's language model (``model_type`` ``KeyeVL2``): one
chip's share of it under expert parallelism. Grouped-query attention under
a DeepSeek-Sparse-Attention indexer, routed experts of which this chip
holds ``num_local_experts``, RMSNorm, multi-component rotary positions,
untied embedding and head. The program's side is
``apex_tpu.models.GPTModel`` over a ``TransformerConfig`` with the
indexer, the rotary sections and the held share of the experts; the plain
reference, with the equations, is ``benchmark/reference/keye_vl2.py``."""

import numpy as np

from benchmark import loadgen
from benchmark.families import megatron

TOP_LEAVES = {
    "wte": ("word_embeddings", "weight"),
    "lnf_g": ("final_layernorm", "weight"),
    "head": ("lm_head",),
}
# canonical per-layer tensor -> its leaf in ``transformer/layer_<i>``;
# ``wq | wk | wv`` and ``egate | eup`` are fused there (``_fuse``)
LAYER_LEAVES = {
    "ln1_g": ("input_layernorm", "weight"),
    "ln2_g": ("post_attention_layernorm", "weight"),
    "wo": ("self_attention", "dense", "weight"),
    "qn_g": ("self_attention", "q_norm", "weight"),
    "kn_g": ("self_attention", "k_norm", "weight"),
    "iwq": ("self_attention", "indexer", "wq"),
    "iwk": ("self_attention", "indexer", "wk"),
    "iww": ("self_attention", "indexer", "weights_proj"),
    "ikn_g": ("self_attention", "indexer", "k_norm", "weight"),
    "ikn_b": ("self_attention", "indexer", "k_norm", "bias"),
    "router": ("mlp", "router", "gate_weight"),
    "edown": ("mlp", "experts", "w2"),
}
QKV = ("self_attention", "query_key_value", "weight")
W1 = ("mlp", "experts", "w1")


def lm_batches(mix: dict, arch: dict, seed: int):
    """``loadgen.causal_lm_batches`` with the rotary positions beside the
    ids: ``positions`` ``[batch, 3, seq]``, text positions ``0..seq-1`` in
    all three components."""
    pos = np.broadcast_to(np.arange(mix["seq"], dtype=np.int32),
                          (mix["batch"], 3, mix["seq"]))
    for batch in loadgen.causal_lm_batches(mix, arch, seed):
        yield dict(batch, positions=pos)


TASKS = {"causal_lm": lm_batches}


def arch(config: dict) -> dict:
    sa = config["sa_config"]
    assumed = config.get("assumed", {})
    return {
        "family": config["family"], "hidden": config["hidden_size"],
        "layers": config["num_hidden_layers"],
        "heads": config["num_attention_heads"],
        "kv_heads": config["num_key_value_heads"],
        "head_dim": config["head_dim"],
        "ffn": config["moe_intermediate_size"],
        "experts": config["num_experts"],
        "top_k": config["num_experts_per_tok"],
        "experts_held": config.get("num_local_experts",
                                   config["num_experts"]),
        "expert_offset": config.get("expert_offset", 0),
        "indexer_heads": sa["indexer_num_heads"],
        "indexer_dim": sa["indexer_head_dim"],
        "indexer_topk": sa["topk"],
        "theta": float(config["rope_theta"]),
        "sections": tuple(config["rope_scaling"]["mrope_section"]),
        "positions": config["max_position_embeddings"],
        "eps": config["rms_norm_eps"],
        "aux_coef": assumed.get("router_aux_loss_coef", 0.001),
        # the held experts' rows are gathered into this many times their
        # expected number (a static shape); experts / held is room for
        # every assignment: nothing is dropped whatever the router does
        "held_rows_factor": assumed["held_rows_factor"],
        "vocab_real": config["vocab_size"],
        "vocab": assumed.get("padded_vocab_size", config["vocab_size"]),
    }


def shapes(arch: dict) -> dict:
    h, L, d = arch["hidden"], arch["layers"], arch["head_dim"]
    q, kv = arch["heads"] * d, arch["kv_heads"] * d
    H, D = arch["indexer_heads"], arch["indexer_dim"]
    n, f = arch["experts_held"], arch["ffn"]
    return {
        "wte": (arch["vocab"], h), "head": (h, arch["vocab"]),
        "lnf_g": (h,),
        "layers.ln1_g": (L, h), "layers.ln2_g": (L, h),
        "layers.wq": (L, h, q), "layers.wk": (L, h, kv),
        "layers.wv": (L, h, kv), "layers.wo": (L, q, h),
        "layers.qn_g": (L, d), "layers.kn_g": (L, d),
        "layers.iwq": (L, h, H * D), "layers.iwk": (L, h, D),
        "layers.iww": (L, h, H),
        "layers.ikn_g": (L, D), "layers.ikn_b": (L, D),
        "layers.router": (L, h, arch["experts"]),
        "layers.egate": (L, n, h, f), "layers.eup": (L, n, h, f),
        "layers.edown": (L, n, f, h),
    }


def matmul_params(arch: dict) -> int:
    """Parameters in a matrix product on a token's path on this chip:
    attention, indexer and router matrices whole, the head's slice, and
    of the held experts' matrices the ``top_k / experts`` share a token
    is expected to use (each token's ``top_k`` choices fall on a held
    expert with probability ``held / experts``)."""
    h, d = arch["hidden"], arch["head_dim"]
    q, kv = arch["heads"] * d, arch["kv_heads"] * d
    layer = (2 * h * q + 2 * h * kv
             + h * arch["indexer_dim"] * (arch["indexer_heads"] + 1)
             + h * arch["indexer_heads"] + h * arch["experts"])
    held = arch["top_k"] * arch["experts_held"] / arch["experts"]
    return int(arch["layers"] * (layer + held * 3 * h * arch["ffn"])
               + h * arch["vocab"])


def mean_selected_keys(arch: dict, seq: int) -> float:
    """``kbar``: the mean over the sequence of ``min(t + 1, topk)``."""
    k = min(arch["indexer_topk"], seq)
    return (k * (k + 1) / 2 + (seq - k) * k) / seq


def fwd_flops_per_token(arch: dict, seq: int) -> float:
    """What the algorithm needs on this chip: 2 per matrix parameter, QK^T
    and PV over the selected keys alone (``4 * kbar * heads * head_dim``),
    and the indexer's scores over the causal pairs
    (``2 * (seq + 1) / 2 * indexer_heads * indexer_dim``). Dense work a
    masked implementation does beyond that is not model work."""
    attn = 4.0 * mean_selected_keys(arch, seq) \
        * arch["heads"] * arch["head_dim"]
    index = 2.0 * ((seq + 1) / 2) * arch["indexer_heads"] \
        * arch["indexer_dim"]
    return 2.0 * matmul_params(arch) + arch["layers"] * (attn + index)


def sparse_attention_train_flops_per_step(arch, batch, seq) -> float:
    """What the ``sparse_attention_*`` kernels have to do in one training
    step, over the selected (query, key) pairs alone: QK^T and PV forward
    (4 per pair, head and head dimension), twice that again backward (dV,
    dP, dQ, dK), and QK^T once more for the head-summed probabilities (2).
    Scores the backward computes again, and kernel runs under
    recomputation, are not counted."""
    pairs = mean_selected_keys(arch, seq) * seq * batch * arch["layers"]
    return (4.0 * 3 + 2.0) * pairs * arch["heads"] * arch["head_dim"]


def sparse_attention_train_bytes_per_step(arch, batch, seq) -> float:
    """The bytes those kernels have to move at the least: q, k, v, out
    and their gradients once each (bf16, k and v at the query heads'
    count, as the kernels take them), the selection once a kernel (int8,
    four kernels) and the head-summed probabilities once (float32)."""
    rows = batch * seq * arch["layers"]
    qkvo = 8 * rows * arch["heads"] * arch["head_dim"] * 2
    return qkvo + rows * seq * (4 * 1 + 4)


# ------------------------------------------------------- the program's side

def _fuse_qkv(wq, wk, wv, arch):
    """``[h, q heads | per KV group: k, v]``, the fused projection's
    columns (``ParallelAttention``, grouped-query branch)."""
    import jax.numpy as jnp

    g, d = arch["kv_heads"], arch["head_dim"]
    h = wq.shape[0]
    kv = jnp.concatenate([wk.reshape(h, g, d), wv.reshape(h, g, d)], -1)
    return jnp.concatenate([wq, kv.reshape(h, 2 * g * d)], -1)


def _split_qkv(w, arch):
    g, d = arch["kv_heads"], arch["head_dim"]
    q = arch["heads"] * d
    h = w.shape[0]
    kv = w[:, q:].reshape(h, g, 2 * d)
    return (w[:, :q], kv[..., :d].reshape(h, g * d),
            kv[..., d:].reshape(h, g * d))


def to_program(canon: dict, arch: dict) -> dict:
    import jax.numpy as jnp

    out = {}
    for name, path in TOP_LEAVES.items():
        megatron._set(out, path, canon[name])
    for i in range(arch["layers"]):
        at = ("transformer", f"layer_{i}")
        lp = {k[len("layers."):]: v[i] for k, v in canon.items()
              if k.startswith("layers.")}
        for name, path in LAYER_LEAVES.items():
            megatron._set(out, at + path, lp[name])
        megatron._set(out, at + QKV,
                      _fuse_qkv(lp["wq"], lp["wk"], lp["wv"], arch))
        megatron._set(out, at + W1,
                      jnp.concatenate([lp["egate"], lp["eup"]], -1))
    return out


def from_program(tree: dict, arch: dict) -> dict:
    import jax.numpy as jnp

    out = {name: megatron._get(tree, path)
           for name, path in TOP_LEAVES.items()}
    layers = []
    for i in range(arch["layers"]):
        at = ("transformer", f"layer_{i}")
        lp = {name: megatron._get(tree, at + path)
              for name, path in LAYER_LEAVES.items()}
        lp["wq"], lp["wk"], lp["wv"] = _split_qkv(
            megatron._get(tree, at + QKV), arch)
        lp["egate"], lp["eup"] = jnp.split(megatron._get(tree, at + W1), 2,
                                           axis=-1)
        layers.append(lp)
    for name in layers[0]:
        out[f"layers.{name}"] = jnp.stack([lp[name] for lp in layers])
    return out


def model_config(arch: dict, mix: dict):
    import jax.numpy as jnp

    from apex_tpu.models import TransformerConfig

    return TransformerConfig(
        hidden_size=arch["hidden"], num_layers=arch["layers"],
        num_attention_heads=arch["heads"], head_dim=arch["head_dim"],
        num_query_groups=arch["kv_heads"], ffn_hidden_size=arch["ffn"],
        vocab_size=arch["vocab"], max_position_embeddings=arch["positions"],
        layernorm_epsilon=arch["eps"], compute_dtype=jnp.bfloat16,
        normalization="rmsnorm", activation="swiglu", attention_bias=False,
        qk_norm="head", position_embedding_type="rope",
        rotary_base=arch["theta"], rope_sections=arch["sections"],
        indexer_heads=arch["indexer_heads"],
        indexer_head_dim=arch["indexer_dim"],
        indexer_topk=arch["indexer_topk"],
        num_moe_experts=arch["experts"], moe_top_k=arch["top_k"],
        moe_normalize_topk=True, moe_local_experts=arch["experts_held"],
        moe_capacity_factor=arch["held_rows_factor"],
        moe_expert_offset=arch["expert_offset"],
        moe_aux_loss_coeff=arch["aux_coef"],
        use_flash_attention=bool(mix.get("flash_attention", True)),
        tie_word_embeddings=False,
        activation_checkpointing=bool(mix.get("recompute", False)))


def build_model(arch: dict, mix: dict, decode: bool = False):
    from apex_tpu.models import GPTModel

    return GPTModel(model_config(arch, mix), decode=decode)


def loss(model):
    """Cross-entropy plus the router's load-balancing loss (its
    coefficient the configuration's) plus the indexers' loss, collected
    from the ``moe_losses`` collection."""
    from apex_tpu.models.gpt import gpt_loss_fn
    from apex_tpu.models.transformer_lm import indexer_loss_from_variables
    from apex_tpu.transformer.moe import moe_loss_from_variables

    cfg = model.config

    def keye_loss(params, batch):
        logits, sown = model.apply(
            {"params": params}, batch["tokens"],
            position_ids=batch["positions"].transpose(1, 0, 2),
            mutable=["moe_losses"])
        return (gpt_loss_fn(logits, batch["labels"])
                + moe_loss_from_variables(sown, cfg.moe_aux_loss_coeff)
                + indexer_loss_from_variables(sown))
    return keye_loss
