"""SDAR-30B-A3B-Chat (``model_type`` ``sdar_moe``): one chip's share of it
under expert parallelism, trained by diffusion over blocks. Qwen3-MoE's
network (grouped-query attention with per-head QK RMSNorm and rotary
positions, routed SwiGLU experts of which this chip holds some, RMSNorm,
untied embedding and head) run on rows ``[x0 ; xt]``, a clean and a noised
copy of each sequence side by side under the block-diffusion attention
rule, the head and the loss on the noisy half. The program's side is
``apex_tpu.models.GPTModel`` over a ``TransformerConfig`` with
``AttnMaskType.block_diffusion``; the plain reference, with the equations,
is ``benchmark/reference/sdar_moe.py``. What it shares with Keye's family
(the fused projections' layout, the held experts' tensors) it imports from
``families/keye_vl2.py``."""

import numpy as np

from benchmark import loadgen
from benchmark.families import keye_vl2, megatron

TOP_LEAVES = keye_vl2.TOP_LEAVES
# Keye's per-layer leaves without the indexer's
LAYER_LEAVES = {name: path for name, path in keye_vl2.LAYER_LEAVES.items()
                if "indexer" not in path}
QKV, W1 = keye_vl2.QKV, keye_vl2.W1


def block_diffusion_batches(mix: dict, arch: dict, seed: int):
    """Endless ``{"tokens", "noisy", "weights"}`` ``[batch, seq]``: the
    data ids (uniform over the slice's rows before the mask token), their
    noised copy and each position's loss weight. A block of
    ``block_length`` tokens draws ``t`` uniform on ``[t_min, 1]``; each of
    its tokens is replaced by the mask token with probability ``t``
    (``m``); ``weights`` = ``m / t``. The noise is part of the batch, so
    program and reference see the same rows and weights."""
    bl, mask_id = arch["block_length"], arch["mask_id"]
    b, s = mix["batch"], mix["seq"]
    if mix["block_length"] != bl or s % bl:
        raise ValueError(f"the mix's block_length ({mix['block_length']}) "
                         f"has to be the configuration's ({bl}) and divide "
                         f"seq ({s})")
    rng = loadgen._rng(seed, 7)
    while True:
        ids = rng.integers(0, mask_id, (b, s), dtype=np.int32)
        t = np.repeat(rng.uniform(mix["t_min"], 1.0, (b, s // bl)), bl,
                      axis=1)
        masked = rng.random((b, s)) < t
        yield {"tokens": ids,
               "noisy": np.where(masked, mask_id, ids).astype(np.int32),
               "weights": (masked / t).astype(np.float32)}


TASKS = {"block_diffusion": block_diffusion_batches}


def arch(config: dict) -> dict:
    assumed = config["assumed"]
    return {
        "family": config["family"], "hidden": config["hidden_size"],
        "layers": config["num_hidden_layers"],
        "heads": config["num_attention_heads"],
        "kv_heads": config["num_key_value_heads"],
        "head_dim": config["head_dim"],
        "ffn": config["moe_intermediate_size"],
        # the router's width is the published count; ``num_experts`` in
        # the file counts the experts held here (listed in ``reduced``)
        "experts": assumed.get("routed_experts", config["num_experts"]),
        "top_k": config["num_experts_per_tok"],
        "experts_held": config["num_experts"],
        "expert_offset": config.get("expert_offset", 0),
        "theta": float(config["rope_theta"]),
        "positions": config["max_position_embeddings"],
        "eps": config["rms_norm_eps"],
        "aux_coef": assumed.get("router_aux_loss_coef", 0.001),
        "held_rows_factor": assumed["held_rows_factor"],
        "block_length": assumed["block_length"],
        "mask_id": assumed.get("mask_token_id", config["vocab_size"] - 1),
        "vocab_real": config["vocab_size"],
        "vocab": assumed.get("padded_vocab_size", config["vocab_size"]),
    }


def shapes(arch: dict) -> dict:
    h, L, d = arch["hidden"], arch["layers"], arch["head_dim"]
    q, kv = arch["heads"] * d, arch["kv_heads"] * d
    n, f = arch["experts_held"], arch["ffn"]
    return {
        "wte": (arch["vocab"], h), "head": (h, arch["vocab"]),
        "lnf_g": (h,),
        "layers.ln1_g": (L, h), "layers.ln2_g": (L, h),
        "layers.wq": (L, h, q), "layers.wk": (L, h, kv),
        "layers.wv": (L, h, kv), "layers.wo": (L, q, h),
        "layers.qn_g": (L, d), "layers.kn_g": (L, d),
        "layers.router": (L, h, arch["experts"]),
        "layers.egate": (L, n, h, f), "layers.eup": (L, n, h, f),
        "layers.edown": (L, n, f, h),
    }


def layer_matmul_params(arch: dict) -> float:
    """Parameters in a matrix product on one row's path through one layer
    on this chip: attention and router matrices whole, and of the held
    experts' matrices the ``top_k / experts`` share a row is expected to
    use."""
    h, d = arch["hidden"], arch["head_dim"]
    q, kv = arch["heads"] * d, arch["kv_heads"] * d
    held = arch["top_k"] * arch["experts_held"] / arch["experts"]
    return (2 * h * q + 2 * h * kv + h * arch["experts"]
            + held * 3 * h * arch["ffn"])


def matmul_params(arch: dict) -> int:
    """The same for one row through the whole model: the layers and the
    head's slice."""
    return int(arch["layers"] * layer_matmul_params(arch)
               + arch["hidden"] * arch["vocab"])


def fwd_flops_per_token(arch: dict, seq: int) -> float:
    """What the algorithm needs on this chip for one DATA token (the
    harness counts ``batch x seq`` of them a step): its two rows, the
    clean and the noisy copy, through the layers' matrices, the noisy one
    through the head, and QK^T and PV over the pairs the rule lets its two
    queries see: ``seq + block_length`` a layer between them
    (``4 * heads * head_dim`` a pair)."""
    pairs = seq + arch["block_length"]
    return (2.0 * (2 * arch["layers"] * layer_matmul_params(arch)
                   + arch["hidden"] * arch["vocab"])
            + arch["layers"] * 4.0 * pairs * arch["heads"]
            * arch["head_dim"])


def visible_pairs(arch: dict, seq: int) -> int:
    """(query, key) pairs a head sees in one row of ``2 * seq``:
    ``L^2 + L * bl``."""
    return seq * (seq + arch["block_length"])


def blockdiff_attention_train_flops_per_step(arch, batch, seq) -> float:
    """What the ``blockdiff_attention_*`` kernels have to do in one
    training step, over the visible pairs alone: QK^T and PV forward
    (``4 * head_dim`` a pair and query head), twice that again backward
    (dV, dP, dQ, dK). Scores the backward computes again, masked pairs of
    a tile that runs, and the forward under recomputation are not
    counted."""
    pairs = visible_pairs(arch, seq) * batch * arch["layers"]
    return 4.0 * 3 * pairs * arch["heads"] * arch["head_dim"]


def blockdiff_attention_train_bytes_per_step(arch, batch, seq) -> float:
    """The bytes those kernels have to move at the least: q, k, v, out and
    their gradients once each over the ``2 * seq`` rows (bf16, k and v at
    the query heads' count, as the kernels take them), the log-sum-exp and
    its backward counterpart (the rows' ``delta``) once each (float32)."""
    rows = 2 * seq * batch * arch["layers"]
    return rows * arch["heads"] * (8 * arch["head_dim"] * 2 + 2 * 4)


# ------------------------------------------------------- the program's side

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
        megatron._set(out, at + QKV, keye_vl2._fuse_qkv(
            lp["wq"], lp["wk"], lp["wv"], arch))
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
        lp["wq"], lp["wk"], lp["wv"] = keye_vl2._split_qkv(
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
    from apex_tpu.transformer.enums import AttnMaskType

    return TransformerConfig(
        hidden_size=arch["hidden"], num_layers=arch["layers"],
        num_attention_heads=arch["heads"], head_dim=arch["head_dim"],
        num_query_groups=arch["kv_heads"], ffn_hidden_size=arch["ffn"],
        vocab_size=arch["vocab"], max_position_embeddings=arch["positions"],
        layernorm_epsilon=arch["eps"], compute_dtype=jnp.bfloat16,
        normalization="rmsnorm", activation="swiglu", attention_bias=False,
        qk_norm="head", position_embedding_type="rope",
        rotary_base=arch["theta"],
        attn_mask_type=AttnMaskType.block_diffusion,
        diffusion_block_length=arch["block_length"],
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
    """The weighted cross-entropy at the masked positions of the noisy
    half plus the router's load-balancing loss (its coefficient the
    configuration's), collected from the ``moe_losses`` collection. The
    model's row is ``[tokens ; noisy]``."""
    import jax.numpy as jnp

    from apex_tpu.models.gpt import block_diffusion_loss_fn
    from apex_tpu.transformer.moe import moe_loss_from_variables

    cfg = model.config

    def sdar_loss(params, batch):
        rows = jnp.concatenate([batch["tokens"], batch["noisy"]], axis=1)
        logits, sown = model.apply({"params": params}, rows,
                                   mutable=["moe_losses"])
        return (block_diffusion_loss_fn(logits, batch["tokens"],
                                        batch["weights"])
                + moe_loss_from_variables(sown, cfg.moe_aux_loss_coeff))
    return sdar_loss
