"""Moonlight-16B-A3B (``model_type`` ``deepseek_v3``): one chip's share of
it. Multi-head latent attention (queries and keys of ``qk_nope_head_dim``
positionless and ``qk_rope_head_dim`` rotary channels beside values of
``v_head_dim``; the rotary key one vector a token), ``first_k_dense_replace``
leading dense SwiGLU layers, then gated experts under a bias-balanced
sigmoid router with shared experts and the sequence-wise balance loss;
RMSNorm, untied embedding and head. The chip holds the configuration's
count of experts and its slice of the vocabulary; the router scores all
the published experts. The program's side is ``apex_tpu.models.GPTModel``
over a ``TransformerConfig`` with ``kv_lora_rank``; the plain reference,
with the equations, is ``benchmark/reference/deepseek_v3.py``.

The canonical tensors are laid out as the published checkpoint's: ``wq``'s
columns a head's ``[nope | rope]``, ``wukv``'s a head's ``[key | value]``.
The program keeps ``[every head's nope | every head's rope]`` and ``[every
head's key | every head's value]`` (the kernels' layout) and fuses each
SwiGLU's ``[gate | up]``: ``to_program`` / ``from_program`` move the
columns."""

from benchmark import loadgen
from benchmark.families import megatron

TOP_LEAVES = {
    "wte": ("word_embeddings", "weight"),
    "lnf_g": ("final_layernorm", "weight"),
    "head": ("lm_head",),
}
ATTN = ("self_attention",)
# canonical tensor of every layer -> its leaf in ``transformer/layer_<i>``
LAYER_LEAVES = {
    "ln1_g": ("input_layernorm", "weight"),
    "ln2_g": ("post_attention_layernorm", "weight"),
    "wdkv": ATTN + ("kv_down", "kernel"),
    "kvn_g": ATTN + ("kv_norm", "weight"),
    "wo": ATTN + ("dense", "weight"),
}
Q_PROJ = ATTN + ("q_proj", "weight")
KV_UP = ATTN + ("kv_up", "weight")
# a SwiGLU's (gate, up) -> the program's fused [gate | up] leaf, and down
DENSE = {("d_gate", "d_up"): ("mlp", "dense_h_to_4h", "weight")}
DENSE_LEAVES = {"d_down": ("mlp", "dense_4h_to_h", "weight")}
EXPERT = {("e_gate", "e_up"): ("mlp", "routed", "experts", "w1"),
          ("s_gate", "s_up"): ("mlp", "shared_gate_up", "weight")}
EXPERT_LEAVES = {
    "e_router": ("mlp", "routed", "router", "gate_weight"),
    "e_down": ("mlp", "routed", "experts", "w2"),
    "s_down": ("mlp", "shared_down", "weight"),
}
# a buffer in the source, a parameter that no gradient reaches in the
# program: zeros go in, and it is no canonical tensor
ROUTER_BIAS = ("mlp", "routed", "router", "e_score_correction_bias")

TASKS = {"causal_lm": loadgen.causal_lm_batches}


def arch(config: dict) -> dict:
    assumed = config.get("assumed", {})
    if config["q_lora_rank"] is not None or config["n_group"] != 1 \
            or config["topk_group"] != 1 or config["moe_layer_freq"] != 1 \
            or config["scoring_func"] != "sigmoid" \
            or not config["norm_topk_prob"]:
        raise ValueError(
            "this family runs a direct q projection, a sigmoid router "
            "with normalised gates and no group limit, an expert layer "
            "after every dense one")
    return {
        "family": config["family"], "hidden": config["hidden_size"],
        "layers": config["num_hidden_layers"],
        "dense_layers": config["first_k_dense_replace"],
        "heads": config["num_attention_heads"],
        "nope_dim": config["qk_nope_head_dim"],
        "rope_dim": config["qk_rope_head_dim"],
        "v_dim": config["v_head_dim"], "kv_rank": config["kv_lora_rank"],
        "theta": float(config["rope_theta"]),
        "dense_ffn": config["intermediate_size"],
        # the router's width is the published count; the file's own key
        # counts the experts held here
        "experts": config.get("published", config)["n_routed_experts"],
        "experts_held": config["n_routed_experts"],
        "expert_offset": config.get("expert_offset", 0),
        "top_k": config["num_experts_per_tok"],
        "ffn": config["moe_intermediate_size"],
        "shared_ffn": (config["n_shared_experts"]
                       * config["moe_intermediate_size"]),
        "routed_scale": float(config["routed_scaling_factor"]),
        "aux_alpha": float(assumed["aux_loss_alpha"])
        if config["seq_aux"] else 0.0,
        "positions": config["max_position_embeddings"],
        "eps": config["rms_norm_eps"],
        # the held experts' rows are gathered into this many times their
        # expected number (a static shape); experts / held is room for
        # every assignment: nothing is dropped whatever the router does
        "held_rows_factor": assumed["held_rows_factor"],
        "vocab_real": config["vocab_size"],
        "vocab": assumed.get("padded_vocab_size", config["vocab_size"]),
    }


def _is_dense(arch, i):
    return i < arch["dense_layers"]


def shapes(arch: dict) -> dict:
    """A layer's tensors are named ``l<i>.<name>``, each on its own (the
    layers are of two kinds, so there is no stack): a tensor of its own
    in every per-tensor number of the comparison."""
    h, n = arch["hidden"], arch["heads"]
    dc, dr, dv, lat = (arch["nope_dim"], arch["rope_dim"], arch["v_dim"],
                       arch["kv_rank"])
    F, f, fs = arch["dense_ffn"], arch["ffn"], arch["shared_ffn"]
    held = arch["experts_held"]
    attention = {"ln1_g": (h,), "ln2_g": (h,), "wq": (h, n * (dc + dr)),
                 "wdkv": (h, lat + dr), "kvn_g": (lat,),
                 "wukv": (lat, n * (dc + dv)), "wo": (n * dv, h)}
    dense = {"d_gate": (h, F), "d_up": (h, F), "d_down": (F, h)}
    experts = {"e_router": (h, arch["experts"]), "e_gate": (held, h, f),
               "e_up": (held, h, f), "e_down": (held, f, h),
               "s_gate": (h, fs), "s_up": (h, fs), "s_down": (fs, h)}
    out = {"wte": (arch["vocab"], h), "head": (h, arch["vocab"]),
           "lnf_g": (h,)}
    for i in range(arch["layers"]):
        ffn = dense if _is_dense(arch, i) else experts
        out.update({f"l{i}.{name}": shape
                    for name, shape in {**attention, **ffn}.items()})
    return out


def _attention_params(arch):
    h, n = arch["hidden"], arch["heads"]
    dc, dr, dv, lat = (arch["nope_dim"], arch["rope_dim"], arch["v_dim"],
                       arch["kv_rank"])
    return h * n * (dc + dr) + h * (lat + dr) + lat * n * (dc + dv) \
        + n * dv * h


def matmul_params(arch: dict) -> int:
    """Parameters in a matrix product on a token's path on this chip:
    attention whole, the dense layers' SwiGLU, router and shared experts
    whole, of the held experts' matrices the ``top_k / experts`` share a
    token is expected to use (each of its ``top_k`` choices falls on a
    held expert with probability ``held / experts``), and the head's
    slice."""
    h = arch["hidden"]
    held = arch["top_k"] * arch["experts_held"] / arch["experts"]
    expert = (h * arch["experts"] + 3 * h * arch["shared_ffn"]
              + held * 3 * h * arch["ffn"])
    dense = arch["dense_layers"]
    return int(arch["layers"] * _attention_params(arch)
               + dense * 3 * h * arch["dense_ffn"]
               + (arch["layers"] - dense) * expert + h * arch["vocab"])


def fwd_flops_per_token(arch: dict, seq: int) -> float:
    """What this chip's share computes: 2 per matrix parameter and the
    attention's ``QK^T`` over ``nope + rope`` channels and ``PV`` over the
    value's (PaLM's count, no causal discount)."""
    attn = 2.0 * seq * arch["heads"] * (
        arch["nope_dim"] + arch["rope_dim"] + arch["v_dim"])
    return 2.0 * matmul_params(arch) + arch["layers"] * attn


def mla_attention_train_flops_per_step(arch, batch, seq) -> float:
    """What the latent attention's kernels have to do in one training
    step, from shapes alone and whatever the kernels' design: a causal
    (query, key) pair and head costs the forward its score (2 a channel of
    q and k) and its value sum (2 a channel of v); the backward the score
    again and the gradients of q and k (3 x 2 a channel of q and k) and
    of the probabilities and v (2 x 2 a channel of v). The pass under
    recomputation is not counted."""
    qk = arch["nope_dim"] + arch["rope_dim"]
    v = arch["v_dim"]
    pairs = batch * arch["heads"] * seq * (seq + 1) / 2.0
    return pairs * ((2 * qk + 2 * v) + 2 * (3 * qk + 2 * v)) \
        * arch["layers"]


def mla_attention_train_bytes_per_step(arch, batch, seq) -> float:
    """The least those kernels move: the forward reads q, the heads'
    positionless keys, the one rotary key a token and v (the compute
    dtype, 2 bytes) and writes the context and the log-sum-exp (float32);
    the backward reads all of those and the context's gradient and writes
    the gradients of q, both keys and v. Scores and probabilities never
    have to leave the chip's fast memory."""
    n = arch["heads"]
    qk = arch["nope_dim"] + arch["rope_dim"]
    ins = 2 * (n * qk + n * arch["nope_dim"] + arch["rope_dim"]
               + n * arch["v_dim"])
    out = 2 * n * arch["v_dim"]
    fwd = ins + out + 4 * n
    bwd = fwd + out + ins
    return float(batch * seq * arch["layers"] * (fwd + bwd))


# ------------------------------------------------------- the program's side

def _heads_apart(w, arch, second):
    """Published columns, a head's ``[first | second]``, -> the
    program's ``[every head's first | every head's second]``."""
    import jax.numpy as jnp

    n, dc = arch["heads"], arch["nope_dim"]
    w = w.reshape(*w.shape[:-1], n, dc + second)
    return jnp.concatenate(
        [w[..., :dc].reshape(*w.shape[:-2], n * dc),
         w[..., dc:].reshape(*w.shape[:-2], n * second)], axis=-1)


def _heads_together(w, arch, second):
    """The inverse of :func:`_heads_apart`."""
    import jax.numpy as jnp

    n, dc = arch["heads"], arch["nope_dim"]
    first = w[..., :n * dc].reshape(*w.shape[:-1], n, dc)
    rest = w[..., n * dc:].reshape(*w.shape[:-1], n, second)
    return jnp.concatenate([first, rest], axis=-1).reshape(w.shape)


def _ffn_leaves(arch, i):
    return (DENSE, DENSE_LEAVES) if _is_dense(arch, i) \
        else (EXPERT, EXPERT_LEAVES)


def to_program(canon: dict, arch: dict) -> dict:
    import jax.numpy as jnp

    out = {}
    for name, path in TOP_LEAVES.items():
        megatron._set(out, path, canon[name])
    for i in range(arch["layers"]):
        at = ("transformer", f"layer_{i}")
        fused, leaves = _ffn_leaves(arch, i)
        for name, path in {**LAYER_LEAVES, **leaves}.items():
            megatron._set(out, at + path, canon[f"l{i}.{name}"])
        for (gate, up), path in fused.items():
            megatron._set(out, at + path, jnp.concatenate(
                [canon[f"l{i}.{gate}"], canon[f"l{i}.{up}"]], axis=-1))
        megatron._set(out, at + Q_PROJ, _heads_apart(
            canon[f"l{i}.wq"], arch, arch["rope_dim"]))
        megatron._set(out, at + KV_UP, _heads_apart(
            canon[f"l{i}.wukv"], arch, arch["v_dim"]))
        if not _is_dense(arch, i):
            megatron._set(out, at + ROUTER_BIAS,
                          jnp.zeros((arch["experts"],), jnp.float32))
    return out


def from_program(tree: dict, arch: dict) -> dict:
    import jax.numpy as jnp

    out = {name: megatron._get(tree, path)
           for name, path in TOP_LEAVES.items()}
    for i in range(arch["layers"]):
        at = ("transformer", f"layer_{i}")
        fused, leaves = _ffn_leaves(arch, i)
        for name, path in {**LAYER_LEAVES, **leaves}.items():
            out[f"l{i}.{name}"] = megatron._get(tree, at + path)
        for (gate, up), path in fused.items():
            out[f"l{i}.{gate}"], out[f"l{i}.{up}"] = jnp.split(
                megatron._get(tree, at + path), 2, axis=-1)
        out[f"l{i}.wq"] = _heads_together(
            megatron._get(tree, at + Q_PROJ), arch, arch["rope_dim"])
        out[f"l{i}.wukv"] = _heads_together(
            megatron._get(tree, at + KV_UP), arch, arch["v_dim"])
    return out


def model_config(arch: dict, mix: dict):
    import jax.numpy as jnp

    from apex_tpu.models import TransformerConfig

    return TransformerConfig(
        hidden_size=arch["hidden"], num_layers=arch["layers"],
        num_attention_heads=arch["heads"],
        ffn_hidden_size=arch["dense_ffn"], vocab_size=arch["vocab"],
        max_position_embeddings=arch["positions"],
        layernorm_epsilon=arch["eps"], compute_dtype=jnp.bfloat16,
        normalization="rmsnorm", activation="swiglu", attention_bias=False,
        position_embedding_type="rope", rotary_base=arch["theta"],
        rotary_interleaved=True, kv_lora_rank=arch["kv_rank"],
        qk_nope_head_dim=arch["nope_dim"], qk_rope_head_dim=arch["rope_dim"],
        v_head_dim=arch["v_dim"],
        num_moe_experts=arch["experts"], moe_top_k=arch["top_k"],
        moe_first_dense_layers=arch["dense_layers"],
        moe_ffn_hidden_size=arch["ffn"], moe_normalize_topk=True,
        moe_router_score="sigmoid_bias",
        moe_routed_scaling_factor=arch["routed_scale"],
        moe_seq_aux_loss_coeff=arch["aux_alpha"],
        moe_shared_expert_size=arch["shared_ffn"],
        moe_shared_expert_gated=False,
        moe_local_experts=arch["experts_held"],
        moe_capacity_factor=arch["held_rows_factor"],
        moe_expert_offset=arch["expert_offset"],
        use_flash_attention=bool(mix.get("flash_attention", True)),
        tie_word_embeddings=False,
        activation_checkpointing=bool(mix.get("recompute", False)))


def build_model(arch: dict, mix: dict, decode: bool = False):
    from apex_tpu.models import GPTModel

    return GPTModel(model_config(arch, mix), decode=decode)


def loss(model):
    """Cross-entropy plus the configuration's coefficient times the
    expert layers' sequence-wise balance losses, collected from the
    ``moe_losses`` collection (where the held share's counts are sown
    too)."""
    from apex_tpu.models.gpt import gpt_loss_fn
    from apex_tpu.transformer.moe import seq_aux_loss_from_variables

    alpha = model.config.moe_seq_aux_loss_coeff

    def deepseek_loss(params, batch):
        logits, sown = model.apply({"params": params}, batch["tokens"],
                                   mutable=["moe_losses"])
        return (gpt_loss_fn(logits, batch["labels"])
                + alpha * seq_aux_loss_from_variables(sown))
    return deepseek_loss
