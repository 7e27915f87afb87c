"""Laguna-XS.2 (``model_type`` ``laguna``): one chip's share of it under
expert parallelism. Layers of two attention kinds in the published order
(``layer_types``): full causal attention with ``num_attention_heads_per_layer``
query heads and YaRN on half of each head, and a sliding window with more
heads and plain rotary on all of it; 8 key/value heads of 128 on both, a
per-head sigmoid gate on attention's output, one leading dense SwiGLU layer
(``mlp_layer_types``), then SwiGLU experts under a sigmoid router with one
shared expert; RMSNorm, untied embedding and head. The program's side is
``apex_tpu.models.GPTModel`` over a ``TransformerConfig`` with
``attention_kinds``; the plain reference, with the equations, is
``benchmark/reference/laguna.py``.

The canonical tensors are a layer's own (``l<i>.<name>``: the layers differ
in heads and in their feed-forward), ``wq`` ``[hidden, heads x 128]``,
``wk`` and ``wv`` ``[hidden, 8 x 128]``, ``wg`` ``[hidden, heads]``. The
program fuses ``wq | wk | wv`` (per K/V group ``[k | v]``) and each
SwiGLU's ``[gate | up]``: ``to_program`` / ``from_program`` move the
columns."""

from benchmark import loadgen
from benchmark.families import deepseek_v3, keye_vl2, megatron

FULL, WINDOW = "full_attention", "sliding_attention"
TOP_LEAVES = deepseek_v3.TOP_LEAVES
ATTN = ("self_attention",)
LAYER_LEAVES = {
    "ln1_g": ("input_layernorm", "weight"),
    "ln2_g": ("post_attention_layernorm", "weight"),
    "wo": ATTN + ("dense", "weight"),
    "wg": ATTN + ("gate", "weight"),
}
QKV = ATTN + ("query_key_value", "weight")
DENSE, DENSE_LEAVES = deepseek_v3.DENSE, deepseek_v3.DENSE_LEAVES
EXPERT, EXPERT_LEAVES = deepseek_v3.EXPERT, deepseek_v3.EXPERT_LEAVES
ROUTER_BIAS = deepseek_v3.ROUTER_BIAS

TASKS = {"causal_lm": loadgen.causal_lm_batches}


def arch(config: dict) -> dict:
    assumed = config["assumed"]
    rope = config["rope_parameters"]
    full, window = rope[FULL], rope[WINDOW]
    if (full["rope_type"] != "yarn" or window["rope_type"] != "default"
            or set(config["layer_types"]) - {FULL, WINDOW}
            or set(config["mlp_layer_types"]) - {"dense", "sparse"}
            or config["moe_apply_router_weight_on_input"]):
        raise ValueError(
            "this family runs full layers under YaRN and window layers "
            "under plain rotary, dense and sparse MLP layers, and the "
            "router's weight on the experts' output")
    layers = config["num_hidden_layers"]
    return {
        "family": config["family"], "hidden": config["hidden_size"],
        "layers": layers,
        "heads": config["num_attention_heads"],
        "layer_types": tuple(config["layer_types"][:layers]),
        "layer_heads": tuple(config["num_attention_heads_per_layer"]
                             [:layers]),
        "mlp_types": tuple(config["mlp_layer_types"][:layers]),
        "kv_heads": config["num_key_value_heads"],
        "head_dim": config["head_dim"],
        "window": config["sliding_window"],
        "gate": bool(config["gating"]),
        "full_theta": float(full["rope_theta"]),
        "full_percent": float(full["partial_rotary_factor"]),
        "yarn_factor": float(full["factor"]),
        "yarn_original": int(full["original_max_position_embeddings"]),
        "yarn_beta_fast": float(full["beta_fast"]),
        "yarn_beta_slow": float(full["beta_slow"]),
        "yarn_attention_factor": float(full["attention_factor"]),
        "window_theta": float(window["rope_theta"]),
        "window_percent": float(window["partial_rotary_factor"]),
        "dense_ffn": config["intermediate_size"],
        # the router's width is the published count; ``num_experts`` in
        # the file counts the experts held here (listed in ``reduced``)
        "experts": config["published"]["num_experts"],
        "experts_held": config["num_experts"],
        "expert_offset": config.get("expert_offset", 0),
        "top_k": config["num_experts_per_tok"],
        "ffn": config["moe_intermediate_size"],
        "shared_ffn": config["shared_expert_intermediate_size"],
        "routed_scale": float(config["moe_routed_scaling_factor"]),
        "positions": config["max_position_embeddings"],
        "eps": config["rms_norm_eps"],
        # the held experts' rows are gathered into this many times their
        # expected number (a static shape); experts / held is room for
        # every assignment: nothing is dropped whatever the router does
        "held_rows_factor": assumed["held_rows_factor"],
        "vocab_real": config["vocab_size"],
        "vocab": assumed.get("padded_vocab_size", config["vocab_size"]),
    }


def is_dense(arch, i):
    return arch["mlp_types"][i] == "dense"


def is_window(arch, i):
    return arch["layer_types"][i] == WINDOW


def shapes(arch: dict) -> dict:
    """A layer's tensors are named ``l<i>.<name>``, each on its own: the
    layers differ in heads and in their feed-forward, so there is no
    stack."""
    h, d, g = arch["hidden"], arch["head_dim"], arch["kv_heads"]
    F, f, fs = arch["dense_ffn"], arch["ffn"], arch["shared_ffn"]
    held = arch["experts_held"]
    dense = {"d_gate": (h, F), "d_up": (h, F), "d_down": (F, h)}
    experts = {"e_router": (h, arch["experts"]), "e_gate": (held, h, f),
               "e_up": (held, h, f), "e_down": (held, f, h),
               "s_gate": (h, fs), "s_up": (h, fs), "s_down": (fs, h)}
    out = {"wte": (arch["vocab"], h), "head": (h, arch["vocab"]),
           "lnf_g": (h,)}
    for i, n in enumerate(arch["layer_heads"]):
        layer = {"ln1_g": (h,), "ln2_g": (h,), "wq": (h, n * d),
                 "wk": (h, g * d), "wv": (h, g * d), "wo": (n * d, h)}
        if arch["gate"]:
            layer["wg"] = (h, n)
        layer.update(dense if is_dense(arch, i) else experts)
        out.update({f"l{i}.{name}": shape for name, shape in layer.items()})
    return out


def _attention_params(arch, i):
    h, d = arch["hidden"], arch["head_dim"]
    n = arch["layer_heads"][i]
    return (2 * h * n * d + 2 * h * arch["kv_heads"] * d
            + (h * n if arch["gate"] else 0))


def matmul_params(arch: dict) -> int:
    """Parameters in a matrix product on a token's path on this chip:
    attention and its gate whole (by the layer's heads), the dense layer's
    SwiGLU, router and shared expert whole, of the held experts' matrices
    the ``top_k / experts`` share a token is expected to use, and the
    head's slice."""
    h = arch["hidden"]
    held = arch["top_k"] * arch["experts_held"] / arch["experts"]
    expert = (h * arch["experts"] + 3 * h * arch["shared_ffn"]
              + held * 3 * h * arch["ffn"])
    total = h * arch["vocab"]
    for i in range(arch["layers"]):
        total += _attention_params(arch, i) + (
            3 * h * arch["dense_ffn"] if is_dense(arch, i) else expert)
    return int(total)


def visible_pairs(arch: dict, i: int, seq: int) -> float:
    """(query, key) pairs a head of layer ``i`` sees in a row of ``seq``:
    the causal ones on a full layer, the band's ``w (w + 1) / 2 + (s - w)
    w`` on a window layer (all causal pairs where ``w >= s``)."""
    w = min(arch["window"], seq) if is_window(arch, i) else seq
    return w * (w + 1) / 2 + (seq - w) * w


def fwd_flops_per_token(arch: dict, seq: int) -> float:
    """What the algorithm needs on this chip: 2 per matrix parameter, and
    QK^T and PV over the pairs each layer's heads see (``4 x head_dim`` a
    pair and head): the band on a window layer, the causal pairs on a
    full one."""
    attn = sum(4.0 * visible_pairs(arch, i, seq) / seq
               * arch["layer_heads"][i] * arch["head_dim"]
               for i in range(arch["layers"]))
    return 2.0 * matmul_params(arch) + attn


def _window_layers(arch):
    return [i for i in range(arch["layers"]) if is_window(arch, i)]


def window_attention_train_flops_per_step(arch, batch, seq) -> float:
    """What the ``window_attention_*`` kernels have to do in one training
    step, over the band's visible pairs alone: QK^T and PV forward (``4 x
    head_dim`` a pair and query head), twice that again backward (dV, dP,
    dQ, dK). Scores the backward computes again, masked pairs of a tile
    that runs and the forward under recomputation are not counted."""
    return sum(4.0 * 3 * visible_pairs(arch, i, seq) * batch
               * arch["layer_heads"][i] * arch["head_dim"]
               for i in _window_layers(arch))


def window_attention_train_bytes_per_step(arch, batch, seq) -> float:
    """The bytes those kernels have to move at the least, once a pass
    (bf16): the forward reads q and k, v at their 8 K/V heads and writes
    the context and the log-sum-exp (float32); the backward reads those
    and the context's gradient and writes the gradients of q, k and v.
    Scores and probabilities never have to leave the chip's fast
    memory."""
    d, g = arch["head_dim"], arch["kv_heads"]
    total = 0.0
    for i in _window_layers(arch):
        n = arch["layer_heads"][i]
        ins = 2 * d * (n + 2 * g)
        out = 2 * n * d
        fwd = ins + out + 4 * n
        bwd = fwd + out + ins
        total += batch * seq * (fwd + bwd)
    return total


# ------------------------------------------------------- the program's side

def _leaves(arch, i):
    fused, leaves = (DENSE, DENSE_LEAVES) if is_dense(arch, i) \
        else (EXPERT, EXPERT_LEAVES)
    layer = {k: v for k, v in LAYER_LEAVES.items()
             if k != "wg" or arch["gate"]}
    return fused, {**layer, **leaves}


def _qkv_arch(arch, i):
    return {"heads": arch["layer_heads"][i], "kv_heads": arch["kv_heads"],
            "head_dim": arch["head_dim"]}


def to_program(canon: dict, arch: dict) -> dict:
    import jax.numpy as jnp

    out = {}
    for name, path in TOP_LEAVES.items():
        megatron._set(out, path, canon[name])
    for i in range(arch["layers"]):
        at = ("transformer", f"layer_{i}")
        fused, leaves = _leaves(arch, i)
        for name, path in leaves.items():
            megatron._set(out, at + path, canon[f"l{i}.{name}"])
        for (gate, up), path in fused.items():
            megatron._set(out, at + path, jnp.concatenate(
                [canon[f"l{i}.{gate}"], canon[f"l{i}.{up}"]], axis=-1))
        megatron._set(out, at + QKV, keye_vl2._fuse_qkv(
            canon[f"l{i}.wq"], canon[f"l{i}.wk"], canon[f"l{i}.wv"],
            _qkv_arch(arch, i)))
        if not is_dense(arch, i):
            megatron._set(out, at + ROUTER_BIAS,
                          jnp.zeros((arch["experts"],), jnp.float32))
    return out


def from_program(tree: dict, arch: dict) -> dict:
    import jax.numpy as jnp

    out = {name: megatron._get(tree, path)
           for name, path in TOP_LEAVES.items()}
    for i in range(arch["layers"]):
        at = ("transformer", f"layer_{i}")
        fused, leaves = _leaves(arch, i)
        for name, path in leaves.items():
            out[f"l{i}.{name}"] = megatron._get(tree, at + path)
        for (gate, up), path in fused.items():
            out[f"l{i}.{gate}"], out[f"l{i}.{up}"] = jnp.split(
                megatron._get(tree, at + path), 2, axis=-1)
        out[f"l{i}.wq"], out[f"l{i}.wk"], out[f"l{i}.wv"] = \
            keye_vl2._split_qkv(megatron._get(tree, at + QKV),
                                _qkv_arch(arch, i))
    return out


def attention_kinds(arch: dict) -> tuple:
    """The two kinds as ``apex_tpu.models.AttentionKind`` records, each
    with its heads (the first layer of the kind's)."""
    from apex_tpu.models import AttentionKind, RopeScaling

    heads = {}
    for kind, n in zip(arch["layer_types"], arch["layer_heads"]):
        heads.setdefault(kind, n)
    yarn = RopeScaling(
        rope_type="yarn", factor=arch["yarn_factor"],
        original_max_position_embeddings=arch["yarn_original"],
        beta_fast=arch["yarn_beta_fast"], beta_slow=arch["yarn_beta_slow"],
        attention_factor=arch["yarn_attention_factor"])
    return tuple(
        AttentionKind(FULL, heads=heads[FULL], rotary_base=arch["full_theta"],
                      rotary_percent=arch["full_percent"], rope_scaling=yarn)
        if kind == FULL else
        AttentionKind(WINDOW, window=arch["window"], heads=heads[WINDOW],
                      rotary_base=arch["window_theta"],
                      rotary_percent=arch["window_percent"])
        for kind in dict.fromkeys(arch["layer_types"]))


def model_config(arch: dict, mix: dict):
    import jax.numpy as jnp

    from apex_tpu.models import TransformerConfig

    kinds = attention_kinds(arch)
    for kind, n in zip(arch["layer_types"], arch["layer_heads"]):
        if next(k.heads for k in kinds if k.name == kind) != n:
            raise ValueError(f"the layers of kind {kind} differ in heads")
    dense = sum(map(lambda i: is_dense(arch, i), range(arch["layers"])))
    if any(is_dense(arch, i) for i in range(dense, arch["layers"])):
        raise ValueError("the dense layers have to lead the stack")
    return TransformerConfig(
        hidden_size=arch["hidden"], num_layers=arch["layers"],
        num_attention_heads=arch["heads"], head_dim=arch["head_dim"],
        num_query_groups=arch["kv_heads"],
        ffn_hidden_size=arch["dense_ffn"], vocab_size=arch["vocab"],
        max_position_embeddings=arch["positions"],
        layernorm_epsilon=arch["eps"], compute_dtype=jnp.bfloat16,
        normalization="rmsnorm", activation="swiglu", attention_bias=False,
        position_embedding_type="rope", attention_kinds=kinds,
        layer_attention=arch["layer_types"],
        attention_output_gate=arch["gate"],
        num_moe_experts=arch["experts"], moe_top_k=arch["top_k"],
        moe_first_dense_layers=dense, moe_ffn_hidden_size=arch["ffn"],
        moe_normalize_topk=True, moe_router_score="sigmoid_bias",
        moe_routed_scaling_factor=arch["routed_scale"],
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
    """Cross-entropy alone: the sigmoid router takes no auxiliary loss
    (the held share's counts are sown into ``moe_losses`` and left)."""
    from apex_tpu.models.gpt import gpt_loss_fn

    def laguna_loss(params, batch):
        logits, _ = model.apply({"params": params}, batch["tokens"],
                                mutable=["moe_losses"])
        return gpt_loss_fn(logits, batch["labels"])
    return laguna_loss
