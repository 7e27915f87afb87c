"""NVIDIA-Nemotron-3-Nano-30B-A3B (``model_type`` ``nemotron_h``): one
chip's share of it. One sub-block a layer by ``hybrid_override_pattern``:
Mamba-2 mixers (``M``), relu² experts under a bias-balanced sigmoid
router with a shared expert (``E``), grouped-query attention with no
positional encoding (``*``); RMSNorm, untied embedding and head. The chip
holds the configuration's counts of mixer heads, B/C groups, attention
heads and experts, and its slice of the vocabulary; the router scores all
the published experts. The program's side is ``apex_tpu.models.GPTModel``
over a ``TransformerConfig`` with ``layer_pattern``; the plain reference,
with the equations, is ``benchmark/reference/nemotron_h.py``."""

from benchmark import loadgen
from benchmark.families import megatron
# the fused grouped-query projection's columns, as ``ParallelAttention``
# lays them out: one spelling for both families that use it
from benchmark.families.keye_vl2 import _fuse_qkv, _split_qkv
from benchmark.reference.nemotron_h import init_offsets

TOP_LEAVES = {
    "wte": ("word_embeddings", "weight"),
    "lnf_g": ("final_layernorm", "weight"),
    "head": ("lm_head",),
}
# canonical tensor of a kind of layer -> its leaf in ``transformer/
# layer_<i>``; ``a_wq | a_wk | a_wv`` are fused there (``_fuse_qkv``)
KIND_LEAVES = {
    "M": {
        "m_in": ("mixer", "in_proj"),
        "m_conv_w": ("mixer", "conv_weight"),
        "m_conv_b": ("mixer", "conv_bias"),
        "m_dt_bias": ("mixer", "dt_bias"),
        "m_a_log": ("mixer", "A_log"),
        "m_d_g": ("mixer", "D"),
        "m_norm_g": ("mixer", "norm_weight"),
        "m_out": ("mixer", "out_proj"),
    },
    "E": {
        "e_router": ("mlp", "routed", "router", "gate_weight"),
        "e_up": ("mlp", "routed", "experts", "w1"),
        "e_down": ("mlp", "routed", "experts", "w2"),
        "e_sup": ("mlp", "shared_up", "weight"),
        "e_sdown": ("mlp", "shared_down", "weight"),
    },
    "*": {"a_wo": ("self_attention", "dense", "weight")},
}
LN = ("input_layernorm", "weight")
QKV = ("self_attention", "query_key_value", "weight")
# a buffer in the source, a parameter that no gradient reaches in the
# program: zeros go in, and it is no canonical tensor
ROUTER_BIAS = ("mlp", "routed", "router", "e_score_correction_bias")

TASKS = {"causal_lm": loadgen.causal_lm_batches}


def arch(config: dict) -> dict:
    assumed = config.get("assumed", {})
    pattern = config["hybrid_override_pattern"]
    if len(pattern) != config["num_hidden_layers"] or set(pattern) - set("ME*"):
        raise ValueError(f"hybrid_override_pattern {pattern!r} does not "
                         f"spell {config['num_hidden_layers']} layers")
    return {
        "family": config["family"], "hidden": config["hidden_size"],
        "layers": config["num_hidden_layers"], "pattern": pattern,
        "heads": config["num_attention_heads"],
        "kv_heads": config["num_key_value_heads"],
        "head_dim": config["head_dim"],
        "m_heads": config["mamba_num_heads"],
        "m_head_dim": config["mamba_head_dim"],
        "m_groups": config["n_groups"],
        "state": config["ssm_state_size"],
        "conv_kernel": config["conv_kernel"],
        "chunk": config["chunk_size"],
        "dt_min": config["time_step_min"],
        "dt_max": config["time_step_max"],
        "dt_floor": config["time_step_floor"],
        # the router's width is the published count; the file's own key
        # counts the experts held here
        "experts": config.get("published", config)["n_routed_experts"],
        "experts_held": config["n_routed_experts"],
        "expert_offset": config.get("expert_offset", 0),
        "top_k": config["num_experts_per_tok"],
        "ffn": config["moe_intermediate_size"],
        "shared_ffn": config["moe_shared_expert_intermediate_size"],
        "routed_scale": float(config["routed_scaling_factor"]),
        "positions": config["max_position_embeddings"],
        "eps": config["norm_eps"],
        # the held experts' rows are gathered into this many times their
        # expected number (a static shape); experts / held is room for
        # every assignment: nothing is dropped whatever the router does
        "held_rows_factor": assumed["held_rows_factor"],
        "vocab_real": config["vocab_size"],
        "vocab": assumed.get("padded_vocab_size", config["vocab_size"]),
    }


def _widths(arch):
    inner = arch["m_heads"] * arch["m_head_dim"]
    bc = arch["m_groups"] * arch["state"]
    return inner, bc


def shapes(arch: dict) -> dict:
    """A layer's tensors are named ``l<i>.<name>``, each on its own (the
    layers are of three kinds, so there is no stack): a tensor of its own
    in every per-tensor number of the comparison."""
    h, d = arch["hidden"], arch["head_dim"]
    inner, bc = _widths(arch)
    H, K = arch["m_heads"], arch["conv_kernel"]
    q, kv = arch["heads"] * d, arch["kv_heads"] * d
    n, f, fs = arch["experts_held"], arch["ffn"], arch["shared_ffn"]
    kinds = {
        "M": {"m_in": (h, 2 * inner + 2 * bc + H),
              "m_conv_w": (K, inner + 2 * bc), "m_conv_b": (inner + 2 * bc,),
              "m_dt_bias": (H,), "m_a_log": (H,), "m_d_g": (H,),
              "m_norm_g": (inner,), "m_out": (inner, h)},
        "E": {"e_router": (h, arch["experts"]), "e_up": (n, h, f),
              "e_down": (n, f, h), "e_sup": (h, fs), "e_sdown": (fs, h)},
        "*": {"a_wq": (h, q), "a_wk": (h, kv), "a_wv": (h, kv),
              "a_wo": (q, h)},
    }
    out = {"wte": (arch["vocab"], h), "head": (h, arch["vocab"]),
           "lnf_g": (h,)}
    for i, kind in enumerate(arch["pattern"]):
        out[f"l{i}.ln_g"] = (h,)
        out.update({f"l{i}.{name}": shape
                    for name, shape in kinds[kind].items()})
    return out


def matmul_params(arch: dict) -> int:
    """Parameters in a matrix product on a token's path on this chip: the
    held heads' projections of the mixers and of attention, router and
    shared expert whole, of the held experts' matrices the ``top_k /
    experts`` share a token is expected to use (each of its ``top_k``
    choices falls on a held expert with probability ``held / experts``),
    and the head's slice."""
    h, d = arch["hidden"], arch["head_dim"]
    inner, bc = _widths(arch)
    mixer = h * (2 * inner + 2 * bc + arch["m_heads"]) + inner * h
    attn = h * (arch["heads"] + 2 * arch["kv_heads"]) * d \
        + arch["heads"] * d * h
    held = arch["top_k"] * arch["experts_held"] / arch["experts"]
    expert = (h * arch["experts"] + 2 * h * arch["shared_ffn"]
              + held * 2 * h * arch["ffn"])
    pattern = arch["pattern"]
    return int(pattern.count("M") * mixer + pattern.count("*") * attn
               + pattern.count("E") * expert + h * arch["vocab"])


def scan_flops_per_token(arch: dict) -> float:
    """The recurrence itself, a token and mixer layer, forward: a step and
    held head decays the state, forms ``dt x B^T``, adds it (3 P N) and
    contracts the state with ``C`` (2 P N). Not a chunking's count."""
    return 5.0 * arch["m_heads"] * arch["m_head_dim"] * arch["state"]


def fwd_flops_per_token(arch: dict, seq: int) -> float:
    """What this chip's share computes: 2 per matrix parameter, the
    attention layers' ``QK^T`` and ``PV`` over the held heads (PaLM's
    count, no causal discount), the mixers' recurrence and their
    convolution's taps."""
    inner, bc = _widths(arch)
    pattern = arch["pattern"]
    attn = 4.0 * seq * arch["heads"] * arch["head_dim"]
    conv = 2.0 * arch["conv_kernel"] * (inner + 2 * bc)
    return (2.0 * matmul_params(arch) + pattern.count("*") * attn
            + pattern.count("M") * (scan_flops_per_token(arch) + conv))


def ssm_scan_train_flops_per_step(arch, batch, seq) -> float:
    """What the operations under scope ``ssm/scan`` have to do in one
    training step, from shapes alone: the recurrence forward, and twice
    that backward (each of its products has two gradients). A pass under
    recomputation is not counted, nor what a chunked algorithm computes
    beyond the recurrence."""
    return 3.0 * scan_flops_per_token(arch) * batch * seq \
        * arch["pattern"].count("M")


def ssm_scan_train_bytes_per_step(arch, batch, seq) -> float:
    """The least those operations move: the forward reads x, B, C (the
    compute dtype, 2 bytes) and dt (float32) once and writes y (float32)
    once; the backward reads the same and y's gradient and writes the
    four inputs' gradients. The state never has to leave the chip's fast
    memory."""
    inner, bc = _widths(arch)
    ins = 2 * (inner + 2 * bc) + 4 * arch["m_heads"]
    fwd = ins + 4 * inner
    bwd = fwd + ins
    return float(batch * seq * arch["pattern"].count("M") * (fwd + bwd))


# ------------------------------------------------------- the program's side

def to_program(canon: dict, arch: dict) -> dict:
    import jax.numpy as jnp

    out = {}
    for name, path in TOP_LEAVES.items():
        megatron._set(out, path, canon[name])
    for i, kind in enumerate(arch["pattern"]):
        at = ("transformer", f"layer_{i}")
        megatron._set(out, at + LN, canon[f"l{i}.ln_g"])
        for name, path in KIND_LEAVES[kind].items():
            megatron._set(out, at + path, canon[f"l{i}.{name}"])
        if kind == "*":
            megatron._set(out, at + QKV, _fuse_qkv(
                *(canon[f"l{i}.a_w{x}"] for x in "qkv"), arch))
        if kind == "E":
            megatron._set(out, at + ROUTER_BIAS,
                          jnp.zeros((arch["experts"],), jnp.float32))
    return out


def from_program(tree: dict, arch: dict) -> dict:
    out = {name: megatron._get(tree, path)
           for name, path in TOP_LEAVES.items()}
    for i, kind in enumerate(arch["pattern"]):
        at = ("transformer", f"layer_{i}")
        out[f"l{i}.ln_g"] = megatron._get(tree, at + LN)
        for name, path in KIND_LEAVES[kind].items():
            out[f"l{i}.{name}"] = megatron._get(tree, at + path)
        if kind == "*":
            for x, w in zip("qkv", _split_qkv(megatron._get(tree, at + QKV),
                                              arch)):
                out[f"l{i}.a_w{x}"] = w
    return out


def model_config(arch: dict, mix: dict):
    import jax.numpy as jnp

    from apex_tpu.models import TransformerConfig

    return TransformerConfig(
        hidden_size=arch["hidden"], num_layers=arch["layers"],
        layer_pattern=arch["pattern"],
        num_attention_heads=arch["heads"], head_dim=arch["head_dim"],
        num_query_groups=arch["kv_heads"], ffn_hidden_size=arch["ffn"],
        vocab_size=arch["vocab"], max_position_embeddings=arch["positions"],
        layernorm_epsilon=arch["eps"], compute_dtype=jnp.bfloat16,
        normalization="rmsnorm", activation="relu2", attention_bias=False,
        position_embedding_type="none",
        mamba_num_heads=arch["m_heads"], mamba_head_dim=arch["m_head_dim"],
        mamba_n_groups=arch["m_groups"], mamba_state_size=arch["state"],
        mamba_conv_kernel=arch["conv_kernel"],
        mamba_chunk_size=arch["chunk"], mamba_dt_min=arch["dt_min"],
        mamba_dt_max=arch["dt_max"], mamba_dt_floor=arch["dt_floor"],
        num_moe_experts=arch["experts"], moe_top_k=arch["top_k"],
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


def with_init_offsets(params: dict, arch: dict) -> dict:
    """The tree the model is applied to: the stepped parameters with the
    published starts of ``A_log``, ``dt_bias`` and the convolution added
    (``reference/nemotron_h.py`` ``init_offsets``, where the reference
    adds the same), in float32."""
    import jax.numpy as jnp

    layers = dict(params["transformer"])
    for name, offset in init_offsets(arch).items():
        layer, leaf = name.split(".")
        layer = "layer_" + layer[1:]
        leaf = KIND_LEAVES["M"][leaf][-1]
        mixer = dict(layers[layer]["mixer"])
        mixer[leaf] = mixer[leaf].astype(jnp.float32) + offset
        layers[layer] = dict(layers[layer], mixer=mixer)
    return dict(params, transformer=layers)


def loss(model):
    """Cross-entropy alone: the router has no auxiliary loss. The held
    share's counts are sown into ``moe_losses`` as for any expert layer."""
    from apex_tpu.models.gpt import gpt_loss_fn

    cfg = model.config
    arch = {"pattern": cfg.layer_pattern, "m_heads": cfg.mamba_num_heads,
            "m_head_dim": cfg.mamba_head_dim,
            "m_groups": cfg.mamba_n_groups, "state": cfg.mamba_state_size,
            "conv_kernel": cfg.mamba_conv_kernel,
            "dt_min": cfg.mamba_dt_min, "dt_max": cfg.mamba_dt_max,
            "dt_floor": cfg.mamba_dt_floor}

    def nemotron_loss(params, batch):
        logits, _ = model.apply(
            {"params": with_init_offsets(params, arch)}, batch["tokens"],
            mutable=["moe_losses"])
        return gpt_loss_fn(logits, batch["labels"])
    return nemotron_loss
