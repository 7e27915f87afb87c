"""What the Megatron-style families (``gpt2``, ``bert``) share: the
block's canonical tensors, where each sits in the program's
``transformer/layer_<i>`` tree, the program's ``TransformerConfig``, and
the PaLM FLOP count. Not a family itself."""

import jax.numpy as jnp

LAYER_LEAVES = {
    "ln1_g": ("input_layernorm", "weight"),
    "ln1_b": ("input_layernorm", "bias"),
    "qkv_w": ("self_attention", "query_key_value", "weight"),
    "qkv_b": ("self_attention", "query_key_value", "bias"),
    "proj_w": ("self_attention", "dense", "weight"),
    "proj_b": ("self_attention", "dense", "bias"),
    "ln2_g": ("post_attention_layernorm", "weight"),
    "ln2_b": ("post_attention_layernorm", "bias"),
    "fc_w": ("mlp", "dense_h_to_4h", "weight"),
    "fc_b": ("mlp", "dense_h_to_4h", "bias"),
    "out_w": ("mlp", "dense_4h_to_h", "weight"),
    "out_b": ("mlp", "dense_4h_to_h", "bias"),
}


def act_name(published: str) -> str:
    return "gelu_tanh" if published in ("gelu_new", "gelu_tanh") \
        else "gelu_erf"


def stack_shapes(arch: dict) -> dict:
    """Embeddings, the stacked blocks and the final LayerNorm (see
    ``reference/transformer.py`` for what each tensor does)."""
    h, L, f = arch["hidden"], arch["layers"], arch["ffn"]
    return {
        "wte": (arch["vocab"], h), "wpe": (arch["positions"], h),
        "layers.ln1_g": (L, h), "layers.ln1_b": (L, h),
        "layers.qkv_w": (L, h, 3 * h), "layers.qkv_b": (L, 3 * h),
        "layers.proj_w": (L, h, h), "layers.proj_b": (L, h),
        "layers.ln2_g": (L, h), "layers.ln2_b": (L, h),
        "layers.fc_w": (L, h, f), "layers.fc_b": (L, f),
        "layers.out_w": (L, f, h), "layers.out_b": (L, h),
        "lnf_g": (h,), "lnf_b": (h,),
    }


def _set(tree, path, value):
    for key in path[:-1]:
        tree = tree.setdefault(key, {})
    tree[path[-1]] = value


def _get(tree, path):
    for key in path:
        tree = tree[key]
    return tree


def to_program(canon: dict, arch: dict, top_leaves: dict) -> dict:
    """Canonical flat dict -> the program's ``params`` tree."""
    out = {}
    for name, x in canon.items():
        if name.startswith("layers."):
            path = LAYER_LEAVES[name[len("layers."):]]
            for i in range(arch["layers"]):
                _set(out, ("transformer", f"layer_{i}") + path, x[i])
        else:
            _set(out, top_leaves[name], x)
    return out


def from_program(tree: dict, arch: dict, names, top_leaves: dict) -> dict:
    """The program's tree (parameters, or a state shaped like them) ->
    canonical flat dict of ``names``, layers stacked."""
    out = {}
    for name in names:
        if name.startswith("layers."):
            path = LAYER_LEAVES[name[len("layers."):]]
            out[name] = jnp.stack([
                _get(tree, ("transformer", f"layer_{i}") + path)
                for i in range(arch["layers"])])
        else:
            out[name] = _get(tree, top_leaves[name])
    return out


def model_config(arch: dict, mix: dict, *, causal: bool):
    """The program's ``TransformerConfig`` for ``arch`` under ``mix``."""
    from apex_tpu.models import TransformerConfig
    from apex_tpu.transformer.enums import AttnMaskType

    return TransformerConfig(
        hidden_size=arch["hidden"], num_layers=arch["layers"],
        num_attention_heads=arch["heads"], ffn_hidden_size=arch["ffn"],
        vocab_size=arch["vocab"], max_position_embeddings=arch["positions"],
        layernorm_epsilon=arch["eps"], compute_dtype=jnp.bfloat16,
        activation="gelu" if arch["act"] == "gelu_tanh" else "gelu_exact",
        use_flash_attention=bool(mix.get("flash_attention", causal)),
        attn_mask_type=(AttnMaskType.causal if causal
                        else AttnMaskType.padding),
        tie_word_embeddings=bool(arch["tied"]),
        activation_checkpointing=bool(mix.get("recompute", False)))


def palm_fwd_flops_per_token(arch: dict, seq: int, matmul_params: int):
    """PaLM's count (Chowdhery et al. 2022, appendix B): 2 per matrix
    parameter touched, plus the attention matrices ``4 * seq * hidden``
    per layer with no causal discount."""
    return (2.0 * matmul_params
            + 4.0 * seq * arch["hidden"] * arch["layers"])
