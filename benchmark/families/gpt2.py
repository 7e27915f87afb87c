"""GPT-2 (``openai-community/gpt2-medium`` keys): learned positions,
pre-LN causal blocks, head tied to the token embedding. The program's
side is ``apex_tpu.models.GPTModel``; the plain reference is
``benchmark/reference/gpt2.py``."""

from benchmark import loadgen
from benchmark.families import megatron

TOP_LEAVES = {
    "wte": ("word_embeddings", "weight"),
    "wpe": ("position_embeddings",),
    "lnf_g": ("final_layernorm", "weight"),
    "lnf_b": ("final_layernorm", "bias"),
    "head": ("lm_head",),
}
TASKS = {"causal_lm": loadgen.causal_lm_batches}


def arch(config: dict) -> dict:
    hidden = config["n_embd"]
    return {
        "family": config["family"], "hidden": hidden,
        "layers": config["n_layer"], "heads": config["n_head"],
        "ffn": config.get("n_inner") or 4 * hidden,
        "positions": config["n_positions"],
        "vocab_real": config["vocab_size"],
        "vocab": config.get("assumed", {}).get("padded_vocab_size",
                                               config["vocab_size"]),
        "eps": config["layer_norm_epsilon"],
        "act": megatron.act_name(config.get("activation_function")),
        "tied": bool(config.get("tie_word_embeddings", False)),
    }


def shapes(arch: dict) -> dict:
    out = megatron.stack_shapes(arch)
    if not arch["tied"]:
        out["head"] = (arch["hidden"], arch["vocab"])
    return out


def matmul_params(arch: dict) -> int:
    """Parameters that sit in a matrix product on a token's path: qkv and
    output projection, the two MLP matrices, the head."""
    h, f = arch["hidden"], arch["ffn"]
    return arch["layers"] * (4 * h * h + 2 * h * f) + h * arch["vocab"]


def fwd_flops_per_token(arch: dict, seq: int) -> float:
    return megatron.palm_fwd_flops_per_token(arch, seq, matmul_params(arch))


# ------------------------------------------------------- the program's side

def to_program(canon: dict, arch: dict) -> dict:
    return megatron.to_program(canon, arch, TOP_LEAVES)


def from_program(tree: dict, arch: dict) -> dict:
    return megatron.from_program(tree, arch, shapes(arch), TOP_LEAVES)


def build_model(arch: dict, mix: dict, decode: bool = False):
    from apex_tpu.models import GPTModel

    return GPTModel(megatron.model_config(arch, mix, causal=True),
                    decode=decode)


def loss(model):
    from apex_tpu.models.gpt import gpt_loss_fn

    def gpt_loss(params, batch):
        return gpt_loss_fn(model.apply({"params": params}, batch["tokens"]),
                           batch["labels"])
    return gpt_loss
