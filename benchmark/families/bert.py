"""BERT as Megatron-LM trains it (``bert-large-uncased`` keys): token,
position and segment embeddings, pre-LN bidirectional blocks under a
padding mask, MLM and NSP heads. The program's side is
``apex_tpu.models.BertModel``; the plain reference is
``benchmark/reference/bert.py``."""

from benchmark import loadgen
from benchmark.families import megatron

TOP_LEAVES = {
    "wte": ("word_embeddings", "weight"),
    "wpe": ("position_embeddings",),
    "lnf_g": ("final_layernorm", "weight"),
    "lnf_b": ("final_layernorm", "bias"),
    "tte": ("tokentype_embeddings",),
    "mlm_dense_w": ("lm_dense", "kernel"),
    "mlm_dense_b": ("lm_dense", "bias"),
    "mlm_ln_g": ("lm_layernorm", "weight"),
    "mlm_ln_b": ("lm_layernorm", "bias"),
    "mlm_head": ("lm_head",),
    "pooler_w": ("pooler", "kernel"),
    "pooler_b": ("pooler", "bias"),
    "nsp_w": ("binary_head", "kernel"),
    "nsp_b": ("binary_head", "bias"),
}
TASKS = {"mlm_nsp": loadgen.mlm_nsp_batches}


def arch(config: dict) -> dict:
    return {
        "family": config["family"], "hidden": config["hidden_size"],
        "layers": config["num_hidden_layers"],
        "heads": config["num_attention_heads"],
        "ffn": config["intermediate_size"],
        "positions": config["max_position_embeddings"],
        "vocab_real": config["vocab_size"],
        "vocab": config.get("assumed", {}).get("padded_vocab_size",
                                               config["vocab_size"]),
        "eps": config["layer_norm_eps"],
        "act": megatron.act_name(config.get("hidden_act")),
        "tied": False,
        "type_vocab": config["type_vocab_size"],
    }


def shapes(arch: dict) -> dict:
    h, v = arch["hidden"], arch["vocab"]
    out = megatron.stack_shapes(arch)
    out.update({
        "tte": (arch["type_vocab"], h),
        "mlm_dense_w": (h, h), "mlm_dense_b": (h,),
        "mlm_ln_g": (h,), "mlm_ln_b": (h,),
        "mlm_head": (h, v),
        "pooler_w": (h, h), "pooler_b": (h,),
        "nsp_w": (h, 2), "nsp_b": (2,),
    })
    return out


def matmul_params(arch: dict) -> int:
    """As GPT-2's (blocks and output matrix) plus the MLM head's dense."""
    h, f = arch["hidden"], arch["ffn"]
    return (arch["layers"] * (4 * h * h + 2 * h * f) + h * arch["vocab"]
            + h * h)


def fwd_flops_per_token(arch: dict, seq: int) -> float:
    return megatron.palm_fwd_flops_per_token(arch, seq, matmul_params(arch))


# ------------------------------------------------------- the program's side

def to_program(canon: dict, arch: dict) -> dict:
    return megatron.to_program(canon, arch, TOP_LEAVES)


def from_program(tree: dict, arch: dict) -> dict:
    return megatron.from_program(tree, arch, shapes(arch), TOP_LEAVES)


def build_model(arch: dict, mix: dict, decode: bool = False):
    from apex_tpu.models import BertModel

    return BertModel(megatron.model_config(arch, mix, causal=False))


def loss(model):
    from apex_tpu.models import bert_loss_fn

    def bert_loss(params, batch):
        mlm, nsp = model.apply({"params": params}, batch["tokens"],
                               batch["padding_mask"], batch["segments"])
        return bert_loss_fn(mlm, nsp, batch["labels"], batch["loss_mask"],
                            batch["nsp_labels"])
    return bert_loss
