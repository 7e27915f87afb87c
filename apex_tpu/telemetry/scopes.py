"""Device time by module: which block of the program a compiled
instruction belongs to, and which phase of the step.

A device profile names each operation by its HLO instruction
(``fusion.85``, ``self_attention_flash_dq.3``); the compiled module says
which scope of the program every instruction was traced under
(:func:`scope_table`), and :func:`classify` folds a scope into the
block and phase an operator thinks in::

    compiled = step.lower(params, opt_state, batch).compile()
    table = scope_table(compiled)            # {"fusion.85": "jit(step)/jvp(GPTModel)/.../mlp/..."}
    block, phase = classify(table["fusion.85"])      # ("mlp", "forward")

Join that to the profile's events on the instruction name (the part of
an ``XLA Ops`` event's name between ``%`` and `` = ``) and sum durations
by block. Nothing here touches a device or a trace: it reads
``compiled.as_text()``. docs/observability.md, "Device time by module".
"""

import re

from apex_tpu.analysis import hlo


def scope_table(compiled) -> dict:
    """``{instruction name: scope}`` for every instruction of a compiled
    step (``jitted.lower(...).compile()``) that has one; a fusion
    answers for what it holds (the matmul or kernel inside, else most of
    its instructions): :func:`apex_tpu.analysis.hlo.instruction_scopes`."""
    return hlo.instruction_scopes(compiled.as_text())


# block -> the flax module names and ``jax.named_scope`` names that open
# it, as the program spells them (models/{gpt,bert,transformer_lm}.py,
# amp/amp_optimizer.py, optimizers/fused_*.py)
_BLOCKS = {
    "embedding": ("embedding", "word_embeddings", "position_embeddings",
                  "tokentype_embeddings", "embedding_layernorm"),
    "layernorm": ("input_layernorm", "post_attention_layernorm",
                  "final_layernorm", "post_self_attn_norm",
                  "post_mlp_norm"),
    "attention": ("self_attention", "attention_mask"),
    "mlp": ("mlp",),
    # inside attention and the mlp, and named for themselves: the
    # sparse-attention indexer (flax module ``indexer``, scopes
    # ``indexer/{project,scores,select,loss}``) and the expert layer's
    # routed path and shared expert (scopes ``moe/{router,dispatch,
    # experts,combine,shared}``)
    "indexer": ("indexer",),
    "moe": ("moe",),
    # the Mamba-2 mixer (transformer/ssm.py; scopes ``ssm/{in_proj,conv,
    # scan,gate_norm,out_proj}`` in the flax module ``mixer`` of a
    # ``layer_pattern`` layer)
    "ssm": ("ssm",),
    # latent attention (models/transformer_lm.py ``ParallelAttention``'s
    # latent path and ``latent_attention``; scopes ``mla/{q_proj,kv_down,
    # kv_up,rope,kernel,out_proj}`` in the flax module ``self_attention``)
    "mla": ("mla",),
    # training by diffusion over blocks (models/gpt.py under
    # ``diffusion_block_length``): what runs on the noisy half alone, scopes
    # ``diffusion/{select_noisy,head,loss}``: the slice of rows L..2L-1, the
    # head on them and the weighted cross-entropy
    "diffusion_head": ("diffusion",),
    "head": ("head", "word_embeddings.attend", "lm_dense", "lm_layernorm",
             "lm_head", "lm_head_bias", "pooler", "binary_head"),
    "loss": ("loss",),
    # ``scaler`` and ``inner``: AmpOptimizer's state, where an instruction
    # is named after the argument it reads
    "amp": ("amp", "scaler"),
    "optimizer": ("optimizer", "fused_adam", "fused_lamb", "fused_sgd",
                  "fused_novograd", "fused_adagrad",
                  "fused_mixed_precision_lamb", "inner"),
}
_BLOCK_OF = {name: block for block, names in _BLOCKS.items()
             for name in names}
# blocks that sit inside another block's module and take its time out of it
_INNER = ("indexer", "moe", "ssm", "mla")
# XLA replaces ``lax.ragged_dot`` by a grouped-matmul kernel of its own and
# names it, and the call that prepares its group metadata, by what it is
# and not by the scope it was traced under (``op_name="ragged-dot-none"``):
# the expert layer is the package's one user, so these are its block; the
# phase is lost with the scope and reads ``update``
_RAGGED_DOT = re.compile(r"ragged-dot(-\w+)*")
# parallel/distributed.py and parallel/pipeline.py number or suffix theirs
_COLLECTIVE = re.compile(r"ddp_allreduce_bucket_\d+|pp_\w+")
# inside the model but in none of its blocks: what a layer or the model
# does to the residual stream itself (adds, casts, layout changes)
_MODEL = re.compile(r"layer_\d+|layers?|transformer|\w+Model")
# sub-blocks of attention: the two projections by their module names, the
# kernel by a Pallas call (contrib/fmha.py and kernels/softmax.py name
# theirs; an unnamed one ends in ``pallas_call``)
_ATTENTION_PARTS = {"query_key_value": "qkv", "dense": "dense",
                    "pallas_call": "kernel"}
# sub-blocks of the Mamba-2 mixer: the scopes it opens under ``ssm/``
_SSM_PARTS = ("in_proj", "conv", "scan", "gate_norm", "out_proj")
# sub-blocks of latent attention: the scopes it opens under ``mla/``
_MLA_PARTS = ("q_proj", "kv_down", "kv_up", "rope", "kernel", "out_proj")
_PARTS = {"ssm": _SSM_PARTS, "mla": _MLA_PARTS}
# ``jvp(GPTModel)`` / ``transpose(jvp(loss))`` / ``jit(_where)`` -> the name
_WRAPPED = re.compile(r"^(?:\w+\()+([^()]*)\)+$")


def _components(scope: str):
    for part in scope.split("/"):
        if "[" in part:
            # XLA names what it does to an argument ahead of its first
            # use (a weight's relayout) after the argument's path,
            # ``params['transformer']['layer_0']['mlp'][...]``: the keys
            # of the parameter tree are the modules' names
            yield from re.findall(r"\w+", part)
            continue
        m = _WRAPPED.match(part)
        yield m.group(1) if m else part


def classify(scope: str) -> tuple:
    """``(block, phase)`` of a scope from :func:`scope_table`.

    ``block`` is set by the first component of the path that the table
    knows: ``embedding``, ``layernorm``, ``attention`` (``attention/qkv``,
    ``attention/kernel``, ``attention/dense`` where a later component
    says which part; ``indexer`` where a later component is the
    sparse-attention indexer), ``mlp`` (``moe`` where a later component
    is one of the expert layer's scopes), ``ssm`` (``ssm/in_proj``,
    ``ssm/conv``, ``ssm/scan``, ``ssm/gate_norm``, ``ssm/out_proj`` where
    the next component says which part of the Mamba-2 mixer), ``mla``
    (``mla/q_proj``, ``mla/kv_down``, ``mla/kv_up``, ``mla/rope``,
    ``mla/kernel``, ``mla/out_proj``: latent attention, inside the
    ``self_attention`` module as the indexer is), ``diffusion_head``
    (the noisy half's slice, head and loss of a block-diffusion model),
    ``head``, ``loss``, ``amp``, ``optimizer``, ``collective``; ``residual`` for a
    scope inside the model that names none of them; ``None`` for any
    other. ``phase`` is
    ``recompute`` under ``jax.checkpoint``'s ``rematted_computation``,
    else ``backward`` under a transposed jvp, ``forward`` under a jvp,
    and ``update`` outside differentiation."""
    if "rematted_computation" in scope:
        phase = "recompute"
    elif "transpose(jvp(" in scope:
        phase = "backward"
    elif "jvp(" in scope:
        phase = "forward"
    else:
        phase = "update"
    parts = list(_components(scope))
    for i, part in enumerate(parts):
        if _COLLECTIVE.fullmatch(part):
            return "collective", phase
        if _RAGGED_DOT.fullmatch(part):
            return "moe", phase
        block = _BLOCK_OF.get(part)
        if block in ("attention", "mlp"):
            for j, sub in enumerate(parts[i + 1:], i + 1):
                if _BLOCK_OF.get(sub) in _INNER:
                    block, i = _BLOCK_OF[sub], j
                    break
        if block in _PARTS:
            part = parts[i + 1] if i + 1 < len(parts) else None
            return (f"{block}/{part}" if part in _PARTS[block]
                    else block), phase
        if block == "attention":
            for sub in parts[i + 1:]:
                if sub in _ATTENTION_PARTS:
                    return f"attention/{_ATTENTION_PARTS[sub]}", phase
            return block, phase
        if block is not None:
            return block, phase
    if any(_MODEL.fullmatch(part) for part in parts):
        return "residual", phase
    return None, phase
