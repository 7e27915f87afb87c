"""GPT-2 (Radford et al. 2019; ``openai-community/gpt2-medium``): learned
positions, pre-LN causal blocks, tanh gelu, output head tied to the token
embedding. Logits and loss run over the padded table, as in Megatron-LM.
"""

import jax.numpy as jnp

from benchmark.reference import transformer as T


def logits(params, arch, tokens, quant=T.identity):
    """``tokens`` ``[b, s]`` -> next-token logits ``[b, s, vocab]``."""
    s = tokens.shape[1]
    x = params["wte"][tokens] + params["wpe"][jnp.arange(s)][None]
    x = T.stack(x, params, arch, None, True, quant)
    head = params["wte"].T if arch["tied"] else params["head"]
    return T.matmul(x, head, quant)


def loss_part(params, arch, batch, totals, quant=T.identity):
    """This block of rows' part of the batch loss: the parts of all
    blocks add up to the mean over every token of the batch."""
    nll = T.token_nll(logits(params, arch, batch["tokens"], quant),
                      batch["labels"])
    return jnp.sum(nll) / totals["tokens"]


def totals(batch):
    """What a block's part is divided by, from the whole batch."""
    return {"tokens": float(batch["tokens"].size)}
