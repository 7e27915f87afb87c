"""BERT as Megatron-LM trains it (arXiv:1909.08053 section 5.3; apex
``standalone_bert.py``): token + position + segment embeddings, pre-LN
bidirectional blocks under a key-padding mask, a final LayerNorm, an MLM
head (dense, gelu, LayerNorm, output matrix) and an NSP head on the
pooled first token. Departures from the 2018 paper, all the program's and
listed in ``configs/bert-large.json``: LayerNorm placement, no LayerNorm
on the embeddings, an untied MLM output matrix without bias, tanh gelu in
the MLM head.
"""

import jax.numpy as jnp

from benchmark.reference import transformer as T


def heads(params, arch, batch, quant=T.identity):
    """-> (MLM logits ``[b, s, vocab]``, NSP logits ``[b, 2]``)."""
    tokens = batch["tokens"]
    s = tokens.shape[1]
    x = (params["wte"][tokens] + params["wpe"][jnp.arange(s)][None]
         + params["tte"][batch["segments"]])
    key_bias = jnp.where(batch["padding_mask"] > 0, 0.0, -1e9)
    x = T.stack(x, params, arch, key_bias, False, quant)
    y = T.matmul(x, params["mlm_dense_w"], quant) + params["mlm_dense_b"]
    y = T.layer_norm(T.gelu_tanh(y), params["mlm_ln_g"], params["mlm_ln_b"],
                     arch["eps"])
    mlm = T.matmul(y, params["mlm_head"], quant)
    pooled = jnp.tanh(T.matmul(x[:, 0], params["pooler_w"], quant)
                      + params["pooler_b"])
    nsp = T.matmul(pooled, params["nsp_w"], quant) + params["nsp_b"]
    return mlm, nsp


def loss_part(params, arch, batch, totals, quant=T.identity):
    """MLM loss over the masked positions plus NSP loss over the rows;
    a block's part is divided by the whole batch's counts."""
    mlm, nsp = heads(params, arch, batch, quant)
    mlm_nll = T.token_nll(mlm, batch["labels"]) * batch["loss_mask"]
    nsp_nll = T.token_nll(nsp, batch["nsp_labels"])
    return (jnp.sum(mlm_nll) / totals["masked"]
            + jnp.sum(nsp_nll) / totals["rows"])


def totals(batch):
    return {"masked": float(max(batch["loss_mask"].sum(), 1.0)),
            "rows": float(batch["tokens"].shape[0])}
