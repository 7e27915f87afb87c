"""Train step, whole: model FLOPs per token (PaLM count, forward x 3, no
recomputation; ``benchmark/flops.py``) x tokens per second per chip over
the chip's published peak (``benchmark/peaks.json``). Only on a device
that is in the table: no peak, no number."""


def read(ctx):
    if ctx["peaks"] is None:
        return None
    per_token = ctx["flops"].train_flops_per_token(ctx["arch"],
                                                   ctx["mix"]["seq"])
    return (100.0 * per_token * ctx["values"]["train_tokens_per_s_per_chip"]
            / ctx["peaks"]["flops_per_s"])
