"""Model FLOP utilisation of a round: the model operations of one round
(LocalUpdate's training passes, Extract's lower forward, MetaTraining's
training passes; ``bench/flops.py``) over the traced window's seconds per
round times the chip's bf16 peak (``bench/peaks.json``)."""


def read(ctx):
    if not ctx["rounds"] or not ctx["peaks"] or not ctx["trace"]:
        return None
    per_round = sum(ctx["flops"].values())
    seconds = ctx["window_s"] / ctx["rounds"]
    return 100.0 * per_round / (seconds * ctx["peaks"]["flops_bf16"])
