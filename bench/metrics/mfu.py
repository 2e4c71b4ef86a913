"""Operations the forward and backward passes require per step, over the
traced step time, over the chip's peak (the kind's ``model_flops``,
``bench/models/gcn.py``)."""


def compute(ctx):
    tr, steps = ctx["trace"], ctx["steps"]
    if steps <= 0 or tr["window_s"] <= 0:
        return None
    rate = ctx["work"]["model_flops"] * steps / tr["window_s"]
    return 100.0 * rate / ctx["peak"]["flops_per_s"]
