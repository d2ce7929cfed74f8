"""The whole step's share of the chip's peak: the operations one epoch
needs (work.py, from shapes; recomputation never counts) over the traced
epoch time (the traced window on the device's clock over its epochs) and
the bf16 peak of the chips used."""


def read(ctx):
    trace, peaks = ctx["trace"], ctx["peaks"]
    if not trace or not peaks or not trace["window_s"] > 0:
        return None
    epoch_s = trace["window_s"] / ctx["epochs_traced"]
    return 100.0 * ctx["work"]["flops"] / epoch_s / (
        peaks["bf16_flops_per_s"] * ctx["chips"])
