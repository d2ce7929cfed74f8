"""The aggregation kernels' share of their roofline: the least time the
chip could take over the step's aggregation passes (each the larger of
2*E*F over peak FLOP/s and its compulsory bytes over peak bytes/s; bytes
hold on both shapes) over the traced `spmm` time per epoch."""


def read(ctx):
    trace, peaks = ctx["trace"], ctx["peaks"]
    if not trace or not peaks or not trace["scope_s"].get("spmm"):
        return None
    spmm_s = trace["scope_s"]["spmm"] / ctx["epochs_traced"]
    least = ctx["work_module"].aggregation_least_s(ctx["work"], peaks)
    return 100.0 * least["least_s"] / spmm_s
