"""Device self time per epoch of the aggregation kernels' reduction, the
f32 cast and sum over a bucket's width: every scope path
(`trace["path_s"]`) with `spmm` among its components whose last component
is `reduce` (the bucket kernel) or `rem_reduce` (the block kernel's
remainder), forward and `bwd`. Nothing to read where no operation carries
such a path."""

from benchmark.trace_reduce import path_seconds

LAST = ("reduce", "rem_reduce")


def read(ctx):
    trace = ctx["trace"]
    if not trace:
        return None
    seconds = path_seconds(trace["path_s"], "spmm", LAST)
    return seconds / ctx["epochs_traced"] if seconds else None
