"""Device self time per epoch of the aggregation kernels' row gathers:
every scope path (`trace["path_s"]`) with `spmm` among its components
whose last component is `gather` (the bucket kernel) or `rem_gather` (the
block kernel's remainder), forward and `bwd`. Named by what the operation
does, not by kernel. Nothing to read where no operation carries such a
path."""

from benchmark.trace_reduce import path_seconds

LAST = ("gather", "rem_gather")


def read(ctx):
    trace = ctx["trace"]
    if not trace:
        return None
    seconds = path_seconds(trace["path_s"], "spmm", LAST)
    return seconds / ctx["epochs_traced"] if seconds else None
