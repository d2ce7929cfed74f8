"""Device self time per epoch of everything the dense-tile MXU path of
the block kernels costs: every scope path (`trace["path_s"]`) with `spmm`
among its components whose last component is `tile` (the products) or
`unpack` (the tiles' bits to bf16), forward and `bwd`; the two are only
meaningful added (PERF.md section 5). Nothing to read where no operation
carries such a path: the bucket kernels have none."""

from benchmark.trace_reduce import path_seconds

LAST = ("tile", "unpack")


def read(ctx):
    trace = ctx["trace"]
    if not trace:
        return None
    seconds = path_seconds(trace["path_s"], "spmm", LAST)
    return seconds / ctx["epochs_traced"] if seconds else None
