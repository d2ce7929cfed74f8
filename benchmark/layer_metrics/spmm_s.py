"""Device self time per epoch of the operations under the program's
`spmm` named scope (forward and backward aggregation kernels), from the
trace. Nothing to read where no operation carries the scope."""


def read(ctx):
    trace = ctx["trace"]
    if not trace or not trace["scope_s"].get("spmm"):
        return None
    return trace["scope_s"]["spmm"] / ctx["epochs_traced"]
