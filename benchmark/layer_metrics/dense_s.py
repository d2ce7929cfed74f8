"""Device self time per epoch of the operations under the program's
`dense` named scope (the linears of every layer, forward and backward),
from the trace. Nothing to read where no operation carries the scope."""


def read(ctx):
    trace = ctx["trace"]
    if not trace or not trace["scope_s"].get("dense"):
        return None
    return trace["scope_s"]["dense"] / ctx["epochs_traced"]
