"""Device self time per epoch of the elementwise passes over the
activations: the program's `dropout` (mask, scale and their random bits)
and `norm` (layer or batch norm with the activation behind it) named
scopes, from the trace. Nothing to read where neither scope is there."""


def read(ctx):
    trace = ctx["trace"]
    if not trace:
        return None
    scope_s = trace["scope_s"]
    if not scope_s.get("dropout") and not scope_s.get("norm"):
        return None
    return (scope_s.get("dropout", 0.0)
            + scope_s.get("norm", 0.0)) / ctx["epochs_traced"]
