"""Seconds JAX spent tracing the program's functions and lowering them to
MLIR: the `lower_s` the program's spans carry, each compile event counted
once, on the innermost span open when it happened. Every run pays it,
cache or no cache: it builds the cache's key. Nothing to read where no
span carries the counter."""


def read(ctx):
    spans = [r for r in ctx["stream"].get("span", []) if "lower_s" in r]
    if not spans:
        return None
    return sum(float(r["lower_s"]) for r in spans)
