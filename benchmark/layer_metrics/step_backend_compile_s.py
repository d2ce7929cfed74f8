"""Seconds of the backend compiles no cache hit served: the `compile_s`
the program's spans carry. In a run whose cache is warm this is what JAX
never writes to the cache (programs that compile in under
`jax_persistent_cache_min_compile_time_secs`), paid again in every run.
Nothing to read where no span carries the counter."""


def read(ctx):
    spans = [r for r in ctx["stream"].get("span", []) if "compile_s" in r]
    if not spans:
        return None
    return sum(float(r["compile_s"]) for r in spans)
