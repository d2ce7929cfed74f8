"""Seconds of the programs the persistent compile cache served: the
`cache_load_s` the program's spans carry (the cache key and the read of
the executable, for each cache hit). Nothing to read where no span
carries the counter."""


def read(ctx):
    spans = [r for r in ctx["stream"].get("span", [])
             if "cache_load_s" in r]
    if not spans:
        return None
    return sum(float(r["cache_load_s"]) for r in spans)
