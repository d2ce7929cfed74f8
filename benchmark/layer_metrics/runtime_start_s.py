"""Seconds from the process's start to the end of the runtime's start,
from the program's own set-up spans: `setup/process` (from the start the
OS records to the program's first span: the interpreter, the imports
and, under the benchmark, the TPU runtime's start) plus `setup/runtime`
(`import jax`, the compile cache's placement, the first device query).
Nothing to read where the stream carries neither span."""


def read(ctx):
    spans = [r for r in ctx["stream"].get("span", [])
             if r.get("op") in ("setup/process", "setup/runtime")]
    if not spans:
        return None
    return sum(float(r["dur_ms"]) for r in spans) / 1e3
