"""Price a 256-byte row gather by the height of the table it reads.

One chunk of random rows is gathered as `u16[., 128]` words (an fp8
slab row as bucket_spmm hands it to the gather) from a table of R rows
cut into k equal row ranges. Each part is its own array with its own
zero row, packed from its rows of the fp8 slab, gathered one after the
other and summed in float32 as the step sums a bucket. A part's height
decides whether the chip's compiler can keep it in memory space S(1);
this prints, per (R, k): device nanoseconds a request of the gather
fusions, the pack passes' time, and the S(1) mark of every part's word
table and of every gathered chunk in the compiled program.

Usage: python scripts/gather_parts_microbench.py [--out DIR] [--reps N]
"""

import argparse
import json
import os
import re
import sys
import tempfile

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

# (source rows, parts): the Reddit remainder's height, Yelp's cut into
# 1 to 4, and single tables between the two
CASES = [(232_966, 1), (716_848, 1), (716_848, 2), (716_848, 3),
         (716_848, 4), (300_000, 1), (360_000, 1), (420_000, 1)]
WIDTH, ROWS = 8, 16_352          # one chunk: 130,816 requests a part


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=os.path.join(REPO, "profiles",
                                                  "gather_parts"))
    ap.add_argument("--reps", type=int, default=20)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from pipegcn_tpu.backend import device_line, place_compile_cache
    from pipegcn_tpu.obs.profiler import load_xplane, newest_xplane
    from pipegcn_tpu.ops.bucket_spmm import (_gather_sum, _pack_words,
                                             part_bounds)

    place_compile_cache()
    print(f"# {device_line()}", file=sys.stderr)
    dt = jnp.float8_e4m3fn
    rng = np.random.default_rng(0)
    os.makedirs(args.out, exist_ok=True)
    results = []
    for n_rows, k in CASES:
        bounds = part_bounds(n_rows, k)

        def fn(x, mats, bounds=bounds):
            acc = jnp.zeros((ROWS, 256), jnp.float32)
            for (lo, hi), m in zip(bounds, mats):
                table = _pack_words(jnp.concatenate(
                    [x[lo:hi], jnp.zeros((1, 256), dt)]))
                acc = acc + jnp.concatenate(_gather_sum(table, m, "", dt),
                                            axis=-1)
            return acc

        x = jnp.asarray(rng.standard_normal((n_rows, 256)),
                        jnp.float32).astype(dt)
        mats = [jnp.asarray(rng.integers(0, hi - lo, (WIDTH, ROWS)),
                            jnp.int32) for lo, hi in bounds]
        jf = jax.jit(fn)
        compiled = jf.lower(x, mats).compile()
        hlo = compiled.as_text()
        ent = hlo[hlo.index("ENTRY"):]
        gathers, packs = {}, {}
        for line in ent.splitlines():
            m = re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = (\S+) fusion\(", line)
            if not m:
                continue
            name, shape = m.groups()
            if shape.startswith(f"u16[{WIDTH * ROWS},128]"):
                gathers[name] = "S(1)" in shape
            elif shape.startswith("u16[") and "shift-left" in name:
                packs[name] = "S(1)" in shape
        jf(x, mats).block_until_ready()
        trace = tempfile.mkdtemp(dir=args.out)
        with jax.profiler.trace(trace):
            for _ in range(args.reps):
                out = jf(x, mats)
            out.block_until_ready()
        tr = load_xplane(newest_xplane(trace))
        busy = {}
        for line in tr["lines"]:
            for op, _, dur, _ in line["events"]:
                busy[op] = busy.get(op, 0.0) + dur
        g_ns = sum(busy.get(n, 0.0) for n in gathers) / args.reps
        p_ns = sum(busy.get(n, 0.0) for n in packs) / args.reps
        all_ns = sum(busy.values()) / args.reps
        rec = {"rows": n_rows, "parts": k,
               "part_mib": round((bounds[0][1] - bounds[0][0] + 1) * 256
                                 / 2**20, 1),
               "ns_a_request": round(g_ns / (k * WIDTH * ROWS), 3),
               "gather_ms": round(g_ns / 1e6, 4),
               "pack_ms": round(p_ns / 1e6, 4),
               "device_ms": round(all_ns / 1e6, 4),
               "tables_in_s1": list(packs.values()),
               "gathered_in_s1": list(gathers.values())}
        results.append(rec)
        print(json.dumps(rec), flush=True)
    with open(os.path.join(args.out, "results.json"), "w") as f:
        json.dump(results, f, indent=1)


if __name__ == "__main__":
    main()
