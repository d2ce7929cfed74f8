"""One-off: record the small chip trace that `tests/test_trace_reduce.py`
reads (`testdata/chip_trace_<cell>.json.gz`). Not part of a run.

    python3 benchmark/record_trace.py --workload <cell> --seed <n>

runs the cell's set-up and one traced cycle on the chip, and keeps the first
events of each device's op line, what the trace says of the ops they name,
the module and host events inside their span and, of the compiled scans'
HLO text, only the `op_name` metadata of those ops. It also writes the
trace's layout (planes, lines, event counts, stat keys) for a look by hand.
"""

from __future__ import annotations

import argparse
import gzip
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def save_recorded(tr: dict, path: str, hlo_texts=(),
                  max_events: int = 6000) -> None:
    from benchmark.trace_reduce import hlo_scope_map

    devices = {k: sorted(v, key=lambda e: e[1])[:max_events]
               for k, v in tr["devices"].items()}
    names = {e[0] for v in devices.values() for e in v}
    lo = min(e[1] for v in devices.values() for e in v)
    hi = max(e[1] + e[2] for v in devices.values() for e in v)

    def inside(e):
        return e[1] + e[2] >= lo and e[1] <= hi

    host = [e for e in tr["host"] if inside(e)]
    with gzip.open(path, "wt") as f:
        json.dump({"devices": devices,
                   "modules": {k: [e for e in v if inside(e)]
                               for k, v in tr["modules"].items()},
                   "op_text": {k: v for k, v in tr["op_text"].items()
                               if k in names},
                   "hlo_scopes": [{k: v for k, v in hlo_scope_map(t).items()
                                   if k in names} for t in hlo_texts],
                   "host": host[:2000], "layout": tr["layout"]}, f)


def main(argv, root: str = ROOT) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    opts = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    from benchmark import harness, trace_reduce

    def log(msg):
        print(msg, file=sys.stderr, flush=True)

    spec = harness.load_spec(root, opts.workload)
    harness.require_device(int(spec["cell"]["chips"]))
    su = harness.set_up(spec, opts.seed, time.perf_counter(), log)
    trace_dir = os.path.join(su["out_dir"], "trace")
    harness.measure_window(su, 0.0, trace_dir)
    hlo = [t for t in (harness.multi_step_hlo(su["trainer"], n)
                       for n in su["lengths"]) if t]
    raw = trace_reduce.load_xplane(trace_reduce.newest_xplane(trace_dir))
    out = os.path.join(su["out_dir"], f"chip_trace_{opts.workload}.json.gz")
    save_recorded(raw, out, hlo)
    with open(os.path.join(su["out_dir"], "trace_layout.txt"), "w") as f:
        f.write("\n".join(raw["layout"]) + "\n")
    log(f"wrote {out} and trace_layout.txt beside it")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
