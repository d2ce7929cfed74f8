"""Cross-rank timeline CLI: metrics JSONL -> Perfetto timeline.json.

    python -m pipegcn_tpu.cli.timeline rank0.jsonl rank1.jsonl \
        [--out timeline.json] [--ranks 0,1]

Merges one metrics JSONL stream per rank (written with --metrics-out;
schema obs/schema.py) into a single Chrome-trace file loadable in
Perfetto (ui.perfetto.dev) or chrome://tracing: ranks as processes,
epochs as slices aligned at dispatch boundaries, faults/recoveries as
instant events, loss and staleness drift as counters, profile-window
phase decompositions as sub-slices, serving windows as counter
tracks, fleet/membership/stream/soak/alert records as instants, and
sampled serving spans (--trace-sample-rate) as slices stitched into
per-query Perfetto flows (docs/OBSERVABILITY.md "Timelines"). Rank
ids come from --ranks, else from each stream's own rank-tagged
records, else from file order.
"""

from __future__ import annotations

import argparse
import sys
from typing import Optional, Sequence

from ..obs.metrics import read_metrics
from ..obs.timeline import build_timeline, write_timeline


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m pipegcn_tpu.cli.timeline",
        description="Merge per-rank metrics JSONL files into one "
                    "Perfetto/Chrome-trace timeline.json")
    ap.add_argument("files", nargs="+",
                    help="metrics JSONL file(s), one per rank")
    ap.add_argument("--out", default="timeline.json",
                    help="output Chrome-trace path (default timeline.json)")
    ap.add_argument("--ranks", default="",
                    help="comma-separated rank ids matching the file "
                         "order (default: rank fields in the records, "
                         "else file order)")
    args = ap.parse_args(argv)

    ranks = []
    if args.ranks:
        try:
            ranks = [int(x) for x in args.ranks.split(",")]
        except ValueError:
            print(f"--ranks must be comma-separated integers, got "
                  f"{args.ranks!r}", file=sys.stderr)
            return 2
        if len(ranks) != len(args.files):
            print(f"--ranks lists {len(ranks)} ids for "
                  f"{len(args.files)} files", file=sys.stderr)
            return 2

    rank_records = []
    for i, path in enumerate(args.files):
        try:
            records = read_metrics(path)
        except (OSError, ValueError) as exc:
            print(f"{path}: {exc}", file=sys.stderr)
            return 1
        if ranks:
            rank = ranks[i]
        else:
            rank = next((r["rank"] for r in records
                         if isinstance(r.get("rank"), int)), i)
        rank_records.append((rank, records))

    obj = build_timeline(rank_records)
    write_timeline(obj, args.out)
    n_ev = sum(1 for e in obj["traceEvents"] if e.get("ph") != "M")
    print(f"wrote {args.out}: {len(rank_records)} rank(s), {n_ev} "
          f"events — open in ui.perfetto.dev or chrome://tracing")
    return 0


if __name__ == "__main__":
    sys.exit(main())
