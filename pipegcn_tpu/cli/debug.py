"""Postmortem CLI (docs/OBSERVABILITY.md "Postmortem & flight
recorder").

    python -m pipegcn_tpu.cli.debug explain <run-dir> [--json] \
        [--out metrics.jsonl]

Collects everything a dead run left behind — black-box flight-recorder
dumps (``blackbox-r<k>.json``), every metrics JSONL stream, child log
tails, checkpoint metadata, environment fingerprint — and runs the
evidence-citing rule engine (obs/postmortem.py) over it. Prints a
confidence-ranked verdict with remediation and a last-minutes
timeline; `--json` emits the contracted ``diagnosis`` record instead.
`--out` additionally appends that record to a metrics JSONL sink (the
supervisor uses the library entry point directly).

    python -m pipegcn_tpu.cli.debug scrub <run-dir> [--json]

``scrub`` is the offline arm of the integrity plane
(docs/RESILIENCE.md "Silent data corruption"): it digest-verifies
every artifact under a run directory that carries its own integrity
metadata — checkpoint generations (``state-*.npz`` digest manifests
via utils/checkpoint.verify_checkpoint), membership-ledger records
(CRC32, resilience/elastic.MembershipLedger), and kernel-tuning
sidecars (``tuning.json`` format/winner validation) — and lists any
standing rank-quarantine markers. Exit 0 when everything verifies,
2 when ANY artifact is corrupt (so cron/window sweeps can alarm on
at-rest rot before a resume trips over it).

Exit code: 0 when a diagnosis was reached / everything verified, 4
when the verdict is ``unknown`` (nothing matched — collect more and
retry), 2 when ``scrub`` found corruption, 1 on usage / IO errors."""

from __future__ import annotations

import argparse
import glob as _glob
import json
import os
import sys
from typing import Optional, Sequence

EXIT_UNKNOWN = 4
EXIT_CORRUPT = 2


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="python -m pipegcn_tpu.cli.debug",
        description="Automated postmortem: diagnose why a run died "
                    "from the artifacts it left behind")
    sub = p.add_subparsers(dest="command", required=True)
    ex = sub.add_parser(
        "explain", help="diagnose a run directory and print the "
                        "verdict with evidence")
    ex.add_argument("run_dir",
                    help="run directory (checkpoint/coordination/"
                         "metrics dir — anything holding the run's "
                         "artifacts)")
    ex.add_argument("--json", action="store_true",
                    help="emit the contracted diagnosis record as "
                         "JSON instead of the human report")
    ex.add_argument("--out", default=None, metavar="METRICS.JSONL",
                    help="also append the diagnosis record to this "
                         "metrics JSONL sink")
    sc = sub.add_parser(
        "scrub", help="digest-verify every self-describing artifact "
                      "under a run directory (checkpoints, membership "
                      "ledger, tuning sidecars); exit 2 on corruption")
    sc.add_argument("run_dir",
                    help="run directory to sweep recursively")
    sc.add_argument("--json", action="store_true",
                    help="emit the scrub report as JSON instead of "
                         "the human summary")
    return p


def _scrub(run_dir: str) -> dict:
    """Sweep `run_dir` recursively and digest-verify everything that
    carries integrity metadata. Pure host-side reads — never mutates,
    never needs a device."""
    from ..ops.tuner import TUNING_FILE, load_tuning
    from ..resilience.elastic import (LEDGER_PREFIX, LedgerCorrupt,
                                      MembershipLedger)
    from ..resilience.integrity import read_quarantines
    from ..utils.checkpoint import CheckpointCorrupt, verify_checkpoint

    report: dict = {"run_dir": os.path.abspath(run_dir),
                    "checkpoints": [], "ledger": [], "tuning": [],
                    "quarantines": [], "corrupt": 0}

    for path in sorted(_glob.glob(
            os.path.join(run_dir, "**", "state-*.npz"), recursive=True)):
        rel = os.path.relpath(path, run_dir)
        try:
            epoch = verify_checkpoint(path)
            report["checkpoints"].append(
                {"path": rel, "ok": True, "epoch": epoch})
        except CheckpointCorrupt as exc:
            report["corrupt"] += 1
            report["checkpoints"].append(
                {"path": rel, "ok": False, "error": str(exc)[:300]})

    ledger_dirs = sorted({os.path.dirname(p) for p in _glob.glob(
        os.path.join(run_dir, "**", LEDGER_PREFIX + "*.json"),
        recursive=True)})
    for d in ledger_dirs:
        led = MembershipLedger(d)
        for gen in led.generations():
            rel = os.path.relpath(led.path_for(gen), run_dir)
            try:
                led.read(gen)
                report["ledger"].append(
                    {"path": rel, "ok": True, "generation": gen})
            except LedgerCorrupt as exc:
                report["corrupt"] += 1
                report["ledger"].append(
                    {"path": rel, "ok": False, "generation": gen,
                     "error": str(exc)[:300]})
        for member, info in sorted(read_quarantines(d).items()):
            report["quarantines"].append(
                {"coord_dir": os.path.relpath(d, run_dir),
                 "member": member,
                 "reason": info.get("reason", "unreadable marker")})

    for path in sorted(_glob.glob(
            os.path.join(run_dir, "**", TUNING_FILE), recursive=True)):
        cache_dir = os.path.dirname(path)
        rel = os.path.relpath(path, run_dir)
        rec, reason = load_tuning(cache_dir)
        if rec is not None:
            report["tuning"].append({"path": rel, "ok": True})
        else:
            report["corrupt"] += 1
            report["tuning"].append(
                {"path": rel, "ok": False, "error": reason})

    report["ok"] = report["corrupt"] == 0
    return report


def _render_scrub(report: dict) -> str:
    lines = [f"scrub {report['run_dir']}"]
    for section in ("checkpoints", "ledger", "tuning"):
        items = report[section]
        bad = [i for i in items if not i["ok"]]
        lines.append(f"  {section}: {len(items) - len(bad)}/"
                     f"{len(items)} verified")
        for i in bad:
            lines.append(f"    CORRUPT {i['path']}: {i['error']}")
    for q in report["quarantines"]:
        lines.append(f"  quarantined member {q['member']} "
                     f"({q['coord_dir']}): {q['reason']}")
    lines.append("verdict: " + ("clean" if report["ok"] else
                                f"{report['corrupt']} corrupt "
                                f"artifact(s)"))
    return "\n".join(lines) + "\n"


def main(argv: Optional[Sequence[str]] = None) -> int:
    args = build_parser().parse_args(argv)

    if not os.path.isdir(args.run_dir):
        print(f"pipegcn-debug: not a directory: {args.run_dir}",
              file=sys.stderr)
        return 1

    if args.command == "scrub":
        report = _scrub(args.run_dir)
        if args.json:
            print(json.dumps(report))
        else:
            print(_render_scrub(report), end="")
        return 0 if report["ok"] else EXIT_CORRUPT

    from ..obs import postmortem

    verdict = postmortem.diagnose_run(args.run_dir)

    if args.out:
        from ..obs.metrics import MetricsLogger

        ml = MetricsLogger(args.out)
        try:
            ml.diagnosis(
                verdict=verdict["verdict"],
                confidence=verdict["confidence"],
                evidence=verdict["evidence"],
                remediation=verdict["remediation"],
                deterministic=verdict["deterministic"],
                run_dir=verdict.get("run_dir", ""),
            )
        finally:
            ml.close()

    if args.json:
        print(json.dumps(verdict))
    else:
        print(postmortem.render(verdict), end="")
    return EXIT_UNKNOWN if verdict["verdict"] == "unknown" else 0


if __name__ == "__main__":
    sys.exit(main())
