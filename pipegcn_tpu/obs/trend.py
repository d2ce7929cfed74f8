"""Bench trend tracking over the repo's measurement artifacts
(docs/OBSERVABILITY.md "Live monitoring", scripts/bench_trend.py).

A driver round leaves ``BENCH_r<N>.json`` (the bench headline, or a
failure tail when the round died) and ``MULTICHIP_r<N>.json`` /
``MULTICHIP_40part.json`` behind. This module folds whatever series is
present (none is a valid, empty series) into a per-lever delta
history — epoch time, fused-candidate epoch time, pipeline speedup,
MFU, vs-baseline ratio — flags any lever whose latest value regressed
past tolerance from its best-known headline, and renders the table.

Pure stdlib + filesystem reads; no jax.
"""

from __future__ import annotations

import glob
import json
import os
import re
from typing import Any, Dict, List, Optional

_ROUND_RE = re.compile(r"_r(\d+)\.json$")

# lever key -> direction ("down" = lower is better)
LEVERS: Dict[str, str] = {
    "value": "down",                    # headline metric (s/epoch)
    "candidate_epoch_s": "down",
    "candidate_fused_epoch_s": "down",
    "default_epoch_s": "down",
    "default_vanilla_epoch_s": "down",
    "default_pipeline_speedup": "up",
    "vs_baseline": "up",
    "mfu_pct": "up",
}


def _round_of(path: str) -> int:
    m = _ROUND_RE.search(os.path.basename(path))
    return int(m.group(1)) if m else -1


def _headline_from_tail(tail: str) -> Optional[Dict[str, Any]]:
    """The bench headline is echoed as a JSON line in the captured
    tail; failed rounds (r01's backend traceback) have none."""
    best = None
    for line in tail.splitlines():
        line = line.strip()
        if not (line.startswith("{") and '"metric"' in line):
            continue
        try:
            d = json.loads(line)
        except ValueError:
            continue
        if isinstance(d, dict) and "metric" in d:
            best = d  # last one wins: the final headline of the round
    return best


def load_bench_round(path: str) -> Dict[str, Any]:
    """One ``BENCH_r<N>.json``: {round, ok, headline?} — `headline`
    comes from the pre-parsed field when present, else from scanning
    the tail (r01-style rounds parsed nothing), else None."""
    with open(path, encoding="utf-8") as f:
        d = json.load(f)
    headline = d.get("parsed") or _headline_from_tail(d.get("tail", ""))
    return {"round": _round_of(path) if _round_of(path) >= 0
            else int(d.get("n", -1)),
            "path": os.path.basename(path),
            "ok": d.get("rc", 1) == 0,
            "headline": headline if isinstance(headline, dict) else None}


def load_series(root: str = ".") -> Dict[str, Any]:
    """The whole measurement series under `root`: bench rounds,
    multichip rounds, and the 40-part sweep when present."""
    bench = [load_bench_round(p) for p in sorted(
        glob.glob(os.path.join(root, "BENCH_*.json")), key=_round_of)]
    bench = [b for b in bench if b["round"] >= 0]
    multi = []
    for p in sorted(glob.glob(os.path.join(root, "MULTICHIP_r*.json")),
                    key=_round_of):
        try:
            with open(p, encoding="utf-8") as f:
                d = json.load(f)
        except (OSError, ValueError):
            continue
        multi.append({"round": _round_of(p),
                      "ok": bool(d.get("ok")),
                      "skipped": bool(d.get("skipped")),
                      "n_devices": d.get("n_devices")})
    sweep = None
    p40 = os.path.join(root, "MULTICHIP_40part.json")
    if os.path.isfile(p40):
        try:
            with open(p40, encoding="utf-8") as f:
                sweep = json.load(f)
        except (OSError, ValueError):
            sweep = None
    return {"bench": bench, "multichip": multi, "sweep": sweep}


def _num(v: Any) -> Optional[float]:
    if isinstance(v, bool) or not isinstance(v, (int, float)):
        return None
    return float(v)


# headline fields that make two rounds comparable: when the bench
# harness moves to a new shape/config (r2-r4 measured
# small_epoch_time, r5 reddit_scale_epoch_time), best-known resets —
# comparing epoch seconds across different graphs is not a regression
_CONFIG_KEYS = ("metric", "unit", "n_parts", "pipeline", "spmm_impl",
                "dtype", "headline_config")


def _config_of(h: Dict[str, Any]) -> str:
    return "|".join(str(h.get(k)) for k in _CONFIG_KEYS)


def trend(series: Dict[str, Any], tol: float = 0.05) -> Dict[str, Any]:
    """Per-lever delta history + regression verdict.

    For each lever with >= 1 data point: the (round, value) history,
    the consecutive deltas, the best-known value and its round, and a
    `regressed` flag — latest worse than best by more than `tol`
    (fractional). Best-known is scoped to rounds sharing the latest
    round's config fingerprint (_CONFIG_KEYS): a harness that moved
    to a bigger graph starts a fresh comparison segment instead of
    flagging the shape change as a regression. The top-level verdict
    regresses iff any lever does, or the latest bench round itself
    failed after a previous success."""
    bench = series.get("bench", [])
    levers: Dict[str, Any] = {}
    for key, direction in LEVERS.items():
        hist = []
        for b in bench:
            h = b.get("headline")
            if not h:
                continue
            v = _num(h.get(key))
            if v is not None:
                hist.append({"round": b["round"], "value": v,
                             "config": _config_of(h)})
        if not hist:
            continue
        latest = hist[-1]
        cmp_hist = [h for h in hist if h["config"] == latest["config"]]
        vals = [h["value"] for h in cmp_hist]
        if direction == "down":
            best = min(vals)
        else:
            best = max(vals)
        best_round = cmp_hist[vals.index(best)]["round"]
        deltas = [round(b2["value"] - b1["value"], 6)
                  for b1, b2 in zip(cmp_hist, cmp_hist[1:])]
        if best == 0:
            rel = 0.0
        elif direction == "down":
            rel = (latest["value"] - best) / abs(best)
        else:
            rel = (best - latest["value"]) / abs(best)
        levers[key] = {
            "direction": direction,
            "history": [{"round": h["round"], "value": h["value"]}
                        for h in hist],
            "deltas": deltas,
            "n_comparable": len(cmp_hist),
            "best": best,
            "best_round": best_round,
            "latest": latest["value"],
            "latest_round": latest["round"],
            "vs_best_pct": round(100.0 * rel, 2),
            "regressed": rel > tol,
        }
    ok_rounds = [b["round"] for b in bench if b["ok"]]
    failed_rounds = [b["round"] for b in bench if not b["ok"]]
    latest_failed_after_ok = bool(
        bench and not bench[-1]["ok"] and ok_rounds)
    flags = sorted(k for k, v in levers.items() if v["regressed"])
    if latest_failed_after_ok:
        flags.append("latest-round-failed")
    multi = series.get("multichip", [])
    multi_not_ok = [m["round"] for m in multi
                    if not m["ok"] and not m["skipped"]]
    if multi_not_ok:
        flags.append("multichip-round-failed")
    return {
        "n_rounds": len(bench),
        "ok_rounds": ok_rounds,
        "failed_rounds": failed_rounds,
        "levers": levers,
        "multichip_rounds": len(multi),
        "multichip_failed": multi_not_ok,
        "flags": flags,
        "regressed": bool(flags),
        "tol": tol,
    }


def format_trend(t: Dict[str, Any]) -> str:
    """The human table: one row per lever with its delta history."""
    lines = [f"bench trend over {t['n_rounds']} round(s) "
             f"(ok: {t['ok_rounds']}, failed: {t['failed_rounds']}, "
             f"tol {t['tol'] * 100:.0f}%)"]
    if not t["levers"]:
        lines.append("  no headline data (no BENCH_*.json record, or "
                     "every round failed)")
    w = max((len(k) for k in t["levers"]), default=0)
    for key, v in sorted(t["levers"].items()):
        hist = " -> ".join(f"r{h['round']}:{h['value']:.4g}"
                           for h in v["history"])
        flag = " REGRESSED" if v["regressed"] else ""
        arrow = "v" if v["direction"] == "down" else "^"
        lines.append(
            f"  {key:<{w}} [{arrow}] {hist}  "
            f"best r{v['best_round']}:{v['best']:.4g}  "
            f"latest {v['vs_best_pct']:+.1f}% vs best{flag}")
    if t["multichip_rounds"]:
        lines.append(
            f"  multichip: {t['multichip_rounds']} round(s), "
            f"failed: {t['multichip_failed'] or 'none'}")
    lines.append("verdict: "
                 + ("REGRESSED " + ", ".join(t["flags"])
                    if t["regressed"] else "clean"))
    return "\n".join(lines)
