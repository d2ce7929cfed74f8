"""Cross-rank Perfetto/Chrome-trace timelines from metrics JSONL.

Multi-host runs leave one metrics JSONL stream per rank (PR 3's
fault/recovery records are rank-attributed for exactly this reason).
Reading N streams side by side in a text editor is how desync bugs
hide; this module merges them into ONE ``timeline.json`` readable in
Perfetto (ui.perfetto.dev) or chrome://tracing:

  - each rank is a trace *process* (pid = rank), its epochs a track of
    ``X`` (complete) slices; loss and grad-norm ride as ``C`` counter
    tracks per rank;
  - epochs are aligned at dispatch boundaries: when every epoch record
    carries the ``time_unix`` extra (the MetricsLogger stamps it), real
    wall-clock alignment is used; otherwise epoch e of every rank is
    aligned at max-over-ranks of the rank-local cumulative step time —
    the lockstep boundary the SPMD program enforces;
  - fault / recovery / preemption records appear as instant events on
    the owning rank's track, so a chaos drill's kill -> detect ->
    checkpoint -> resume sequence reads as a single picture;
  - ``profile`` records (obs/profiler.py) contribute per-phase span
    estimates inside their capture window;
  - ``staleness`` records ride a counter track (max relative drift);
  - ``serving`` windows ride counter tracks (qps / p50 / queue depth /
    shed), and fleet / membership / stream / soak / alert records are
    instant events on an "events" track — all aligned on their
    ``time_unix`` stamps;
  - ``span`` records (the --trace-sample-rate serving path,
    docs/SERVING.md) become ``X`` slices on a "spans" track, and every
    trace id shared across streams is stitched into a Perfetto *flow*
    (``s``/``t``/``f`` events) so one query reads as an arrow chain
    router -> replica -> engine across processes;
  - training-path spans (``train-e<E>`` trace ids, obs/trainspan.py)
    ride a dedicated per-rank "train" track on the tracesync-ALIGNED
    clock (per-rank offsets from the grad_reduce barrier anchors), and
    each epoch's matching collective spans (grad_reduce /
    bgrad_return / per-layer halo_exchange) are stitched into
    cross-rank flows — the rank-skew picture the straggler
    attribution quantifies.

Chrome-trace JSON contract kept deliberately strict (the timeline test
pins it): object with "traceEvents" (list) + "displayTimeUnit"; every
non-metadata event has numeric ts >= 0 (microseconds) and X events a
numeric dur >= 0; events are emitted sorted by ts.
"""

from __future__ import annotations

import json
import zlib
from typing import Any, Dict, List, Optional, Sequence, Tuple

from .trainspan import COMM_OPS, TRAIN_OPS, estimate_offsets

# wall-clock-stamped record kinds rendered beyond the training tracks
_WALL_KINDS = ("serving", "fleet", "membership", "stream", "soak",
               "alert")
_TRAIN_TRACE = "train-e"


def _scalar_args(r: Dict[str, Any]) -> Dict[str, Any]:
    return {k: v for k, v in r.items() if k != "event"
            and isinstance(v, (int, float, str, bool))}


def _rank_of(records: Sequence[Dict[str, Any]], fallback: int) -> int:
    for r in records:
        if isinstance(r.get("rank"), int):
            return r["rank"]
    return fallback


def _epoch_starts(epochs: List[Dict[str, Any]]
                  ) -> Tuple[Dict[int, float], bool]:
    """{epoch -> start seconds} for one rank + whether real wall-clock
    timestamps were available. Records are written at dispatch END, so
    start = time_unix - step_time_s when stamped; the fallback is the
    rank-local cumulative sum of step times."""
    stamped = all(isinstance(r.get("time_unix"), (int, float))
                  for r in epochs) and bool(epochs)
    starts: Dict[int, float] = {}
    if stamped:
        for r in epochs:
            starts[r["epoch"]] = (float(r["time_unix"])
                                  - float(r.get("step_time_s", 0.0)))
        return starts, True
    t = 0.0
    for r in sorted(epochs, key=lambda x: x.get("epoch", 0)):
        starts[r["epoch"]] = t
        t += float(r.get("step_time_s", 0.0))
    return starts, False


def build_timeline(rank_records: Sequence[Tuple[int, Sequence[Dict[str, Any]]]]
                   ) -> Dict[str, Any]:
    """Merge per-rank metrics records into one Chrome-trace object.

    `rank_records`: [(rank, records), ...] — rank ids need not be
    contiguous; duplicate ranks are kept apart by their input order."""
    events: List[Dict[str, Any]] = []
    meta: List[Dict[str, Any]] = []

    # training-span clock alignment (obs/trainspan.py): per-rank
    # offsets estimated from the tracesync / grad_reduce barrier
    # anchors; every train span renders (and stitches) on the aligned
    # clock t - offset
    train_off = estimate_offsets(
        [r for _, records in rank_records for r in records])

    def _train_aligned(rec: Dict[str, Any], rank: int,
                       t: float) -> float:
        r = rec.get("rank")
        return t - train_off.get(r if isinstance(r, int) else rank, 0.0)

    # pass 1: per-rank epoch start maps; establish the global alignment
    per_rank = []
    any_unstamped = False
    wall_min: Optional[float] = None
    for order, (rank, records) in enumerate(rank_records):
        records = list(records)
        epochs = [r for r in records if r.get("event") == "epoch"
                  and isinstance(r.get("epoch"), int)]
        starts, stamped = _epoch_starts(epochs)
        any_unstamped |= not stamped
        per_rank.append((order, rank, records, epochs, starts, stamped))
        for r in records:
            t = (r.get("t_start") if r.get("event") == "span"
                 else r.get("time_unix")
                 if r.get("event") in _WALL_KINDS else None)
            if isinstance(t, (int, float)):
                if r.get("event") == "span" and str(
                        r.get("trace_id", "")).startswith(_TRAIN_TRACE):
                    t = _train_aligned(r, rank, float(t))
                wall_min = t if wall_min is None else min(wall_min, t)

    if any_unstamped:
        # lockstep alignment: every rank's epoch e starts at the max of
        # the rank-local cumulative starts (the dispatch boundary the
        # slowest rank sets); re-map every rank onto that shared axis
        all_epochs = sorted({e for _, _, _, eps, st, _ in per_rank
                             for e in st})
        shared: Dict[int, float] = {}
        t = 0.0
        for e in all_epochs:
            t = max([t] + [st[e] for _, _, _, _, st, _ in per_rank
                           if e in st])
            shared[e] = t
            durs = [float(r.get("step_time_s", 0.0))
                    for _, _, _, eps, _, _ in per_rank
                    for r in eps if r.get("epoch") == e]
            t += max(durs, default=0.0)
        per_rank = [(o, rk, recs, eps, {e: shared[e] for e in st}, False)
                    for o, rk, recs, eps, st, _ in per_rank]
        t0 = 0.0
        # the shared lockstep axis is synthetic; wall-stamped kinds
        # (serving/fleet/span/...) get their own zero so a mixed file
        # still renders with small timestamps on both axes
        wall_ref = wall_min if wall_min is not None else 0.0
    else:
        t0 = min((min(st.values()) for _, _, _, _, st, _ in per_rank
                  if st), default=0.0)
        if wall_min is not None:
            # wall-stamped kinds may precede the first epoch dispatch
            t0 = min(t0, wall_min)
        wall_ref = t0

    def us(t: float) -> float:
        return round(max(t - t0, 0.0) * 1e6, 3)

    def wus(t: float) -> float:
        return round(max(t - wall_ref, 0.0) * 1e6, 3)

    span_sites: Dict[str, List[Tuple[float, int, int]]] = {}

    for order, rank, records, epochs, starts, stamped in per_rank:
        pid = rank if rank >= 0 else order
        meta.append({"ph": "M", "pid": pid, "name": "process_name",
                     "args": {"name": f"rank {rank}"}})
        meta.append({"ph": "M", "pid": pid, "name": "process_sort_index",
                     "args": {"sort_index": pid}})
        for tid, tname in ((0, "epochs"), (1, "faults"), (2, "profile")):
            meta.append({"ph": "M", "pid": pid, "tid": tid,
                         "name": "thread_name", "args": {"name": tname}})
            meta.append({"ph": "M", "pid": pid, "tid": tid,
                         "name": "thread_sort_index",
                         "args": {"sort_index": tid}})

        for r in epochs:
            e = r["epoch"]
            ts = us(starts[e])
            dur = round(float(r.get("step_time_s", 0.0)) * 1e6, 3)
            events.append({
                "ph": "X", "pid": pid, "tid": 0, "ts": ts, "dur": dur,
                "name": f"epoch {e}",
                "args": {k: r[k] for k in
                         ("loss", "grad_norm", "staleness_age",
                          "halo_bytes") if k in r},
            })
            if isinstance(r.get("loss"), (int, float)):
                events.append({"ph": "C", "pid": pid, "tid": 0,
                               "ts": ts + dur, "name": "loss",
                               "args": {"loss": float(r["loss"])}})

        def _epoch_ts(ep: Optional[Any], end: bool = False) -> float:
            """Best-effort ts for a record anchored to an epoch index."""
            if isinstance(ep, int) and ep in starts:
                base = starts[ep]
                if end:
                    rec = next((x for x in epochs if x["epoch"] == ep),
                               None)
                    base += float(rec.get("step_time_s", 0.0)) if rec \
                        else 0.0
                return us(base)
            if isinstance(ep, int) and starts:
                lo, hi = min(starts), max(starts)
                if ep <= lo:
                    return us(starts[lo])
                last = next(x for x in epochs if x["epoch"] == hi)
                return us(starts[hi]
                          + float(last.get("step_time_s", 0.0)))
            return 0.0

        extra_tids: set = set()

        def _wall_ts(r: Dict[str, Any]) -> float:
            t = r.get("time_unix")
            return wus(float(t)) if isinstance(t, (int, float)) else 0.0

        for r in records:
            ev = r.get("event")
            if ev in ("fault", "recovery"):
                ts = r.get("time_unix")
                ts = (us(float(ts)) if stamped
                      and isinstance(ts, (int, float))
                      else _epoch_ts(r.get("epoch"), end=True))
                events.append({
                    "ph": "i", "pid": pid, "tid": 1, "ts": ts, "s": "t",
                    "name": f"{ev}:{r.get('kind', '?')}",
                    "args": {k: v for k, v in r.items()
                             if k not in ("event",)
                             and isinstance(v, (int, float, str, bool))},
                })
            elif ev == "staleness":
                md = r.get("max_rel_drift")
                if isinstance(md, (int, float)):
                    events.append({
                        "ph": "C", "pid": pid, "tid": 1,
                        "ts": _epoch_ts(r.get("epoch"), end=True),
                        "name": "staleness_rel_drift",
                        "args": {"max_rel_drift": float(md)}})
            elif ev == "serving":
                ts = _wall_ts(r)
                for key in ("qps", "p50_ms", "p99_ms", "queue_depth",
                            "shed"):
                    v = r.get(key)
                    if isinstance(v, (int, float)) \
                            and not isinstance(v, bool):
                        extra_tids.add(3)
                        events.append({
                            "ph": "C", "pid": pid, "tid": 3, "ts": ts,
                            "name": f"serving_{key}",
                            "args": {key: float(v)}})
            elif ev in ("fleet", "membership", "stream", "soak",
                        "alert"):
                if ev == "fleet":
                    name = f"fleet:{r.get('kind', '?')}"
                elif ev == "membership":
                    name = f"membership:g{r.get('generation', '?')}" \
                           f" ({r.get('trigger', '?')})"
                elif ev == "stream":
                    name = f"stream:seq{r.get('seq', '?')}"
                elif ev == "soak":
                    name = f"soak:ep{r.get('episode', '?')}:" \
                           f"{r.get('verdict', '?')}"
                else:
                    name = f"alert:{r.get('state', '?')}:" \
                           f"{r.get('rule', '?')}"
                extra_tids.add(4)
                events.append({
                    "ph": "i", "pid": pid, "tid": 4, "ts": _wall_ts(r),
                    "s": "t", "name": name, "args": _scalar_args(r)})
            elif ev == "span":
                tid_ = r.get("trace_id")
                t_start = r.get("t_start")
                dur_ms = r.get("dur_ms")
                if not (isinstance(tid_, str)
                        and isinstance(t_start, (int, float))
                        and isinstance(dur_ms, (int, float))):
                    continue
                is_train = (tid_.startswith(_TRAIN_TRACE)
                            and r.get("op") in TRAIN_OPS)
                if is_train:
                    # dedicated per-rank "train" track on the ALIGNED
                    # clock; flows stitch each epoch's MATCHING
                    # collective spans across ranks (per op, per halo
                    # layer), not every span of the epoch
                    ts = wus(_train_aligned(r, rank, float(t_start)))
                    track = 6
                    if r.get("op") in COMM_OPS:
                        fkey = f"{tid_}|{r['op']}"
                        if isinstance(r.get("layer"), int):
                            fkey += f"|L{r['layer']}"
                        span_sites.setdefault(fkey, []).append(
                            (ts, pid, track))
                else:
                    ts = wus(float(t_start))
                    track = 5
                    span_sites.setdefault(tid_, []).append(
                        (ts, pid, track))
                extra_tids.add(track)
                events.append({
                    "ph": "X", "pid": pid, "tid": track, "ts": ts,
                    "dur": round(max(float(dur_ms), 0.0) * 1e3, 3),
                    "name": str(r.get("op", "span")),
                    "args": _scalar_args(r)})
            elif ev == "profile":
                a = r.get("epoch_start")
                b = r.get("epoch_end")
                ts = _epoch_ts(a if isinstance(a, int) else None)
                te = _epoch_ts(b - 1 if isinstance(b, int) else None,
                               end=True)
                phases = r.get("phases") or {}
                cursor = ts
                span = max(te - ts, 0.0)
                tot = sum(v for v in phases.values()
                          if isinstance(v, (int, float))) or 1.0
                for name, sec in sorted(phases.items()):
                    if not isinstance(sec, (int, float)) or sec <= 0:
                        continue
                    dur = round(span * sec / tot, 3) if span else \
                        round(sec * 1e6, 3)
                    events.append({"ph": "X", "pid": pid, "tid": 2,
                                   "ts": round(cursor, 3), "dur": dur,
                                   "name": name,
                                   "args": {"device_s": sec}})
                    cursor += dur

        for tid, tname in ((3, "serving"), (4, "events"), (5, "spans"),
                           (6, "train")):
            if tid in extra_tids:
                meta.append({"ph": "M", "pid": pid, "tid": tid,
                             "name": "thread_name",
                             "args": {"name": tname}})
                meta.append({"ph": "M", "pid": pid, "tid": tid,
                             "name": "thread_sort_index",
                             "args": {"sort_index": tid}})

    # flow stitching: every trace id seen in >1 span slice becomes a
    # Perfetto flow (s -> t... -> f) binding the slices it rode —
    # submit -> rpc -> replica -> engine reads as one arrow chain
    for trace_id, sites in span_sites.items():
        if len(sites) < 2:
            continue
        sites.sort()
        fid = zlib.crc32(trace_id.encode("utf-8"))
        # train-collective flow keys carry "|" (trace|op[|layer]);
        # serving flows stay the plain per-query chain
        cat = "collective" if "|" in trace_id else "query"
        for i, (ts, pid, tid) in enumerate(sites):
            ph = "s" if i == 0 else ("f" if i == len(sites) - 1
                                     else "t")
            fe = {"ph": ph, "pid": pid, "tid": tid, "ts": ts,
                  "cat": cat, "name": cat, "id": fid}
            if ph == "f":
                fe["bp"] = "e"
            events.append(fe)

    events.sort(key=lambda e: (e.get("ts", 0.0), e.get("pid", 0),
                               e.get("tid", 0)))
    return {"displayTimeUnit": "ms", "traceEvents": meta + events}


def write_timeline(obj: Dict[str, Any], path: str) -> None:
    with open(path, "w", encoding="utf-8") as f:
        json.dump(obj, f)


__all__ = ["build_timeline", "write_timeline"]
