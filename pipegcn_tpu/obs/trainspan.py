"""Always-on training-path span plane (schema v14 spans + tracesync).

The serving path got per-query distributed tracing in the fleet PR
(serve/tracing.py); this module gives the TRAINER the same treatment
so every multi-chip (or multi-process CPU-mesh) run self-measures
pipeline overlap, comm cost, and rank skew without a profiler capture
window.  Everything is host-side bookkeeping: no jax imports, no
effect on the compiled programs — the zero-recompile pins in
tests/test_trainspan.py hold with spans hot.

Span model (docs/OBSERVABILITY.md "Training traces"):

* One ``compute`` span per dispatched block — the REAL dispatch→
  harvest wall window, tagged (rank, generation, epoch, epochs).
* Once the trainer's one-shot standalone collective measurement
  (``Trainer.measure_comm``) lands, every block additionally gets a
  comm tail: one ``halo_exchange`` span per graph layer (standalone
  halo cost apportioned by wire bytes, tagged with bytes + dtype),
  one ``bgrad_return`` and one ``grad_reduce`` span — placed
  back-to-back ENDING at the harvest barrier, ``grad_reduce`` last.
  Blocks before the measurement gate carry compute spans only.
* ``checkpoint`` / ``eval`` spans bracket those host phases.

All spans for epoch E share the deterministic trace id ``train-e<E>``
— identical on every rank with zero coordination, so ``cli.timeline``
stitches cross-rank flows exactly as it does for serving spans.

Clock alignment: every rank's ``grad_reduce`` for epoch E ends at the
same collective barrier (the jit program cannot complete on any rank
until the reduce has), so each block also emits a contracted
``tracesync`` record anchoring that barrier in the rank's wall clock.
:func:`estimate_offsets` recovers per-rank clock offsets from those
anchors (median over epochs of each rank's deviation from the
cross-rank median) and :func:`fold_spans` uses the aligned clock for
straggler attribution.

Derived verdicts (:func:`fold_spans`, surfaced by obs/live.py,
obs/health.py and ``pipegcn-report``):

* ``overlap_spans`` — per-epoch MEASURED overlap fraction: the
  interval-union of comm spans covered by compute spans, the same
  math as ``obs/profiler.fold_xplane`` but from always-on spans (the
  fraction of the measured comm cost the measured wall window
  absorbs; comm-bound epochs spill past the window start and read
  exposed).
* ``comm_wait_share_by_rank`` — exposed comm seconds / wall seconds.
* straggler attribution — which rank's compute window STARTED last at
  each dispatch boundary on the aligned clock, and by how much.
"""

from __future__ import annotations

import time
from typing import Callable, Dict, Iterable, List, Optional, Tuple

from ..serve.tracing import SpanWriter
from .profiler import _overlap_with_union, merge_intervals

#: comm-phase span ops (the trainer-side mirror of profiler.COMM_PHASES)
COMM_OPS = ("halo_exchange", "bgrad_return", "grad_reduce")
#: every op the training-span plane emits
TRAIN_OPS = ("compute",) + COMM_OPS + ("checkpoint", "eval")
_TRACE_PREFIX = "train-e"


def trace_id(epoch: int) -> str:
    """Deterministic cross-rank trace id for epoch `epoch` — the same
    string on every rank with zero coordination, which is what lets the
    timeline stitch flows across processes."""
    return f"{_TRACE_PREFIX}{int(epoch)}"


def _median(xs: List[float]) -> float:
    s = sorted(xs)
    n = len(s)
    if n == 0:
        return 0.0
    return s[n // 2] if n % 2 else 0.5 * (s[n // 2 - 1] + s[n // 2])


class TrainSpanPlane:
    """Per-rank training-span emitter over the contracted span sink.

    Reuses the serving path's :class:`SpanWriter` (injectable clocks,
    thread-safe ids, wall-aligned t_start) with ``source`` set to the
    rank tag ``r<k>``. Span volume is a handful per dispatched block —
    always-on by design; ``--no-train-traces`` disables construction
    entirely."""

    def __init__(self, ml, rank: int = 0, generation: int = 0,
                 clock: Callable[[], float] = time.monotonic,
                 now: Callable[[], float] = time.time):
        self._ml = ml
        self.rank = int(rank)
        self.generation = int(generation)
        self._clock = clock
        self._now = now
        self.writer = SpanWriter(ml, clock=clock,
                                 source=f"r{int(rank)}", now=now)
        self.counts: Dict[str, int] = {}
        self.blocks = 0           # dispatched blocks span-covered
        self._costs = None        # standalone per-epoch medians, or None
        self._layer_bytes: Tuple[Tuple[int, int], ...] = ()
        self._dtype = "none"

    def clock(self) -> float:
        """The plane's monotonic clock — the trainer brackets its
        dispatch window with this so fake-clock tests stay exact."""
        return self._clock()

    # ---------------- comm arming -------------------------------------

    def set_comm(self, costs: Dict[str, float],
                 layer_bytes: Iterable[Tuple[int, int]],
                 dtype: str) -> None:
        """Arm the comm tail once ``Trainer.measure_comm()`` lands:
        `costs` holds the standalone per-epoch medians ({"comm",
        "reduce", "bgrad"} seconds), `layer_bytes` the per-graph-layer
        halo wire bytes used to apportion the halo cost, `dtype` the
        wire dtype tag. Until this is called blocks emit compute spans
        only (documented: the measurement gate fires a few epochs in)."""
        self._costs = {k: max(float(costs.get(k, 0.0)), 0.0)
                       for k in ("comm", "reduce", "bgrad")}
        self._layer_bytes = tuple((int(li), max(int(b), 0))
                                  for li, b in layer_bytes)
        self._dtype = str(dtype)

    @property
    def comm_armed(self) -> bool:
        return self._costs is not None

    # ---------------- emission ----------------------------------------

    def _emit(self, tid: str, op: str, t0: float, t1: float,
              status: str = "ok", **extra) -> None:
        extra.setdefault("rank", self.rank)
        extra.setdefault("generation", self.generation)
        self.counts[op] = self.counts.get(op, 0) + 1
        self.writer.emit(tid, op, t0, t1, status, **extra)

    def block(self, epoch: int, chunk: int, dur_s: float,
              t_end: Optional[float] = None, **extra) -> None:
        """Spans for one dispatched block of `chunk` epochs starting at
        epoch `epoch`, whose dispatch→harvest wall window measured
        `dur_s` seconds and ended at plane-clock `t_end` (defaults to
        now — call right after harvest). Also lands the block's
        ``tracesync`` barrier anchor. `extra` rides the compute span
        (`span_id` and what the host did since the previous harvest:
        obs/trace.BlockSpans)."""
        if t_end is None:
            t_end = self._clock()
        tid = trace_id(epoch)
        dur_s = max(float(dur_s), 0.0)
        chunk = max(int(chunk), 1)
        comm_total = (sum(self._costs.values()) * chunk
                      if self._costs is not None else 0.0)
        # exposed comm: the slice of the standalone comm cost the wall
        # window could not have absorbed even at perfect overlap
        wait = max(comm_total - dur_s, 0.0)
        self._emit(tid, "compute", t_end - dur_s, t_end, epoch=epoch,
                   epochs=chunk, comm_wait_s=round(wait, 6), **extra)
        self.blocks += 1
        if self._ml is not None:
            # wall-clock barrier anchor (same clock->unix offset rule
            # as SpanWriter.emit, captured per record)
            self._ml.tracesync(self.rank, epoch,
                               t_end + (self._now() - self._clock()),
                               self.generation)
        if self._costs is None:
            return
        # comm tail, back-to-back ENDING at the harvest barrier:
        # halo layers in layer order, bgrad_return, grad_reduce last —
        # so grad_reduce's end IS the cross-rank alignment anchor
        cur = t_end
        d = self._costs["reduce"] * chunk
        self._emit(tid, "grad_reduce", cur - d, cur, epoch=epoch)
        cur -= d
        d = self._costs["bgrad"] * chunk
        self._emit(tid, "bgrad_return", cur - d, cur, epoch=epoch)
        cur -= d
        halo = self._costs["comm"] * chunk
        total_b = sum(b for _, b in self._layer_bytes)
        for li, b in reversed(self._layer_bytes):
            d = (halo * b / total_b if total_b > 0
                 else halo / max(len(self._layer_bytes), 1))
            self._emit(tid, "halo_exchange", cur - d, cur, epoch=epoch,
                       layer=li, wire_bytes=b * chunk,
                       dtype=self._dtype)
            cur -= d

    def eval_span(self, epoch: int, wait_s: float,
                  t_end: Optional[float] = None) -> None:
        """The eval harvest wait for epoch `epoch` (`wait_s` seconds
        ending at `t_end`, default now)."""
        if t_end is None:
            t_end = self._clock()
        self._emit(trace_id(epoch), "eval",
                   t_end - max(float(wait_s), 0.0), t_end, epoch=epoch)

    def checkpoint_span(self, epoch: int, dur_s: float,
                        t_end: Optional[float] = None,
                        status: str = "ok") -> None:
        """One checkpoint save window (epoch tag = the boundary's
        completed-epoch label)."""
        if t_end is None:
            t_end = self._clock()
        self._emit(trace_id(epoch), "checkpoint",
                   t_end - max(float(dur_s), 0.0), t_end,
                   status=status, epoch=epoch)

    def flush(self) -> None:
        """Hard-flush the sink: called from fault paths so the spans
        already emitted survive a crash or watchdog ``_hard_exit``
        (which also hard-flushes the shared sink in its own finally)."""
        if self._ml is not None:
            self._ml.hard_flush()


# ---------------- folding: records -> verdicts ------------------------


def train_spans(records: Iterable[dict]) -> List[dict]:
    """The training-path span records in `records` (merged streams ok)."""
    return [r for r in records
            if r.get("event") == "span" and r.get("op") in TRAIN_OPS
            and str(r.get("trace_id", "")).startswith(_TRACE_PREFIX)]


def _rank_of(rec: dict) -> int:
    r = rec.get("rank")
    if r is not None:
        return int(r)
    src = str(rec.get("source", ""))
    if src.startswith("r") and src[1:].isdigit():
        return int(src[1:])
    return 0


def _epoch_of(rec: dict) -> Optional[int]:
    e = rec.get("epoch")
    if e is not None:
        return int(e)
    tid = str(rec.get("trace_id", ""))
    if tid.startswith(_TRACE_PREFIX) and tid[len(_TRACE_PREFIX):].isdigit():
        return int(tid[len(_TRACE_PREFIX):])
    return None


def _interval(rec: dict) -> Tuple[float, float]:
    t0 = float(rec["t_start"])
    return (t0, t0 + float(rec["dur_ms"]) / 1e3)


def estimate_offsets(records: Iterable[dict]) -> Dict[int, float]:
    """Per-rank clock offsets from collective-boundary alignment.

    Every rank's epoch-E barrier anchor (``tracesync`` record, falling
    back to the ``grad_reduce`` span end) marks the same physical
    instant; a rank's offset is the median over shared epochs of its
    deviation from the cross-rank median anchor. Subtracting the
    offset aligns that rank's timestamps (``t_aligned = t - offset``).
    Ranks with no shared epoch (or a single-rank run) get offset 0."""
    anchors: Dict[int, Dict[int, float]] = {}  # epoch -> rank -> t
    for rec in records:
        if rec.get("event") == "tracesync":
            e, r = int(rec["epoch"]), int(rec["rank"])
            anchors.setdefault(e, {})[r] = float(rec["t_anchor"])
    if not anchors:  # fallback: reduce-span ends are the same barrier
        for rec in train_spans(records):
            if rec.get("op") != "grad_reduce":
                continue
            e = _epoch_of(rec)
            if e is None:
                continue
            anchors.setdefault(e, {})[_rank_of(rec)] = _interval(rec)[1]
    deltas: Dict[int, List[float]] = {}
    for e, by_rank in anchors.items():
        if len(by_rank) < 2:
            continue
        med = _median(list(by_rank.values()))
        for r, t in by_rank.items():
            deltas.setdefault(r, []).append(t - med)
    return {r: _median(ds) for r, ds in deltas.items()}


def fold_spans(records: Iterable[dict],
               offsets: Optional[Dict[int, float]] = None) -> dict:
    """Fold training spans (+ tracesync anchors) into the derived
    verdicts: measured overlap fraction, per-rank comm-wait share, and
    straggler attribution — the always-on counterpart of
    ``obs/profiler.fold_xplane`` (same interval-union overlap math).

    Returns a plain dict (all keys present, Nones when undecidable):
    ``overlap_spans`` (comm-weighted mean fraction), ``per_epoch``
    ({epoch: {overlap, straggler_rank, gap_s}}), ``comm_wait_share_by_
    rank``, ``straggler_gap_s_by_rank``, ``straggler_max_gap_s``,
    ``straggler_rank``, ``counts``, ``offsets``."""
    records = list(records)
    spans = train_spans(records)
    if offsets is None:
        offsets = estimate_offsets(records)
    counts: Dict[str, int] = {}
    # (rank, epoch) -> op-partitioned intervals
    comp: Dict[Tuple[int, int], List[Tuple[float, float]]] = {}
    comm: Dict[Tuple[int, int], List[Tuple[float, float]]] = {}
    wall: Dict[int, float] = {}
    for rec in spans:
        op = rec["op"]
        counts[op] = counts.get(op, 0) + 1
        e = _epoch_of(rec)
        if e is None:
            continue
        key = (_rank_of(rec), e)
        iv = _interval(rec)
        if op == "compute":
            comp.setdefault(key, []).append(iv)
            wall[key[0]] = wall.get(key[0], 0.0) + (iv[1] - iv[0])
        elif op in COMM_OPS:
            comm.setdefault(key, []).append(iv)

    covered_total = comm_total = 0.0
    exposed: Dict[int, float] = {}
    per_epoch: Dict[int, dict] = {}
    for key, comm_iv in comm.items():
        union = merge_intervals(comp.get(key, []))
        cov = sum(_overlap_with_union(iv, union) for iv in comm_iv)
        tot = sum(b - a for a, b in comm_iv)
        covered_total += cov
        comm_total += tot
        exposed[key[0]] = exposed.get(key[0], 0.0) + max(tot - cov, 0.0)
        if tot > 0:
            pe = per_epoch.setdefault(key[1], {})
            frac = min(max(cov / tot, 0.0), 1.0)
            # per-epoch overlap: mean across the ranks seen so far
            n = pe.get("_n", 0)
            pe["overlap"] = ((pe.get("overlap", 0.0) * n + frac)
                             / (n + 1))
            pe["_n"] = n + 1

    # straggler attribution: aligned compute-window STARTs per epoch
    gaps: Dict[int, float] = {}
    for e in {k[1] for k in comp}:
        starts = {r: min(iv[0] for iv in comp[(r, e)])
                  - offsets.get(r, 0.0)
                  for r, ee in comp if ee == e}
        if len(starts) < 2:
            continue
        med = _median(list(starts.values()))
        worst, gap = max(((r, t - med) for r, t in starts.items()),
                         key=lambda x: x[1])
        pe = per_epoch.setdefault(e, {})
        pe["straggler_rank"] = worst
        pe["gap_s"] = round(gap, 6)
        for r, t in starts.items():
            gaps[r] = max(gaps.get(r, 0.0), t - med)
    for pe in per_epoch.values():
        pe.pop("_n", None)

    max_rank, max_gap = None, 0.0
    for r, g in gaps.items():
        if g > max_gap:
            max_rank, max_gap = r, g
    return {
        "overlap_spans": (min(max(covered_total / comm_total, 0.0), 1.0)
                          if comm_total > 0 else None),
        "per_epoch": {e: per_epoch[e] for e in sorted(per_epoch)},
        "comm_wait_share_by_rank": {
            r: min(max(exposed.get(r, 0.0) / w, 0.0), 1.0)
            for r, w in sorted(wall.items()) if w > 0},
        "comm_wait_s_by_rank": {r: round(s, 6)
                                for r, s in sorted(exposed.items())},
        "straggler_gap_s_by_rank": {r: round(max(g, 0.0), 6)
                                    for r, g in sorted(gaps.items())},
        "straggler_max_gap_s": (round(max_gap, 6)
                                if max_rank is not None else None),
        "straggler_rank": max_rank,
        "counts": counts,
        "offsets": {r: round(o, 6) for r, o in sorted(offsets.items())},
    }
