"""MetricsLogger — the JSONL event sink — and host-side probes.

One line per record, appended and flushed immediately so a crashed run
still leaves every completed epoch on disk (the trainer's crash
checkpoint philosophy applied to telemetry). Values are sanitized
through `_jsonable` (numpy scalars/arrays, jnp dtypes, tuples) so
callers can pass device-adjacent objects without ceremony.

jax is imported lazily and only by the probes (device_info /
mesh_info / memory_snapshot): the logger itself must stay importable
from jax-free host processes (partition builders, report tooling).
"""

from __future__ import annotations

import collections
import json
import os
import time
import warnings
from typing import Any, Dict, List, Optional, Sequence, Union

from .schema import SCHEMA_VERSION, validate_record

# ring-buffer capacity while the sink is io-degraded; beyond this the
# OLDEST buffered records are dropped (and counted in the recovery
# record) — fault/recovery records are small, so 4096 lines outlasts
# any realistic disk-full window
_RING_CAPACITY = 4096


def _storage_io():
    # lazy: resilience/__init__ -> elastic -> this module would cycle
    # on a top-level import of the storage shim
    from ..resilience.storage import FAULTY_IO
    return FAULTY_IO


def _jsonable(v: Any) -> Any:
    """Best-effort conversion to JSON-serializable types."""
    if v is None or isinstance(v, (bool, int, float, str)):
        return v
    if isinstance(v, dict):
        return {str(k): _jsonable(x) for k, x in v.items()}
    if isinstance(v, (list, tuple)):
        return [_jsonable(x) for x in v]
    # numpy / jax scalars and arrays without importing either
    item = getattr(v, "item", None)
    if callable(item) and getattr(v, "ndim", None) == 0:
        return _jsonable(v.item())
    tolist = getattr(v, "tolist", None)
    if callable(tolist):
        try:
            return _jsonable(tolist())
        except Exception:
            pass
    return str(v)


class MetricsLogger:
    """Append-only JSONL sink with schema validation.

    `path` may be a filesystem path (parent dirs created, file opened
    in append mode) or any object with ``write``. Use as a context
    manager or call :meth:`close`; a logger left open still has every
    record on disk (each write is flushed).

    Storage-fault degradation (docs/RESILIENCE.md "Storage faults"):
    when the sink's disk fails (ENOSPC, EROFS, a yanked mount — or the
    injected equivalents, resilience/storage.py) the logger goes
    *io-degraded* instead of raising or silently dropping: records
    accumulate in an in-memory ring buffer (one loud warning per
    episode), every subsequent write retries the disk, and on recovery
    the ring re-drains in order followed by a ``recovery/io-degraded``
    record counting what was re-drained and what (if anything) the
    ring had to drop. Fault/recovery records are therefore never
    silently lost — the worst case is a bounded, counted gap."""

    def __init__(self, path: Union[str, "os.PathLike", Any],
                 validate: bool = True):
        self._validate = validate
        self._owns_file = isinstance(path, (str, os.PathLike))
        if self._owns_file:
            path = os.fspath(path)
            parent = os.path.dirname(path)
            if parent:
                os.makedirs(parent, exist_ok=True)
            self._f = open(path, "a", encoding="utf-8")
            self.path: Optional[str] = path
        else:
            self._f = path
            self.path = None
        self.header_written = False
        self._ring: collections.deque = collections.deque(
            maxlen=_RING_CAPACITY)
        self._degraded = False
        self._dropped = 0
        self._n_records = 0

    # ---------------- degradation policy ------------------------------

    @property
    def degraded(self) -> bool:
        """True while the sink is io-degraded (records ring-buffered)."""
        return self._degraded

    def _enter_degraded(self, exc: BaseException,
                        line: Optional[str]) -> None:
        if not self._degraded:
            self._degraded = True
            warnings.warn(
                f"metrics sink {self.path or self._f!r} is io-degraded "
                f"({exc!r}); buffering records in memory (capacity "
                f"{_RING_CAPACITY}) and retrying on every write")
        if line is not None:
            if len(self._ring) == self._ring.maxlen:
                self._dropped += 1
            self._ring.append(line)

    def _try_recover(self) -> bool:
        """Attempt to re-drain the ring to the sink; True on success.
        The recovery record is appended DIRECTLY (not via write()) so a
        failure mid-drain can never recurse back into degradation
        bookkeeping with half the ring gone — lines leave the ring only
        after they hit the file."""
        if not self._degraded:
            return True
        try:
            if self._owns_file and getattr(self._f, "closed", False):
                if self.path is not None:
                    _storage_io().gate(self.path, "open")
                self._f = open(self.path, "a", encoding="utf-8")
            if self.path is not None:
                _storage_io().gate(self.path, "write")
            redrained = 0
            while self._ring:
                self._f.write(self._ring[0])
                self._ring.popleft()
                redrained += 1
            from ..resilience.storage import IO_DEGRADED
            self._f.write(json.dumps({
                "event": "recovery", "kind": IO_DEGRADED, "epoch": -1,
                "rank": _local_rank(), "redrained": redrained,
                "dropped": self._dropped,
                "time_unix": time.time()}) + "\n")
            self._f.flush()
        except (OSError, ValueError):
            return False
        self._degraded = False
        self._dropped = 0
        return True

    # ---------------- record writers ----------------------------------

    def write(self, rec: Dict[str, Any]) -> Dict[str, Any]:
        rec = {k: _jsonable(v) for k, v in rec.items()}
        if self._validate:
            validate_record(rec)
        line = json.dumps(rec) + "\n"
        self._n_records += 1
        if self._degraded and not self._try_recover():
            self._enter_degraded(OSError("sink still degraded"), line)
            return rec
        try:
            if self.path is not None:
                _storage_io().gate(self.path, "write")
            self._f.write(line)
            self._f.flush()
        except OSError as exc:
            self._enter_degraded(exc, line)
        return rec

    def run_header(self, config: Optional[dict] = None,
                   device: Optional[dict] = None,
                   mesh: Optional[dict] = None, **extra) -> Dict[str, Any]:
        """The one-per-run header: schema version + what produced the
        numbers. Idempotent guard lives in `header_written` — callers
        that may be second in line (fit() after the CLI) check it."""
        rec = self.write({
            "event": "run",
            "schema_version": SCHEMA_VERSION,
            "time_unix": time.time(),
            "config": config or {},
            "device": device or {},
            "mesh": mesh or {},
            **extra,
        })
        self.header_written = True
        return rec

    def epoch(self, epoch: int, step_time_s: float, loss: float,
              grad_norm: float, halo_bytes: int, staleness_age: int,
              memory: Optional[dict] = None, **extra) -> Dict[str, Any]:
        # time_unix (record write time = dispatch end) is an optional
        # extra: the timeline CLI uses it for real wall-clock alignment
        # across ranks when every epoch record carries it
        extra.setdefault("time_unix", time.time())
        return self.write({
            "event": "epoch",
            "epoch": int(epoch),
            "step_time_s": float(step_time_s),
            "loss": float(loss),
            "grad_norm": float(grad_norm),
            "halo_bytes": int(halo_bytes),
            "staleness_age": int(staleness_age),
            "memory": memory,
            **extra,
        })

    def eval_record(self, epoch: int, eval_time_s: float, val_acc: float,
                    **extra) -> Dict[str, Any]:
        extra.setdefault("time_unix", time.time())
        return self.write({
            "event": "eval",
            "epoch": int(epoch),
            "eval_time_s": float(eval_time_s),
            "val_acc": float(val_acc),
            **extra,
        })

    def summary(self, n_epochs: int, epoch_time_s: Optional[float],
                best_val: float, **extra) -> Dict[str, Any]:
        return self.write({
            "event": "summary",
            "n_epochs": int(n_epochs),
            "epoch_time_s": (None if epoch_time_s is None
                             else float(epoch_time_s)),
            "best_val": float(best_val),
            **extra,
        })

    def fault(self, kind: str, epoch: int, rank: Optional[int] = None,
              **extra) -> Dict[str, Any]:
        """A detected fault: divergence trip, preemption request,
        injected chaos fault, corrupt checkpoint generation, cross-rank
        desync, lost peer. Extras carry the kind-specific detail
        (reason, retry, trip values, source_rank/agreed for
        consensus-driven actions). `rank` defaults to this process's
        rank so multi-host JSONL streams stay attributable when merged.

        Fault records are durability-critical — they often explain a
        death the process is about to execute via ``os._exit`` (which
        skips atexit AND io buffers) — so every fault/recovery write is
        followed by :meth:`hard_flush` (flush + fsync)."""
        extra.setdefault("time_unix", time.time())
        rec = self.write({
            "event": "fault",
            "kind": str(kind),
            "epoch": int(epoch),
            "rank": _local_rank() if rank is None else int(rank),
            **extra,
        })
        self.hard_flush()
        return rec

    def recovery(self, kind: str, epoch: int, rank: Optional[int] = None,
                 **extra) -> Dict[str, Any]:
        """A completed recovery from the matching fault kind (training
        progressed past the faulted epoch, or a resume restored).
        Hard-flushed like fault records (the recovery may immediately
        precede a preemption exit)."""
        extra.setdefault("time_unix", time.time())
        rec = self.write({
            "event": "recovery",
            "kind": str(kind),
            "epoch": int(epoch),
            "rank": _local_rank() if rank is None else int(rank),
            **extra,
        })
        self.hard_flush()
        return rec

    def profile(self, phases: Dict[str, float], comm_s: float,
                compute_s: float, overlap_fraction: float,
                **extra) -> Dict[str, Any]:
        """A captured profiling window's MEASURED device-time
        decomposition (obs/profiler.py): per-phase seconds + the
        comm/compute overlap fraction. Extras (obs/schema.py
        PROFILE_FIELDS): the window, self seconds by scope path, busy
        time, the programs joined, idle gaps by host span."""
        return self.write({
            "event": "profile",
            "phases": dict(phases),
            "comm_s": float(comm_s),
            "compute_s": float(compute_s),
            "overlap_fraction": float(overlap_fraction),
            **extra,
        })

    def anatomy(self, phases: Dict[str, Any], est_flops: float,
                flops: Optional[float] = None,
                attributed_flops_fraction: Optional[float] = None,
                **extra) -> Dict[str, Any]:
        """A compiled-step anatomy (obs/anatomy.py): estimated
        FLOPs/bytes per phase + XLA's own totals."""
        return self.write({
            "event": "anatomy",
            "phases": dict(phases),
            "est_flops": float(est_flops),
            "flops": None if flops is None else float(flops),
            "attributed_flops_fraction": (
                None if attributed_flops_fraction is None
                else float(attributed_flops_fraction)),
            **extra,
        })

    def staleness(self, epoch: int, layers: Dict[str, Any],
                  max_rel_drift: float, **extra) -> Dict[str, Any]:
        """A staleness probe's per-layer relative drift between stale
        and fresh boundary features (--staleness-probe-every)."""
        return self.write({
            "event": "staleness",
            "epoch": int(epoch),
            "layers": dict(layers),
            "max_rel_drift": float(max_rel_drift),
            **extra,
        })

    def numerics(self, kind: str, epoch: int, **extra) -> Dict[str, Any]:
        """A numerics-guardrail event (resilience/numerics.py): a
        loss-scale overflow (step skipped, scale backed off), a scale
        regrowth, or a tripwire provenance record naming the phase a
        non-finite value was born in. Hard-flushed: a tripwire record
        often immediately precedes a DivergenceError exit."""
        extra.setdefault("time_unix", time.time())
        rec = self.write({
            "event": "numerics",
            "kind": str(kind),
            "epoch": int(epoch),
            **extra,
        })
        self.hard_flush()
        return rec

    def fallback(self, epoch: int, from_impl: str, to_impl: str,
                 **extra) -> Dict[str, Any]:
        """A kernel-fallback-ladder downgrade: the aggregation kernel
        crashed at compile/first dispatch and the trainer rebuilt one
        rung down instead of dying. Hard-flushed — the run may still be
        about to lose the device."""
        extra.setdefault("time_unix", time.time())
        rec = self.write({
            "event": "fallback",
            "epoch": int(epoch),
            "from_impl": str(from_impl),
            "to_impl": str(to_impl),
            **extra,
        })
        self.hard_flush()
        return rec

    def tuning(self, winner: Dict[str, Any], source: str,
               costs, sample_dense_coverage: Optional[float] = None,
               shard_dense_coverage: Optional[float] = None,
               sample_tile_rows: Optional[int] = None,
               timed_edges: Optional[List[int]] = None,
               shard_edges: Optional[int] = None,
               **extra) -> Dict[str, Any]:
        """The SpMM auto-tuner's dispatch decision (ops/tuner.py +
        Trainer._resolve_auto): the winning kernel config, where the
        decision came from (artifact | live | default), the full
        measured per-candidate cost table (sampled seconds and, from
        the two nested samples, fixed_s / per_edge_s / est_call_s: what
        was ranked), and what the timed samples carried (null where
        nothing was timed) — the record that says WHY this kernel
        dispatches."""
        extra.setdefault("time_unix", time.time())
        return self.write({
            "event": "tuning",
            "winner": dict(winner),
            "source": str(source),
            "costs": list(costs),
            "sample_dense_coverage": sample_dense_coverage,
            "shard_dense_coverage": shard_dense_coverage,
            "sample_tile_rows": sample_tile_rows,
            "timed_edges": timed_edges,
            "shard_edges": shard_edges,
            **extra,
        })

    def serving(self, window_s: float, queries: int, qps: float,
                batch_fill: Optional[float], queue_depth: int,
                p50_ms: Optional[float], p95_ms: Optional[float],
                p99_ms: Optional[float],
                cache_hit_rate: Optional[float], staleness_age: int,
                shed: int = 0, param_generation: int = -1,
                param_staleness: int = 0, **extra) -> Dict[str, Any]:
        """One serving report window (serve/loadgen.run_serving_loop):
        QPS, batch fill, queue depth, latency percentiles, cache hit
        rate, the max served staleness age, plus (v7) the load-shed
        row count and the parameter-staleness axis (checkpoint
        generation served / newer generations published but not yet
        swapped in). Hard-flushed — the shutdown path's final record
        (extra ``final: true``) must survive a SIGTERM'd load
        generator (scripts/chaos.sh serving lane asserts exactly
        this)."""
        extra.setdefault("time_unix", time.time())
        rec = self.write({
            "event": "serving",
            "window_s": float(window_s),
            "queries": int(queries),
            "qps": float(qps),
            "batch_fill": None if batch_fill is None else float(batch_fill),
            "queue_depth": int(queue_depth),
            "p50_ms": None if p50_ms is None else float(p50_ms),
            "p95_ms": None if p95_ms is None else float(p95_ms),
            "p99_ms": None if p99_ms is None else float(p99_ms),
            "cache_hit_rate": (None if cache_hit_rate is None
                               else float(cache_hit_rate)),
            "staleness_age": int(staleness_age),
            "shed": int(shed),
            "param_generation": int(param_generation),
            "param_staleness": int(param_staleness),
            **extra,
        })
        self.hard_flush()
        return rec

    def fleet(self, kind: str, replica: int, window: int = -1,
              **extra) -> Dict[str, Any]:
        """One serving-fleet lifecycle event (serve/fleet.py): replica
        death / failover / relaunch / rejoin, a zero-downtime checkpoint
        hot-swap, or a supervisor stop. `window` is the serving report
        window index the event fell in (-1 outside the load loop).
        Hard-flushed — replica-dead records often immediately precede
        more dying."""
        extra.setdefault("time_unix", time.time())
        rec = self.write({
            "event": "fleet",
            "kind": str(kind),
            "replica": int(replica),
            "window": int(window),
            **extra,
        })
        self.hard_flush()
        return rec

    def autoscale(self, action: str, reason: str, window: int,
                  n_replicas: int, target: int,
                  evidence: Dict[str, Any], **extra) -> Dict[str, Any]:
        """One autoscaler decision (serve/autoscale.py): an executed
        scale-up/scale-down proposal or a brake refusal, with the
        triggering telemetry snapshot as evidence. Hard-flushed — the
        decision ledger is what the soak harness's replica-trajectory
        invariant replays, so it must survive a crash mid-scale."""
        extra.setdefault("time_unix", time.time())
        rec = self.write({
            "event": "autoscale",
            "action": str(action),
            "reason": str(reason),
            "window": int(window),
            "n_replicas": int(n_replicas),
            "target": int(target),
            "evidence": dict(evidence),
            **extra,
        })
        self.hard_flush()
        return rec

    def stream(self, epoch: int, seq: int, edges_added: int,
               edges_deleted: int, nodes_added: int, patch_ms: float,
               tables_rebuilt: int, repadded: bool,
               slack_remaining: Dict[str, Any],
               drift: Optional[float] = None, **extra) -> Dict[str, Any]:
        """One applied graph delta batch (stream/, docs/STREAMING.md):
        what changed, what the incremental patch cost, how much of the
        reserved slack survives, and the forced probe's drift across
        the first post-patch step. Hard-flushed — a delta that blows
        the slack may be the last thing the run does, and the record
        explaining the re-pad must be on disk."""
        extra.setdefault("time_unix", time.time())
        rec = self.write({
            "event": "stream",
            "epoch": int(epoch),
            "seq": int(seq),
            "edges_added": int(edges_added),
            "edges_deleted": int(edges_deleted),
            "nodes_added": int(nodes_added),
            "patch_ms": float(patch_ms),
            "tables_rebuilt": int(tables_rebuilt),
            "repadded": bool(repadded),
            "slack_remaining": dict(slack_remaining),
            "drift": None if drift is None else float(drift),
            **extra,
        })
        self.hard_flush()
        return rec

    def journal(self, op: str, seq: int, topo_generation: int,
                n_records: int = 0, source: str = "trainer",
                **extra) -> Dict[str, Any]:
        """One write-ahead delta-journal lifecycle event
        (stream/journal.py, docs/STREAMING.md "Durability & replay"):
        append/watermark from the trainer's stream boundary,
        replay/truncate/verify from a resume, degraded/recovered from
        the journal's own pending queue, skew from the router.
        Hard-flushed — the journal records ARE the durability audit
        trail, so they must survive the very crash they describe."""
        extra.setdefault("time_unix", time.time())
        rec = self.write({
            "event": "journal",
            "op": str(op),
            "seq": int(seq),
            "topo_generation": int(topo_generation),
            "n_records": int(n_records),
            "source": str(source),
            **extra,
        })
        self.hard_flush()
        return rec

    def membership(self, generation: int, assignment: Dict[str, Any],
                   trigger: str,
                   restart_latency_s: Optional[float] = None,
                   **extra) -> Dict[str, Any]:
        """One elastic membership generation (resilience/elastic.py):
        who owns which partitions and why the fleet was (re)launched.
        `assignment` is Assignment.as_json(); restart_latency_s is the
        death-detect -> relaunch wall time (None on the initial
        launch). Hard-flushed — the supervisor may be SIGKILL'd
        between generations and the ledger/metrics must never
        disagree about how far membership advanced."""
        extra.setdefault("time_unix", time.time())
        rec = self.write({
            "event": "membership",
            "generation": int(generation),
            "assignment": dict(assignment),
            "trigger": str(trigger),
            "restart_latency_s": (None if restart_latency_s is None
                                  else float(restart_latency_s)),
            **extra,
        })
        self.hard_flush()
        return rec

    def soak(self, episode: int, seed: int, schedule: Sequence[str],
             invariants: Dict[str, Any], verdict: str,
             **extra) -> Dict[str, Any]:
        """One chaos-soak episode verdict (resilience/soak.py): the
        composed fault schedule and the per-invariant results. Hard-
        flushed — a red verdict must survive even if the soak driver
        itself dies right after."""
        extra.setdefault("time_unix", time.time())
        rec = self.write({
            "event": "soak",
            "episode": int(episode),
            "seed": int(seed),
            "schedule": list(schedule),
            "invariants": dict(invariants),
            "verdict": str(verdict),
            **extra,
        })
        self.hard_flush()
        return rec

    def alert(self, rule: str, state: str, severity: str, source: str,
              value: Optional[float], threshold: Optional[float],
              message: str, **extra) -> Dict[str, Any]:
        """One SLO alert edge (obs/health.py rule engine): state "fire"
        when the rule's predicate first holds, "resolve" when it first
        stops. Hard-flushed — an alert often describes a run that is
        about to get worse, and the operator trail must survive the
        monitor dying with it."""
        extra.setdefault("time_unix", time.time())
        rec = self.write({
            "event": "alert",
            "rule": str(rule),
            "state": str(state),
            "severity": str(severity),
            "source": str(source),
            "value": None if value is None else float(value),
            "threshold": None if threshold is None else float(threshold),
            "message": str(message),
            **extra,
        })
        self.hard_flush()
        return rec

    def span(self, trace_id: str, span_id: str, op: str, t_start: float,
             dur_ms: float, status: str = "ok", **extra) -> Dict[str, Any]:
        """One sampled serving-path span (docs/SERVING.md tracing):
        queue/dispatch/shed on the driver, rpc/replica/engine across
        the fleet hop. NOT hard-flushed — spans are high-volume and
        advisory; the flush-per-write default already lands them."""
        return self.write({
            "event": "span",
            "trace_id": str(trace_id),
            "span_id": str(span_id),
            "op": str(op),
            "t_start": float(t_start),
            "dur_ms": float(dur_ms),
            "status": str(status),
            **extra,
        })

    def tracesync(self, rank: int, epoch: int, t_anchor: float,
                  generation: int = 0, **extra) -> Dict[str, Any]:
        """One training clock anchor (obs/trainspan.py): this rank's
        wall-clock reading of the dispatched block's harvest barrier.
        NOT hard-flushed — same volume/durability class as spans (one
        per dispatched block; the flush-per-write default lands them,
        and every fault path hard-flushes the whole sink anyway)."""
        return self.write({
            "event": "tracesync",
            "rank": int(rank),
            "epoch": int(epoch),
            "t_anchor": float(t_anchor),
            "generation": int(generation),
            **extra,
        })

    def blackbox(self, rank: int, reason: str,
                 crumbs: Sequence[Dict[str, Any]],
                 last_crumb: Optional[Dict[str, Any]],
                 open_spans: Sequence[Dict[str, Any]],
                 stacks: Optional[str] = None,
                 **extra) -> Dict[str, Any]:
        """One flight-recorder dump mirrored into the metrics stream
        (obs/flight.py writes the authoritative blackbox-r<k>.json
        itself; this record makes the dump discoverable through the
        same stream tail every other consumer follows). Hard-flushed —
        by definition the process is dying or wedged when one of these
        is written."""
        extra.setdefault("time_unix", time.time())
        rec = self.write({
            "event": "blackbox",
            "rank": int(rank),
            "reason": str(reason),
            "crumbs": list(crumbs),
            "last_crumb": (None if last_crumb is None
                           else dict(last_crumb)),
            "open_spans": list(open_spans),
            "stacks": None if stacks is None else str(stacks),
            **extra,
        })
        self.hard_flush()
        return rec

    def diagnosis(self, verdict: str, confidence: float,
                  evidence: Sequence[str], remediation: str,
                  deterministic: bool, **extra) -> Dict[str, Any]:
        """One postmortem verdict (obs/postmortem.py): the rule
        engine's confidence-ranked root cause with its citing
        evidence. Hard-flushed — the supervisor's fail-fast decision
        rides on this record and must never be lost to a crash."""
        extra.setdefault("time_unix", time.time())
        rec = self.write({
            "event": "diagnosis",
            "verdict": str(verdict),
            "confidence": float(confidence),
            "evidence": [str(e) for e in evidence],
            "remediation": str(remediation),
            "deterministic": bool(deterministic),
            **extra,
        })
        self.hard_flush()
        return rec

    def integrity(self, epoch: int, check: str, outcome: str,
                  target: Optional[str], cadence: int,
                  overhead_s: float, **extra) -> Dict[str, Any]:
        """One SDC-detector verdict (resilience/integrity.py): a
        digest scrub, Freivalds compute verification, or halo wire
        checksum outcome at a check boundary. Mismatch records are
        hard-flushed (the run may be about to roll back or quarantine
        itself); ok records take the ordinary flush-per-write path —
        they are cadence-periodic bookkeeping, not last words."""
        extra.setdefault("time_unix", time.time())
        rec = self.write({
            "event": "integrity",
            "epoch": int(epoch),
            "check": str(check),
            "outcome": str(outcome),
            "target": None if target is None else str(target),
            "cadence": int(cadence),
            "overhead_s": float(overhead_s),
            **extra,
        })
        if outcome != "ok":
            self.hard_flush()
        return rec

    def event(self, event: str, **fields) -> Dict[str, Any]:
        """Free-form record (e.g. bench headline, rank progress) — only
        the ``event`` discriminator is contracted."""
        return self.write({"event": event, **fields})

    def stats(self) -> Dict[str, Any]:
        """Sink health counters for the live exporter (docs/
        OBSERVABILITY.md "Live monitoring"): records accepted since
        open, the PR-14 io-degraded state, the ring-buffer depth, and
        how many buffered records the ring has had to drop. Cheap and
        side-effect free — safe to poll from a monitor thread."""
        return {
            "records": self._n_records,
            "degraded": self._degraded,
            "ring_depth": len(self._ring),
            "dropped": self._dropped,
        }

    # ---------------- lifecycle ---------------------------------------

    def hard_flush(self) -> None:
        """Flush AND fsync: records survive even an ``os._exit`` (which
        skips atexit handlers and io teardown) or a SIGKILL an instant
        later. Call before every hard-exit / crash-checkpoint path;
        fault/recovery writers call it automatically. Best-effort on
        sinks without a file descriptor (StringIO tests); a DISK
        failure here enters io-degraded instead of being swallowed —
        the records this method exists to make durable are exactly the
        ones that must not vanish without a trace."""
        if self._degraded and not self._try_recover():
            return
        try:
            self._f.flush()
        except ValueError:
            return  # closed/detached sink: nothing to make durable
        except OSError as exc:
            self._enter_degraded(exc, None)
            return
        try:
            # isolated: io.UnsupportedOperation (StringIO sinks) is BOTH
            # an OSError and a ValueError — a missing fd means "nothing
            # to fsync", never "the disk failed"
            fd = self._f.fileno()
        except (AttributeError, OSError, ValueError):
            return
        try:
            if self.path is not None:
                _storage_io().gate(self.path, "fsync")
            os.fsync(fd)
        except OSError as exc:
            self._enter_degraded(exc, None)

    def close(self) -> None:
        if self._degraded:
            self._try_recover()
        if self._degraded and (self._ring or self._dropped):
            warnings.warn(
                f"metrics sink {self.path or self._f!r} closed while "
                f"io-degraded: {len(self._ring)} buffered and "
                f"{self._dropped} dropped records were lost")
        if self._owns_file and not self._f.closed:
            try:
                self._f.close()
            except OSError:
                pass  # close-flush of a dead disk; the ring warning
                # above already reported the loss

    def __enter__(self) -> "MetricsLogger":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


def read_metrics(path: Union[str, "os.PathLike"]) -> List[Dict[str, Any]]:
    """Parse a metrics JSONL file; skips blank lines, raises on a
    malformed one (a torn final line from a killed run is reported with
    its line number rather than silently dropped)."""
    out = []
    with open(os.fspath(path), encoding="utf-8") as f:
        for i, line in enumerate(f, 1):
            line = line.strip()
            if not line:
                continue
            try:
                out.append(json.loads(line))
            except ValueError as exc:
                raise ValueError(f"{path}:{i}: malformed JSONL line "
                                 f"({exc})") from exc
    return out


# ---------------- host probes (lazy jax) ------------------------------


def _local_rank() -> int:
    """This process's rank (jax.process_index) for fault/recovery
    attribution; 0 in jax-free or uninitialized-backend contexts so the
    logger itself stays importable without jax."""
    try:
        import jax

        return int(jax.process_index())
    except Exception:
        return 0


def device_info() -> Dict[str, Any]:
    """Backend identity for the run header; {} when jax has no
    initialized backend (pure-host tooling)."""
    try:
        import jax

        d = jax.local_devices()[0]
        return {
            "platform": d.platform,
            "device_kind": d.device_kind,
            "n_devices": jax.device_count(),
            "n_local_devices": jax.local_device_count(),
            "process_index": jax.process_index(),
            "process_count": jax.process_count(),
        }
    except Exception:
        return {}


def mesh_info(mesh) -> Dict[str, Any]:
    """Axis names/sizes of a jax.sharding.Mesh (header `mesh` field)."""
    try:
        return {
            "axis_names": list(mesh.axis_names),
            "shape": {str(k): int(v) for k, v in
                      dict(mesh.shape).items()},
            "n_devices": int(len(mesh.devices.flat)),
        }
    except Exception:
        return {}


def memory_snapshot() -> Dict[str, Any]:
    """HBM watermarks of local device 0 (`memory_stats()`), with the
    keys always present: platforms without allocator stats (CPU) report
    nulls so epoch records keep a stable shape."""
    stats = None
    try:
        import jax

        stats = jax.local_devices()[0].memory_stats()
    except Exception:
        stats = None
    if not stats:
        return {"bytes_in_use": None, "peak_bytes_in_use": None,
                "bytes_limit": None}
    return {
        "bytes_in_use": int(stats.get("bytes_in_use", 0)),
        "peak_bytes_in_use": int(stats.get(
            "peak_bytes_in_use", stats.get("bytes_in_use", 0))),
        "bytes_limit": (int(stats["bytes_limit"])
                        if "bytes_limit" in stats else None),
    }
