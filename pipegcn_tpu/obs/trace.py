"""XLA trace annotations, the set-up span recorder, and the PhaseTimer.

`named_phase` labels TRACED code: it is `jax.named_scope`, whose names
flow into XLA op metadata, so a --profile-dir trace shows
"layer0/halo_exchange"-style phases instead of anonymous fusions.
`trace_span` labels HOST spans (`jax.profiler.TraceAnnotation`): a
no-op unless a trace is being captured, so it is safe on every
dispatch. `SpanCursor` lays consecutive host spans over a long loop
body and adds up the seconds of each.

`span(name)` RECORDS a host span whether or not a trace is captured:
start and end on `time.perf_counter`, its parent (the innermost span
open on the same thread), and the compile work JAX did while it was
the innermost one, from JAX's own monitoring events (`install`
registers the one process-wide listener). Spans are kept in memory
until a metrics sink is attached (`SpanRecorder.attach`), then written
as `span` records with trace id "setup" (docs/OBSERVABILITY.md
"Set-up spans").

PhaseTimer is the host-side phase clock the epoch loop runs on —
the generalization of the reference-parity CommTimer
(helper/timer/comm_timer.py semantics, now a shim in utils/timer.py):

  - exception-safe: a span that raises still records its duration
    (try/finally around the yield), so a crashed epoch's partial
    timing reaches the crash telemetry;
  - re-entrant keys: repeated spans ACCUMULATE (durations) and count
    (counts) instead of raising — per-epoch keys no longer force a
    clear() discipline;
  - nesting: phases may nest freely; each records its own wall-clock;
  - optional trace annotation: phase(key, annotate=True) also opens a
    TraceAnnotation so profiler timelines show the same phase names
    the JSONL records use.

Both jax imports are lazy: PhaseTimer itself must work in jax-free
host processes.
"""

from __future__ import annotations

import collections
import gc
import itertools
import os
import sys
import threading
import time
from contextlib import contextmanager
from typing import Dict, Optional, Tuple


def named_phase(name: str):
    """Name a traced-code region (forward/backward layers, halo
    exchange, gradient reduce): `with named_phase("layer0"): ...`."""
    import jax

    return jax.named_scope(name)


def trace_span(name: str):
    """Name a host-side span in the profiler timeline (step dispatch,
    eval harvest). No-op when no trace is active."""
    import jax

    return jax.profiler.TraceAnnotation(name)


class SpanCursor:
    """Consecutive host spans over a long stretch of code: `to(name)`
    closes the span that is open and opens the next, `to()` closes
    only. `Trainer.fit` names the sections of its loop with it
    (`fit/boundary`, `fit/harvest`, `fit/log`, ...) without nesting
    hundreds of lines under `with`. Like `trace_span`, nothing is
    written unless a trace is being captured; the seconds of each
    closed section add up until `take()` hands them over."""

    def __init__(self):
        self._open = None
        self._name = ""
        self._t0 = 0.0
        self.seconds: Dict[str, float] = {}

    def to(self, name: str = "") -> None:
        now = time.perf_counter()
        if self._open is not None:
            self._open.__exit__(None, None, None)
        if self._name:
            self.seconds[self._name] = (self.seconds.get(self._name, 0.0)
                                        + now - self._t0)
        self._name, self._t0 = name, now
        self._open = trace_span(name) if name else None
        if self._open is not None:
            self._open.__enter__()

    def take(self) -> Dict[str, float]:
        """The seconds of each section closed since the last take."""
        out = {k: round(v, 6) for k, v in self.seconds.items()}
        self.seconds = {}
        return out


# ---------------------------------------------------------------------
# recorded spans and the compile counters


SETUP_TRACE_ID = "setup"
_MAX_PROGRAMS = 16
_MAX_PENDING = 4096
_TRACE_EV = "/jax/core/compile/jaxpr_trace_duration"
_MLIR_EV = "/jax/core/compile/jaxpr_to_mlir_module_duration"
# the backend compile's duration includes the persistent cache's lookup
_BACKEND_EV = "/jax/core/compile/backend_compile_duration"
_PHASE_EVS = (_TRACE_EV, _MLIR_EV, _BACKEND_EV)
_HIT_EV = "/jax/compilation_cache/cache_hits"
_REQUEST_EV = "/jax/compilation_cache/compile_requests_use_cache"
_WRITE_EV = "/jax/compilation_cache/cache_misses"


# one id space a process, shared with serve/tracing.SpanWriter: a
# set-up span's `parent` names a training span another writer wrote
_SPAN_IDS = itertools.count()


def next_span_id() -> str:
    """A span id unique within this process."""
    return f"s{next(_SPAN_IDS)}"


class Span:
    """One recorded span: its name, parent, perf_counter start and end,
    the extras it is written with (`attrs`) and its own counters of the
    compile work JAX did while it was the innermost open span: seconds
    tracing and lowering to MLIR (`lower_s`), programs the persistent
    cache served and the seconds of those loads, the key and the read
    (`cache_hits`, `cache_load_s`), backend compiles no cache hit served
    and their seconds (`compiles`, `compile_s`), and the names JAX gave
    the programs (`programs`)."""

    __slots__ = ("name", "parent", "span_id", "t0", "t1", "attrs",
                 "counts", "programs", "lowered", "emit",
                 "only_if_compiled", "_ann")

    def __init__(self, name: str, parent: Optional["Span"], span_id: str,
                 emit: bool = True, only_if_compiled: bool = False,
                 attrs: Optional[dict] = None):
        self.t0 = time.perf_counter()
        self.t1: Optional[float] = None
        self.name, self.parent, self.span_id = name, parent, span_id
        self.emit, self.only_if_compiled = emit, only_if_compiled
        self.attrs = dict(attrs or {})
        self.counts: Dict[str, float] = {"lower_s": 0.0, "cache_hits": 0,
                                         "cache_load_s": 0.0,
                                         "compiles": 0, "compile_s": 0.0}
        self.programs: list = []
        self.lowered = False
        self._ann = None

    @property
    def dur_s(self) -> float:
        end = self.t1 if self.t1 is not None else time.perf_counter()
        return end - self.t0

    @property
    def compiled(self) -> bool:
        """JAX lowered, loaded or compiled a program in it. Tracing
        alone is not enough: a `vmap` retraces its function at every
        call, for microseconds."""
        c = self.counts
        return bool(self.lowered or c["cache_hits"] or c["compiles"])

    def merge_into(self, other: "Span") -> None:
        for k, v in self.counts.items():
            other.counts[k] += v
        other.lowered = other.lowered or self.lowered
        for name in self.programs:
            if name not in other.programs and \
                    len(other.programs) < _MAX_PROGRAMS:
                other.programs.append(name)

    def fields(self) -> dict:
        """The counters as a span record carries them."""
        out = {k: round(v, 6) if isinstance(v, float) else v
               for k, v in self.counts.items()}
        out["programs"] = list(self.programs)
        return out


def process_age_s() -> Optional[float]:
    """Seconds since this process started, as the OS records it: the
    start time of /proc/self/stat (clock ticks after boot) against the
    boot-time clock now. None where there is no /proc."""
    try:
        with open("/proc/self/stat") as f:
            stat = f.read()
        ticks = int(stat.rsplit(")", 1)[1].split()[19])
        return (time.clock_gettime(time.CLOCK_BOOTTIME)
                - ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError, AttributeError):
        return None


class SpanRecorder:
    """The process's recorded spans and compile counters.

    Each thread has its own stack of open spans; a compile event counts
    on the innermost span open on the thread that compiled, and only
    there, so sums over spans never count an event twice. An event
    with no span open counts on `root`, which is never written.
    `totals` counts every event of the process."""

    def __init__(self):
        self._tls = threading.local()
        self._lock = threading.Lock()
        self._pending: collections.deque = collections.deque(
            maxlen=_MAX_PENDING)
        self._writer = None
        self._installed = False
        self._gc_t0: Optional[float] = None
        self._process_done = False
        self.first_t0: Optional[float] = None
        self.root = Span("(root)", None, "")
        self.totals = {"requests": 0, "written": 0, "lower_s": 0.0,
                       "cache_hits": 0, "cache_load_s": 0.0,
                       "compiles": 0, "compile_s": 0.0,
                       "compile_times": []}
        self.gc_s = 0.0
        self.gc_collections = 0

    # ---- the span tree ----------------------------------------------

    def _stack(self) -> list:
        st = getattr(self._tls, "stack", None)
        if st is None:
            st = self._tls.stack = []
        return st

    def current(self) -> Optional[Span]:
        st = self._stack()
        return st[-1] if st else None

    def open(self, name: str, *, emit: bool = True,
             only_if_compiled: bool = False, annotate: bool = True,
             **attrs) -> Span:
        """Open a span under the innermost open one. `emit=False`: the
        caller writes it (or nothing does); `only_if_compiled`: written
        only if JAX compiled in it; `annotate`: also a TraceAnnotation
        (once JAX is imported: no trace can run before)."""
        st = self._stack()
        sp = Span(name, st[-1] if st else None, next_span_id(), emit,
                  only_if_compiled, attrs)
        if self.first_t0 is None:
            self.first_t0 = sp.t0
        if annotate and "jax" in sys.modules:
            sp._ann = trace_span(name)
            sp._ann.__enter__()
        st.append(sp)
        return sp

    def close(self, sp: Span) -> None:
        if sp.t1 is not None:
            return
        sp.t1 = time.perf_counter()
        if sp._ann is not None:
            sp._ann.__exit__(None, None, None)
            sp._ann = None
        st = self._stack()
        if sp in st:
            st.remove(sp)
        if sp.only_if_compiled and not sp.compiled:
            # not written: what it counted counts on its parent
            with self._lock:
                sp.merge_into(sp.parent or self.root)
            return
        if sp.emit:
            with self._lock:
                writer = self._writer
                if writer is None:
                    self._pending.append(sp)
            if writer is not None:
                self._write(writer, sp)

    @contextmanager
    def span(self, name: str, **kw):
        sp = self.open(name, **kw)
        try:
            yield sp
        finally:
            self.close(sp)

    # ---- the sink ---------------------------------------------------

    @staticmethod
    def _write(writer, sp: Span) -> None:
        writer.emit(SETUP_TRACE_ID, sp.name, sp.t0, sp.t1,
                    span_id=sp.span_id,
                    parent=sp.parent.span_id if sp.parent else None,
                    **sp.fields(), **sp.attrs)

    def attach(self, ml, source: str = "r0", flush: bool = True):
        """Write spans to the metrics sink `ml` from now on (`flush`:
        and those kept since `begin_run`). Returns the writer it
        replaced, for `restore`."""
        from ..serve.tracing import SpanWriter

        writer = SpanWriter(ml, clock=time.perf_counter, source=source)
        with self._lock:
            prev, self._writer = self._writer, writer
            todo = list(self._pending) if flush else []
            if flush:
                self._pending.clear()
        for sp in todo:
            self._write(writer, sp)
        return prev

    def restore(self, prev) -> None:
        with self._lock:
            self._writer = prev

    def begin_run(self) -> None:
        """A run's set-up starts (`cli.main.run`): no sink until the run
        attaches its own, and the kept spans dropped. In the process's
        first run a `setup/graph` tree of a `prepare` called before it
        (the benchmark's artifact) stays, and `setup/process` is added:
        from the process's start to the first span it opened (or
        now)."""
        def root_of(sp):
            while sp.parent is not None:
                sp = sp.parent
            return sp

        with self._lock:
            self._writer = None
            first, self._process_done = not self._process_done, True
            keep = [sp for sp in self._pending
                    if first and root_of(sp).name == "setup/graph"]
            self._pending.clear()
            self._pending.extend(keep)
        age = process_age_s() if first else None
        if age is None:
            return
        now = time.perf_counter()
        sp = Span("setup/process", None, next_span_id())
        sp.t0 = now - age
        sp.t1 = self.first_t0 if self.first_t0 is not None else now
        with self._lock:
            self._pending.appendleft(sp)

    # ---- JAX's compile events and the collector ---------------------

    def install(self) -> None:
        """Register the process-wide listeners (idempotent): JAX's
        compile events and the garbage collector's."""
        with self._lock:
            if self._installed:
                return
            self._installed = True
        import jax

        jax.monitoring.register_scalar_listener(self._on_scalar)
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)
        gc.callbacks.append(self._on_gc)

    def _phases(self) -> list:
        ph = getattr(self._tls, "phases", None)
        if ph is None:
            ph = self._tls.phases = []
        return ph

    def _on_scalar(self, event, _value, **_kw) -> None:
        # JAX records a compile phase's start as a scalar: the frames
        # let a phase nested in another (an inner jit traced inside an
        # outer one) count once, as its own seconds
        if event in _PHASE_EVS:
            self._phases().append([event, 0.0, False])

    def _on_event(self, event, **_kw) -> None:
        if event == _HIT_EV:
            ph = self._phases()
            if ph and ph[-1][0] == _BACKEND_EV:
                ph[-1][2] = True
        elif event in (_REQUEST_EV, _WRITE_EV):
            with self._lock:
                self.totals["requests" if event == _REQUEST_EV
                            else "written"] += 1

    def _on_duration(self, event, secs, fun_name=None, **_kw) -> None:
        if event not in _PHASE_EVS:
            return
        ph = self._phases()
        child, hit = 0.0, False
        if ph and ph[-1][0] == event:
            _, child, hit = ph.pop()
        if ph:
            ph[-1][1] += secs
        own = max(secs - child, 0.0)
        sp = self.current() or self.root
        if event == _MLIR_EV:
            sp.lowered = True
        if event != _BACKEND_EV:
            keys = ("lower_s",)
        elif hit:
            keys = ("cache_hits", "cache_load_s")
        else:
            keys = ("compiles", "compile_s")
        with self._lock:     # the root and the totals are every thread's
            if keys[0] == "compiles":
                self.totals["compile_times"].append(own)
            for c in (sp.counts, self.totals):
                if len(keys) == 2:
                    c[keys[0]] += 1
                c[keys[-1]] += own
            if event == _BACKEND_EV and fun_name and \
                    fun_name not in sp.programs and \
                    len(sp.programs) < _MAX_PROGRAMS:
                sp.programs.append(str(fun_name))

    def _on_gc(self, phase, _info) -> None:
        if phase == "start":
            self._gc_t0 = time.perf_counter()
        elif self._gc_t0 is not None:
            self.gc_s += time.perf_counter() - self._gc_t0
            self.gc_collections += 1
            self._gc_t0 = None

    def gc_totals(self) -> Tuple[float, int]:
        return self.gc_s, self.gc_collections


_RECORDER = SpanRecorder()


def recorder() -> SpanRecorder:
    """The process's span recorder."""
    return _RECORDER


def span(name: str, **kw):
    """Record a host span (`SpanRecorder.open`'s keywords)."""
    return _RECORDER.span(name, **kw)


class BlockSpans:
    """The host side of `Trainer.fit`'s blocks, from one harvest to the
    next: a span the block's `compute` record stands for (it is its
    `span_id`, and what JAX compiled outside the dispatch counts on
    it), with the collector's seconds and the loop's sections. What
    follows the last harvest is written as the set-up span `fit/tail`
    when fit returns."""

    def __init__(self, cursor: SpanCursor):
        self._cursor = cursor
        self._gc = _RECORDER.gc_totals()
        self._span = _RECORDER.open("fit/block", emit=False,
                                    annotate=False)

    def _host(self) -> dict:
        gc_s, gc_n = _RECORDER.gc_totals()
        out = dict(gc_s=round(gc_s - self._gc[0], 6),
                   gc_collections=gc_n - self._gc[1],
                   sections=self._cursor.take())
        self._gc = (gc_s, gc_n)
        return out

    def harvest(self) -> dict:
        """Close the block, return the extras of its `compute` span
        and open the next block."""
        sp = self._span
        _RECORDER.close(sp)
        out = dict(span_id=sp.span_id, **sp.fields(), **self._host())
        self._span = _RECORDER.open("fit/block", emit=False,
                                    annotate=False)
        return out

    def close(self) -> None:
        sp = self._span
        sp.name, sp.emit = "fit/tail", True
        sp.attrs.update(self._host())
        _RECORDER.close(sp)


class PhaseTimer:
    def __init__(self):
        self._durs: Dict[str, float] = {}
        self._counts: Dict[str, int] = {}

    @contextmanager
    def phase(self, key: str, annotate: bool = False):
        span = trace_span(key) if annotate else None
        t0 = time.perf_counter()
        if span is not None:
            span.__enter__()
        try:
            yield
        finally:
            if span is not None:
                span.__exit__(None, None, None)
            self._durs[key] = (self._durs.get(key, 0.0)
                               + time.perf_counter() - t0)
            self._counts[key] = self._counts.get(key, 0) + 1

    def durations(self) -> Dict[str, float]:
        """Accumulated seconds per key."""
        return dict(self._durs)

    def counts(self) -> Dict[str, int]:
        """Completed span count per key (mean = durations/counts)."""
        return dict(self._counts)

    def tot_time(self) -> float:
        return sum(self._durs.values())

    def clear(self) -> None:
        self._durs.clear()
        self._counts.clear()
