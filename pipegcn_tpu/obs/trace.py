"""XLA trace annotations + the PhaseTimer.

`named_phase` labels TRACED code: it is `jax.named_scope`, whose names
flow into XLA op metadata, so a --profile-dir trace shows
"layer0/halo_exchange"-style phases instead of anonymous fusions.
`trace_span` labels HOST spans (`jax.profiler.TraceAnnotation`): a
no-op unless a trace is being captured, so it is safe on every
dispatch. `SpanCursor` lays consecutive host spans over a long loop
body.

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

import time
from contextlib import contextmanager
from typing import Dict


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
    written unless a trace is being captured."""

    def __init__(self):
        self._open = None

    def to(self, name: str = "") -> None:
        if self._open is not None:
            self._open.__exit__(None, None, None)
        self._open = trace_span(name) if name else None
        if self._open is not None:
            self._open.__enter__()


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
