"""Sampled per-query tracing for the serving path (schema v10 `span`
records, docs/OBSERVABILITY.md "Live monitoring").

A trace id is minted at submit time with probability
``--trace-sample-rate`` and rides the :class:`~.batcher.Ticket`
through every hop — micro-batcher queue/dispatch on the driver, the
router RPC, the replica handler, and the engine's chunked execution —
each hop landing one contracted `span` record in that process's
metrics stream. ``cli.timeline`` stitches spans sharing a trace id
into Perfetto flow events.

Everything here is host-side bookkeeping: no jax, no effect on the
compiled programs (the no-recompile pin in tests/test_serve.py holds
with sampling at 100%). At the default rate 0 no ids are minted and
the per-submit cost is one comparison.
"""

from __future__ import annotations

import random
import threading
import time
from typing import Callable, Optional

from ..obs.trace import next_span_id


class TraceSampler:
    """Deterministic Bernoulli sampler minting trace ids at submit.

    ``rate`` 0 (the default everywhere) never mints; 1 always mints;
    in between a seeded PRNG decides, so a replayed load run samples
    the same queries. Ids are ``q<seq>-<run tag>`` — unique within a
    run and readable in raw JSONL."""

    def __init__(self, rate: float = 0.0, seed: int = 0,
                 tag: str = "t"):
        self.rate = float(rate)
        self.tag = str(tag)
        self._rng = random.Random(seed)
        self._seq = 0
        self.n_sampled = 0

    def sample(self) -> Optional[str]:
        """One submit's verdict: a fresh trace id, or None."""
        if self.rate <= 0.0:
            return None
        self._seq += 1
        if self.rate < 1.0 and self._rng.random() >= self.rate:
            return None
        self.n_sampled += 1
        return f"q{self._seq}-{self.tag}"


class SpanWriter:
    """Bridge from loop-clock span callbacks to contracted records.

    The serving loops and the batcher run on an injectable monotonic
    (or fake) clock; span records need cross-process-alignable unix
    t_start. The writer captures the clock->unix offset once per emit
    so fake-clock tests stay deterministic in shape while real runs
    stay alignable. Thread-safe (the fleet loop emits from worker
    threads)."""

    def __init__(self, ml, clock: Callable[[], float] = time.monotonic,
                 source: str = "", now: Callable[[], float] = time.time):
        self._ml = ml
        self._clock = clock
        self._now = now
        self.source = str(source)
        self._lock = threading.Lock()
        self.n_spans = 0

    def emit(self, trace_id: Optional[str], op: str, t0: float,
             t1: float, status: str = "ok", span_id: Optional[str] = None,
             **extra) -> Optional[str]:
        """One span: [t0, t1] in the loop clock's frame, under
        `span_id` (a fresh id if None). Returns the span's id; no-op
        returning None when the ticket was unsampled (trace_id None) or
        there is no sink."""
        if trace_id is None or self._ml is None:
            return None
        off = self._now() - self._clock()
        sid = span_id or next_span_id()
        with self._lock:
            self.n_spans += 1
        if self.source:
            extra.setdefault("source", self.source)
        self._ml.span(trace_id, sid, op, t0 + off,
                      max(t1 - t0, 0.0) * 1e3, status, **extra)
        return sid
