"""Observability: structured telemetry for the pipelined trainer.

Three pillars (docs/OBSERVABILITY.md):

  schema.py   versioned record schema (run header / epoch / eval /
              summary) + validation — the stable contract bench.py,
              scripts/*.py and the report CLI consume
  metrics.py  MetricsLogger, the JSONL event sink, plus host probes
              (device_info / mesh_info / memory_snapshot)
  trace.py    XLA trace annotations (named_phase for traced code,
              trace_span for host spans), the recorded set-up spans
              with JAX's compile counters (span, recorder), and
              PhaseTimer — the
              exception-safe, nesting-aware generalization of the
              reference-parity CommTimer (utils/timer.py is now a shim
              over it)
  format.py   the canonical log-line formatters; the reference-format
              lines (train.py:369-371, :33-39, :54-60) are pinned
              byte-exact by tests/test_obs.py
  hw.py       public per-chip peak-FLOPs table (MFU reporting)
  profiler.py device-trace profiling windows: fold a jax.profiler
              capture into MEASURED per-phase device time + the
              comm/compute overlap fraction (docs/OBSERVABILITY.md
              "Profiling")
  anatomy.py  compiled-step anatomy: per-phase FLOP/byte attribution
              from the optimized HLO + the on-chip ablation clock
  timeline.py cross-rank Perfetto/Chrome-trace timelines from merged
              metrics JSONL streams (cli/timeline.py is the CLI)
  live.py     live telemetry plane: tail-following stream discovery
              + the rolling LiveAggregator behind cli/monitor.py
  health.py   SLO alert rules, the Prometheus /metrics + /health
              exporters, and the MonitorServer HTTP endpoint
  flight.py   black-box flight recorder: bounded breadcrumb ring,
              atomic blackbox-r<k>.json crash dumps, faulthandler
              all-thread stack capture, sub-watchdog stall detector
  postmortem.py  automated root-cause diagnosis: bundles blackbox
              dumps + stream tails + ledgers, runs the ordered
              evidence-citing rule set to a ranked verdict
              (cli/debug.py is the `pipegcn-debug explain` CLI)

The reporting CLI lives in cli/report.py (`python -m
pipegcn_tpu.cli.report metrics.jsonl`); the timeline CLI in
cli/timeline.py (`python -m pipegcn_tpu.cli.timeline r0.jsonl ...`).

No reference counterpart: the reference's only telemetry is stdout
print lines and the result txt files; this subsystem is the
machine-readable record every perf claim reports through.
"""

from .flight import (
    FlightRecorder,
    StallDetector,
    capture_stacks,
    dump_blackbox,
    get_recorder,
)
from .format import epoch_line, reference_eval_line, reference_train_line
from .live import (
    LiveAggregator,
    discover_streams,
    merge_streams,
    read_stream,
)
from .metrics import (
    MetricsLogger,
    device_info,
    memory_snapshot,
    mesh_info,
    read_metrics,
)
from .schema import (
    ALERT_FIELDS,
    ANATOMY_FIELDS,
    BLACKBOX_FIELDS,
    DIAGNOSIS_FIELDS,
    EPOCH_FIELDS,
    EVAL_FIELDS,
    FAULT_FIELDS,
    PROFILE_FIELDS,
    RECOVERY_FIELDS,
    RUN_FIELDS,
    SCHEMA_VERSION,
    SPAN_FIELDS,
    STALENESS_FIELDS,
    SUMMARY_FIELDS,
    validate_record,
)
from .trace import PhaseTimer, named_phase, trace_span

__all__ = [
    "SCHEMA_VERSION",
    "RUN_FIELDS",
    "EPOCH_FIELDS",
    "EVAL_FIELDS",
    "SUMMARY_FIELDS",
    "FAULT_FIELDS",
    "RECOVERY_FIELDS",
    "PROFILE_FIELDS",
    "ANATOMY_FIELDS",
    "STALENESS_FIELDS",
    "ALERT_FIELDS",
    "SPAN_FIELDS",
    "BLACKBOX_FIELDS",
    "DIAGNOSIS_FIELDS",
    "validate_record",
    "FlightRecorder",
    "StallDetector",
    "capture_stacks",
    "dump_blackbox",
    "get_recorder",
    "LiveAggregator",
    "discover_streams",
    "merge_streams",
    "read_stream",
    "MetricsLogger",
    "read_metrics",
    "device_info",
    "mesh_info",
    "memory_snapshot",
    "PhaseTimer",
    "named_phase",
    "trace_span",
    "epoch_line",
    "reference_train_line",
    "reference_eval_line",
]
