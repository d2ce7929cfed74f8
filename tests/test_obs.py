"""Telemetry subsystem tests: schema round-trip + drift guard, the
JSONL sink, PhaseTimer/CommTimer semantics, byte-exact reference log
formats, the CLI --metrics-out end-to-end path, and the report CLI."""

import json
import re

import numpy as np
import pytest

from pipegcn_tpu.cli.main import result_file_name, run
from pipegcn_tpu.cli.parser import create_parser
from pipegcn_tpu.cli.report import main as report_main
from pipegcn_tpu.cli.report import summarize_run
from pipegcn_tpu.obs import (
    MetricsLogger,
    PhaseTimer,
    read_metrics,
    validate_record,
)
from pipegcn_tpu.obs import schema as obs_schema
from pipegcn_tpu.obs.format import (
    epoch_line,
    reference_eval_line,
    reference_train_line,
)
from pipegcn_tpu.utils.timer import CommTimer

# ---------------- schema -------------------------------------------------

# FROZEN copy of the v7 contract (v6 + the fleet kind and the
# serving shed/param-generation fields the serving-fleet PR added,
# bumping the version to 7). If any assert below fires, a field was
# removed or retyped without bumping SCHEMA_VERSION — consumers
# (bench trajectory, report CLI, timeline CLI, scripts) would break
# silently.
_V7_FIELDS = {
    "run": {
        "event": "string", "schema_version": "integer",
        "time_unix": "number", "config": "object", "device": "object",
        "mesh": "object",
    },
    "epoch": {
        "event": "string", "epoch": "integer", "step_time_s": "number",
        "loss": "number", "grad_norm": "number", "halo_bytes": "integer",
        "staleness_age": "integer", "memory": "object?",
    },
    "eval": {
        "event": "string", "epoch": "integer", "eval_time_s": "number",
        "val_acc": "number",
    },
    "summary": {
        "event": "string", "n_epochs": "integer",
        "epoch_time_s": "number?", "best_val": "number",
    },
    "fault": {
        "event": "string", "kind": "string", "epoch": "integer",
    },
    "recovery": {
        "event": "string", "kind": "string", "epoch": "integer",
    },
    "profile": {
        "event": "string", "phases": "object", "comm_s": "number",
        "compute_s": "number", "overlap_fraction": "number",
    },
    "anatomy": {
        "event": "string", "phases": "object", "est_flops": "number",
        "flops": "number?", "attributed_flops_fraction": "number?",
    },
    "staleness": {
        "event": "string", "epoch": "integer", "layers": "object",
        "max_rel_drift": "number",
    },
    "numerics": {
        "event": "string", "kind": "string", "epoch": "integer",
    },
    "fallback": {
        "event": "string", "epoch": "integer", "from_impl": "string",
        "to_impl": "string",
    },
    "tuning": {
        "event": "string", "winner": "object", "source": "string",
        "costs": "array",
    },
    "serving": {
        "event": "string", "window_s": "number", "queries": "integer",
        "qps": "number", "batch_fill": "number?",
        "queue_depth": "integer", "p50_ms": "number?",
        "p95_ms": "number?", "p99_ms": "number?",
        "cache_hit_rate": "number?", "staleness_age": "integer",
        "shed": "integer", "param_generation": "integer",
        "param_staleness": "integer",
    },
    "membership": {
        "event": "string", "generation": "integer",
        "assignment": "object", "trigger": "string",
        "restart_latency_s": "number?",
    },
    "fleet": {
        "event": "string", "kind": "string", "replica": "integer",
        "window": "integer",
    },
}


def test_schema_v7_drift_guard():
    current = {"run": obs_schema.RUN_FIELDS,
               "epoch": obs_schema.EPOCH_FIELDS,
               "eval": obs_schema.EVAL_FIELDS,
               "summary": obs_schema.SUMMARY_FIELDS,
               "fault": obs_schema.FAULT_FIELDS,
               "recovery": obs_schema.RECOVERY_FIELDS,
               "profile": obs_schema.PROFILE_FIELDS,
               "anatomy": obs_schema.ANATOMY_FIELDS,
               "staleness": obs_schema.STALENESS_FIELDS,
               "numerics": obs_schema.NUMERICS_FIELDS,
               "fallback": obs_schema.FALLBACK_FIELDS,
               "tuning": obs_schema.TUNING_FIELDS,
               "serving": obs_schema.SERVING_FIELDS,
               "membership": obs_schema.MEMBERSHIP_FIELDS,
               "fleet": obs_schema.FLEET_FIELDS}
    if obs_schema.SCHEMA_VERSION == 7:
        for kind, fields in _V7_FIELDS.items():
            for name, tag in fields.items():
                assert current[kind].get(name) == tag, (
                    f"schema field {kind}.{name} removed or retyped "
                    f"without bumping SCHEMA_VERSION")
    else:
        # a bump legitimizes any field change; the contract is that the
        # version moved WITH the change
        assert obs_schema.SCHEMA_VERSION > 7


# FROZEN copy of the v8 additions (v7 + the `stream` kind the
# streaming-graphs PR added, bumping the version to 8). Same contract
# as the v7 guard: removing/retyping a field without bumping
# SCHEMA_VERSION fires the assert.
_V8_STREAM_FIELDS = {
    "event": "string", "epoch": "integer", "seq": "integer",
    "edges_added": "integer", "edges_deleted": "integer",
    "nodes_added": "integer", "patch_ms": "number",
    "tables_rebuilt": "integer", "repadded": "boolean",
    "slack_remaining": "object", "drift": "number?",
}


def test_schema_v8_drift_guard():
    if obs_schema.SCHEMA_VERSION == 8:
        for name, tag in _V8_STREAM_FIELDS.items():
            assert obs_schema.STREAM_FIELDS.get(name) == tag, (
                f"schema field stream.{name} removed or retyped "
                f"without bumping SCHEMA_VERSION")
    else:
        assert obs_schema.SCHEMA_VERSION > 8


# FROZEN copy of the v9 additions (v8 + the `soak` kind the storage-
# fault PR added, bumping the version to 9; the same PR added the
# io-degraded fault/recovery kind, which needs no new fields). Same
# contract as the earlier guards.
_V9_SOAK_FIELDS = {
    "event": "string", "episode": "integer", "seed": "integer",
    "schedule": "array", "invariants": "object", "verdict": "string",
}


def test_schema_v9_drift_guard():
    if obs_schema.SCHEMA_VERSION == 9:
        for name, tag in _V9_SOAK_FIELDS.items():
            assert obs_schema.SOAK_FIELDS.get(name) == tag, (
                f"schema field soak.{name} removed or retyped "
                f"without bumping SCHEMA_VERSION")
    else:
        assert obs_schema.SCHEMA_VERSION > 9


# frozen copies of the v10 contracts (the live-monitoring PR added the
# alert record — the SLO rule engine's edge-triggered fire/resolve log
# — and the span record carrying the sampled serving-path traces that
# cli.timeline stitches into Perfetto flows). Same contract as the
# earlier guards.
_V10_ALERT_FIELDS = {
    "event": "string", "rule": "string", "state": "string",
    "severity": "string", "source": "string", "value": "number?",
    "threshold": "number?", "message": "string",
}
_V10_SPAN_FIELDS = {
    "event": "string", "trace_id": "string", "span_id": "string",
    "op": "string", "t_start": "number", "dur_ms": "number",
    "status": "string",
}


def test_schema_v10_drift_guard():
    if obs_schema.SCHEMA_VERSION == 10:
        for name, tag in _V10_ALERT_FIELDS.items():
            assert obs_schema.ALERT_FIELDS.get(name) == tag, (
                f"schema field alert.{name} removed or retyped "
                f"without bumping SCHEMA_VERSION")
        for name, tag in _V10_SPAN_FIELDS.items():
            assert obs_schema.SPAN_FIELDS.get(name) == tag, (
                f"schema field span.{name} removed or retyped "
                f"without bumping SCHEMA_VERSION")
    else:
        assert obs_schema.SCHEMA_VERSION > 10


_V14_TRACESYNC_FIELDS = {
    "event": "string", "rank": "integer", "epoch": "integer",
    "t_anchor": "number", "generation": "integer",
}


def test_schema_v14_drift_guard():
    if obs_schema.SCHEMA_VERSION == 14:
        for name, tag in _V14_TRACESYNC_FIELDS.items():
            assert obs_schema.TRACESYNC_FIELDS.get(name) == tag, (
                f"schema field tracesync.{name} removed or retyped "
                f"without bumping SCHEMA_VERSION")
    else:
        assert obs_schema.SCHEMA_VERSION > 14


# FROZEN copy of the v15 additions (v14 + the `journal` kind the
# crash-consistent-streaming PR added: the write-ahead delta journal's
# append/watermark/replay/truncate/verify/degraded/recovered/skew
# lifecycle). Same contract as the earlier guards.
_V15_JOURNAL_FIELDS = {
    "event": "string", "op": "string", "seq": "integer",
    "topo_generation": "integer", "n_records": "integer",
    "source": "string",
}


def test_schema_v15_drift_guard():
    if obs_schema.SCHEMA_VERSION == 15:
        for name, tag in _V15_JOURNAL_FIELDS.items():
            assert obs_schema.JOURNAL_FIELDS.get(name) == tag, (
                f"schema field journal.{name} removed or retyped "
                f"without bumping SCHEMA_VERSION")
    else:
        assert obs_schema.SCHEMA_VERSION > 15


# FROZEN copy of the v16 additions (v15 + what the tuning record says
# about its sample: the tile-structure-keeping sampler of
# ops/tuner.py). Same contract as the earlier guards.
_V16_TUNING_FIELDS = {
    "event": "string", "winner": "object", "source": "string",
    "costs": "array", "sample_dense_coverage": "number?",
    "shard_dense_coverage": "number?", "sample_tile_rows": "integer?",
    "call_overhead_s": "number?",
}


def test_schema_v16_drift_guard():
    if obs_schema.SCHEMA_VERSION == 16:
        for name, tag in _V16_TUNING_FIELDS.items():
            assert obs_schema.TUNING_FIELDS.get(name) == tag, (
                f"schema field tuning.{name} removed or retyped "
                f"without bumping SCHEMA_VERSION")
    else:
        assert obs_schema.SCHEMA_VERSION > 16


# FROZEN copy of the v17 additions (v16 + what the tuner RANKS: a call
# at the shard's size from two nested samples, ops/tuner.py
# shard_estimate; call_overhead_s went with the subtraction that used
# it). Same contract as the earlier guards.
_V17_TUNING_FIELDS = {
    "event": "string", "winner": "object", "source": "string",
    "costs": "array", "sample_dense_coverage": "number?",
    "shard_dense_coverage": "number?", "sample_tile_rows": "integer?",
    "timed_edges": "array?", "shard_edges": "integer?",
}
_V17_TUNING_COST_FIELDS = {
    "name": "string", "spmm_fwdbwd_s": "number?", "spread_s": "number?",
    "fixed_s": "number?", "per_edge_s": "number?",
    "est_call_s": "number?", "est_epoch_spmm_s": "number?",
    "error": "string?",
}


def test_schema_v17_drift_guard():
    if obs_schema.SCHEMA_VERSION == 17:
        for frozen, live, what in (
                (_V17_TUNING_FIELDS, obs_schema.TUNING_FIELDS, "tuning"),
                (_V17_TUNING_COST_FIELDS, obs_schema.TUNING_COST_FIELDS,
                 "tuning cost")):
            for name, tag in frozen.items():
                assert live.get(name) == tag, (
                    f"schema field {what}.{name} removed or retyped "
                    f"without bumping SCHEMA_VERSION")
    else:
        assert obs_schema.SCHEMA_VERSION > 17


# FROZEN copy of the v18 additions (v17 + the run record's `tables_pad`
# typed a direction, and what the block kernel's dense half stores:
# ops/block_spmm.py dense_pad_stats). Same contract as the earlier
# guards.
_V18_TABLES_PAD_FIELDS = {
    "widths": "array", "slots": "integer", "edges": "integer",
    "pad_ratio": "number",
}
_V18_TABLES_PAD_DENSE_FIELDS = {
    "dense_blocks": "integer", "dense_slots": "integer",
    "dense_pad": "number", "a_bytes": "integer",
}


def test_schema_v18_drift_guard():
    if obs_schema.SCHEMA_VERSION == 18:
        for frozen, live, what in (
                (_V17_TUNING_FIELDS, obs_schema.TUNING_FIELDS, "tuning"),
                (_V17_TUNING_COST_FIELDS, obs_schema.TUNING_COST_FIELDS,
                 "tuning cost"),
                (_V18_TABLES_PAD_FIELDS, obs_schema.TABLES_PAD_FIELDS,
                 "run tables_pad"),
                (_V18_TABLES_PAD_DENSE_FIELDS,
                 obs_schema.TABLES_PAD_DENSE_FIELDS, "run tables_pad")):
            for name, tag in frozen.items():
                assert live.get(name) == tag, (
                    f"schema field {what}.{name} removed or retyped "
                    f"without bumping SCHEMA_VERSION")
    else:
        assert obs_schema.SCHEMA_VERSION > 18


# FROZEN copy of the v19 additions (v18 + how each direction's source
# rows are cut for the gathers: ops/bucket_spmm.py source_parts). Same
# contract as the earlier guards.
_V19_TABLES_PAD_FIELDS = {**_V18_TABLES_PAD_FIELDS, "parts": "integer",
                          "part_rows": "integer"}


def test_schema_v19_drift_guard():
    if obs_schema.SCHEMA_VERSION == 19:
        for frozen, live, what in (
                (_V17_TUNING_FIELDS, obs_schema.TUNING_FIELDS, "tuning"),
                (_V17_TUNING_COST_FIELDS, obs_schema.TUNING_COST_FIELDS,
                 "tuning cost"),
                (_V19_TABLES_PAD_FIELDS, obs_schema.TABLES_PAD_FIELDS,
                 "run tables_pad"),
                (_V18_TABLES_PAD_DENSE_FIELDS,
                 obs_schema.TABLES_PAD_DENSE_FIELDS, "run tables_pad")):
            for name, tag in frozen.items():
                assert live.get(name) == tag, (
                    f"schema field {what}.{name} removed or retyped "
                    f"without bumping SCHEMA_VERSION")
    else:
        assert obs_schema.SCHEMA_VERSION > 19


# FROZEN copy of the v20 additions (set-up spans and the compile
# counters, obs/trace.py). Same contract as the earlier guards.
_V20_SPAN_COUNTER_FIELDS = {
    "lower_s": "number", "cache_hits": "integer", "cache_load_s": "number",
    "compiles": "integer", "compile_s": "number", "programs": "array",
}
_V20_SPAN_BLOCK_FIELDS = {"gc_s": "number", "gc_collections": "integer",
                          "sections": "object"}


def test_schema_v20_drift_guard():
    if obs_schema.SCHEMA_VERSION == 20:
        for frozen, live, what in (
                (_V19_TABLES_PAD_FIELDS, obs_schema.TABLES_PAD_FIELDS,
                 "run tables_pad"),
                (_V20_SPAN_COUNTER_FIELDS, obs_schema.SPAN_COUNTER_FIELDS,
                 "span"),
                (_V20_SPAN_BLOCK_FIELDS, obs_schema.SPAN_BLOCK_FIELDS,
                 "compute span"),
                ({"parent": "string?"}, obs_schema.SPAN_SETUP_FIELDS,
                 "setup span")):
            for name, tag in frozen.items():
                assert live.get(name) == tag, (
                    f"schema field {what}.{name} removed or retyped "
                    f"without bumping SCHEMA_VERSION")
        base = {"event": "span", "trace_id": "setup", "span_id": "s1",
                "op": "setup/graph", "t_start": 1.0, "dur_ms": 2.0,
                "status": "ok", "parent": None,
                **{k: 0 for k in _V20_SPAN_COUNTER_FIELDS}, "programs": []}
        validate_record(base)
        for broken in ({k: v for k, v in base.items() if k != "parent"},
                       {k: v for k, v in base.items() if k != "compiles"},
                       dict(base, trace_id="train-e0", gc_s=0.1)):
            with pytest.raises(ValueError):
                validate_record(broken)
    else:
        assert obs_schema.SCHEMA_VERSION > 20


# FROZEN copy of the v21 additions (what the training step keeps of its
# dropout masks, models/sage.py dropout_masks). Same contract as the
# earlier guards.
_V21_RUN_DROPOUT_FIELDS = {"dropout_masks": "integer",
                           "dropout_mask_bytes": "integer"}


def test_schema_v21_drift_guard():
    if obs_schema.SCHEMA_VERSION == 21:
        for frozen, live, what in (
                (_V19_TABLES_PAD_FIELDS, obs_schema.TABLES_PAD_FIELDS,
                 "run tables_pad"),
                (_V20_SPAN_COUNTER_FIELDS, obs_schema.SPAN_COUNTER_FIELDS,
                 "span"),
                (_V21_RUN_DROPOUT_FIELDS, obs_schema.RUN_DROPOUT_FIELDS,
                 "run")):
            for name, tag in frozen.items():
                assert live.get(name) == tag, (
                    f"schema field {what}.{name} removed or retyped "
                    f"without bumping SCHEMA_VERSION")
    else:
        assert obs_schema.SCHEMA_VERSION > 21


# FROZEN copy of the v22 additions (what one aggregation writes for its
# float32 sums, ops/bucket_spmm.py result_bytes). Same contract as the
# earlier guards.
_V22_TABLES_PAD_FIELDS = {**_V19_TABLES_PAD_FIELDS,
                          "result_bytes": "integer"}


def test_schema_v22_drift_guard():
    if obs_schema.SCHEMA_VERSION == 22:
        for frozen, live, what in (
                (_V22_TABLES_PAD_FIELDS, obs_schema.TABLES_PAD_FIELDS,
                 "run tables_pad"),
                (_V18_TABLES_PAD_DENSE_FIELDS,
                 obs_schema.TABLES_PAD_DENSE_FIELDS, "run tables_pad"),
                (_V20_SPAN_COUNTER_FIELDS, obs_schema.SPAN_COUNTER_FIELDS,
                 "span"),
                (_V20_SPAN_BLOCK_FIELDS, obs_schema.SPAN_BLOCK_FIELDS,
                 "compute span"),
                (_V21_RUN_DROPOUT_FIELDS, obs_schema.RUN_DROPOUT_FIELDS,
                 "run")):
            for name, tag in frozen.items():
                assert live.get(name) == tag, (
                    f"schema field {what}.{name} removed or retyped "
                    f"without bumping SCHEMA_VERSION")
    else:
        assert obs_schema.SCHEMA_VERSION > 22


def test_validate_run_record_dropout_masks():
    """A run record that says what the step keeps of its dropout masks
    says both numbers, as integers (v21); one that says neither passes."""
    run = {"event": "run", "schema_version": obs_schema.SCHEMA_VERSION,
           "time_unix": 0.0, "config": {}, "device": {}, "mesh": {}}
    masks = {"dropout_masks": 3, "dropout_mask_bytes": 1_101_078_528}
    validate_record(run)
    validate_record({**run, **masks})
    validate_record({**run, "dropout_masks": 0, "dropout_mask_bytes": 0})
    with pytest.raises(ValueError, match="dropout_mask_bytes"):
        validate_record({**run, "dropout_masks": 3})
    with pytest.raises(ValueError, match="expected integer"):
        validate_record({**run, **masks, "dropout_mask_bytes": 1.1e9})


def test_validate_run_record_tables_pad():
    """A run record's `tables_pad` is held to TABLES_PAD_FIELDS a
    direction; where a direction says what the block kernel's dense
    half stores it says all of it (v18); every direction says how its
    source rows are cut for the gathers, its widths a list a part
    (v19), and the float32 bytes one call writes for its sums (v22);
    null under `xla`."""
    run = {"event": "run", "schema_version": obs_schema.SCHEMA_VERSION,
           "time_unix": 0.0, "config": {}, "device": {}, "mesh": {}}
    rows = {"widths": [[7, 32]], "slots": 1036, "edges": 1000,
            "pad_ratio": 1.036, "parts": 1, "part_rows": 232_966,
            "result_bytes": 356_222_976}
    dense = {"dense_blocks": 38744, "dense_slots": 46135,
             "dense_pad": 1.1908, "a_bytes": 377_937_920}
    validate_record(run)
    validate_record({**run, "tables_pad": None})
    validate_record({**run, "tables_pad": {"fwd": rows, "bwd": rows}})
    validate_record({**run, "tables_pad": {"fwd": {**rows, **dense},
                                           "bwd": {**rows, **dense}}})
    with pytest.raises(ValueError, match="tables_pad.bwd.*pad_ratio"):
        validate_record({**run, "tables_pad": {
            "fwd": rows, "bwd": {k: v for k, v in rows.items()
                                 if k != "pad_ratio"}}})
    with pytest.raises(ValueError, match="tables_pad.fwd.*a_bytes"):
        validate_record({**run, "tables_pad": {"fwd": {
            **rows, **{k: v for k, v in dense.items()
                       if k != "a_bytes"}}}})
    with pytest.raises(ValueError, match="expected integer"):
        validate_record({**run, "tables_pad": {"fwd": {
            **rows, **dense, "dense_slots": 1.5}}})
    cut = {**rows, "widths": [[1, 9, 17], [1, 8, 15], [1, 8, 16]],
           "parts": 3, "part_rows": 238_950}
    validate_record({**run, "tables_pad": {"fwd": cut, "bwd": cut}})
    with pytest.raises(ValueError, match="tables_pad.fwd.*part_rows"):
        validate_record({**run, "tables_pad": {"fwd": {
            k: v for k, v in cut.items() if k != "part_rows"}}})
    with pytest.raises(ValueError, match="tables_pad.bwd.*result_bytes"):
        validate_record({**run, "tables_pad": {"fwd": cut, "bwd": {
            k: v for k, v in cut.items() if k != "result_bytes"}}})
    with pytest.raises(ValueError, match="expected integer"):
        validate_record({**run, "tables_pad": {"fwd": {
            **cut, "result_bytes": 3.9e9}}})


def test_validate_record():
    validate_record({"event": "epoch", "epoch": 0, "step_time_s": 0.1,
                     "loss": 1.0, "grad_norm": 0.5, "halo_bytes": 128,
                     "staleness_age": 1, "memory": None})
    with pytest.raises(ValueError, match="missing field"):
        validate_record({"event": "epoch", "epoch": 0})
    with pytest.raises(ValueError, match="expected integer"):
        validate_record({"event": "epoch", "epoch": 0.5,
                         "step_time_s": 0.1, "loss": 1.0,
                         "grad_norm": 0.5, "halo_bytes": 128,
                         "staleness_age": 1, "memory": None})
    # bool must not pass as an integer count
    with pytest.raises(ValueError, match="bool"):
        validate_record({"event": "epoch", "epoch": True,
                         "step_time_s": 0.1, "loss": 1.0,
                         "grad_norm": 0.5, "halo_bytes": 128,
                         "staleness_age": 1, "memory": None})
    # unknown event kinds are free-form
    validate_record({"event": "bench", "whatever": [1, 2]})


def test_validate_tuning_record():
    sample = {"sample_dense_coverage": 0.79,
              "shard_dense_coverage": 0.8, "sample_tile_rows": 16,
              "timed_edges": [1_000_000, 250_000],
              "shard_edges": 114_000_000}
    validate_record({"event": "tuning",
                     "winner": {"name": "block-u4-bf16",
                                "impl": "block"},
                     "source": "artifact", "costs": [],
                     "stale_reason": None, **sample})
    # the no-measurement default timed no sample: nulls, never absent
    validate_record({"event": "tuning", "winner": {},
                     "source": "default", "costs": [],
                     **dict.fromkeys(sample)})
    with pytest.raises(ValueError, match="winner"):
        validate_record({"event": "tuning", "source": "live",
                         "costs": [], **sample})
    with pytest.raises(ValueError, match="expected array"):
        validate_record({"event": "tuning", "winner": {},
                         "source": "live", "costs": {}, **sample})
    with pytest.raises(ValueError, match="sample_dense_coverage"):
        validate_record({"event": "tuning", "winner": {},
                         "source": "live", "costs": []})
    # every entry of the cost table says what was ranked (v17)
    cost = {"name": "block-f8", "spmm_fwdbwd_s": 7.2e-3,
            "spread_s": 1e-4, "fixed_s": 5e-3, "per_edge_s": 2.2e-9,
            "est_call_s": 0.26, "est_epoch_spmm_s": 0.78, "error": None}
    validate_record({"event": "tuning", "winner": {}, "source": "live",
                     "costs": [cost, dict.fromkeys(cost) | {
                         "name": "xla", "error": "boom"}], **sample})
    with pytest.raises(ValueError, match="tuning cost.*est_call_s"):
        validate_record({"event": "tuning", "winner": {},
                         "source": "live", "costs": [
                             {k: v for k, v in cost.items()
                              if k != "est_call_s"}], **sample})


def test_validate_serving_record():
    validate_record({"event": "serving", "window_s": 2.0, "queries": 40,
                     "qps": 20.0, "batch_fill": 0.5, "queue_depth": 0,
                     "p50_ms": 1.2, "p95_ms": 3.4, "p99_ms": 5.6,
                     "cache_hit_rate": 1.0, "staleness_age": 0,
                     "shed": 0, "param_generation": -1,
                     "param_staleness": 0})
    # empty windows carry nullable latency/fill fields
    validate_record({"event": "serving", "window_s": 2.0, "queries": 0,
                     "qps": 0.0, "batch_fill": None, "queue_depth": 0,
                     "p50_ms": None, "p95_ms": None, "p99_ms": None,
                     "cache_hit_rate": None, "staleness_age": 0,
                     "shed": 4, "param_generation": 7,
                     "param_staleness": 1})
    with pytest.raises(ValueError, match="missing field"):
        validate_record({"event": "serving", "window_s": 2.0})


def test_validate_fleet_record():
    validate_record({"event": "fleet", "kind": "replica-dead",
                     "replica": 1, "window": 3})
    # hot-swap records ride with free extras (swap_ms, incarnation, …)
    validate_record({"event": "fleet", "kind": "hot-swap", "replica": 0,
                     "window": -1, "param_generation": 2,
                     "swap_ms": 12.5, "incarnation": 0})
    with pytest.raises(ValueError, match="missing field"):
        validate_record({"event": "fleet", "kind": "failover"})
    with pytest.raises(ValueError, match="expected integer"):
        validate_record({"event": "fleet", "kind": "relaunch",
                         "replica": "one", "window": 0})


# ---------------- sink ---------------------------------------------------

def test_metrics_logger_roundtrip(tmp_path):
    p = tmp_path / "m.jsonl"
    with MetricsLogger(p) as ml:
        ml.run_header(config={"lr": 0.01}, device={"platform": "cpu"},
                      mesh={"n_parts": 4})
        assert ml.header_written
        # numpy scalars/arrays must serialize transparently
        ml.epoch(epoch=np.int64(0), step_time_s=np.float32(0.25),
                 loss=np.float32(1.5), grad_norm=np.float64(0.1),
                 halo_bytes=np.int64(4096), staleness_age=0,
                 memory={"bytes_in_use": None,
                         "peak_bytes_in_use": None})
        ml.eval_record(9, 0.01, 0.9, test_acc=0.88)
        ml.summary(n_epochs=10, epoch_time_s=0.25, best_val=0.9,
                   comm_cost={"comm": 0.1, "reduce": 0.2})
    recs = read_metrics(p)
    assert [r["event"] for r in recs] == ["run", "epoch", "eval",
                                          "summary"]
    for r in recs:
        validate_record(r)  # the file round-trips through the schema
    assert recs[0]["schema_version"] == obs_schema.SCHEMA_VERSION
    assert recs[1]["loss"] == pytest.approx(1.5)
    assert isinstance(recs[1]["halo_bytes"], int)
    assert recs[2]["test_acc"] == pytest.approx(0.88)

    # validation rejects a bad record at write time
    with MetricsLogger(tmp_path / "bad.jsonl") as ml:
        with pytest.raises(ValueError):
            ml.write({"event": "epoch", "epoch": 1})

    # a torn final line is reported, not silently dropped
    with open(p, "a") as f:
        f.write('{"event": "epo')
    with pytest.raises(ValueError, match="malformed"):
        read_metrics(p)


# ---------------- timers --------------------------------------------------

def test_phase_timer_exception_safety_and_accumulation():
    pt = PhaseTimer()
    with pytest.raises(KeyError):
        with pt.phase("outer"):
            with pt.phase("inner"):  # nesting is free
                pass
            raise KeyError("boom")
    # the raising span still recorded its duration
    assert pt.durations()["outer"] >= pt.durations()["inner"] >= 0.0
    # repeated keys accumulate instead of raising
    with pt.phase("inner"):
        pass
    assert pt.counts()["inner"] == 2
    pt.clear()
    assert pt.tot_time() == 0.0 and pt.counts() == {}


def test_comm_timer_records_on_exception():
    t = CommTimer()
    with pytest.raises(KeyError):
        with t.timer("forward_0"):
            raise KeyError("device loss mid-span")
    assert "forward_0" in t.durations()  # recorded despite the raise
    with pytest.raises(RuntimeError, match="duplicate"):
        with t.timer("forward_0"):
            pass


# ---------------- reference log-format byte parity ------------------------

def test_reference_log_lines_byte_exact():
    """The pre-refactor f-strings, pinned byte-for-byte: the formatters
    must never drift (tooling parses these lines)."""
    assert reference_train_line(0, 9, 0.1234, 0.015, 0.002, 1.5) == (
        "Process 000 | Epoch 00009 | Time(s) 0.1234 | Comm(s) 0.0150 | "
        "Reduce(s) 0.0020 | Loss 1.5000")
    assert reference_eval_line(9, 0.95) == "Epoch 00009 | Accuracy 95.00%"
    assert reference_eval_line(19, 0.9512, 0.9401) == (
        "Epoch 00019 | Validation Accuracy 95.12% | "
        "Test Accuracy 94.01%")
    assert epoch_line(10, 0.0312, 0.6931) == (
        "Epoch 00010 | Time(s) 0.0312 | Loss 0.6931")
    assert epoch_line(10, 0.0312, 0.6931, 0.875) == (
        "Epoch 00010 | Time(s) 0.0312 | Loss 0.6931 | Val 0.8750")


# ---------------- CLI end-to-end ------------------------------------------

_TRAIN_RE = re.compile(
    r"Process \d{3} \| Epoch \d{5} \| Time\(s\) \d+\.\d{4} \| "
    r"Comm\(s\) \d+\.\d{4} \| Reduce\(s\) \d+\.\d{4} \| Loss \d+\.\d{4}")
_EVAL_RE = re.compile(
    r"Epoch (\d{5}) \| Validation Accuracy (\d+\.\d{2})% \| "
    r"Test Accuracy (\d+\.\d{2})%")


def _cli_args(tmp_path, extra):
    base = [
        "--dataset", "synthetic:600:8:16:4",
        "--n-partitions", "4",
        "--n-epochs", "12",
        "--n-layers", "2",
        "--n-hidden", "32",
        "--dropout", "0.2",
        "--log-every", "5",
        "--fix-seed", "--seed", "7",
        "--partition-dir", str(tmp_path / "partitions"),
        "--model-dir", str(tmp_path / "model"),
        "--results-dir", str(tmp_path / "results"),
    ]
    return create_parser().parse_args(base + extra)


@pytest.fixture(scope="module")
def cli_metrics_run(tmp_path_factory):
    """One pipelined CLI smoke run with --metrics-out, shared by the
    telemetry-content, report-CLI and reference-log tests."""
    tmp_path = tmp_path_factory.mktemp("obs_cli")
    mpath = tmp_path / "metrics.jsonl"
    args = _cli_args(tmp_path, ["--enable-pipeline",
                                "--metrics-out", str(mpath)])
    res = run(args)
    return tmp_path, mpath, args, res


def test_cli_metrics_end_to_end(cli_metrics_run):
    tmp_path, mpath, args, res = cli_metrics_run
    assert res["metrics_out"] == str(mpath)
    recs = read_metrics(mpath)
    for r in recs:
        validate_record(r)

    header = recs[0]
    assert header["event"] == "run"
    assert header["schema_version"] == obs_schema.SCHEMA_VERSION
    assert header["config"]["enable_pipeline"] is True
    assert header["mesh"]["n_parts"] == 4
    assert header["device"].get("platform") == "cpu"

    epochs = [r for r in recs if r["event"] == "epoch"]
    assert [r["epoch"] for r in epochs] == list(range(12))
    for r in epochs:
        assert r["step_time_s"] > 0
        assert np.isfinite(r["loss"])
        assert r["grad_norm"] > 0
        assert r["halo_bytes"] > 0  # P=4: real halo traffic
        assert set(r["memory"]) >= {"bytes_in_use", "peak_bytes_in_use"}
    # staleness-1 pipelining: epoch 0 consumes zero-initialized buffers
    assert epochs[0]["staleness_age"] == 0
    assert all(r["staleness_age"] == 1 for r in epochs[1:])
    # the pipelined loss still goes down on this easy graph
    assert epochs[-1]["loss"] < epochs[0]["loss"]

    evals = [r for r in recs if r["event"] == "eval"]
    assert evals and all(0 <= r["val_acc"] <= 1 for r in evals)
    assert "test_acc" in evals[0]  # transductive eval scores test too

    summ = [r for r in recs if r["event"] == "summary"]
    assert len(summ) == 1
    assert summ[0]["n_epochs"] == 12
    assert summ[0]["best_val"] == pytest.approx(res["best_val"])
    assert summ[0]["comm_cost"]["comm"] > 0  # measure_comm_cost path


def test_cli_run_record_counts_dropout_masks(cli_metrics_run):
    """The CLI's run header says what the training step keeps of its
    dropout masks: two graph layers at dropout 0.2, each mask over a
    shard's inner and halo rows at the layer's input width (16 + 32),
    a byte an element."""
    _, mpath, args, _ = cli_metrics_run
    header = read_metrics(mpath)[0]
    assert args.dropout == 0.2 and args.n_layers == 2
    assert header["dropout_masks"] == 2
    assert header["dropout_mask_bytes"] > 0
    assert header["dropout_mask_bytes"] % (16 + 32) == 0


def test_cli_reference_logs_unchanged(cli_metrics_run):
    """--reference-logs output must stay byte-identical through the
    telemetry refactor: every result-file line matches the reference
    format exactly, and re-rendering the parsed values through the
    pinned formatter reproduces each line byte-for-byte."""
    tmp_path, mpath, args, res = cli_metrics_run
    rfile = result_file_name(args)
    lines = open(rfile).read().strip().splitlines()
    assert lines
    for line in lines:
        m = _EVAL_RE.fullmatch(line)
        assert m, f"reference-format line drifted: {line!r}"
        rebuilt = reference_eval_line(int(m.group(1)),
                                      float(m.group(2)) / 100.0,
                                      float(m.group(3)) / 100.0)
        assert rebuilt == line


def test_cli_stdout_train_lines_reference_format(tmp_path, capsys):
    """The Process/Comm/Reduce stdout lines keep the reference's exact
    field layout (train.py:369-371)."""
    args = _cli_args(tmp_path, ["--no-eval"])
    run(args)
    out = capsys.readouterr().out
    train_lines = [ln for ln in out.splitlines()
                   if ln.startswith("Process")]
    assert train_lines  # 12 epochs -> the epoch-9 boundary logs once
    for ln in train_lines:
        assert _TRAIN_RE.fullmatch(ln), f"drifted: {ln!r}"


# ---------------- report CLI ----------------------------------------------

def test_report_cli_summarizes_run(cli_metrics_run, capsys):
    _, mpath, _, res = cli_metrics_run
    rc = report_main([str(mpath)])
    assert rc == 0
    out = capsys.readouterr().out
    assert "median epoch" in out
    assert "best val" in out
    # --json emits a machine-readable summary
    rc = report_main([str(mpath), "--json"])
    assert rc == 0
    s = json.loads(capsys.readouterr().out)
    assert s["n_epoch_records"] == 12
    assert s["pipeline"] is True
    assert s["median_epoch_s"] > 0
    assert s["best_val"] == pytest.approx(res["best_val"])
    assert s["loss_delta"] < 0
    assert 0 < s["comm_fraction"] <= 1
    assert s["overlapped_comm_fraction"] == s["comm_fraction"]
    assert s["halo_bytes_per_epoch"] > 0
    assert s["staleness_age_max"] == 1


def test_report_json_pins_floor_share_and_halo_compression(tmp_path,
                                                           capsys):
    """--json shape pin for the round-8 floor fields: compressed-halo
    runs expose before/after wire bytes + ratio, and anatomy-bearing
    runs expose the non-SpMM floor share (1 - spmm phase shares)."""
    p = tmp_path / "floor.jsonl"
    with MetricsLogger(p) as ml:
        ml.run_header(config={}, device={}, mesh={})
        for e in range(3):
            ml.epoch(epoch=e, step_time_s=0.5, loss=1.0 - 0.1 * e,
                     grad_norm=0.5, halo_bytes=250, staleness_age=1,
                     memory=None, halo_bytes_uncompressed=1000)
        ml.anatomy(
            phases={"spmm_fwd": {"flops": 60.0},
                    "spmm_bwd": {"flops": 20.0},
                    "dense": {"flops": 15.0},
                    "norm": {"flops": 5.0}},
            est_flops=100.0, attributed_flops_fraction=0.9)
    rc = report_main([str(p), "--json"])
    assert rc == 0
    s = json.loads(capsys.readouterr().out)
    assert s["halo_bytes_per_epoch"] == 250
    assert s["halo_bytes_uncompressed_per_epoch"] == 1000
    assert s["halo_compression_ratio"] == pytest.approx(4.0)
    assert s["anatomy_non_spmm_share"] == pytest.approx(0.2)
    # human-readable lines render the same facts
    rc = report_main([str(p)])
    assert rc == 0
    out = capsys.readouterr().out
    assert "halo wire compression" in out
    assert "non-SpMM floor share" in out


def test_report_json_pins_serving_summary(tmp_path, capsys):
    """--json shape pin for the round-10 serving fields: windowed
    `serving` records roll up to total QPS, query-weighted latency
    percentiles / batch fill / cache hit rate, and a drained flag off
    the hard-flushed final record."""
    p = tmp_path / "serve.jsonl"
    with MetricsLogger(p) as ml:
        ml.run_header(config={}, device={}, mesh={})
        ml.serving(window_s=2.0, queries=40, qps=20.0, batch_fill=0.5,
                   queue_depth=1, p50_ms=1.0, p95_ms=2.0, p99_ms=3.0,
                   cache_hit_rate=1.0, staleness_age=0, shed=3,
                   param_generation=1, param_staleness=1)
        ml.serving(window_s=2.0, queries=120, qps=60.0, batch_fill=0.75,
                   queue_depth=3, p50_ms=2.0, p95_ms=4.0, p99_ms=6.0,
                   cache_hit_rate=0.5, staleness_age=2, shed=5,
                   param_generation=2, param_staleness=0, final=True)
    rc = report_main([str(p), "--json"])
    assert rc == 0
    s = json.loads(capsys.readouterr().out)
    assert s["n_serving_records"] == 2
    assert s["serving_queries"] == 160
    assert s["serving_qps"] == pytest.approx(40.0)
    # query-weighted means: (40*1 + 120*2) / 160
    assert s["serving_p50_ms"] == pytest.approx(1.75)
    assert s["serving_p99_ms"] == pytest.approx(5.25)
    assert s["serving_batch_fill"] == pytest.approx(0.6875)
    assert s["serving_cache_hit_rate"] == pytest.approx(0.625)
    assert s["serving_staleness_age_max"] == 2
    assert s["serving_queue_depth_max"] == 3
    # v7 rollups: total shed rows, last served generation, worst lag
    assert s["serving_shed_total"] == 8
    assert s["serving_param_generation_last"] == 2
    assert s["serving_param_staleness_max"] == 1
    assert s["serving_drained"] is True
    # human-readable lines render the same facts
    rc = report_main([str(p)])
    assert rc == 0
    out = capsys.readouterr().out
    assert "serving QPS" in out
    assert "serving latency" in out
    # without a final record the report flags the shutdown
    q = tmp_path / "undrained.jsonl"
    with MetricsLogger(q) as ml:
        ml.run_header(config={}, device={}, mesh={})
        ml.serving(window_s=2.0, queries=10, qps=5.0, batch_fill=None,
                   queue_depth=0, p50_ms=None, p95_ms=None, p99_ms=None,
                   cache_hit_rate=None, staleness_age=0)
    summ = summarize_run(read_metrics(q))
    assert summ["serving_drained"] is False
    assert report_main([str(q)]) == 0
    assert "!! serving shutdown" in capsys.readouterr().out


def test_report_json_pins_stream_summary(tmp_path, capsys):
    """--json shape pin for the v8 stream fields: `stream` records roll
    up to delta totals, median/max patch cost, max/last probe drift, a
    re-pad count, and the last slack headroom snapshot."""
    p = tmp_path / "stream.jsonl"
    with MetricsLogger(p) as ml:
        ml.run_header(config={}, device={}, mesh={})
        ml.stream(epoch=4, seq=0, edges_added=10, edges_deleted=2,
                  nodes_added=1, patch_ms=1.5, tables_rebuilt=4,
                  repadded=False,
                  slack_remaining={"n": 9, "b": 5, "e": 80},
                  drift=0.31)
        ml.stream(epoch=8, seq=1, edges_added=6, edges_deleted=4,
                  nodes_added=0, patch_ms=2.5, tables_rebuilt=12,
                  repadded=True,
                  slack_remaining={"n": 20, "b": 11, "e": 150},
                  drift=0.12)
    rc = report_main([str(p), "--json"])
    assert rc == 0
    s = json.loads(capsys.readouterr().out)
    assert s["n_stream_records"] == 2
    assert s["stream_edges_added"] == 16
    assert s["stream_edges_deleted"] == 6
    assert s["stream_nodes_added"] == 1
    assert s["stream_patch_ms_median"] == pytest.approx(2.0)
    assert s["stream_patch_ms_max"] == pytest.approx(2.5)
    assert s["stream_drift_max"] == pytest.approx(0.31)
    assert s["stream_drift_last"] == pytest.approx(0.12)
    assert s["stream_tables_rebuilt"] == 16
    assert s["stream_repads"] == 1
    assert s["stream_slack_remaining_last"] == {"n": 20, "b": 11,
                                                "e": 150}
    # human-readable lines render the same facts, incl. the loud
    # slack-exhaustion flag
    rc = report_main([str(p)])
    assert rc == 0
    out = capsys.readouterr().out
    assert "stream deltas" in out
    assert "stream patch cost" in out
    assert "!! stream re-pads" in out


def test_report_json_pins_fleet_summary(tmp_path, capsys):
    """--json shape pin for the round-12 fleet fields: `fleet` records
    roll up to a per-kind event count, the max measured hot-swap
    latency, and the last swapped generation; a death without a rejoin
    prints the degraded warning."""
    p = tmp_path / "fleet.jsonl"
    with MetricsLogger(p) as ml:
        ml.run_header(config={}, device={}, mesh={})
        ml.fleet("hot-swap", 0, window=-1, param_generation=2,
                 swap_ms=12.5, incarnation=0)
        ml.fleet("hot-swap", 1, window=-1, param_generation=2,
                 swap_ms=30.25, incarnation=0)
        ml.fleet("replica-dead", 1, window=3, reason="heartbeat-stale")
        ml.fleet("failover", 0, window=3, n_retried=16, attempts=2)
        ml.fleet("relaunch", 1, window=3, incarnation=1, delay_s=0.5)
    rc = report_main([str(p), "--json"])
    assert rc == 0
    s = json.loads(capsys.readouterr().out)
    assert s["n_fleet_records"] == 5
    assert s["fleet_events"] == {"hot-swap": 2, "replica-dead": 1,
                                 "failover": 1, "relaunch": 1}
    assert s["fleet_param_swap_ms_max"] == pytest.approx(30.25)
    assert s["fleet_param_generation_last"] == 2
    # human-readable lines render the same facts + the degraded flag
    # (1 death, 0 rejoins)
    rc = report_main([str(p)])
    assert rc == 0
    out = capsys.readouterr().out
    assert "fleet" in out
    assert "replica-dead=1" in out
    assert "!! fleet degraded" in out


def test_membership_record_roundtrip(tmp_path):
    """MetricsLogger.membership writes a hard-flushed v6 record that
    validates, carrying the supervisor's assignment verbatim."""
    from pipegcn_tpu.resilience.elastic import plan_assignment

    a = plan_assignment(4, [0, 1])
    p = tmp_path / "m.jsonl"
    with MetricsLogger(p) as ml:
        ml.membership(generation=0, assignment=a.as_json(),
                      trigger="start", n_members=2)
        ml.membership(generation=1,
                      assignment=plan_assignment(4, [0]).as_json(),
                      trigger="rank-death", restart_latency_s=3.25,
                      n_members=1)
    recs = [r for r in read_metrics(p) if r["event"] == "membership"]
    assert len(recs) == 2
    for r in recs:
        validate_record(r)
    assert recs[0]["restart_latency_s"] is None
    assert recs[0]["assignment"]["parts"] == {"0": [0, 1], "1": [2, 3]}
    assert recs[1]["trigger"] == "rank-death"
    assert recs[1]["assignment"]["parts"] == {"0": [0, 1, 2, 3]}
    # contract violations are loud
    bad = dict(recs[0], generation="zero")
    with pytest.raises(ValueError):
        validate_record(bad)


def test_report_json_pins_membership_summary(tmp_path, capsys):
    """--json shape pin for the round-11 membership fields: the ledger's
    generation records roll up to a timeline, the max restart latency,
    and a stopped flag when the supervisor gave up."""
    from pipegcn_tpu.resilience.elastic import plan_assignment

    p = tmp_path / "elastic.jsonl"
    with MetricsLogger(p) as ml:
        ml.run_header(config={}, device={}, mesh={})
        ml.membership(generation=0,
                      assignment=plan_assignment(2, [0, 1]).as_json(),
                      trigger="start", n_members=2)
        ml.membership(generation=1,
                      assignment=plan_assignment(2, [0]).as_json(),
                      trigger="rank-death", restart_latency_s=7.5,
                      n_members=1)
        ml.membership(generation=1,
                      assignment=plan_assignment(2, [0]).as_json(),
                      trigger="max-restarts", n_members=1)
    rc = report_main([str(p), "--json"])
    assert rc == 0
    s = json.loads(capsys.readouterr().out)
    assert s["n_membership_records"] == 3
    assert s["membership_last_generation"] == 1
    tl = s["membership_timeline"]
    assert [t["generation"] for t in tl] == [0, 1, 1]
    assert tl[0]["trigger"] == "start"
    assert tl[0]["n_members"] == 2
    assert tl[0]["parts_per_node"] == 1
    assert tl[1]["restart_latency_s"] == pytest.approx(7.5)
    assert s["restart_latency_max_s"] == pytest.approx(7.5)
    assert s["membership_stopped"] == "max-restarts"
    # human-readable lines render the same facts
    rc = report_main([str(p)])
    assert rc == 0
    out = capsys.readouterr().out
    assert "membership" in out
    assert "rank-death" in out
    assert "!! supervisor stopped" in out


def test_report_cli_tolerates_partial_files(tmp_path, capsys):
    """A crashed run's file (header + some epochs, no summary) still
    summarizes; a missing file errors with rc=1, not a traceback."""
    p = tmp_path / "partial.jsonl"
    with MetricsLogger(p) as ml:
        ml.run_header(config={}, device={}, mesh={})
        for e in range(3):
            ml.epoch(epoch=e, step_time_s=0.5 + e, loss=1.0 - 0.1 * e,
                     grad_norm=0.5, halo_bytes=0, staleness_age=0,
                     memory=None)
    assert report_main([str(p)]) == 0
    s_out = capsys.readouterr().out
    assert "epochs recorded" in s_out
    summ = summarize_run(read_metrics(p))
    assert summ["n_epoch_records"] == 3
    assert summ["median_epoch_s"] == pytest.approx(1.5)
    assert summ["loss_delta"] == pytest.approx(-0.2)
    assert report_main([str(tmp_path / "nope.jsonl")]) == 1


# ---------------- sequential runner records -------------------------------

def test_sequential_runner_emits_epoch_records(tmp_path):
    from pipegcn_tpu.graph import synthetic_graph
    from pipegcn_tpu.models import ModelConfig
    from pipegcn_tpu.parallel import SequentialRunner, TrainConfig
    from pipegcn_tpu.partition import ShardedGraph, partition_graph

    g = synthetic_graph(num_nodes=400, avg_degree=6, n_feat=8,
                        n_class=3, seed=3)
    parts = partition_graph(g, 4, seed=0)
    sg = ShardedGraph.build(g, parts, n_parts=4)
    cfg = ModelConfig(layer_sizes=(8, 16, 3), dropout=0.0,
                      train_size=sg.n_train_global, spmm_impl="bucket")
    mpath = tmp_path / "seq.jsonl"
    with MetricsLogger(mpath) as ml:
        ml.run_header(config={"runner": "sequential"}, device={},
                      mesh={"n_parts": 4})
        runner = SequentialRunner(
            sg, cfg, TrainConfig(n_epochs=2, enable_pipeline=True),
            metrics=ml)
        for e in range(2):
            runner.run_epoch(e)
    recs = read_metrics(mpath)
    epochs = [r for r in recs if r["event"] == "epoch"]
    assert len(epochs) == 2
    for r in epochs:
        validate_record(r)
        assert r["grad_norm"] > 0 and r["halo_bytes"] > 0
    assert epochs[0]["staleness_age"] == 0
    assert epochs[1]["staleness_age"] == 1


def test_alert_and_span_records_roundtrip(tmp_path):
    """MetricsLogger.alert (hard-flushed) and .span write v10 records
    that validate and read back; stats() exposes the sink's record
    count and io-degradation state for the monitor exporter."""
    p = tmp_path / "a.jsonl"
    with MetricsLogger(p) as ml:
        ml.alert(rule="fault-rate", state="fire", severity="page",
                 source="*", value=3.0, threshold=1.0,
                 message="3 fault(s) in the last 60s")
        ml.alert(rule="fault-rate", state="resolve", severity="page",
                 source="*", value=None, threshold=None,
                 message="resolved")
        ml.span(trace_id="q1-serve", span_id="s1", op="queue",
                t_start=1234.5, dur_ms=2.25, status="ok", rows=4)
        st = ml.stats()
        assert st["records"] == 3
        assert st["degraded"] is False
        assert st["dropped"] == 0
    recs = read_metrics(p)
    assert [r["event"] for r in recs] == ["alert", "alert", "span"]
    for r in recs:
        validate_record(r)
    assert recs[0]["state"] == "fire"
    assert recs[1]["value"] is None
    assert recs[2]["trace_id"] == "q1-serve"
    # contract violations are loud
    with pytest.raises(ValueError):
        validate_record(dict(recs[2], dur_ms="fast"))
    with pytest.raises(ValueError):
        validate_record({k: v for k, v in recs[0].items()
                         if k != "message"})
