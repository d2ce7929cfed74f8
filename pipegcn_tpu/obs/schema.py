"""Versioned metrics schema.

Every JSONL record carries an ``event`` discriminator; the required
fields (and their JSON types) per event kind are listed below. Records
may carry EXTRA fields freely — consumers must ignore unknown keys —
but a required field may never be removed or retyped without bumping
``SCHEMA_VERSION`` (tests/test_obs.py pins the v1 field list; the
drift check fails any PR that breaks the contract silently).

Type tags are JSON types: "string" | "integer" | "number" | "object"
| "array" | "boolean". "integer" excludes booleans; "number" accepts
ints and floats. A required field may be null only when its tag ends
with "?" (e.g. the memory probe returns nulls off-accelerator).
"""

from __future__ import annotations

from typing import Dict, Mapping

SCHEMA_VERSION = 22  # v22: each direction of `tables_pad` says what
#                      one aggregation writes for its float32 sums:
#                      `result_bytes` (the buffer of every part's
#                      bucket sums and one take a part,
#                      bucket_spmm.result_bytes)
#                 v21: the run record says what the training step
#                      keeps of its dropout masks (RUN_DROPOUT_FIELDS,
#                      both or neither: models/sage.py dropout_masks)
#                 v20: set-up spans (trace id "setup", obs/trace.py:
#                      setup/process, setup/runtime, setup/graph,
#                      setup/trainer and its phases, setup/services,
#                      fit/measure_comm, fit/load, fit/tail) carry
#                      `parent` and the compile counters
#                      (SPAN_COUNTER_FIELDS); a training `compute` span
#                      (and fit/tail) carries the counters too and what
#                      the host did since the previous harvest
#                      (SPAN_BLOCK_FIELDS)
#                 v19: each direction of `tables_pad` says how its
#                      source rows are cut for the gathers: `parts`
#                      (tables, bucket_spmm.source_parts) and
#                      `part_rows` (rows of the tallest part); `widths`
#                      is a list a part
#                 v18: the run record's `tables_pad` is typed
#                      (TABLES_PAD_FIELDS a direction) and, under the
#                      block kernel, says what the dense half STORES:
#                      dense_blocks, dense_slots, dense_pad, a_bytes
#                      (ops/block_spmm.py dense_pad_stats)
#                 v17: the tuning record says what was RANKED:
#                      every entry of `costs` carries fixed_s /
#                      per_edge_s / est_call_s from two nested samples
#                      (ops/tuner.py shard_estimate; TUNING_COST_FIELDS
#                      types an entry), the record the samples' and the
#                      shard's edges (timed_edges, shard_edges);
#                      call_overhead_s went with the subtraction that
#                      used it
#                 v16: the tuning record says what its sample
#                      carried (sample_dense_coverage beside
#                      shard_dense_coverage, sample_tile_rows,
#                      call_overhead_s — ops/tuner.py sample_slice)
#                 v15: journal record kind (write-ahead delta
#                      journal lifecycle: append / watermark / replay /
#                      truncate / verify / degraded / recovered / skew
#                      — stream/journal.py, docs/STREAMING.md
#                      "Durability & replay")
#                 v14: tracesync record kind (per-rank training
#                      clock anchors at collective barriers —
#                      obs/trainspan.py, docs/OBSERVABILITY.md
#                      "Training traces")
#                 v13: integrity record kind (SDC detector
#                      outcomes: digest scrub, Freivalds compute
#                      verification, halo wire checksum —
#                      resilience/integrity.py)
#                 v12: autoscale record kind (closed-loop scale
#                          decisions with triggering evidence)
#                 v11: blackbox record kind (flight-recorder crash
#                          dumps, obs/flight.py) + diagnosis record kind
#                          (postmortem verdicts, obs/postmortem.py +
#                          pipegcn-debug) — docs/OBSERVABILITY.md
#                          "Postmortem & flight recorder"

# one run header per file/run: what produced the numbers
RUN_FIELDS: Dict[str, str] = {
    "event": "string",           # "run"
    "schema_version": "integer",
    "time_unix": "number",
    "config": "object",          # model/train/CLI config snapshot
    "device": "object",          # platform / device_kind / counts
    "mesh": "object",            # n_parts, axis names/shape
}

# what one training step keeps of its dropout masks, where a run record
# says it (validate_record holds it to both): the masks drawn once and
# stored for the backward (models/sage.py _dropout) and their bytes on
# one device
RUN_DROPOUT_FIELDS: Dict[str, str] = {
    "dropout_masks": "integer",
    "dropout_mask_bytes": "integer",
}

# one direction (`fwd`, `bwd`) of a run record's `tables_pad`, where it
# carries one (validate_record holds both to it): the row-bucket tables
# in use (bucket_spmm.pad_stats) and, under the block kernel, what the
# dense half stores (block_spmm.dense_pad_stats)
TABLES_PAD_FIELDS: Dict[str, str] = {
    "widths": "array",           # a part's row buckets that hold a row
    "slots": "integer",          # gather requests: width x rows
    "edges": "integer",          # entries that are no sentinel
    "pad_ratio": "number",       # slots / edges
    "parts": "integer",          # tables the source rows are cut into
    "part_rows": "integer",      # source rows of the tallest part
    "result_bytes": "integer",   # f32 bytes a call writes for its sums
}
TABLES_PAD_DENSE_FIELDS: Dict[str, str] = {
    "dense_blocks": "integer",   # [tile, tile] slots that hold an edge
    "dense_slots": "integer",    # slots stored, pads and tails included
    "dense_pad": "number",       # dense_slots / dense_blocks
    "a_bytes": "integer",        # bytes of A one device stores
}

# one record per training epoch
EPOCH_FIELDS: Dict[str, str] = {
    "event": "string",           # "epoch"
    "epoch": "integer",          # 0-based global epoch index
    "step_time_s": "number",     # wall-clock of this epoch's dispatch
    "loss": "number",            # global mean train loss
    "grad_norm": "number",       # l2 norm of the reduced gradient
    "halo_bytes": "integer",     # est. halo wire bytes this epoch
    "staleness_age": "integer",  # age (epochs) of consumed boundary data
    "memory": "object?",         # bytes_in_use / peak_bytes_in_use
}

# one record per harvested evaluation
EVAL_FIELDS: Dict[str, str] = {
    "event": "string",           # "eval"
    "epoch": "integer",          # epoch the evaluated params belong to
    "eval_time_s": "number",     # exposed harvest wait (async) / full
    "val_acc": "number",
}

# one summary per completed run
SUMMARY_FIELDS: Dict[str, str] = {
    "event": "string",           # "summary"
    "n_epochs": "integer",
    "epoch_time_s": "number?",   # warmup-excluded mean (fit() semantics)
    "best_val": "number",
}

# one record per detected fault (divergence trip, preemption request,
# injected fault, corrupt checkpoint generation, cross-rank desync,
# lost peer, or — v9 — an ``io-degraded`` durable-write failure: the
# disk rejected a checkpoint / ledger / metrics write and the writer
# fell back to its degradation policy, resilience/storage.py);
# extras carry the kind-specific detail (reason, retry
# count, trip values). Multi-host extras the MetricsLogger always adds
# (optional in the contract so v1 files stay valid):
#   rank         integer — process that wrote the record
#   source_rank  integer — rank that raised a consensus-propagated
#                fault (-1 when several raised at once)
#   agreed       boolean — the action executed in cross-rank lockstep
#   peer_rank    integer — the silent peer of a peer-lost fault
FAULT_FIELDS: Dict[str, str] = {
    "event": "string",           # "fault"
    "kind": "string",            # divergence | preemption | injected
    #                              | desync | peer-lost | ...
    "epoch": "integer",          # epoch the fault surfaced at
}

# one record per completed recovery (training progressed past the
# faulted epoch after rollback/backoff, a resume restored state, or a
# desync resync adopted rank 0's state); carries the same optional
# rank/agreement extras as fault records
RECOVERY_FIELDS: Dict[str, str] = {
    "event": "string",           # "recovery"
    "kind": "string",            # matches the fault it recovers from
    "epoch": "integer",          # epoch training had reached on recovery
}

# one record per captured profiling window (obs/profiler.py): MEASURED
# device self seconds per phase, folded from a jax.profiler trace
# (.xplane.pb) against the compiled HLO of every scan length the window
# dispatched, plus the measured comm/compute overlap fraction: the
# report CLI prints it next to (and flags divergence from) the
# host-side estimate. Seconds are per device. Extras: epoch_start /
# epoch_end (the window as dispatched), paths ({scope path: self
# seconds}, `spmm/bwd/gather`), window_s / busy_s (union of the op
# line's intervals), unscoped_s (self seconds under no named scope),
# other_programs_s, programs ([{scan_length, modules, n_events,
# matched}]), idle_gaps ([{span, inner, s, n}]: the longest gaps by the
# program's host span open in them), t0_unix (the trace clock's zero
# on the clock of the `span` / `tracesync` records) and first_event_s
# behind it, fold_s, trace_files, trace_bytes, n_devices,
# n_device_events / n_matched_events (parser coverage).
PROFILE_FIELDS: Dict[str, str] = {
    "event": "string",             # "profile"
    "phases": "object",            # {spmm|dense|halo_comm|...: seconds}
    "comm_s": "number",            # device seconds in comm phases
    "compute_s": "number",         # device seconds in everything else
    "overlap_fraction": "number",  # measured, in [0, 1]
}

# one record per compiled-step anatomy (obs/anatomy.py): estimated
# FLOPs/bytes per phase from the optimized HLO walk + XLA's own cost /
# memory analysis. flops/bytes_accessed are XLA's totals (null when the
# backend exposes no analysis); attributed_flops_fraction is the share
# of the estimate landing in a named (non-"other") phase.
ANATOMY_FIELDS: Dict[str, str] = {
    "event": "string",             # "anatomy"
    "phases": "object",            # {phase: {flops, bytes, n_ops}}
    "est_flops": "number",         # this parser's own total estimate
    "flops": "number?",            # XLA cost_analysis total
    "attributed_flops_fraction": "number?",
}

# one record per staleness probe epoch (--staleness-probe-every):
# per-layer relative drift between the stale boundary features the
# pipelined step consumed and the fresh ones it shipped —
# ||h_stale - h_fresh|| / ||h_fresh|| — the approximation the pipeline
# actually pays, measured for the first time.
STALENESS_FIELDS: Dict[str, str] = {
    "event": "string",             # "staleness"
    "epoch": "integer",            # probe epoch
    "layers": "object",            # {layer: {rel_drift, fresh_norm}}
    "max_rel_drift": "number",     # max over layers
}

# one record per numerics-guardrail event (resilience/numerics.py):
#   kind "overflow"  — a loss-scale overflow epoch: the in-graph select
#                      skipped the update; extras: scale, skipped,
#                      new_scale (when auto mode backed off)
#   kind "growth"    — the dynamic scale regrew after a clean streak;
#                      extras: scale
#   kind "tripwire"  — a sentinel trip's NaN provenance; extras: phase
#                      (resilience/numerics.PHASES), counts (per-phase
#                      non-finite element counts of the tripped epoch)
NUMERICS_FIELDS: Dict[str, str] = {
    "event": "string",             # "numerics"
    "kind": "string",              # overflow | growth | tripwire
    "epoch": "integer",
}

# one record per kernel-fallback-ladder downgrade (resilience/numerics
# + Trainer._dispatch): a compile-or-dispatch crash of the aggregation
# kernel was absorbed by rebuilding one rung down (block -> bucket ->
# sorted-XLA) instead of killing the run. Extras: reason (the absorbed
# error, truncated).
FALLBACK_FIELDS: Dict[str, str] = {
    "event": "string",             # "fallback"
    "epoch": "integer",            # epoch the downgrade happened at
    "from_impl": "string",         # kernel that failed
    "to_impl": "string",           # kernel the step rebuilt on
}

# one record per run with spmm_impl='auto' (ops/tuner.py +
# Trainer._resolve_auto): WHY this kernel dispatches. winner carries
# {name, impl, rem_dtype, rem_amax, block_group}; costs is the full
# measured per-candidate micro-bench table (empty for the
# no-measurement default); source says where the decision came from:
#   "artifact" — trusted persisted tuning.json in the partition artifact
#   "live"     — micro-bench ran at trainer setup (cache miss); extras
#                carry stale_reason (why the persisted table, if any,
#                was rejected — the LOUD part of the stale-table path)
#   "default"  — no table and no live tune allowed (multi-process or
#                --no-tune): the tuner's fixed deterministic default
# v16: what the timed sample carried (null for "default", which times
# nothing). The sample is whole blocks of destination tile-rows; its
# dense coverage (block_spmm._part_block_stats at the block candidates'
# tile, threshold and budget) beside the whole shard's says whether the
# block candidates met the shard's dense tiles.
# v17: candidates are ranked by est_call_s, a call at the shard's size
# (shard_edges), the line through its best reps on the nested samples
# of timed_edges edges (one entry where the shard was timed whole: the
# estimate is then the time, fixed_s and per_edge_s null). Extras:
# est_epoch_spmm_s (the winner's est_call_s x the step's aggregations,
# to hold against a traced spmm_s).
TUNING_FIELDS: Dict[str, str] = {
    "event": "string",             # "tuning"
    "winner": "object",            # the dispatched kernel config
    "source": "string",            # artifact | live | default
    "costs": "array",              # per-candidate table (below)
    "sample_dense_coverage": "number?",  # of the sampled tile-rows
    "shard_dense_coverage": "number?",   # of the heaviest shard
    "sample_tile_rows": "integer?",      # destination tile-rows timed
    "timed_edges": "array?",             # edges of each timed sample
    "shard_edges": "integer?",           # edges the estimates are for
}

# one entry of a tuning record's `costs` (validate_record holds every
# entry to it): a candidate that failed carries its `error` and nulls
TUNING_COST_FIELDS: Dict[str, str] = {
    "name": "string",              # candidate_grid's name
    "spmm_fwdbwd_s": "number?",    # best rep on the larger sample
    "spread_s": "number?",         # its median rep over the best
    "fixed_s": "number?",          # of a call, whatever its edges
    "per_edge_s": "number?",       # slope between the two samples
    "est_call_s": "number?",       # fixed_s + per_edge_s * shard_edges
    "est_epoch_spmm_s": "number?",  # est_call_s x aggregations a step
    "error": "string?",            # why the candidate did not run
}

# one record per serving report window (serve/loadgen.run_serving_loop,
# default every --serve-report-every seconds, plus one final record on
# shutdown carrying the extra field `final: true`): the online-serving
# health tuple. Latency percentiles are per-query wall times measured
# submit -> batch-flush-complete (null in a window that served nothing);
# batch_fill is mean served-rows / padded-bucket-rows over the window's
# flushed batches; staleness_age is the max bounded-staleness age (in
# applied update batches) any query in the window was served at — 0
# means every answer reflected every accepted update (docs/SERVING.md).
# v7 grows the parameter-staleness axis + load-shedding accounting:
# param_generation is the checkpoint generation (epoch) of the params
# that served this window (-1 = freshly-initialized, no checkpoint);
# param_staleness counts CRC-verified published generations NEWER than
# the serving one (0 = serving the newest model); shed counts query
# rows explicitly rejected this window (bounded queue / per-ticket
# deadline) instead of silently growing the queue.
SERVING_FIELDS: Dict[str, str] = {
    "event": "string",             # "serving"
    "window_s": "number",          # report window wall-clock length
    "queries": "integer",          # queries answered this window
    "qps": "number",               # queries / window_s
    "batch_fill": "number?",       # mean batch fill ratio in (0, 1]
    "queue_depth": "integer",      # queued rows at snapshot time
    "p50_ms": "number?",           # per-query latency percentiles
    "p95_ms": "number?",
    "p99_ms": "number?",
    "cache_hit_rate": "number?",   # fully-fresh served fraction
    "staleness_age": "integer",    # max served staleness (update batches)
    "shed": "integer",             # rows load-shed this window
    "param_generation": "integer",  # checkpoint gen of served params
    "param_staleness": "integer",  # newer published gens not yet served
}

# one record per serving-fleet lifecycle event (serve/fleet.py +
# serve/router.py): replica death/failover/relaunch/rejoin and
# zero-downtime checkpoint hot-swaps. kind:
#   replica-dead   a replica stopped answering (process exit, stale
#                  heartbeat, or RPC failure); extras: reason
#   failover       in-flight tickets were retried against survivors;
#                  extras: n_retried, to_replica
#   relaunch       the fleet supervisor restarted the replica process;
#                  extras: incarnation, delay_s
#   replica-rejoin the relaunched replica answered health checks and
#                  re-entered routing; extras: incarnation,
#                  rejoin_latency_s
#   hot-swap       the replica swapped to a newer CRC-verified
#                  checkpoint generation without retracing; extras:
#                  param_generation, swap_ms
#   swap-rejected  a corrupt/truncated generation failed verification
#                  and the replica kept (or walked back to) older
#                  params; extras: reason
#   fleet-stop     the supervisor stopped relaunching (max-restarts /
#                  restart-storm brake); extras: reason
#   topo-skew      (v15) the replica reported a topo_generation behind
#                  the fleet maximum — it serves a stale graph and is
#                  routed around until journal replay catches it up;
#                  extras: topo_generation, fleet_generation
#   topo-caught-up (v15) a previously stale replica reported the fleet
#                  generation again and re-entered routing; extras:
#                  topo_generation
FLEET_FIELDS: Dict[str, str] = {
    "event": "string",             # "fleet"
    "kind": "string",              # see above
    "replica": "integer",          # replica id the event concerns
    "window": "integer",           # serving report window index
}

# one record per membership generation of an elastic-supervised run
# (resilience/elastic.py): who owns which partitions and why the
# fleet was (re)launched. assignment is Assignment.as_json() —
# {n_parts, parts_per_node, n_nodes, members, parts: {member:
# [partition ids]}, idle}. trigger: start | rank-death |
# preempt-resume | rejoin | restart-all | supervisor-resume, or the
# stop reasons max-restarts | restart-storm. restart_latency_s is the
# death-detect -> relaunch wall time (null on the initial launch).
# Extras the supervisor adds: n_members.
MEMBERSHIP_FIELDS: Dict[str, str] = {
    "event": "string",             # "membership"
    "generation": "integer",       # monotonic across restarts (ledger)
    "assignment": "object",        # partition -> member mapping
    "trigger": "string",           # what caused this generation
    "restart_latency_s": "number?",
}

# one record per applied graph delta batch (stream/, docs/STREAMING.md)
# — written from the training loop (scheduled --stream-plan entries and
# injected graph-delta faults alike) at the epoch boundary the patch
# landed on. patch_ms is the host-side incremental patch time;
# tables_rebuilt counts per-shard kernel-table rebuilds the delta
# forced (0 on the raw-edge path); slack_remaining maps each padded
# dimension ({"n": rows, "e": edges, "b": send slots}) to the worst-
# shard free-slot count after this patch; repadded=true flags the loud
# slack-exhaustion path (shapes grew, the step recompiled); drift is
# the forced staleness probe's max relative drift across the first
# post-patch step (null when the pipeline is off).
STREAM_FIELDS: Dict[str, str] = {
    "event": "string",             # "stream"
    "epoch": "integer",            # boundary the delta applied at
    "seq": "integer",              # monotonic delta-batch sequence id
    "edges_added": "integer",
    "edges_deleted": "integer",
    "nodes_added": "integer",
    "patch_ms": "number",          # host incremental-patch time
    "tables_rebuilt": "integer",   # per-shard table rebuilds forced
    "repadded": "boolean",         # slack exhausted -> shapes grew
    "slack_remaining": "object",   # {n|e|b: worst-shard free slots}
    "drift": "number?",            # forced probe max_rel_drift
}

# one record per chaos-soak episode (resilience/soak.py +
# scripts/soak.py): the seeded fault schedule the episode composed and
# the per-invariant verdict. schedule is the fault-plan entry list
# (strings, kind@epoch[...] grammar); invariants maps each invariant
# name (checkpoint | ledger | metrics | tickets | resume) to
# {ok: bool, detail: str}; verdict is "green" | "red". Extras:
# episode wall time, restart counts.
SOAK_FIELDS: Dict[str, str] = {
    "event": "string",             # "soak"
    "episode": "integer",          # 0-based episode index
    "seed": "integer",             # the driving soak seed
    "schedule": "array",           # composed fault-plan entries
    "invariants": "object",        # {name: {ok, detail}}
    "verdict": "string",           # green | red
}

# one record per SLO alert EDGE (obs/health.py rule engine, emitted by
# cli.monitor): state flips to "fire" when a rule's predicate first
# holds and to "resolve" when it first stops holding — the engine
# dedupes, so a firing rule writes exactly one record per edge no
# matter how many evaluation ticks it stays red. rule names the
# built-in predicate (epoch-time-regression | shed-rate |
# staleness-age | fault-rate | silent-source); source is the stream
# key the rule evaluated ("*" for run-wide rules); value/threshold are
# the observed number and the rule bound at the edge (null when the
# edge is a resolve with no fresh observation, e.g. a silent source).
ALERT_FIELDS: Dict[str, str] = {
    "event": "string",             # "alert"
    "rule": "string",              # rule id (see above)
    "state": "string",             # fire | resolve
    "severity": "string",          # info | warn | page
    "source": "string",            # stream key evaluated ("*" run-wide)
    "value": "number?",            # observed value at the edge
    "threshold": "number?",        # rule bound at the edge
    "message": "string",           # human-readable one-liner
}

# one record per sampled serving-path span (serve/*, docs/SERVING.md):
# a trace id minted at submit time (--trace-sample-rate) rides the
# ticket through the micro-batcher and — on the fleet path — the RPC
# to the replica and the engine's chunked execution; every hop lands
# one span. op:
#   queue     submit -> batch dispatch (driver)
#   dispatch  batch dispatch -> result complete (driver)
#   shed      submit -> explicit shed (terminal; extras: reason)
#   rpc       router dispatch RPC round-trip (driver; extras: replica)
#   replica   replica-side request handling (replica process)
#   engine    compiled-engine chunk execution (whichever process runs it)
# Exactly one TERMINAL span (dispatch | shed) exists per sampled
# submit — tests/test_monitor.py pins the conservation. t_start is
# unix seconds (cross-process alignable); cli.timeline stitches spans
# sharing a trace_id into Perfetto flow events.
SPAN_FIELDS: Dict[str, str] = {
    "event": "string",             # "span"
    "trace_id": "string",          # minted at submit, shared by all hops
    "span_id": "string",           # unique per span record
    "op": "string",                # queue|dispatch|shed|rpc|replica|engine
    "t_start": "number",           # unix seconds at span start
    "dur_ms": "number",            # span duration, milliseconds
    "status": "string",            # ok | shed | error
}

# v20: what JAX compiled while a span was the innermost open one
# (obs/trace.SpanRecorder), on every set-up span and every training
# `compute` span (validate_record holds a span carrying any of them to
# all): seconds tracing and lowering, programs the persistent cache
# served and the seconds of those loads, backend compiles no cache hit
# served and their seconds, and the names JAX gave the programs
SPAN_COUNTER_FIELDS: Dict[str, str] = {
    "lower_s": "number",
    "cache_hits": "integer",
    "cache_load_s": "number",
    "compiles": "integer",
    "compile_s": "number",
    "programs": "array",
}
# v20: a set-up span's parent (the innermost span open when it opened;
# null at the top). fit/load and fit/measure_comm name the block's
# `compute` span
SPAN_SETUP_FIELDS: Dict[str, str] = {
    "parent": "string?",
}
# v20: a training `compute` span's host side since the previous harvest
# (obs/trace.BlockSpans; `fit/tail` after the last one): garbage-
# collector seconds and collections, and the seconds of each of fit's
# sections ({"fit/boundary": s, ...})
SPAN_BLOCK_FIELDS: Dict[str, str] = {
    "gc_s": "number",
    "gc_collections": "integer",
    "sections": "object",
}

# one record per black-box flight-recorder dump (obs/flight.py): the
# breadcrumb ring a dying (or stalled, or signalled) process left
# behind, written atomically to blackbox-r<k>.json — and mirrored into
# the metrics stream when a sink is attached. reason: watchdog |
# exception | preemption | signal | stall | fault | manual. crumbs is
# the bounded ring (newest last); last_crumb/open_spans annotate what
# the process was doing (phase, epoch, ring distance, peer rank);
# stacks is faulthandler's all-thread capture (null when the dump path
# had no stack capture, e.g. a clean-exception dump). Extras:
# time_unix, pid, n_crumbs_total, annotation.
BLACKBOX_FIELDS: Dict[str, str] = {
    "event": "string",             # "blackbox"
    "rank": "integer",             # process that wrote the dump
    "reason": "string",            # see above
    "crumbs": "array",             # the breadcrumb ring, newest last
    "last_crumb": "object?",       # newest breadcrumb (null: empty ring)
    "open_spans": "array",         # enter'd-but-never-exit'd spans
    "stacks": "string?",           # all-thread stack text (hang paths)
}

# one record per postmortem verdict (obs/postmortem.py rule engine,
# written by pipegcn-debug / the elastic supervisor): the
# confidence-ranked root cause of a run.
# verdict names the failure class (wedged-collective | oom |
# fallback-exhausted | corrupt-artifact | config-error | desync |
# sdc | storage-fault | recompile-storm | divergence | preemption |
# clean-exit | unknown); evidence is the citing strings (file: record)
# the rule matched on; deterministic says whether a supervisor should
# fail fast (True: relaunching reproduces the failure) or keep its
# restart/backoff policy. Extras: run_dir, candidates (the full ranked
# list), timeline, generation/member (supervisor path).
DIAGNOSIS_FIELDS: Dict[str, str] = {
    "event": "string",             # "diagnosis"
    "verdict": "string",           # failure class (see above)
    "confidence": "number",        # rule confidence in [0, 1]
    "evidence": "array",           # citing strings, most telling first
    "remediation": "string",       # operator hint one-liner
    "deterministic": "boolean",    # fail fast vs restart-and-hope
}

# one record per autoscaler DECISION tick that proposed or refused a
# scale action (serve/autoscale.py, executed by cli/fleet.py's
# FleetManager; docs/SERVING.md "Autoscaling & overload"). Hold ticks
# with nothing to say are NOT recorded — only scale-up | scale-down
# (executed proposals) and refuse (a proposal the brakes vetoed:
# cooldown | storm-brake | max-replicas | min-replicas) land, so the
# stream is the audit ledger of every actuation and every veto.
# evidence carries the triggering telemetry snapshot (queue_depth,
# shed_rate, p99_ms, staleness, firing alert rules, sustain/idle tick
# counts) so a postmortem can replay WHY from the record alone.
AUTOSCALE_FIELDS: Dict[str, str] = {
    "event": "string",             # "autoscale"
    "action": "string",            # scale-up | scale-down | refuse
    "reason": "string",            # queue-pressure | shed-rate | p99-slo
    #                              # | alert:<rule> | idle | cooldown |
    #                              # | storm-brake | max-replicas | ...
    "window": "integer",           # serving report window index
    "n_replicas": "integer",       # fleet size when the decision fired
    "target": "integer",           # proposed fleet size (== n_replicas
    #                              # on refuse)
    "evidence": "object",          # triggering telemetry snapshot
}

# one record per integrity-plane detector verdict (resilience/
# integrity.py, driven by fit() at --integrity-check-every cadence):
# check names the detector (scrub = fletcher digest compare of device
# state against its baseline, freivalds = randomized algebraic SpMM
# verification through the production kernel, wire = the halo
# checksum lane riding each ppermute distance block); outcome is
# "ok" | "mismatch"; target attributes the state class the detector
# guards (params | carry | tables | halo — null when the check spans
# classes); cadence echoes the configured check period so a reader
# can judge detection latency from the record alone; overhead_s is
# the measured host+device cost of THIS check (the bench.py
# integrity_delta_s lever aggregates it). Extras: detail (bounded
# human-readable mismatch description), dirty_shards (shard ids the
# scrubber attributed, drives the dirty-shard rebuild).
INTEGRITY_FIELDS: Dict[str, str] = {
    "event": "string",             # "integrity"
    "epoch": "integer",            # boundary the check ran at
    "check": "string",             # scrub | freivalds | wire
    "outcome": "string",           # ok | mismatch
    "target": "string?",           # params | carry | tables | halo
    "cadence": "integer",          # configured --integrity-check-every
    "overhead_s": "number",        # measured cost of this check
}

# one record per dispatched training block per rank (obs/trainspan.py):
# the rank's wall-clock anchor for the block's harvest barrier. Every
# rank's compiled step for epoch E can only complete once the gradient
# all-reduce has, so the anchors for epoch E mark the same physical
# instant on every rank; trainspan.estimate_offsets folds them into
# per-rank clock offsets and the timeline / straggler attribution /
# overlap math all run on the aligned clock. Extras: source (r<k>).
TRACESYNC_FIELDS: Dict[str, str] = {
    "event": "string",             # "tracesync"
    "rank": "integer",             # process that wrote the anchor
    "epoch": "integer",            # first epoch of the dispatched block
    "t_anchor": "number",          # unix seconds at the harvest barrier
    "generation": "integer",       # membership generation of the run
}

# one record per write-ahead delta-journal lifecycle event
# (stream/journal.py, emitted by the trainer's stream boundary, the
# CLI's resume replay, and serving-replica restarts): op is one of
# append (a batch became durable and was applied; extras: lag_seqs =
# journaled seqs a crash right now would replay), watermark (a
# checkpoint generation landed covering seq), replay (a resume
# re-applied n_records journaled batches; extras: rederived = records
# the torn journal lost and the plan re-derived), truncate (WAL
# rollback past the checkpoint watermark: n_records uncommitted
# entries dropped — the topo-rollback postmortem signature), verify
# (the bit-identity oracle ran post-replay; extras: tables_match),
# degraded / recovered (the journal's own degrade-not-lose queue), and
# skew (the router observed a replica behind the fleet's
# topo_generation). source labels the writer (trainer | resume |
# replica-m<K> | router).
JOURNAL_FIELDS: Dict[str, str] = {
    "event": "string",             # "journal"
    "op": "string",                # append | watermark | replay | ...
    "seq": "integer",              # delta seq the op is about (-1 none)
    "topo_generation": "integer",  # topology generation after the op
    "n_records": "integer",        # records the op touched (0 for point
    #                              # ops like watermark)
    "source": "string",            # trainer | resume | replica-m<K> | …
}

_BY_EVENT = {
    "run": RUN_FIELDS,
    "epoch": EPOCH_FIELDS,
    "eval": EVAL_FIELDS,
    "summary": SUMMARY_FIELDS,
    "fault": FAULT_FIELDS,
    "recovery": RECOVERY_FIELDS,
    "profile": PROFILE_FIELDS,
    "anatomy": ANATOMY_FIELDS,
    "staleness": STALENESS_FIELDS,
    "numerics": NUMERICS_FIELDS,
    "fallback": FALLBACK_FIELDS,
    "tuning": TUNING_FIELDS,
    "serving": SERVING_FIELDS,
    "membership": MEMBERSHIP_FIELDS,
    "fleet": FLEET_FIELDS,
    "stream": STREAM_FIELDS,
    "soak": SOAK_FIELDS,
    "alert": ALERT_FIELDS,
    "span": SPAN_FIELDS,
    "tracesync": TRACESYNC_FIELDS,
    "blackbox": BLACKBOX_FIELDS,
    "diagnosis": DIAGNOSIS_FIELDS,
    "autoscale": AUTOSCALE_FIELDS,
    "integrity": INTEGRITY_FIELDS,
    "journal": JOURNAL_FIELDS,
}

_JSON_TYPES = {
    "string": str,
    "integer": int,
    "number": (int, float),
    "object": dict,
    "array": list,
    "boolean": bool,
}


def validate_record(rec: Mapping) -> None:
    """Raise ValueError when `rec` misses a required field of its event
    kind or carries it with the wrong JSON type. Unknown event kinds
    (free-form ``MetricsLogger.event`` records) and extra fields pass —
    the schema constrains only the contracted record kinds."""
    ev = rec.get("event")
    fields = _BY_EVENT.get(ev)
    if fields is None:
        if not isinstance(ev, str) or not ev:
            raise ValueError(f"record without a string 'event': {rec!r}")
        return
    _check_fields(ev, rec, fields)
    if ev == "run":
        if any(k in rec for k in RUN_DROPOUT_FIELDS):
            _check_fields("run", rec, RUN_DROPOUT_FIELDS)
        for d, pad in (rec.get("tables_pad") or {}).items():
            _check_fields(f"run tables_pad.{d}", pad, TABLES_PAD_FIELDS)
            if any(k in pad for k in TABLES_PAD_DENSE_FIELDS):
                _check_fields(f"run tables_pad.{d}", pad,
                              TABLES_PAD_DENSE_FIELDS)
    if ev == "span":
        if any(k in rec for k in SPAN_COUNTER_FIELDS):
            _check_fields("span", rec, SPAN_COUNTER_FIELDS)
        if rec["trace_id"] == "setup":
            _check_fields("setup span", rec, SPAN_SETUP_FIELDS)
        if any(k in rec for k in SPAN_BLOCK_FIELDS):
            _check_fields("compute span", rec, SPAN_BLOCK_FIELDS)
    if ev == "tuning":
        for c in rec["costs"]:
            if not isinstance(c, dict):
                raise ValueError(f"tuning record: cost entry {c!r} is "
                                 f"no object")
            _check_fields("tuning cost", c, TUNING_COST_FIELDS)


def _check_fields(ev: str, rec: Mapping, fields: Mapping) -> None:
    for name, tag in fields.items():
        nullable = tag.endswith("?")
        if nullable:
            tag = tag[:-1]
        if name not in rec:
            raise ValueError(f"{ev} record missing field {name!r}")
        v = rec[name]
        if v is None:
            if nullable:
                continue
            raise ValueError(f"{ev} record field {name!r} is null")
        py = _JSON_TYPES[tag]
        # bool is an int subclass in python; exclude it from the
        # numeric tags so a True never masquerades as a count
        if isinstance(v, bool) and tag in ("integer", "number"):
            raise ValueError(
                f"{ev} record field {name!r}: expected {tag}, got bool")
        if not isinstance(v, py):
            raise ValueError(
                f"{ev} record field {name!r}: expected {tag}, "
                f"got {type(v).__name__}")
