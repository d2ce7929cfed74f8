"""Reporting CLI over metrics JSONL files.

    python -m pipegcn_tpu.cli.report run1.jsonl [run2.jsonl ...] [--json]

Reads files written by the MetricsLogger sink (obs/metrics.py; schema
obs/schema.py) and emits a per-run summary: epoch-time statistics,
loss-curve deltas, gradient-norm tail, halo traffic, memory peak,
comm/compute overlap fraction and (when the run recorded FLOPs on a
known chip) MFU. `--json` emits one JSON object per file instead of
the human block — the form the bench trajectory consumes.

Everything is best-effort per field: a run that never measured comm
cost, or ran on a platform without memory stats, summarizes without
those rows rather than erroring (consumers must tolerate absent
fields, the schema contract)."""

from __future__ import annotations

import argparse
import json
import sys
from typing import Any, Dict, List, Optional, Sequence

from ..obs.hw import peak_flops_for
from ..obs.metrics import read_metrics


def _median(xs: List[float]) -> Optional[float]:
    if not xs:
        return None
    s = sorted(xs)
    n = len(s)
    mid = n // 2
    return s[mid] if n % 2 else 0.5 * (s[mid - 1] + s[mid])


def summarize_run(records: Sequence[Dict[str, Any]]) -> Dict[str, Any]:
    """Collapse one run's records into the summary dict the CLI
    prints. Tolerates missing header/summary (partial files from
    crashed runs still summarize their epochs)."""
    header = next((r for r in records if r.get("event") == "run"), None)
    summary = next((r for r in records if r.get("event") == "summary"),
                   None)
    epochs = [r for r in records if r.get("event") == "epoch"]
    evals = [r for r in records if r.get("event") == "eval"]

    out: Dict[str, Any] = {"n_epoch_records": len(epochs),
                           "n_eval_records": len(evals)}
    if header:
        out["schema_version"] = header.get("schema_version")
        dev = header.get("device") or {}
        out["device"] = dev.get("device_kind") or dev.get("platform")
        out["n_devices"] = dev.get("n_devices")
        cfg = header.get("config") or {}
        # CLI headers carry args flat; trainer fallback headers nest
        # the TrainConfig under "train"
        out["pipeline"] = bool(
            cfg.get("enable_pipeline",
                    (cfg.get("train") or {}).get("enable_pipeline",
                                                 False)))

    bench = next((r for r in records if r.get("event") == "bench"), None)
    if bench:
        # bench.py --metrics-out: surface the headline measurement
        out["bench_metric"] = bench.get("metric")
        out["bench_value"] = bench.get("value")
        out["bench_unit"] = bench.get("unit")
        out["vs_baseline"] = bench.get("vs_baseline")
        if "pipeline" in bench:
            out["pipeline"] = bool(bench["pipeline"])
        # --reorder layout lever: which layout produced the number
        if bench.get("reorder") is not None:
            out["reorder"] = bench["reorder"]

    steps = [r["step_time_s"] for r in epochs
             if isinstance(r.get("step_time_s"), (int, float))]
    if steps:
        out["median_epoch_s"] = round(_median(steps), 6)
        out["mean_epoch_s"] = round(sum(steps) / len(steps), 6)
        out["total_step_s"] = round(sum(steps), 6)
    losses = [r["loss"] for r in epochs
              if isinstance(r.get("loss"), (int, float))]
    if losses:
        out["loss_first"] = round(losses[0], 6)
        out["loss_last"] = round(losses[-1], 6)
        out["loss_delta"] = round(losses[-1] - losses[0], 6)
    gnorms = [r["grad_norm"] for r in epochs
              if isinstance(r.get("grad_norm"), (int, float))]
    if gnorms:
        out["grad_norm_last"] = round(gnorms[-1], 6)
    halo = [r["halo_bytes"] for r in epochs
            if isinstance(r.get("halo_bytes"), int)]
    if halo:
        out["halo_bytes_per_epoch"] = max(halo)
    # --halo-dtype compression: epochs carry the uncompressed figure
    # alongside, so the report can show wire bytes before/after
    halo_unc = [r["halo_bytes_uncompressed"] for r in epochs
                if isinstance(r.get("halo_bytes_uncompressed"), int)]
    if halo_unc:
        out["halo_bytes_uncompressed_per_epoch"] = max(halo_unc)
        if halo and max(halo):
            out["halo_compression_ratio"] = round(
                max(halo_unc) / max(halo), 4)
    ages = [r["staleness_age"] for r in epochs
            if isinstance(r.get("staleness_age"), int)]
    if ages:
        out["staleness_age_max"] = max(ages)
    peaks = [(r.get("memory") or {}).get("peak_bytes_in_use")
             for r in epochs]
    peaks = [p for p in peaks if isinstance(p, int)]
    if peaks:
        out["memory_peak_bytes"] = max(peaks)

    faults = [r for r in records if r.get("event") == "fault"]
    recoveries = [r for r in records if r.get("event") == "recovery"]
    if faults:
        kinds: Dict[str, int] = {}
        for r in faults:
            k = str(r.get("kind"))
            kinds[k] = kinds.get(k, 0) + 1
        out["n_faults"] = len(faults)
        out["fault_kinds"] = kinds
        out["n_recoveries"] = len(recoveries)
        # multi-host attribution: which rank observed each fault, and
        # which raised the consensus-propagated ones (several ranks'
        # JSONL streams may be concatenated into one file)
        ranks: Dict[str, int] = {}
        sources: Dict[str, int] = {}
        agreed = 0
        for r in faults:
            if isinstance(r.get("rank"), int):
                ranks[f"r{r['rank']}"] = ranks.get(f"r{r['rank']}", 0) + 1
            if r.get("agreed"):
                agreed += 1
            src = r.get("source_rank")
            if isinstance(src, int) and src >= 0:
                sources[f"r{src}"] = sources.get(f"r{src}", 0) + 1
        if ranks:
            out["fault_ranks"] = ranks
        if sources:
            out["fault_source_ranks"] = sources
        if agreed:
            out["n_agreed_faults"] = agreed

    accs = [r["val_acc"] for r in evals
            if isinstance(r.get("val_acc"), (int, float))]
    if accs:
        out["best_val"] = round(max(accs), 6)
        out["final_val"] = round(accs[-1], 6)
    ets = [r["eval_time_s"] for r in evals
           if isinstance(r.get("eval_time_s"), (int, float))]
    if ets:
        out["mean_eval_s"] = round(sum(ets) / len(ets), 6)

    if summary:
        for k in ("best_val", "best_epoch", "test_acc", "n_epochs"):
            if summary.get(k) is not None:
                out[k] = summary[k]
        if summary.get("epoch_time_s") is not None:
            # fit()'s warmup-excluded mean beats the raw record median
            out["epoch_time_s"] = summary["epoch_time_s"]
        cc = summary.get("comm_cost") or {}
        comm_total = sum(v for v in cc.values()
                         if isinstance(v, (int, float)))
        base = out.get("epoch_time_s") or out.get("median_epoch_s")
        if cc and base:
            out["comm_cost_s"] = round(comm_total, 6)
            # standalone collective cost as a fraction of the epoch: in
            # pipelined mode this is the comm the staleness-1 carry
            # lets XLA overlap with compute (the exposed wait is ~0,
            # results/overlap_study.md); in vanilla mode it is an
            # upper bound on the exposed fraction
            out["comm_fraction"] = round(min(comm_total / base, 1.0), 4)
            if out.get("pipeline"):
                out["overlapped_comm_fraction"] = out["comm_fraction"]
        fl = summary.get("flops_per_epoch")
        base = out.get("epoch_time_s") or out.get("median_epoch_s")
        platform = ((header or {}).get("device") or {}).get("platform")
        nd = out.get("n_devices") or 1
        if isinstance(fl, (int, float)) and fl and base \
                and platform == "tpu":
            # a utilization exists only for a chip run; a TPU kind that
            # is not in the peaks table raises rather than guessing
            peak = peak_flops_for(str(out.get("device")))
            out["mfu_pct"] = round(100.0 * fl / (base * peak * nd), 2)

    # ---- measured profiling window (obs/profiler.py) ----
    profiles = [r for r in records if r.get("event") == "profile"]
    if profiles:
        p = profiles[-1]  # the freshest capture wins
        if isinstance(p.get("overlap_fraction"), (int, float)):
            out["measured_overlap_fraction"] = round(
                p["overlap_fraction"], 4)
        if isinstance(p.get("phases"), dict):
            out["profile_phases"] = p["phases"]
        if isinstance(p.get("paths"), dict):
            out["profile_paths"] = p["paths"]
        for k in ("window_s", "busy_s", "unscoped_s"):
            if isinstance(p.get(k), (int, float)):
                out[f"profile_{k}"] = p[k]
        if isinstance(p.get("programs"), list):
            out["profile_scan_lengths"] = [
                q.get("scan_length") for q in p["programs"]
                if isinstance(q, dict) and q.get("n_events")]
        if isinstance(p.get("idle_gaps"), list):
            out["profile_idle_gaps"] = p["idle_gaps"]
        for k in ("comm_s", "compute_s"):
            if isinstance(p.get(k), (int, float)):
                out[f"profile_{k}"] = p[k]
        win = (p.get("epoch_start"), p.get("epoch_end"))
        if all(isinstance(x, int) for x in win):
            out["profile_window"] = list(win)
        # the host-side estimate and the measured fraction describe
        # the same quantity; flag when they disagree materially so
        # the estimate is never trusted past its error
        est = out.get("overlapped_comm_fraction",
                      out.get("comm_fraction"))
        meas = out.get("measured_overlap_fraction")
        if isinstance(est, (int, float)) and isinstance(meas,
                                                        (int, float)):
            out["overlap_divergence"] = bool(abs(meas - est) > 0.25)

    # ---- training-path spans (obs/trainspan.py, schema v14) ----
    # the always-on span plane yields a MEASURED overlap verdict with
    # no profiler capture window, plus per-rank comm-wait share and
    # straggler attribution on the tracesync-aligned clock
    from ..obs.trainspan import fold_spans, train_spans

    if train_spans(records):
        fold = fold_spans(records)
        if fold.get("overlap_spans") is not None:
            out["overlap_spans"] = round(fold["overlap_spans"], 4)
        if fold.get("comm_wait_share_by_rank"):
            out["comm_wait_share_by_rank"] = {
                f"r{r}": round(v, 4)
                for r, v in fold["comm_wait_share_by_rank"].items()}
        if fold.get("straggler_max_gap_s") is not None:
            out["straggler_max_gap_s"] = fold["straggler_max_gap_s"]
            out["straggler_rank"] = fold["straggler_rank"]
        if fold.get("offsets"):
            out["trace_clock_offsets"] = {
                f"r{r}": v for r, v in fold["offsets"].items()}
        # span-derived divergence fallback: the same 0.25 threshold as
        # the profiler window, applied whenever no window ran — runs
        # without a capture still get the trust check
        est = out.get("overlapped_comm_fraction",
                      out.get("comm_fraction"))
        if (isinstance(est, (int, float))
                and out.get("overlap_spans") is not None
                and "overlap_divergence" not in out):
            out["overlap_divergence"] = bool(
                abs(out["overlap_spans"] - est) > 0.25)

    # ---- staleness probes (--staleness-probe-every) ----
    stale = [r for r in records if r.get("event") == "staleness"]
    drifts = [r["max_rel_drift"] for r in stale
              if isinstance(r.get("max_rel_drift"), (int, float))]
    if drifts:
        out["staleness_probes"] = len(drifts)
        out["staleness_max_rel_drift"] = round(max(drifts), 6)
        out["staleness_last_rel_drift"] = round(drifts[-1], 6)

    # ---- numerics health (resilience/numerics.py): first-NaN phase,
    # loss-scale backoff/skip counts, kernel fallbacks taken ----
    from ..resilience.numerics import summarize_numerics

    out.update(summarize_numerics(records))

    # ---- compiled-step anatomy (obs/anatomy.py) ----
    anatomies = [r for r in records if r.get("event") == "anatomy"]
    if anatomies:
        a = anatomies[-1]
        if isinstance(a.get("attributed_flops_fraction"), (int, float)):
            out["anatomy_attributed_flops_fraction"] = round(
                a["attributed_flops_fraction"], 4)
        ph = a.get("phases")
        ef = a.get("est_flops")
        if isinstance(ph, dict) and isinstance(ef, (int, float)) and ef:
            out["anatomy_flop_shares"] = {
                k: round(v.get("flops", 0.0) / ef, 4)
                for k, v in ph.items() if isinstance(v, dict)}
            # the non-SpMM floor: everything the epoch spends that is
            # NOT the aggregation kernel (ROADMAP item 1's target; the
            # four --rng-impl/--halo-dtype/--epoch-block/--comm-prefetch
            # levers attack exactly this share)
            spmm = sum(v for k, v in out["anatomy_flop_shares"].items()
                       if "spmm" in k)
            out["anatomy_non_spmm_share"] = round(
                max(0.0, 1.0 - spmm), 4)

    # ---- online serving windows (serve/, schema v5) ----
    serving = [r for r in records if r.get("event") == "serving"]
    if serving:
        out["n_serving_records"] = len(serving)
        qs = [r.get("queries") for r in serving]
        qs = [q for q in qs if isinstance(q, int)]
        total_q = sum(qs)
        out["serving_queries"] = total_q
        wins = [r.get("window_s") for r in serving]
        total_w = sum(w for w in wins if isinstance(w, (int, float)))
        if total_w > 0:
            out["serving_qps"] = round(total_q / total_w, 2)
        # query-weighted percentile means: an empty window (null
        # percentiles) must not drag the latency picture
        for key in ("p50_ms", "p95_ms", "p99_ms", "batch_fill",
                    "cache_hit_rate"):
            num = den = 0.0
            for r in serving:
                v, q = r.get(key), r.get("queries")
                if isinstance(v, (int, float)) and isinstance(q, int) \
                        and q > 0:
                    num += v * q
                    den += q
            if den:
                out[f"serving_{key}"] = round(num / den, 4)
        ages = [r.get("staleness_age") for r in serving]
        ages = [a for a in ages if isinstance(a, int)]
        if ages:
            out["serving_staleness_age_max"] = max(ages)
        depths = [r.get("queue_depth") for r in serving]
        depths = [d for d in depths if isinstance(d, int)]
        if depths:
            out["serving_queue_depth_max"] = max(depths)
        sheds = [r.get("shed") for r in serving]
        sheds = [x for x in sheds if isinstance(x, int)]
        if sheds:
            out["serving_shed_total"] = sum(sheds)
        gens = [r.get("param_generation") for r in serving]
        gens = [g for g in gens if isinstance(g, int) and g >= 0]
        if gens:
            out["serving_param_generation_last"] = gens[-1]
        stale = [r.get("param_staleness") for r in serving]
        stale = [x for x in stale if isinstance(x, int)]
        if stale:
            out["serving_param_staleness_max"] = max(stale)
        out["serving_drained"] = any(r.get("final") for r in serving)

    # ---- serving fleet (serve/fleet.py, schema v7) ----
    fleet = [r for r in records if r.get("event") == "fleet"]
    if fleet:
        out["n_fleet_records"] = len(fleet)
        by_kind: Dict[str, int] = {}
        for r in fleet:
            k = r.get("kind")
            if isinstance(k, str):
                by_kind[k] = by_kind.get(k, 0) + 1
        out["fleet_events"] = by_kind
        swaps = [r.get("swap_ms") for r in fleet
                 if r.get("kind") == "hot-swap"]
        swaps = [x for x in swaps if isinstance(x, (int, float))]
        if swaps:
            out["fleet_param_swap_ms_max"] = round(max(swaps), 2)
        gens = [r.get("param_generation") for r in fleet
                if r.get("kind") == "hot-swap"]
        gens = [g for g in gens if isinstance(g, int)]
        if gens:
            out["fleet_param_generation_last"] = max(gens)

    # ---- elastic membership timeline (resilience/elastic.py, v6) ----
    membership = [r for r in records if r.get("event") == "membership"]
    if membership:
        membership = sorted(
            membership, key=lambda r: r.get("generation", -1)
            if isinstance(r.get("generation"), int) else -1)
        out["n_membership_records"] = len(membership)
        gens = [r["generation"] for r in membership
                if isinstance(r.get("generation"), int)]
        if gens:
            out["membership_last_generation"] = max(gens)
        timeline = []
        for r in membership:
            a = r.get("assignment") or {}
            timeline.append({
                "generation": r.get("generation"),
                "trigger": r.get("trigger"),
                "n_members": (len(a.get("members", []))
                              if isinstance(a.get("members"), list)
                              else r.get("n_members")),
                "parts_per_node": a.get("parts_per_node"),
                "restart_latency_s": r.get("restart_latency_s"),
            })
        out["membership_timeline"] = timeline
        lats = [r.get("restart_latency_s") for r in membership]
        lats = [x for x in lats if isinstance(x, (int, float))]
        if lats:
            out["restart_latency_max_s"] = round(max(lats), 3)
        stops = [r.get("trigger") for r in membership
                 if r.get("trigger") in ("max-restarts", "restart-storm")]
        if stops:
            out["membership_stopped"] = stops[-1]

    # ---- forensics (obs/flight.py + obs/postmortem.py, v11) ----
    boxes = [r for r in records if r.get("event") == "blackbox"]
    if boxes:
        out["n_blackbox_records"] = len(boxes)
        reasons: Dict[str, int] = {}
        for r in boxes:
            k = str(r.get("reason"))
            reasons[k] = reasons.get(k, 0) + 1
        out["blackbox_reasons"] = reasons
    diags = [r for r in records if r.get("event") == "diagnosis"]
    if diags:
        d = diags[-1]  # the latest postmortem verdict wins
        out["diagnosis_verdict"] = d.get("verdict")
        if isinstance(d.get("confidence"), (int, float)):
            out["diagnosis_confidence"] = round(d["confidence"], 3)
        out["diagnosis_deterministic"] = bool(d.get("deterministic"))
        if isinstance(d.get("remediation"), str):
            out["diagnosis_remediation"] = d["remediation"]

    # ---- streaming graph deltas (stream/, schema v8) ----
    stream = [r for r in records if r.get("event") == "stream"]
    if stream:
        out["n_stream_records"] = len(stream)
        for key in ("edges_added", "edges_deleted", "nodes_added"):
            vals = [r.get(key) for r in stream]
            vals = [v for v in vals if isinstance(v, int)]
            if vals:
                out[f"stream_{key}"] = sum(vals)
        pms = [r.get("patch_ms") for r in stream]
        pms = [v for v in pms if isinstance(v, (int, float))]
        if pms:
            out["stream_patch_ms_median"] = round(_median(pms), 3)
            out["stream_patch_ms_max"] = round(max(pms), 3)
        drifts = [r.get("drift") for r in stream]
        drifts = [v for v in drifts if isinstance(v, (int, float))]
        if drifts:
            out["stream_drift_max"] = round(max(drifts), 6)
            out["stream_drift_last"] = round(drifts[-1], 6)
        reb = [r.get("tables_rebuilt") for r in stream]
        reb = [v for v in reb if isinstance(v, int)]
        if reb:
            out["stream_tables_rebuilt"] = sum(reb)
        out["stream_repads"] = sum(1 for r in stream if r.get("repadded"))
        slack = [r.get("slack_remaining") for r in stream
                 if isinstance(r.get("slack_remaining"), dict)]
        if slack:
            out["stream_slack_remaining_last"] = slack[-1]
    return out


def format_summary(path: str, s: Dict[str, Any]) -> str:
    lines = [f"== {path} =="]

    def row(label, key, fmt="{}", scale=1.0):
        v = s.get(key)
        if v is None:
            return
        if isinstance(v, (int, float)) and scale != 1.0:
            v = v * scale
        lines.append(f"  {label:<26} {fmt.format(v)}")

    row("schema version", "schema_version")
    row("device", "device")
    row("devices", "n_devices")
    row("pipeline", "pipeline")
    if s.get("bench_value") is not None:
        lines.append("  {:<26} {} {} ({})".format(
            "bench headline", s["bench_value"], s.get("bench_unit", ""),
            s.get("bench_metric", "")))
        row("vs baseline", "vs_baseline", "{:.3f}x")
    row("epochs recorded", "n_epoch_records")
    row("epoch time (fit mean)", "epoch_time_s", "{:.4f} s")
    row("median epoch", "median_epoch_s", "{:.4f} s")
    row("loss first -> last", "loss_first", "{:.4f}")
    row("loss last", "loss_last", "{:.4f}")
    row("loss delta", "loss_delta", "{:+.4f}")
    row("grad norm (last)", "grad_norm_last", "{:.4e}")
    row("halo bytes / epoch", "halo_bytes_per_epoch", "{:,}")
    if s.get("halo_bytes_uncompressed_per_epoch") is not None:
        lines.append("  {:<26} {:,} -> {:,} ({}x)".format(
            "halo wire compression",
            s["halo_bytes_uncompressed_per_epoch"],
            s.get("halo_bytes_per_epoch", 0),
            s.get("halo_compression_ratio", "?")))
    row("staleness age (max)", "staleness_age_max")
    row("memory peak", "memory_peak_bytes", "{:,} bytes")
    row("comm cost (standalone)", "comm_cost_s", "{:.4f} s")
    row("comm fraction of epoch", "comm_fraction", "{:.2%}")
    # estimated and measured side by side: the estimate is the
    # host-derived comm_cost/epoch ratio, the measurement a folded
    # device trace (obs/profiler.py) — divergence means the estimate
    # can no longer be trusted at this config
    row("overlap (estimated)", "overlapped_comm_fraction", "{:.2%}")
    row("overlap (measured)", "measured_overlap_fraction", "{:.2%}")
    # always-on span verdict (obs/trainspan.py) — present even when no
    # profiler window ran, so every traced run gets a measured number
    row("overlap (spans)", "overlap_spans", "{:.2%}")
    if s.get("comm_wait_share_by_rank"):
        lines.append("  {:<26} {}".format(
            "comm wait share (spans)", ", ".join(
                f"{k}={v:.1%}" for k, v in
                sorted(s["comm_wait_share_by_rank"].items()))))
    if s.get("straggler_max_gap_s") is not None:
        lines.append("  {:<26} r{} (+{:.0f} ms behind median start)"
                     .format("straggler (spans)",
                             s.get("straggler_rank", "?"),
                             s["straggler_max_gap_s"] * 1e3))
    if s.get("overlap_divergence"):
        lines.append(f"  {'!! overlap divergence':<26} measured and "
                     f"estimated overlap differ by > 0.25")
    if s.get("profile_phases"):
        top = sorted(s["profile_phases"].items(),
                     key=lambda kv: -kv[1])[:4]
        lines.append("  {:<26} {}".format(
            "profiled device time", ", ".join(
                f"{k} {v:.4f}s" for k, v in top)))
    if s.get("profile_paths"):
        # self seconds by scope path, to the depth the kernels name
        if "profile_busy_s" in s and "profile_window_s" in s:
            lines.append("  {:<26} {:.4f}s of {:.4f}s; scans of {}"
                         .format("device busy (profiled)",
                                 s["profile_busy_s"],
                                 s["profile_window_s"],
                                 s.get("profile_scan_lengths", [])))
        for k, v in sorted(s["profile_paths"].items(),
                           key=lambda kv: -kv[1])[:16]:
            lines.append("    {:<24} {:.4f}s".format(k, v))
        if s.get("profile_unscoped_s"):
            lines.append("    {:<24} {:.4f}s".format(
                "(no scope)", s["profile_unscoped_s"]))
        for g in (s.get("profile_idle_gaps") or [])[:3]:
            lines.append("  {:<26} {:.4f}s in {} gap(s): {}{}".format(
                "device idle (profiled)", g.get("s", 0.0), g.get("n", 0),
                g.get("span") or "no span of the program",
                f" > {g['inner']}" if g.get("inner") else ""))
    if s.get("staleness_probes"):
        lines.append("  {:<26} {} probes, max {:.4f}, last {:.4f}"
                     .format("staleness rel drift",
                             s["staleness_probes"],
                             s.get("staleness_max_rel_drift", 0.0),
                             s.get("staleness_last_rel_drift", 0.0)))
    if s.get("anatomy_flop_shares"):
        top = sorted(s["anatomy_flop_shares"].items(),
                     key=lambda kv: -kv[1])[:4]
        lines.append("  {:<26} {}".format(
            "anatomy flop shares", ", ".join(
                f"{k} {v:.1%}" for k, v in top)))
        row("non-SpMM floor share", "anatomy_non_spmm_share", "{:.1%}")
        row("anatomy attributed", "anatomy_attributed_flops_fraction",
            "{:.1%}")
    row("reorder", "reorder")
    row("MFU", "mfu_pct", "{:.2f} %")
    if s.get("n_faults"):
        kinds = ", ".join(f"{k}x{n}" for k, n in
                          sorted(s.get("fault_kinds", {}).items()))
        lines.append(f"  {'faults / recoveries':<26} "
                     f"{s['n_faults']} / {s.get('n_recoveries', 0)}"
                     f" ({kinds})")
        if s.get("fault_ranks"):
            by_rank = ", ".join(f"{k}x{n}" for k, n in
                                sorted(s["fault_ranks"].items()))
            lines.append(f"  {'faults by rank':<26} {by_rank}")
        if s.get("fault_source_ranks"):
            by_src = ", ".join(f"{k}x{n}" for k, n in
                               sorted(s["fault_source_ranks"].items()))
            lines.append(f"  {'consensus source ranks':<26} {by_src} "
                         f"({s.get('n_agreed_faults', 0)} agreed)")
    # ---- numerics health ----
    if s.get("first_nan_phase"):
        lines.append("  {:<26} {} (epoch {})".format(
            "!! first NaN phase", s["first_nan_phase"],
            s.get("first_nan_epoch", "?")))
    if s.get("loss_scale_skips") is not None:
        lines.append("  {:<26} {} skipped, {} backoffs, {} regrowths, "
                     "scale {}".format(
                         "loss-scale events", s["loss_scale_skips"],
                         s.get("loss_scale_backoffs", 0),
                         s.get("loss_scale_growths", 0),
                         s.get("loss_scale_last", "?")))
    elif s.get("loss_scale_growths"):
        lines.append("  {:<26} {} regrowths, scale {}".format(
            "loss-scale events", s["loss_scale_growths"],
            s.get("loss_scale_last", "?")))
    if s.get("kernel_fallbacks"):
        lines.append("  {:<26} {}".format(
            "kernel fallbacks", ", ".join(s["kernel_fallbacks"])))
    # ---- online serving (docs/SERVING.md) ----
    if s.get("n_serving_records"):
        lines.append("  {:<26} {} windows, {} queries".format(
            "serving", s["n_serving_records"],
            s.get("serving_queries", 0)))
        row("serving QPS", "serving_qps", "{:.2f} q/s")
        if s.get("serving_p50_ms") is not None:
            lines.append("  {:<26} p50 {:.2f} / p95 {:.2f} / p99 {:.2f} "
                         "ms".format("serving latency",
                                     s["serving_p50_ms"],
                                     s.get("serving_p95_ms", 0.0),
                                     s.get("serving_p99_ms", 0.0)))
        row("serving batch fill", "serving_batch_fill", "{:.1%}")
        row("serving cache hit rate", "serving_cache_hit_rate", "{:.1%}")
        row("serving staleness (max)", "serving_staleness_age_max")
        row("serving queue depth max", "serving_queue_depth_max")
        row("serving shed (total)", "serving_shed_total")
        row("serving param generation", "serving_param_generation_last")
        row("serving param staleness", "serving_param_staleness_max")
        if not s.get("serving_drained"):
            lines.append(f"  {'!! serving shutdown':<26} no final "
                         f"record — the run died without draining")
    # ---- serving fleet (docs/SERVING.md, "Fleet") ----
    if s.get("n_fleet_records"):
        ev = s.get("fleet_events") or {}
        lines.append("  {:<26} {} events ({})".format(
            "fleet", s["n_fleet_records"],
            ", ".join(f"{k}={v}" for k, v in sorted(ev.items()))
            or "none"))
        row("fleet param swap (max)", "fleet_param_swap_ms_max",
            "{:.2f} ms")
        row("fleet param generation", "fleet_param_generation_last")
        if ev.get("replica-dead", 0) > ev.get("replica-rejoin", 0):
            lines.append(f"  {'!! fleet degraded':<26} "
                         f"{ev.get('replica-dead', 0)} death(s) vs "
                         f"{ev.get('replica-rejoin', 0)} rejoin(s) — "
                         f"ended below full strength")
    # ---- elastic membership (docs/RESILIENCE.md) ----
    if s.get("n_membership_records"):
        lines.append("  {:<26} {} generations (last gen {})".format(
            "membership", s["n_membership_records"],
            s.get("membership_last_generation", "?")))
        for t in s.get("membership_timeline", []):
            lat = t.get("restart_latency_s")
            lat_s = f", relaunched in {lat:.1f}s" \
                if isinstance(lat, (int, float)) else ""
            lines.append(
                "  {:<26} gen {}: {} member(s) x {} part(s) "
                "[{}]{}".format("", t.get("generation"),
                                t.get("n_members", "?"),
                                t.get("parts_per_node", "?"),
                                t.get("trigger", "?"), lat_s))
        row("restart latency (max)", "restart_latency_max_s", "{:.2f} s")
        if s.get("membership_stopped"):
            lines.append(f"  {'!! supervisor stopped':<26} "
                         f"{s['membership_stopped']} — resume from the "
                         f"last checkpoint manually")
    # ---- streaming graph deltas (docs/STREAMING.md) ----
    if s.get("n_stream_records"):
        lines.append("  {:<26} {} delta(s): +{}/-{} edges, +{} nodes"
                     .format("stream deltas", s["n_stream_records"],
                             s.get("stream_edges_added", 0),
                             s.get("stream_edges_deleted", 0),
                             s.get("stream_nodes_added", 0)))
        if s.get("stream_patch_ms_median") is not None:
            lines.append("  {:<26} median {:.1f} / max {:.1f} ms"
                         .format("stream patch cost",
                                 s["stream_patch_ms_median"],
                                 s.get("stream_patch_ms_max", 0.0)))
        if s.get("stream_drift_max") is not None:
            lines.append("  {:<26} max {:.4f}, last {:.4f}".format(
                "stream probe drift", s["stream_drift_max"],
                s.get("stream_drift_last", 0.0)))
        row("stream tables rebuilt", "stream_tables_rebuilt")
        sl = s.get("stream_slack_remaining_last")
        if isinstance(sl, dict):
            lines.append("  {:<26} {}".format(
                "stream slack left", ", ".join(
                    f"{k}={v}" for k, v in sorted(sl.items()))))
        if s.get("stream_repads"):
            lines.append(f"  {'!! stream re-pads':<26} "
                         f"{s['stream_repads']} slack exhaustion(s) — "
                         f"recompiled; raise --stream-slack")
    # ---- forensics (docs/OBSERVABILITY.md "Postmortem") ----
    if s.get("n_blackbox_records"):
        reasons = ", ".join(f"{k}x{n}" for k, n in
                            sorted(s.get("blackbox_reasons",
                                         {}).items()))
        lines.append(f"  {'black-box dumps':<26} "
                     f"{s['n_blackbox_records']} ({reasons})")
    if s.get("diagnosis_verdict"):
        det = (" [deterministic — do not blind-restart]"
               if s.get("diagnosis_deterministic") else "")
        lines.append("  {:<26} {} (confidence {}){}".format(
            "!! postmortem verdict", s["diagnosis_verdict"],
            s.get("diagnosis_confidence", "?"), det))
        if s.get("diagnosis_remediation"):
            lines.append(f"  {'':<26} {s['diagnosis_remediation']}")
    row("best val", "best_val", "{:.4f}")
    row("best epoch", "best_epoch")
    row("test acc", "test_acc", "{:.4f}")
    row("mean eval wait", "mean_eval_s", "{:.4f} s")
    return "\n".join(lines)


def _load_target(path: str):
    """One CLI target's records: a plain JSONL file keeps the strict
    single-file contract (read_metrics raises on malformed lines); a
    run directory, a metrics stem, or a base file WITH per-generation
    siblings goes through the aggregator's tolerant deduped
    generation-ordered merge (obs/live.py). Returns (records,
    n_streams)."""
    import os

    from ..obs.live import discover_streams, merge_streams

    streams = discover_streams(path)
    if streams == [path] and os.path.isfile(path):
        return read_metrics(path), 1
    if not streams:
        raise OSError(f"no metrics streams found under {path!r}")
    return merge_streams(streams), len(streams)


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m pipegcn_tpu.cli.report",
        description="Summarize metrics JSONL files written with "
                    "--metrics-out (schema: pipegcn_tpu/obs/schema.py)")
    ap.add_argument("files", nargs="+",
                    help="metrics JSONL file(s), run directories, or "
                         "metrics stems: a directory or stem expands "
                         "to every stream under it ({stem}.g*.m*.jsonl "
                         "per-generation files, the supervisor ledger, "
                         "replica streams) merged generation-ordered "
                         "and deduped — the live monitor's discovery "
                         "(obs/live.py), applied post-hoc")
    ap.add_argument("--json", action="store_true",
                    help="emit one JSON summary object per file")
    args = ap.parse_args(argv)

    rc = 0
    for path in args.files:
        try:
            recs, n_streams = _load_target(path)
            s = summarize_run(recs)
            if n_streams > 1:
                s["n_streams_merged"] = n_streams
        except (OSError, ValueError) as exc:
            print(f"{path}: {exc}", file=sys.stderr)
            rc = 1
            continue
        if args.json:
            print(json.dumps({"file": path, **s}))
        else:
            print(format_summary(path, s))
    return rc


if __name__ == "__main__":
    sys.exit(main())
