"""Benchmark: per-epoch training time at Reddit scale.

Reproduces the reference's headline measurement — per-epoch wall-clock of
a 4-layer x 256 GraphSAGE with --enable-pipeline --use-pp on Reddit
(232,965 nodes / ~114.6M directed edges / 602 features / 41 classes;
reference README.md:93-94 reports 0.266 s/epoch on 2 GPUs) — on TPU,
using a synthetic graph with Reddit's shape statistics (the real dataset
needs a download this environment does not allow).

Prints ONE JSON line:
  {"metric": ..., "value": ..., "unit": ..., "vs_baseline": ..., ...}
vs_baseline > 1 means faster than the reference's 0.266 s/epoch. Extra
keys: backend/device, MFU, estimated HBM + ICI traffic, and the
pipelined-vs-vanilla epoch-time comparison.

This is a chip measurement and nothing else: without a TPU it exits
non-zero and prints no metric line. There is no probe, no retry, no
re-exec at a smaller size and no CPU fallback; a kernel downgrade
(Trainer.fallbacks) or a missing native library fails the run, because
either changes which program is timed. `--cpu` is an explicit dry run
of the harness: its metric is named cpu_dryrun_* and carries no
vs_baseline and no MFU. One process holds the chip, so
`--serve --replicas` (replica processes that each need it) is refused
on a TPU.

The partition/build artifact is cached under partitions/ so repeat runs
skip the ~minutes of host-side preprocessing. Use --small for a quick
smoke-scale run, --parts N to shard over N devices.
"""

import argparse
import dataclasses
import json
import os
import sys
import time

import numpy as np

from pipegcn_tpu.obs.hw import peak_flops_for

BASELINE_EPOCH_S = 0.266  # reference README.md:93-94 (2x GPU)

# repo root: artifacts and result records anchor here, never the CWD
REPO = os.path.dirname(os.path.abspath(__file__))


class NonFiniteLoss(RuntimeError):
    """A training loss went non-finite mid-measurement: the run is
    diverged, and timing a diverged program measures the wrong program
    — abort IMMEDIATELY (the offshape-products NaN burned three full
    measurement blocks after the first NaN epoch, VERDICT r5) with a
    loud fault record and exit 3 instead of publishing green JSON."""

    def __init__(self, epoch: int, loss: float):
        super().__init__(
            f"non-finite loss {loss!r} at epoch {epoch}")
        self.epoch = epoch
        self.loss = loss


def _check_finite(loss: float, epoch: int) -> None:
    if not np.isfinite(loss):
        print(f"# NON-FINITE LOSS at epoch {epoch} — aborting the "
              f"measurement now (every further block would time a "
              f"diverged program)", file=sys.stderr)
        raise NonFiniteLoss(epoch, float(loss))


class KernelDowngraded(RuntimeError):
    """The kernel fallback ladder fired (Trainer.fallbacks is not
    empty): the step now runs a different aggregation kernel than the
    one the result would be labelled with, so there is no result."""


def _label_dry_run(result: dict, backend: str) -> None:
    """A --cpu run proves the harness and measures nothing: its number
    never goes under a device metric's name or next to the reference."""
    if backend != "tpu":
        result["metric"] = "cpu_dryrun_" + result["metric"]
        result.pop("vs_baseline", None)


def _check_no_downgrade(trainer) -> None:
    if trainer.fallbacks:
        raise KernelDowngraded("; ".join(
            f"{f['from_impl']} -> {f['to_impl']}: {f['reason']}"
            for f in trainer.fallbacks))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--small", action="store_true",
                    help="10k-node smoke config instead of Reddit scale")
    ap.add_argument("--parts", type=int, default=0,
                    help="partitions (default: all available devices)")
    ap.add_argument("--blocks", type=int, default=8,
                    help="timed samples; each sample is one dispatch of "
                         "--fused epochs (sample count is independent of "
                         "--fused so the median is equally stable)")
    ap.add_argument("--no-pipeline", action="store_true",
                    help="measure the vanilla (synchronous-halo) step as "
                         "the headline instead of the pipelined one")
    ap.add_argument("--no-compare", action="store_true",
                    help="skip the pipelined-vs-vanilla comparison run")
    ap.add_argument("--f32", action="store_true",
                    help="float32 compute (default bfloat16, the "
                         "TPU-native choice)")
    ap.add_argument("--fused", type=int, default=4,
                    help="epochs per dispatch (lax.scan); per-epoch time "
                         "= block time / fused")
    ap.add_argument("--spmm-impl", default="auto",
                    choices=["xla", "bucket", "block", "auto"])
    ap.add_argument("--block-tile", type=int, default=256,
                    help="dense-tile edge for the block kernel")
    from pipegcn_tpu.partition.partitioner import DEFAULT_CLUSTER_SIZE

    ap.add_argument("--cluster-size", type=int,
                    default=DEFAULT_CLUSTER_SIZE,
                    help="locality-cluster target size for the local "
                         "renumbering (docs/PERF_NOTES.md round-3 "
                         "addendum: measured sweep)")
    ap.add_argument("--block-nnz", type=int, default=0,
                    help="dense threshold override (0 = break-even)")
    ap.add_argument("--block-group", type=int, default=1,
                    help="union-gather group size for the block "
                         "kernel's dense path (1 = per-tile lists)")
    ap.add_argument("--bucket-merge", type=int, default=0,
                    help="merge bucket widths below 2^k into the 2^k "
                         "bucket (0 = full ladder) — the non-SpMM-floor "
                         "lever: fewer buckets, fewer fixed per-bucket "
                         "dispatch overheads")
    ap.add_argument("--reorder", default="auto",
                    choices=["auto", "none", "degree", "bfs",
                             "degree-bfs"],
                    help="per-partition node reordering baked into the "
                         "bench artifact (locality lever: contiguous "
                         "gather-index runs). 'auto' reuses an existing "
                         "artifact or takes the measured winner "
                         "(ops/tuner.choose_reorder)")
    ap.add_argument("--tune", action="store_true", dest="tune",
                    default=True, help=argparse.SUPPRESS)
    ap.add_argument("--no-tune", action="store_false", dest="tune",
                    help="with --spmm-impl auto: never run the live "
                         "micro-bench tuner; fall back to the "
                         "deterministic default when no persisted "
                         "tuning table is trusted")
    ap.add_argument("--tuner-samples", type=int, default=4_000_000,
                    help="edge budget for the tuner's sample of whole "
                         "destination tile-rows")
    ap.add_argument("--rem-dtype", default="none",
                    choices=["none", "bfloat16", "float8"],
                    help="gather-transport dtype for the remainder "
                         "(float8: e4m3/e5m2, f32 accumulation)")
    ap.add_argument("--rng-impl", default="threefry",
                    choices=["threefry", "rbg", "unsafe_rbg"],
                    help="dropout PRNG implementation (floor lever 1)")
    ap.add_argument("--dropout-bits", type=int, default=32,
                    choices=[8, 32],
                    help="dropout mask generation width (8 = one "
                         "random byte per element)")
    ap.add_argument("--halo-dtype", default="none",
                    choices=["none", "bfloat16", "float8"],
                    help="halo ppermute wire dtype (floor lever 2; "
                         "pipelined runs only)")
    ap.add_argument("--epoch-block", type=int, default=0,
                    help="megastep dispatch size override "
                         "(0 = --fused; floor lever 3)")
    ap.add_argument("--comm-prefetch", action="store_true",
                    help="issue the layer-0 halo collective at step "
                         "top (floor lever 4; no-op under the "
                         "headline's use_pp config)")
    ap.add_argument("--sweep-spmm", action="store_true",
                    help="also time every SpMM impl and report the winner")
    ap.add_argument("--cpu", action="store_true",
                    help="explicit harness dry run on the CPU backend: "
                         "the metric is named cpu_dryrun_* and carries "
                         "no chip number (without this flag bench.py "
                         "fails unless JAX finds a TPU)")
    ap.add_argument("--metrics-out", default="",
                    help="also append the headline result to this "
                         "metrics JSONL file through the obs sink "
                         "(schema: pipegcn_tpu/obs/schema.py; "
                         "summarize with python -m "
                         "pipegcn_tpu.cli.report)")
    ap.add_argument("--force-candidate", action="store_true",
                    help=argparse.SUPPRESS)  # CPU test hook for the
    # candidate-config pass (normally TPU-gated)
    ap.add_argument("--serve", action="store_true",
                    help="measure the online serving runtime instead of "
                         "training: open-loop load against the "
                         "compiled-once engine; headline metric is "
                         "sustained QPS with p50/p99 latency "
                         "(docs/SERVING.md)")
    ap.add_argument("--serve-secs", type=float, default=10.0,
                    help="seconds of open-loop serve load")
    ap.add_argument("--serve-qps", type=float, default=100.0,
                    help="target query arrival rate for --serve")
    ap.add_argument("--serve-max-batch", type=int, default=64,
                    help="top of the serve padded batch ladder")
    ap.add_argument("--serve-max-delay-ms", type=float, default=5.0,
                    help="max queueing delay before a partial serve "
                         "batch flushes")
    ap.add_argument("--serve-update-every", type=float, default=0.5,
                    help="seconds between synthetic feature-update "
                         "churn batches under --serve (0 disables)")
    ap.add_argument("--serve-refresh-every", type=float, default=0.5,
                    help="seconds between serve logits recomputes")
    ap.add_argument("--replicas", type=int, default=0,
                    help="with --serve: run an N-replica serving FLEET "
                         "(each replica its own process + mesh) behind "
                         "the failover router, with a mid-load "
                         "checkpoint hot-swap; headline metric is "
                         "aggregate QPS (near-linear in N). 0 = "
                         "single in-process engine")
    ap.add_argument("--serve-max-queue", type=int, default=0,
                    help="bound on queued query rows (overload sheds "
                         "tickets); 0 = unbounded")
    ap.add_argument("--traffic", type=str, default="",
                    help="with --serve: shaped arrival schedule "
                         "(constant | diurnal[:period[:floor]] | "
                         "flash-crowd[:mult[:t0[:t1]]] | trace:<path>); "
                         "empty = constant-rate Poisson")
    ap.add_argument("--autoscale", action="store_true",
                    help="with --serve: close the loop — run the fleet "
                         "under the scale policy (spawn/retire replicas "
                         "from window telemetry) with the graceful-"
                         "degradation admission ladder; headline shows "
                         "replica count tracking load (implies "
                         "--replicas 1 when unset)")
    ap.add_argument("--stream", action="store_true",
                    help="measure streaming-graph delta ingestion "
                         "instead of training throughput: per-delta "
                         "patch cost + forced-probe drift through the "
                         "live fit() loop, incremental-vs-full table "
                         "rebuild time, and the serving topology "
                         "refresh cost (docs/STREAMING.md)")
    ap.add_argument("--stream-deltas", type=int, default=6,
                    help="delta batches applied during the --stream "
                         "measurement")
    ap.add_argument("--stream-slack", type=float, default=0.10,
                    help="fractional padding headroom reserved for "
                         "in-place growth in the --stream build")
    ap.add_argument("--stream-journal-dir", type=str, default="",
                    help="persistent write-ahead delta journal for the "
                         "--stream measurement (stream/journal.py); "
                         "unset = ephemeral, non-resumable")
    ap.add_argument("--stream-resume", action="store_true",
                    help="resume a --stream measurement mid-schedule: "
                         "replay every journaled delta from "
                         "--stream-journal-dir against the rebuilt "
                         "nominal graph, then deliver only the "
                         "remaining scheduled deltas live")
    args = ap.parse_args()

    global jax
    import jax

    from pipegcn_tpu import native
    from pipegcn_tpu.backend import WrongBackend, start_measurement

    try:
        dev = start_measurement(cpu=args.cpu)
    except WrongBackend as exc:
        sys.exit(f"bench.py: {exc}")
    backend, device_kind = dev["platform"], dev["kind"]
    if not native.available():
        # the numpy partitioner caps the cluster count: another layout,
        # another dense-tile coverage, another epoch
        sys.exit(f"bench.py: native library {native.status()}; the "
                 f"numpy fallback builds a different layout, so there "
                 f"is nothing comparable to measure")
    n_parts = args.parts or dev["count"]
    if args.serve and (args.replicas > 0 or args.autoscale) \
            and backend == "tpu":
        sys.exit("bench.py: --serve --replicas starts replica PROCESSES "
                 "that each need the chip this process already holds "
                 "(a chip belongs to one process at a time), so on a "
                 "TPU they would fail or hang. Placing N one-chip "
                 "replicas on N chips is not built yet (ROADMAP R6); "
                 "the fleet runs on the CPU mesh only (--cpu).")
    if args.small:
        hidden, n_layers = 64, 3
        spmm_chunk = None
    else:
        hidden, n_layers = 256, 4
        spmm_chunk = 2_097_152  # bound gathered messages to [2M, F]
        # ([2M, 602] f32 = 4.8 GB peak for the pp precompute gather)

    if getattr(args, "stream", False):
        # streaming needs the live host graph + parts the cached
        # artifact discards (the patcher mutates both in lockstep with
        # the device state), so it builds in memory and skips the
        # artifact path entirely
        _measure_stream(args, backend, device_kind, n_parts, hidden,
                        n_layers)
        return

    # Artifact naming/recipe live in partition.bench_artifact (shared
    # with the probe scripts); cluster granularity and
    # generator revision are part of the artifact identity (measured
    # sweep in docs/PERF_NOTES.md). load() sets cache_dir so derived
    # kernel tables cache under the artifact dir too.
    from pipegcn_tpu.partition.bench_artifact import (artifact_path,
                                                      ensure,
                                                      resolve_reorder)

    # anchored at the repo root like the probe scripts: bench invoked
    # from another CWD must reuse the same cached artifacts, not build
    # duplicates under ./partitions (ADVICE.md round 5)
    # --reorder auto resolves to a concrete layout first (reuse an
    # existing artifact, else the measured choose_reorder winner) —
    # the mode is artifact identity, so it must be pinned before ensure
    args.reorder_resolved = resolve_reorder(
        n_parts, args.cluster_size, args.small,
        os.path.join(REPO, "partitions"), args.reorder,
        log=lambda m: print(m, file=sys.stderr))
    part_path = artifact_path(n_parts, args.cluster_size,
                              small=args.small,
                              root=os.path.join(REPO, "partitions"),
                              reorder=args.reorder_resolved)
    t0 = time.perf_counter()
    sg = ensure(part_path, log=lambda m: print(m, file=sys.stderr))
    print(f"# partitions ready ({time.perf_counter()-t0:.1f}s)",
          file=sys.stderr)

    try:
        result = _measure(args, backend, device_kind, n_parts, sg,
                          hidden, n_layers, spmm_chunk)
    except NonFiniteLoss as exc:
        # divergence is a numerics failure: loud fault record + red exit
        print(f"# FATAL: {exc} — benchmark invalid; exiting 3",
              file=sys.stderr)
        if args.metrics_out:
            from pipegcn_tpu.obs import MetricsLogger

            try:
                with MetricsLogger(args.metrics_out) as ml:
                    ml.fault(kind="non-finite-loss", epoch=exc.epoch,
                             reason=str(exc), backend=backend)
            except OSError:
                pass
        sys.exit(3)
    if result.get("loss") is None and not result.get("serve"):
        # the headline trained to a non-finite loss (the offshape-
        # products NaN class, VERDICT "Next round" item 1): the JSON
        # above is printed for diagnosis but the exit status must be
        # red — a benchmark of a diverged run is not a measurement
        print("# FINAL LOSS NON-FINITE — benchmark numbers are invalid; "
              "exiting 3", file=sys.stderr)
        sys.exit(3)


def _measure(args, backend, device_kind, n_parts, sg,
             hidden, n_layers, spmm_chunk):
    import jax

    from pipegcn_tpu.models import ModelConfig
    from pipegcn_tpu.parallel import Trainer, TrainConfig

    cfg = ModelConfig(
        layer_sizes=(sg.n_feat,) + (hidden,) * (n_layers - 1) + (sg.n_class,),
        use_pp=True, norm="layer", dropout=0.5,
        train_size=sg.n_train_global, spmm_chunk=spmm_chunk,
        dtype="float32" if args.f32 else "bfloat16",
        spmm_impl=args.spmm_impl,
        block_tile=args.block_tile,
        block_nnz=args.block_nnz or None,
        block_group=args.block_group,
        bucket_merge=args.bucket_merge,
        tune=args.tune,
        tuner_samples=args.tuner_samples,
        rem_dtype=args.rem_dtype,  # 'none' normalized by ModelConfig
        dropout_bits=args.dropout_bits,
    )
    if getattr(args, "serve", False):
        if getattr(args, "autoscale", False) \
                and getattr(args, "replicas", 0) == 0:
            args.replicas = 1  # autoscale needs the fleet path
        if getattr(args, "replicas", 0) > 0:
            return _measure_fleet(args, backend, device_kind, n_parts,
                                  sg, cfg)
        return _measure_serve(args, backend, device_kind, n_parts,
                              sg, cfg)

    blk = max(1, args.fused)
    # utilization exists only on the chip; a chip whose device_kind has
    # no published peaks raises here, before minutes of set-up
    peak = peak_flops_for(device_kind) if backend == "tpu" else None

    def build_trainer(pipeline: bool) -> "Trainer":
        tcfg = TrainConfig(
            lr=0.01, n_epochs=args.blocks * blk,
            enable_pipeline=pipeline, seed=0, eval=False,
            fused_epochs=blk,
            rng_impl=args.rng_impl,
            # halo compression is pipelined-only (vanilla exchange is
            # differentiated and must stay exact)
            halo_dtype=args.halo_dtype if pipeline else "none",
            epoch_block=args.epoch_block,
            comm_prefetch=args.comm_prefetch,
        )
        return Trainer(sg, cfg, tcfg)

    def time_trainer(trainer, n_blocks: int, fused: int = blk):
        """Median per-epoch time over n_blocks dispatches of `fused`
        epochs; returns (median_epoch_s, last_loss). The first dispatch
        compiles, the second warms; neither lands in a timed sample.
        Every timed program is the one asked for: a kernel downgrade
        during warm-up or measurement raises instead of being timed."""
        e = 0

        def run_block(e0):
            if fused == 1:
                loss = trainer.train_epoch(e0)
            else:
                loss = float(trainer.train_epochs(e0, fused)[-1])
            jax.block_until_ready(trainer.state["params"])
            return loss

        for label in ("compile + first block", "warm block"):
            t0 = time.perf_counter()
            _check_finite(run_block(e), e + fused - 1)
            e += fused
            print(f"# {label} of {fused}: "
                  f"{time.perf_counter()-t0:.1f}s", file=sys.stderr)
        _check_no_downgrade(trainer)
        times = []
        loss = float("nan")
        for _ in range(n_blocks):
            t0 = time.perf_counter()
            loss = run_block(e)
            e += fused
            times.append((time.perf_counter() - t0) / fused)
            # abort on the FIRST non-finite block, not after all of them
            _check_finite(loss, e - 1)
        _check_no_downgrade(trainer)
        return float(np.median(times)), loss

    headline_pipeline = not args.no_pipeline
    t0 = time.perf_counter()
    trainer = build_trainer(headline_pipeline)
    print(f"# trainer setup ({time.perf_counter()-t0:.1f}s)", file=sys.stderr)

    epoch_s, loss = time_trainer(trainer, args.blocks)
    print(f"# median epoch {epoch_s:.4f}s over {args.blocks} blocks of "
          f"{blk}, final loss {loss:.4f}", file=sys.stderr)

    # ---- derived metrics: MFU + bytes (from XLA's own cost model) -----
    extras = {
        "backend": backend,
        "device": device_kind,
        "n_parts": n_parts,
        "dtype": cfg.dtype,
        "spmm_impl": args.spmm_impl,
        "pipeline": headline_pipeline,
        "loss": round(loss, 4) if np.isfinite(loss) else None,
        "rng_impl": args.rng_impl,
        "halo_dtype": args.halo_dtype if headline_pipeline else "none",
        "epoch_block": args.epoch_block,
        "reorder": getattr(args, "reorder_resolved", args.reorder),
    }
    try:
        ca = trainer.step_cost_analysis()
        if ca:
            # cost_analysis describes the per-device SPMD module; scale
            # to whole-job totals so the labels mean what they say
            flops_epoch = ca.get("flops", 0.0) * n_parts
            hbm_bytes = ca.get("bytes accessed", 0.0) * n_parts
            extras["flops_per_epoch"] = round(flops_epoch)
            extras["est_hbm_bytes_per_epoch"] = round(hbm_bytes)
            if peak and flops_epoch:
                extras["mfu_pct"] = round(
                    100.0 * flops_epoch / (epoch_s * peak * n_parts), 2
                )
    except Exception as exc:  # cost analysis is best-effort diagnostics
        print(f"# cost analysis unavailable: {exc}", file=sys.stderr)
    extras["est_ici_bytes_per_epoch"] = trainer.est_ici_bytes_per_epoch()
    if getattr(trainer, "tuning", None):
        # the auto-tuner's decision + the full measured per-candidate
        # micro-bench table: WHY this kernel produced the number
        from pipegcn_tpu.ops.tuner import SAMPLE_FIELDS

        tu = trainer.tuning
        extras["tuning"] = {
            "winner": dict(tu["winner"]),
            "source": tu["source"],
            "stale_reason": tu.get("stale_reason"),
            "costs": list(tu.get("costs", [])),
            **{k: tu.get(k) for k in SAMPLE_FIELDS},
        }

    # The headline number is in hand from here on: the optional extras
    # below must never discard it, so a crash there falls through to the
    # JSON print.
    try:
        if trainer._block_tables is not None:
            from pipegcn_tpu.ops.block_spmm import estimate_block_coverage

            w_hint = max(cfg.layer_sizes[:cfg.n_graph_layers])
            # CLI convention: 0 means "use the break-even default"
            extras["dense_coverage"] = round(estimate_block_coverage(
                sg, args.block_tile, w_hint,
                nnz_threshold=args.block_nnz or None,
                group=cfg.block_group), 3)
            extras["dense_blocks"] = trainer.tables_pad["fwd"][
                "dense_blocks"]

        # ---- overlap evidence: pipelined vs vanilla -------------------
        if not args.no_compare:
            del trainer  # free HBM before compiling the second program
            other = build_trainer(not headline_pipeline)
            other_s, _ = time_trainer(other, max(3, args.blocks // 2))
            key = "vanilla_epoch_s" if headline_pipeline \
                else "pipelined_epoch_s"
            extras[key] = round(other_s, 4)
            pipe_s = epoch_s if headline_pipeline else other_s
            van_s = other_s if headline_pipeline else epoch_s
            extras["pipeline_speedup"] = round(van_s / pipe_s, 3)
            print(f"# pipelined {pipe_s:.4f}s vs vanilla {van_s:.4f}s "
                  f"(speedup {van_s / pipe_s:.3f}x)", file=sys.stderr)
            del other

        # ---- candidate-config pass ------------------------------------
        # The union-gather + fp8 stack (--block-group 4 --rem-dtype
        # float8) is parity/accuracy-validated but may not yet have a
        # chip measurement; when the headline ran at defaults on the
        # real chip, measure it too (one extra trainer build — the
        # kernel tables are disk-cached) and report the better of the
        # two as the headline, with BOTH measurements recorded.
        # Crash-isolated by the enclosing try: a failure here must
        # never cost the in-hand default number.
        if (((backend == "tpu" and not args.small)
             or args.force_candidate)
                and args.spmm_impl in ("auto", "block")
                and args.block_group == 1 and args.rem_dtype == "none"):
            try:
                # free the headline trainer's HBM before compiling a
                # second full-scale program (the compare path already
                # deleted it; with --no-compare it is still resident
                # and two programs can OOM the chip)
                del trainer
            except UnboundLocalError:
                pass
            cand_cfg = dataclasses.replace(
                cfg, spmm_impl="block", block_group=4,
                rem_dtype="float8")
            t0 = time.perf_counter()
            tr_c = Trainer(sg, cand_cfg, TrainConfig(
                lr=0.01, n_epochs=args.blocks * blk,
                enable_pipeline=headline_pipeline, seed=0, eval=False,
                fused_epochs=blk))
            def adopt_candidate(name, tr_win, cand_s, cand_loss):
                nonlocal epoch_s
                epoch_s = cand_s
                extras["headline_config"] = name
                extras["spmm_impl"] = "block"
                # loss and ICI bytes described the default run too —
                # keep every published field's provenance the winner's.
                # The candidate trains fewer blocks than the default, so
                # record the basis alongside the loss.
                extras["loss"] = (round(cand_loss, 4)
                                  if np.isfinite(cand_loss) else None)
                extras["loss_blocks"] = max(3, args.blocks // 2)
                extras["est_ici_bytes_per_epoch"] = (
                    tr_win.est_ici_bytes_per_epoch())
                # coverage depends only on (sg, tile, threshold) — if
                # the default headline already published it, the value
                # is identical; only fill the gap when the default ran
                # a non-block kernel
                if (tr_win._block_tables is not None
                        and "dense_coverage" not in extras):
                    from pipegcn_tpu.ops.block_spmm import (
                        estimate_block_coverage)
                    w_hint = max(cfg.layer_sizes[:cfg.n_graph_layers])
                    extras["dense_coverage"] = round(
                        estimate_block_coverage(
                            sg, args.block_tile, w_hint,
                            nnz_threshold=args.block_nnz or None,
                            group=tr_win.cfg.block_group), 3)
                    extras["dense_blocks"] = tr_win.tables_pad["fwd"][
                        "dense_blocks"]
                # the vanilla-vs-pipelined comparison (if it ran) was
                # measured on the DEFAULT config — relabel so no one
                # divides default vanilla time by the candidate headline
                for k in ("vanilla_epoch_s", "pipelined_epoch_s",
                          "pipeline_speedup"):
                    if k in extras:
                        extras[f"default_{k}"] = extras.pop(k)
                # the flops/bytes/mfu extras described the DEFAULT
                # program; recompute them from the winning one (fp8
                # transport exists precisely to change bytes moved)
                try:
                    ca = tr_win.step_cost_analysis()
                    if ca:
                        fl = ca.get("flops", 0.0) * n_parts
                        extras["flops_per_epoch"] = round(fl)
                        extras["est_hbm_bytes_per_epoch"] = round(
                            ca.get("bytes accessed", 0.0) * n_parts)
                        if peak and fl:
                            extras["mfu_pct"] = round(
                                100.0 * fl / (cand_s * peak * n_parts),
                                2)
                except Exception as exc:
                    print(f"# candidate cost analysis unavailable: "
                          f"{exc}", file=sys.stderr)

            cand_s, cand_loss = time_trainer(
                tr_c, max(3, args.blocks // 2))
            print(f"# candidate block-u4-float8: {cand_s:.4f}s/epoch "
                  f"(total {time.perf_counter()-t0:.0f}s)",
                  file=sys.stderr)
            extras["default_epoch_s"] = round(epoch_s, 4)
            extras["candidate_epoch_s"] = round(cand_s, 4)
            if cand_s < epoch_s:
                adopt_candidate("block-u4-float8", tr_c, cand_s,
                                cand_loss)
            del tr_c

        # ---- non-SpMM-floor lever: bucket-width merging ---------------
        # The bucket kernel's fixed per-epoch floor scales with the
        # number of bucket segments it dispatches (one padded
        # gather+reduce per width rung); --bucket-merge k truncates the
        # width ladder below 2^k, trading padding FLOPs for fewer
        # fixed overheads. Measure the SAME bucket program with and
        # without merging and publish the delta — the floor attack's
        # before/after evidence. Crash-isolated like the candidate
        # pass: a failure here never costs the in-hand headline.
        if (((backend == "tpu" and not args.small)
             or args.force_candidate)
                and args.bucket_merge == 0):
            lever = {}
            for name, merge in (("bucket", 0), ("bucket-m8", 8)):
                try:
                    t0 = time.perf_counter()
                    tr_m = Trainer(sg, dataclasses.replace(
                        cfg, spmm_impl="bucket", bucket_merge=merge,
                        block_group=1, rem_dtype=None), TrainConfig(
                            lr=0.01, n_epochs=args.blocks * blk,
                            enable_pipeline=headline_pipeline, seed=0,
                            eval=False, fused_epochs=blk))
                    m_s, _ = time_trainer(
                        tr_m, max(3, args.blocks // 2))
                    lever[name] = round(m_s, 4)
                    print(f"# floor lever {name}: {m_s:.4f}s/epoch "
                          f"(total {time.perf_counter()-t0:.0f}s)",
                          file=sys.stderr)
                    del tr_m
                except Exception as exc:  # noqa: BLE001
                    lever[name] = None
                    print(f"# floor lever {name} failed: {exc!r}",
                          file=sys.stderr)
            extras["bucket_merge_lever"] = lever
            if lever.get("bucket") and lever.get("bucket-m8"):
                extras["bucket_merge_delta_s"] = round(
                    lever["bucket"] - lever["bucket-m8"], 4)

        # ---- non-SpMM floor levers: before/after per lever ------------
        # Each lever is measured against the headline config with exactly
        # one knob flipped, crash-isolated so one broken variant never
        # costs the others or the in-hand headline:
        #   rng-rbg       dropout PRNG threefry -> rbg
        #   dropout-bits8 8-bit mask draws instead of 32-bit
        #   halo-float8   fp8+amax halo wire (pipelined headline only)
        #   unfused       fused=1: the megastep win read backwards
        #                 (base IS the fused dispatch, so the delta is
        #                 unfused - base)
        #   prefetch-*    paired use_pp=False runs, since the layer-0
        #                 exchange the prefetch hoists does not exist
        #                 under the headline's use_pp=True config
        if (((backend == "tpu" and not args.small)
             or args.force_candidate)
                and args.rng_impl == "threefry"
                and args.dropout_bits == 32
                and args.halo_dtype == "none"
                and args.epoch_block == 0
                and not args.comm_prefetch):
            floor = {"base": round(epoch_s, 4)}

            def _floor_lever(name, mkw=None, tkw=None, f_blk=0):
                try:
                    t0 = time.perf_counter()
                    c = dataclasses.replace(cfg, **mkw) if mkw else cfg
                    tr_l = Trainer(sg, c, TrainConfig(
                        lr=0.01, n_epochs=args.blocks * blk,
                        enable_pipeline=headline_pipeline, seed=0,
                        eval=False, fused_epochs=blk, **(tkw or {})))
                    s, _ = time_trainer(
                        tr_l, max(3, args.blocks // 2),
                        fused=f_blk or blk)
                    floor[name] = round(s, 4)
                    print(f"# floor lever {name}: {s:.4f}s/epoch "
                          f"(total {time.perf_counter()-t0:.0f}s)",
                          file=sys.stderr)
                    del tr_l
                except Exception as exc:  # noqa: BLE001
                    floor[name] = None
                    print(f"# floor lever {name} failed: {exc!r}",
                          file=sys.stderr)

            _floor_lever("rng-rbg", tkw=dict(rng_impl="rbg"))
            _floor_lever("dropout-bits8", mkw=dict(dropout_bits=8))
            # integrity plane at its worst-case cadence (a check every
            # boundary): digest capture/verify + static scrub +
            # Freivalds + the wire-checksum lane, all in ONE compile —
            # the guard is a trace-time choice, so the delta is pure
            # check cost, never recompile cost. Expect a NEGATIVE
            # delta (the lever spends time buying detection).
            _floor_lever("integrity-c1",
                         tkw=dict(integrity_check_every=1))
            if headline_pipeline:
                _floor_lever("halo-float8",
                             tkw=dict(halo_dtype="float8"))
            if blk > 1:
                _floor_lever("unfused", f_blk=1)
            if headline_pipeline:
                _floor_lever("prefetch-off", mkw=dict(use_pp=False))
                _floor_lever("prefetch-on", mkw=dict(use_pp=False),
                             tkw=dict(comm_prefetch=True))
            extras["floor_levers"] = floor
            # positive delta == the lever saves time vs its reference
            for dkey, ref, var in (
                    ("rng_impl_delta_s", "base", "rng-rbg"),
                    ("dropout_bits_delta_s", "base", "dropout-bits8"),
                    ("integrity_check_delta_s", "base",
                     "integrity-c1"),
                    ("halo_dtype_delta_s", "base", "halo-float8"),
                    ("epoch_block_delta_s", "unfused", "base"),
                    ("comm_prefetch_delta_s", "prefetch-off",
                     "prefetch-on")):
                if floor.get(ref) and floor.get(var):
                    extras[dkey] = round(floor[ref] - floor[var], 4)

        # ---- training-span pass (obs/trainspan.py) --------------------
        # Two questions, one crash-isolated block. (1) What do the
        # always-on spans SAY about this config: measured overlap
        # (overlap_spans), mean comm-wait share, per-rank straggler
        # gaps (bench is usually single-controller, so the straggler
        # map is often empty). (2) What do they COST: spans-on vs
        # spans-off epoch time published as train_traces_delta_s
        # (positive = tracing off is faster; expect ~0, the plane is
        # host-side bookkeeping). fit() drives both runs because the
        # span plane lives there — eval off, temp metrics sink, and
        # measure_comm_cost so the comm tail arms.
        if ((backend == "tpu" and not args.small)
                or args.force_candidate):
            import tempfile

            from pipegcn_tpu.obs import MetricsLogger
            from pipegcn_tpu.obs.metrics import read_metrics
            from pipegcn_tpu.obs.trainspan import fold_spans

            tspan_t = {}

            def _span_fit(name, traces):
                try:
                    t0 = time.perf_counter()
                    tr_s = Trainer(sg, cfg, TrainConfig(
                        lr=0.01, n_epochs=args.blocks * blk,
                        enable_pipeline=headline_pipeline, seed=0,
                        eval=False, fused_epochs=blk,
                        train_traces=traces))
                    path = os.path.join(
                        tempfile.mkdtemp(prefix="bench-tspan-"),
                        f"{name}.jsonl")
                    with MetricsLogger(path) as ml:
                        r = tr_s.fit(metrics=ml,
                                     log_fn=lambda *_a, **_k: None,
                                     measure_comm_cost=True)
                    tspan_t[name] = (round(r["epoch_time"], 4)
                                     if r.get("epoch_time") else None)
                    print(f"# train-span pass {name}: "
                          f"{tspan_t[name]}s/epoch "
                          f"(total {time.perf_counter()-t0:.0f}s)",
                          file=sys.stderr)
                    del tr_s
                    return path
                except Exception as exc:  # noqa: BLE001
                    tspan_t[name] = None
                    print(f"# train-span pass {name} failed: {exc!r}",
                          file=sys.stderr)
                    return None

            on_path = _span_fit("spans-on", True)
            if on_path:
                try:
                    fold = fold_spans(read_metrics(on_path))
                    if fold.get("overlap_spans") is not None:
                        extras["overlap_spans"] = round(
                            fold["overlap_spans"], 4)
                    shares = fold.get("comm_wait_share_by_rank") or {}
                    if shares:
                        extras["comm_wait_share"] = round(
                            sum(shares.values()) / len(shares), 4)
                    gaps = fold.get("straggler_gap_s_by_rank") or {}
                    if gaps:
                        extras["straggler_gap_s"] = {
                            f"r{r}": v for r, v in gaps.items()}
                except Exception as exc:  # noqa: BLE001
                    print(f"# train-span fold failed: {exc!r}",
                          file=sys.stderr)
            _span_fit("spans-off", False)
            if tspan_t.get("spans-on") and tspan_t.get("spans-off"):
                extras["train_traces_delta_s"] = round(
                    tspan_t["spans-on"] - tspan_t["spans-off"], 4)

        # ---- optional SpMM implementation sweep -----------------------
        if args.sweep_spmm:
            sweep = {}
            # (label, config overrides): the block kernel sweeps its
            # dense layouts and the fp8 remainder transport too —
            # sharing one artifact + warmed table caches, so each extra
            # entry costs one trainer build, not a rebuild of the world
            entries = [  # every knob EXPLICIT: entries must not
                # inherit the headline's --block-group/--rem-dtype
                ("xla", dict(spmm_impl="xla", block_group=1,
                             rem_dtype=None)),
                ("bucket", dict(spmm_impl="bucket", block_group=1,
                                rem_dtype=None)),
                ("block", dict(spmm_impl="block", block_group=1,
                               rem_dtype=None)),
                ("block-u4", dict(spmm_impl="block", block_group=4,
                                  rem_dtype=None)),
                ("block-u4-f8", dict(spmm_impl="block", block_group=4,
                                     rem_dtype="float8")),
                ("bucket-m8", dict(spmm_impl="bucket", block_group=1,
                                   bucket_merge=8, rem_dtype=None)),
            ]
            for impl, overrides in entries:
                try:
                    t0 = time.perf_counter()
                    tr = Trainer(sg,
                        dataclasses.replace(cfg, **overrides),
                        TrainConfig(lr=0.01, n_epochs=blk * 4,
                                    enable_pipeline=headline_pipeline,
                                    seed=0, eval=False, fused_epochs=blk))
                    s, _ = time_trainer(tr, 3)
                    sweep[impl] = round(s, 4)
                    print(f"# spmm sweep: {impl} {s:.4f}s/epoch "
                          f"(total {time.perf_counter()-t0:.0f}s)",
                          file=sys.stderr)
                    del tr
                except Exception as exc:
                    sweep[impl] = None
                    print(f"# spmm sweep: {impl} failed: {exc}",
                          file=sys.stderr)
            extras["spmm_sweep"] = sweep
            valid = {k: v for k, v in sweep.items() if v}
            if valid:
                extras["spmm_best"] = min(valid, key=valid.get)
    except Exception as exc:  # noqa: BLE001 — keep the headline number
        extras["extras_error"] = repr(exc)[:200]
        print(f"# optional comparison/sweep crashed ({exc!r}); "
              f"reporting the headline measurement alone", file=sys.stderr)

    metric = "reddit_scale_epoch_time" if not args.small else \
        "small_epoch_time"
    result = {
        "metric": metric,
        "value": round(epoch_s, 4),
        "unit": "s/epoch",
        "vs_baseline": round(BASELINE_EPOCH_S / epoch_s, 3),
        "n_devices": len(jax.devices()),
        **extras,
    }
    _label_dry_run(result, backend)
    if args.metrics_out:
        # the same sink the trainer logs through: a run header (what
        # produced the number) + one "bench" event with the headline
        from pipegcn_tpu.obs import MetricsLogger, device_info

        try:
            with MetricsLogger(args.metrics_out) as ml:
                ml.run_header(config=vars(args), device=device_info(),
                              mesh={"n_parts": n_parts})
                ml.event("bench", **result)
        except OSError as exc:
            print(f"# metrics sink unavailable: {exc}", file=sys.stderr)
    print(json.dumps(result))
    return result


def _measure_stream(args, backend, device_kind, n_parts, hidden,
                    n_layers):
    """bench.py --stream: streaming-graph delta ingestion cost. Runs
    the PRODUCTION path — deltas scheduled through the live fit() loop
    (forced staleness probe per delta measures the drift each topology
    change induces), then times one incremental apply against a
    from-scratch build+table rebuild, and the serving-side topology
    refresh. The result carries `stream: true` so main() knows there is
    no headline training loss to gate on."""
    import tempfile

    import jax

    from pipegcn_tpu.graph.synthetic import (synthetic_delta_schedule,
                                             synthetic_graph)
    from pipegcn_tpu.models import ModelConfig
    from pipegcn_tpu.obs.metrics import MetricsLogger, read_metrics
    from pipegcn_tpu.ops.bucket_spmm import build_sharded_bucket_tables
    from pipegcn_tpu.parallel import Trainer, TrainConfig
    from pipegcn_tpu.partition.halo import ShardedGraph
    from pipegcn_tpu.partition.partitioner import partition_graph
    from pipegcn_tpu.serve import ServingEngine
    from pipegcn_tpu.stream import GraphPatcher, StreamPlan, save_deltas

    t0 = time.perf_counter()
    if args.small:
        g = synthetic_graph(num_nodes=10_000, avg_degree=12, n_feat=64,
                            n_class=16, seed=0)
    else:
        # Reddit shape statistics, same as the training headline
        g = synthetic_graph(num_nodes=232_965, avg_degree=492,
                            n_feat=602, n_class=41, seed=0)
    parts = partition_graph(g, n_parts)
    sg = ShardedGraph.build(g, parts, n_parts=n_parts,
                            slack=args.stream_slack)
    print(f"# stream: graph + sharded build "
          f"({time.perf_counter()-t0:.1f}s, slack "
          f"{args.stream_slack:.0%})", file=sys.stderr)

    # bucket is the kernel with the dirty-shard incremental table
    # rebuild — the code path this scenario exists to measure
    impl = "bucket" if args.spmm_impl == "auto" else args.spmm_impl
    cfg = ModelConfig(
        layer_sizes=(sg.n_feat,) + (hidden,) * (n_layers - 1)
        + (sg.n_class,),
        use_pp=False, norm="layer", dropout=0.0,
        train_size=sg.n_train_global,
        dtype="float32" if args.f32 else "bfloat16",
        spmm_impl=impl, tune=False,
    )
    n_warm = 3
    n_deltas = max(1, args.stream_deltas)
    tcfg = TrainConfig(lr=0.01, n_epochs=n_warm + n_deltas,
                       enable_pipeline=True, seed=0, eval=False,
                       fused_epochs=1, log_every=10_000)
    trainer = Trainer(sg, cfg, tcfg)
    patcher = GraphPatcher(g, sg, parts, slack=args.stream_slack)
    trainer.enable_stream(patcher)

    # delta sizing: ~0.05% of the edge set per batch (>= 8 edges), so
    # the patch cost is measured against realistic drip-feed churn
    epb = max(8, g.num_edges // 2000)
    batches = synthetic_delta_schedule(
        g, n_batches=n_deltas + 2, edges_per_batch=epb,
        dels_per_batch=max(4, epb // 2),
        nodes_per_batch=max(1, g.num_nodes // 10_000), seed=0)
    # optional durability: a persistent WAL journal makes the
    # measurement resumable mid-schedule — a killed run's applied
    # deltas replay from the journal, the remainder deliver live
    journal = None
    replay_stats = None
    if args.stream_journal_dir:
        from pipegcn_tpu.stream import DeltaJournal, replay_for_resume

        journal = DeltaJournal(args.stream_journal_dir)
    with tempfile.TemporaryDirectory(prefix="bench-stream-") as td:
        dpath = os.path.join(td, "deltas.jsonl")
        save_deltas(dpath, batches[:n_deltas])
        plan = StreamPlan.parse(f"{dpath}@{n_warm}:1")
        if journal is not None and args.stream_resume:
            wm = journal.last_seq()
            replay_stats = replay_for_resume(
                journal, wm, trainer.apply_graph_deltas, plan=plan)
            plan.skip_journaled(wm)
            print(f"# stream: resumed mid-schedule — replayed "
                  f"{replay_stats['replayed']} journaled delta(s) "
                  f"(+{replay_stats['rederived']} re-derived), "
                  f"{plan.remaining()} still scheduled",
                  file=sys.stderr)
        mpath = os.path.join(td, "metrics.jsonl")
        t0 = time.perf_counter()
        with MetricsLogger(mpath) as ml:
            trainer.fit(None, metrics=ml, stream_plan=plan,
                        journal=journal,
                        log_fn=lambda m: print(f"# {m}",
                                               file=sys.stderr))
        fit_s = time.perf_counter() - t0
        stream_recs = [r for r in read_metrics(mpath)
                       if r.get("event") == "stream"]
    print(f"# stream: fit with {len(stream_recs)} deltas "
          f"({fit_s:.1f}s)", file=sys.stderr)

    # one more delta, wall-clock timed end to end: host patch + dirty
    # table rebuild + device upload + carry flush
    t0 = time.perf_counter()
    rep = trainer.apply_graph_deltas(batches[n_deltas])
    jax.block_until_ready(trainer.data)
    inc_apply_ms = (time.perf_counter() - t0) * 1e3

    # the number incremental patching competes against: a from-scratch
    # ShardedGraph.build + full kernel-table rebuild of the SAME
    # post-delta graph. Host-side only — a real full rebuild would ALSO
    # pay a full device re-upload and (shapes changing) a recompile, so
    # this comparison is conservative in the incremental path's favor
    # at scale and can even flip at smoke scale, where the incremental
    # number's device upload dominates.
    t0 = time.perf_counter()
    sg_full = ShardedGraph.build(
        patcher.g, patcher.parts, n_parts=n_parts,
        min_n_max=sg.n_max, min_b_max=sg.b_max, min_e_max=sg.e_max)
    if impl == "bucket":
        build_sharded_bucket_tables(sg_full)
    full_rebuild_ms = (time.perf_counter() - t0) * 1e3
    del sg_full
    print(f"# stream: incremental apply {inc_apply_ms:.1f}ms vs full "
          f"host rebuild {full_rebuild_ms:.1f}ms", file=sys.stderr)

    # serving-side topology refresh: patched send-lists drive layer-0
    # cache invalidation + incremental halo re-exchange, no retracing
    engine = ServingEngine.for_trainer(trainer)
    warm_s = engine.warmup()
    rep2 = trainer.apply_graph_deltas(batches[n_deltas + 1])
    t0 = time.perf_counter()
    touched = engine.apply_graph_deltas(rep2)
    topo_apply_ms = (time.perf_counter() - t0) * 1e3
    t0 = time.perf_counter()
    refreshed = engine.refresh_boundary()
    jax.block_until_ready(engine._halo0)
    refresh_ms = (time.perf_counter() - t0) * 1e3
    print(f"# stream: serve topo apply {topo_apply_ms:.1f}ms "
          f"({touched} slots), boundary refresh {refresh_ms:.1f}ms "
          f"({refreshed} rows)", file=sys.stderr)

    patch_ms = [r["patch_ms"] for r in stream_recs]
    drifts = [r["drift"] for r in stream_recs
              if r.get("drift") is not None]
    rnd = lambda v, k=3: None if v is None else round(v, k)  # noqa: E731
    result = {
        "metric": "stream_patch_ms",
        "value": round(float(np.median(patch_ms)), 3) if patch_ms
        else None,
        "unit": "ms/delta",
        "stream": True,
        "backend": backend,
        "device": device_kind,
        "n_parts": n_parts,
        "dtype": cfg.dtype,
        "spmm_impl": impl,
        "slack": args.stream_slack,
        "n_deltas": len(stream_recs),
        "edges_per_delta": epb,
        "patch_ms_per_delta": [rnd(v) for v in patch_ms],
        "drift_per_delta": [rnd(v, 5) for v in drifts],
        "drift_max": rnd(max(drifts), 5) if drifts else None,
        "tables_rebuilt_per_delta": [r["tables_rebuilt"]
                                     for r in stream_recs],
        "repadded_count": sum(bool(r["repadded"])
                              for r in stream_recs),
        "slack_remaining": rep2.slack_remaining,
        # incremental = host patch + dirty tables + device upload +
        # carry flush; full = host build + tables ONLY (no re-upload,
        # no recompile) — conservative toward the full path
        "incremental_apply_ms": rnd(inc_apply_ms),
        "full_host_rebuild_ms": rnd(full_rebuild_ms),
        "full_vs_incremental": rnd(full_rebuild_ms / inc_apply_ms)
        if inc_apply_ms > 0 else None,
        "serve_topo_apply_ms": rnd(topo_apply_ms),
        "serve_refresh_ms": rnd(refresh_ms),
        "serve_touched_slots": touched,
        "serve_warmup_s": round(warm_s, 2),
        "topo_generation": engine.topo_generation,
        "trainer_topo_generation": int(getattr(trainer,
                                               "topo_generation", 0)),
        "journal_replayed": (replay_stats["replayed"]
                             + replay_stats["rederived"]
                             if replay_stats else 0),
        "journal_last_seq": (journal.last_seq()
                             if journal is not None else -1),
    }
    _label_dry_run(result, backend)
    if args.metrics_out:
        from pipegcn_tpu.obs import MetricsLogger as _ML, device_info

        try:
            with _ML(args.metrics_out) as ml:
                ml.run_header(config=vars(args), device=device_info(),
                              mesh={"n_parts": n_parts})
                ml.event("bench", **result)
        except OSError as exc:
            print(f"# metrics sink unavailable: {exc}", file=sys.stderr)
    print(json.dumps(result))
    return result


def _measure_serve(args, backend, device_kind, n_parts, sg, cfg):
    """bench.py --serve: sustained QPS + latency of the online serving
    runtime under the open-loop load generator. The result carries
    `serve: true` so main() knows there is no training loss to gate on."""
    from pipegcn_tpu.parallel import Trainer, TrainConfig
    from pipegcn_tpu.serve import ServingEngine, run_serving_loop

    # serving measures the halo0-cache inference path with live feature
    # churn: use_pp folds raw features trainer-side (and disables
    # updates), so the serve leg runs without it; dropout is inert at
    # inference either way
    scfg = dataclasses.replace(cfg, use_pp=False, dropout=0.0)
    t0 = time.perf_counter()
    trainer = Trainer(sg, scfg, TrainConfig(
        lr=0.01, n_epochs=0, enable_pipeline=False, seed=0, eval=False))
    engine = ServingEngine.for_trainer(
        trainer, max_batch=args.serve_max_batch)
    warm_s = engine.warmup()
    print(f"# serve setup {time.perf_counter()-t0:.1f}s "
          f"(engine warm in {warm_s:.1f}s, ladder {engine.ladder})",
          file=sys.stderr)

    ml = None
    if args.metrics_out:
        from pipegcn_tpu.obs import MetricsLogger, device_info

        try:
            ml = MetricsLogger(args.metrics_out)
            ml.run_header(config=vars(args), device=device_info(),
                          mesh={"n_parts": n_parts})
        except OSError as exc:
            print(f"# metrics sink unavailable: {exc}", file=sys.stderr)
            ml = None

    summary = run_serving_loop(
        engine, duration_s=args.serve_secs, qps=args.serve_qps,
        max_delay_ms=args.serve_max_delay_ms,
        update_every_s=args.serve_update_every,
        refresh_every_s=args.serve_refresh_every,
        max_queue=args.serve_max_queue or None,
        seed=0, ml=ml)

    rnd = lambda v, k=3: None if v is None else round(v, k)  # noqa: E731
    result = {
        "metric": "serve_qps",
        "value": round(summary["qps"], 2),
        "unit": "q/s",
        "serve": True,
        "backend": backend,
        "device": device_kind,
        "n_parts": n_parts,
        "dtype": scfg.dtype,
        "spmm_impl": args.spmm_impl,
        "target_qps": args.serve_qps,
        "n_queries": summary["n_queries"],
        "duration_s": round(summary["duration_s"], 2),
        "p50_ms": rnd(summary["p50_ms"]),
        "p95_ms": rnd(summary["p95_ms"]),
        "p99_ms": rnd(summary["p99_ms"]),
        "batch_fill": rnd(summary["batch_fill"]),
        "cache_hit_rate": rnd(summary["cache_hit_rate"]),
        "staleness_age_max": summary["staleness_age_max"],
        "n_shed": summary["n_shed"],
        "conserved": summary["conserved"],
        "warmup_s": round(warm_s, 2),
    }
    _label_dry_run(result, backend)
    if ml is not None:
        try:
            ml.event("bench", **result)
        finally:
            ml.close()
    print(json.dumps(result))
    return result


def _measure_fleet(args, backend, device_kind, n_parts, sg, cfg):
    """bench.py --serve --replicas N: aggregate QPS of an N-replica
    serving fleet (each replica a full mesh in its own process) behind
    the failover router, with a mid-load checkpoint hot-swap so the
    headline carries the measured `param_swap_ms` blip. Near-linear
    aggregate QPS in N is the acceptance bar (docs/SERVING.md
    "Fleet")."""
    import glob
    import shutil
    import tempfile
    import threading

    from pipegcn_tpu.parallel import Trainer, TrainConfig
    from pipegcn_tpu.serve.fleet import FleetManager, run_fleet_loop
    from pipegcn_tpu.serve.router import Router
    from pipegcn_tpu.utils.checkpoint import save_checkpoint

    part_path = getattr(sg, "cache_dir", None)
    if not part_path:
        raise RuntimeError(
            "--replicas needs an on-disk partition artifact (bench "
            "always builds one; sg.cache_dir unset)")
    scfg = dataclasses.replace(cfg, use_pp=False, dropout=0.0)

    work_dir = tempfile.mkdtemp(prefix="bench-fleet-")
    ckpt_dir = os.path.join(work_dir, "ckpt")
    fleet_dir = os.path.join(work_dir, "fleet")

    # one driver-side trainer supplies the checkpoint the replicas
    # restore (generation 1) and hot-swap to (generation 2, published
    # mid-load): the zero-downtime refresh path, end to end
    t0 = time.perf_counter()
    trainer = Trainer(sg, scfg, TrainConfig(
        lr=0.01, n_epochs=0, enable_pipeline=False, seed=0, eval=False))
    save_checkpoint(ckpt_dir, trainer.host_state(), 1)
    print(f"# fleet setup: checkpoint generation 1 saved "
          f"({time.perf_counter()-t0:.1f}s)", file=sys.stderr)

    hidden = cfg.layer_sizes[1]
    n_layers = len(cfg.layer_sizes) - 1
    child_args = [
        "--partition-dir", os.path.dirname(os.path.abspath(part_path)),
        # the forwarded graph name IS the full artifact basename
        # (cluster suffix and all) — stop the replica's parser from
        # re-appending its default -c<suffix>
        "--graph-name", os.path.basename(part_path),
        "--local-reorder", "none",
        "--n-partitions", str(n_parts),
        "--checkpoint-dir", ckpt_dir,
        "--model", "graphsage",
        "--n-hidden", str(hidden),
        "--n-layers", str(n_layers),
        "--norm", "layer", "--dropout", "0.0",
        "--dtype", scfg.dtype,
        "--spmm-impl", args.spmm_impl,
        "--seed", "0",
        "--serve-max-batch", str(args.serve_max_batch),
        "--serve-report-every", "2.0",
        "--fleet-swap-poll", "0.3",
    ]
    env = dict(os.environ)
    if "xla_force_host_platform_device_count" not in \
            env.get("XLA_FLAGS", ""):
        env["XLA_FLAGS"] = (
            env.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={n_parts}"
        ).strip()
    # the replicas run where this process runs: main() refuses the
    # fleet on a TPU, so this is the --cpu dry run
    env["JAX_PLATFORMS"] = backend

    ml = None
    if args.metrics_out:
        from pipegcn_tpu.obs import MetricsLogger, device_info

        try:
            ml = MetricsLogger(args.metrics_out)
            ml.run_header(config=vars(args), device=device_info(),
                          mesh={"n_parts": n_parts,
                                "replicas": args.replicas})
        except OSError as exc:
            print(f"# metrics sink unavailable: {exc}", file=sys.stderr)
            ml = None

    manager = FleetManager(fleet_dir, args.replicas,
                           child_args=child_args, ml=ml, env=env,
                           log=lambda m: print(f"# {m}",
                                               file=sys.stderr))
    t0 = time.perf_counter()
    clients = manager.launch_all()
    print(f"# fleet: {args.replicas} replicas ready in "
          f"{time.perf_counter()-t0:.1f}s", file=sys.stderr)
    router = Router(clients, policy="least-queue")

    # publish generation 2 mid-load: every replica's watcher verifies
    # the digests and load_params-swaps without retracing
    def _publish_gen2():
        save_checkpoint(ckpt_dir, trainer.host_state(), 2)
        print("# fleet: checkpoint generation 2 published (hot-swap)",
              file=sys.stderr)

    timer = threading.Timer(max(args.serve_secs / 2, 1.0),
                            _publish_gen2)
    timer.daemon = True
    timer.start()

    num_nodes = int((np.asarray(sg.global_nid) >= 0).sum())
    # --autoscale: bounded queue + degradation ladder + scale policy;
    # cooldown of two report windows is the ramp rate on a short bench
    autoscaler = None
    ladder = None
    max_queue = args.serve_max_queue or None
    if getattr(args, "autoscale", False):
        from pipegcn_tpu.serve.autoscale import AutoscalePolicy
        from pipegcn_tpu.serve.batcher import AdmissionLadder

        max_queue = args.serve_max_queue or 4 * args.serve_max_batch
        ladder = AdmissionLadder()
        autoscaler = AutoscalePolicy(
            min_replicas=1,
            max_replicas=max(4, args.replicas),
            queue_high=max_queue // 2,
            queue_low=max(1, max_queue // 8),
            cooldown_s=4.0)
    try:
        summary = run_fleet_loop(
            manager, router, num_nodes=num_nodes,
            duration_s=args.serve_secs, qps=args.serve_qps,
            max_batch=args.serve_max_batch,
            max_delay_ms=args.serve_max_delay_ms,
            max_queue=max_queue,
            traffic=args.traffic or None,
            ladder=ladder, autoscaler=autoscaler,
            seed=0, ml=ml)
    finally:
        timer.cancel()
        manager.stop_all()

    # the measured swap blip lives in the replicas' own metrics files
    swap_ms = []
    for path in glob.glob(os.path.join(fleet_dir,
                                       "replica-m*-metrics.jsonl")):
        try:
            with open(path) as f:
                for line in f:
                    try:
                        rec = json.loads(line)
                    except ValueError:
                        continue
                    if rec.get("event") == "fleet" \
                            and rec.get("kind") == "hot-swap":
                        swap_ms.append(float(rec.get("swap_ms", 0.0)))
        except OSError:
            pass

    rnd = lambda v, k=3: None if v is None else round(v, k)  # noqa: E731
    result = {
        "metric": "fleet_qps",
        "value": round(summary["qps"], 2),
        "unit": "q/s",
        "serve": True,
        "fleet": True,
        "replicas": args.replicas,
        "backend": backend,
        "device": device_kind,
        "n_parts": n_parts,
        "dtype": scfg.dtype,
        "target_qps": args.serve_qps,
        "n_queries": summary["n_queries"],
        "duration_s": round(summary["duration_s"], 2),
        "p50_ms": rnd(summary["p50_ms"]),
        "p95_ms": rnd(summary["p95_ms"]),
        "p99_ms": rnd(summary["p99_ms"]),
        "batch_fill": rnd(summary["batch_fill"]),
        "n_shed": summary["n_shed"],
        "n_failovers": summary["n_failovers"],
        "replicas_up": summary["replicas_up"],
        "per_replica_dispatched": summary["per_replica_dispatched"],
        "per_replica_queue_depth_max":
            summary["per_replica_queue_depth_max"],
        "param_generation": summary["param_generation"],
        "param_swap_ms": rnd(max(swap_ms), 1) if swap_ms else None,
        "n_hot_swaps": len(swap_ms),
        "conserved": summary["conserved"],
        "drained": summary["drained"],
    }
    if getattr(args, "traffic", ""):
        result["traffic"] = summary.get("traffic")
    if autoscaler is not None:
        result.update({
            "autoscale": summary.get("autoscale"),
            "replicas_active": summary.get("replicas_active"),
            "n_spawned": summary.get("n_spawned"),
            "n_retired": summary.get("n_retired"),
            "scale_events": summary.get("scale_events"),
            "shed_by_reason": summary.get("shed_by_reason"),
            "rung_max": summary.get("rung_max"),
        })
    _label_dry_run(result, backend)
    if ml is not None:
        try:
            ml.event("bench", **result)
        finally:
            ml.close()
    print(json.dumps(result))
    shutil.rmtree(work_dir, ignore_errors=True)
    return result


if __name__ == "__main__":
    main()
