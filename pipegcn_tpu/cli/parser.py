"""Command-line flag surface.

Accepts the exact flag set of the reference (helper/parser.py:4-71, every
flag with both `-` and `_` spellings) so the reference's `scripts/*.sh`
run unchanged, plus TPU-specific extensions listed at the bottom.
Differences in meaning:

  --backend        'xla' (default) — the only real backend; 'gloo' is
                   accepted for script compatibility and treated as xla
                   (the reference's nccl/mpi raise NotImplementedError,
                   main.py:60-63; here they are rejected the same way).
  --master-addr/--port/--node-rank/--parts-per-node
                   map to `jax.distributed.initialize` coordinator
                   config for multi-host SPMD instead of gloo rendezvous.
"""

from __future__ import annotations

import argparse


def create_parser() -> argparse.ArgumentParser:
    parser = argparse.ArgumentParser(description="PipeGCN-TPU")

    parser.add_argument("--dataset", type=str, default="reddit",
                        help="the input dataset")
    parser.add_argument("--graph-name", "--graph_name", type=str, default="")

    parser.add_argument("--model", type=str, default="graphsage",
                        help="model for training")
    parser.add_argument("--dropout", type=float, default=0.5,
                        help="dropout probability")
    parser.add_argument("--lr", type=float, default=1e-2,
                        help="learning rate")
    parser.add_argument("--n-epochs", "--n_epochs", type=int, default=200,
                        help="the number of training epochs")
    parser.add_argument("--n-partitions", "--n_partitions", type=int,
                        default=2, help="the number of partitions")
    parser.add_argument("--n-hidden", "--n_hidden", type=int, default=16,
                        help="the number of hidden units")
    parser.add_argument("--n-layers", "--n_layers", type=int, default=2,
                        help="the number of GCN layers")
    parser.add_argument("--n-linear", "--n_linear", type=int, default=0,
                        help="the number of linear layers")
    parser.add_argument("--norm", choices=["layer", "batch", "none"],
                        default="layer", help="normalization method")
    parser.add_argument("--weight-decay", "--weight_decay", type=float,
                        default=0, help="weight for L2 loss")

    parser.add_argument("--n-feat", "--n_feat", type=int, default=0)
    parser.add_argument("--n-class", "--n_class", type=int, default=0)
    parser.add_argument("--n-train", "--n_train", type=int, default=0)
    parser.add_argument("--skip-partition", "--skip_partition",
                        action="store_true",
                        help="reuse the on-disk partition artifact")

    parser.add_argument("--partition-obj", "--partition_obj",
                        choices=["vol", "cut"], default="vol",
                        help="partition objective function")
    parser.add_argument("--partition-method", "--partition_method",
                        choices=["metis", "random"], default="metis",
                        help="the method for graph partition")

    parser.add_argument("--enable-pipeline", "--enable_pipeline",
                        action="store_true")
    parser.add_argument("--feat-corr", "--feat_corr", action="store_true")
    parser.add_argument("--grad-corr", "--grad_corr", action="store_true")
    parser.add_argument("--corr-momentum", "--corr_momentum", type=float,
                        default=0.95)

    parser.add_argument("--use-pp", "--use_pp", action="store_true",
                        help="whether to use precomputation")
    parser.add_argument("--inductive", action="store_true",
                        help="inductive learning setting")
    parser.add_argument("--fix-seed", "--fix_seed", action="store_true",
                        help="fix random seed")
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--log-every", "--log_every", type=int, default=10)

    parser.add_argument("--backend", type=str, default="xla")
    parser.add_argument("--port", type=int, default=18118,
                        help="coordinator port for multi-host")
    parser.add_argument("--master-addr", "--master_addr", type=str,
                        default="127.0.0.1")
    parser.add_argument("--node-rank", "--node_rank", type=int, default=0)
    parser.add_argument("--parts-per-node", "--parts_per_node", type=int,
                        default=10)
    parser.add_argument("--coordinator-timeout", "--coordinator_timeout",
                        type=int, default=300,
                        help="seconds to wait for the jax.distributed "
                             "coordinator at --master-addr:--port before "
                             "failing with an actionable error instead "
                             "of hanging forever (single-host runs "
                             "never connect)")

    parser.add_argument("--eval", action="store_true",
                        help="enable evaluation")
    parser.add_argument("--no-eval", action="store_false", dest="eval",
                        help="disable evaluation")
    parser.set_defaults(eval=True)

    # ---- TPU-native extensions (not in the reference) ----
    parser.add_argument("--data-root", "--data_root", type=str, default=None,
                        help="dataset root (default $PIPEGCN_DATA or ./dataset)")
    parser.add_argument("--partition-dir", "--partition_dir", type=str,
                        default="partitions",
                        help="directory for partition artifacts")
    parser.add_argument("--model-dir", "--model_dir", type=str,
                        default="model", help="directory for saved models")
    parser.add_argument("--results-dir", "--results_dir", type=str,
                        default="results", help="directory for result logs")
    parser.add_argument("--spmm-chunk", "--spmm_chunk", type=int, default=0,
                        help="edge-chunk size bounding SpMM memory "
                             "(0 = unchunked)")
    parser.add_argument("--spmm-impl", "--spmm_impl",
                        choices=["xla", "bucket", "block", "auto"],
                        default="xla",
                        help="aggregation kernel: XLA gather+segment-sum, "
                             "the scatter-free degree-bucketed kernel, "
                             "the hybrid block-dense MXU kernel, or "
                             "auto — resolved from the artifact's "
                             "measured tuning table / a live "
                             "micro-bench (ops/tuner.py), never from "
                             "shape thresholds")
    parser.add_argument("--n-heads", "--n_heads", type=int, default=4,
                        help="attention heads for --model gat")
    parser.add_argument("--block-tile", "--block_tile", type=int,
                        default=256,
                        help="dense-tile edge length for the block-dense "
                             "kernel")
    parser.add_argument("--block-nnz", "--block_nnz", type=int, default=0,
                        help="minimum edges for a tile pair to go dense "
                             "in the block kernel (0 = read-cost "
                             "break-even)")
    parser.add_argument("--block-group", "--block_group", type=int,
                        default=1,
                        help="union-gather group: that many consecutive "
                             "dst tiles share one gathered source-tile "
                             "union in the block kernel's dense path "
                             "(1 = per-tile block lists)")
    parser.add_argument("--bucket-merge", "--bucket_merge", type=int,
                        default=0,
                        help="no row bucket narrower than this width: "
                             "lower-degree rows share one bucket (fewer "
                             "kernel launches / transients per epoch at "
                             "bounded padding cost; 0 = the widths "
                             "fitted to the degree histogram). Tuner-"
                             "signature relevant: changing it re-tunes")
    parser.add_argument("--tune", action="store_true", dest="tune",
                        default=True,
                        help="allow a live tuner micro-bench when "
                             "--spmm-impl auto finds no trusted "
                             "tuning.json in the partition artifact "
                             "(default on; single-process runs only)")
    parser.add_argument("--no-tune", action="store_false", dest="tune",
                        help="never micro-bench at trainer setup: a "
                             "cache miss falls back to the "
                             "deterministic default kernel with a loud "
                             "record")
    parser.add_argument("--tuner-samples", "--tuner_samples", type=int,
                        default=4_000_000,
                        help="edge budget of the tuner's sample: whole "
                             "blocks of destination tile-rows (1024 rows "
                             "at the default tile), each row with all "
                             "its in-edges and the source ids in place, "
                             "so block candidates meet the shard's "
                             "dense tiles; a shard under the budget is "
                             "taken whole. Single rows drawn uniformly "
                             "carried no dense tile, and 200k edges "
                             "were one host round trip of device work")
    parser.add_argument("--rem-dtype", "--rem_dtype",
                        choices=["none", "bfloat16", "float8"],
                        default="none",
                        help="gather-transport dtype for the bucket "
                             "kernel / block remainder: float8 packs "
                             "256 features into one 256-byte gather "
                             "row (e4m3 activations, e5m2 cotangents, "
                             "f32 accumulation)")
    parser.add_argument("--fused-epochs", "--fused_epochs", type=int,
                        default=1,
                        help="epochs per compiled dispatch (lax.scan); "
                             "amortizes host round-trips")
    parser.add_argument("--rng-impl", "--rng_impl",
                        choices=["threefry", "rbg", "unsafe_rbg"],
                        default="threefry",
                        help="dropout PRNG: threefry (jax default), "
                             "rbg (hardware-RNG-backed, cheaper mask "
                             "generation on TPU; different but equally "
                             "valid masks at the same seed), or "
                             "unsafe_rbg (cheapest; weaker fold_in/split "
                             "guarantees — fine for dropout noise, "
                             "never for init)")
    parser.add_argument("--dropout-bits", "--dropout_bits", type=int,
                        choices=[8, 32], default=32,
                        help="dropout mask generation width: 8 draws "
                             "one random byte per element (quarter the "
                             "generated bits; keep-prob quantized to "
                             "1/256) instead of bernoulli's uniform-f32 "
                             "compare")
    parser.add_argument("--dropout-reuse", "--dropout_reuse", type=int,
                        default=0,
                        help="reuse each dropout mask for N consecutive "
                             "epochs (the per-epoch key folds "
                             "epoch//N), amortizing mask generation "
                             "N-fold inside fused blocks; 0/1 = fresh "
                             "mask every epoch")
    parser.add_argument("--halo-dtype", "--halo_dtype",
                        choices=["none", "bfloat16", "float8"],
                        default="none",
                        help="wire dtype of the halo ppermute payloads "
                             "(pipelined mode only): bfloat16 halves "
                             "ICI bytes per hop, float8 quarters them "
                             "(e4m3 features / e5m2 bgrads, amax-scaled "
                             "per distance block; decoded back to the "
                             "compute dtype on receipt)")
    parser.add_argument("--epoch-block", "--epoch_block", type=int,
                        default=0,
                        help="epochs per megastep dispatch (donated-"
                             "carry lax.scan + one batched metrics "
                             "harvest per block); overrides "
                             "--fused-epochs when set, 0 = inherit it")
    parser.add_argument("--comm-prefetch", "--comm_prefetch",
                        action="store_true",
                        help="issue the layer-0 halo collective at the "
                             "top of the step so it overlaps the "
                             "previous epoch's tail inside a fused "
                             "block (pipelined, no --use-pp; "
                             "numerically identical)")
    parser.add_argument("--local-reorder", "--local_reorder",
                        choices=["none", "cluster"], default="cluster",
                        help="local-id ordering within each partition: "
                             "'cluster' renumbers by locality clusters so "
                             "the shard adjacency forms dense tiles "
                             "(feeds --spmm-impl block); 'none' keeps "
                             "global-id order")
    from ..partition.partitioner import DEFAULT_CLUSTER_SIZE

    parser.add_argument("--cluster-size", "--cluster_size", type=int,
                        default=DEFAULT_CLUSTER_SIZE,
                        help="locality-cluster target size for "
                             "--local-reorder cluster; finer clusters "
                             "(the 1024 default) concentrate edges into "
                             "fewer, denser tiles (docs/PERF_NOTES.md)")
    parser.add_argument("--dtype", choices=["float32", "bfloat16"],
                        default="float32",
                        help="compute dtype for activations/halo exchange "
                             "(params, optimizer and statistics stay f32)")
    parser.add_argument("--checkpoint-dir", "--checkpoint_dir", type=str,
                        default="",
                        help="enable periodic checkpointing to this dir")
    parser.add_argument("--checkpoint-every", "--checkpoint_every", type=int,
                        default=100)
    parser.add_argument("--checkpoint-keep", "--checkpoint_keep", type=int,
                        default=3,
                        help="checkpoint generations retained "
                             "(keep-last-N rotation with a 'latest' "
                             "pointer and digest-verified fallback, "
                             "docs/RESILIENCE.md; 0 keeps all)")
    parser.add_argument("--checkpoint-fallback-dir",
                        "--checkpoint_fallback_dir", type=str, default="",
                        help="second directory (ideally another volume) "
                             "to save into when a periodic checkpoint "
                             "write fails with OSError; with or without "
                             "it the failed save degrades loudly and "
                             "retries at later boundaries "
                             "(docs/RESILIENCE.md 'Storage faults')")
    parser.add_argument("--resume", action="store_true",
                        help="resume from --checkpoint-dir (errors "
                             "without one; warns loudly when the dir "
                             "holds no checkpoint yet)")
    # ---- fault tolerance (docs/RESILIENCE.md) ----
    parser.add_argument("--no-sentinel", "--no_sentinel",
                        action="store_false", dest="sentinel",
                        help="disable the divergence sentinel "
                             "(non-finite/exploding loss detection with "
                             "rollback + LR backoff + bounded retries)")
    parser.set_defaults(sentinel=True)
    parser.add_argument("--sentinel-loss-factor", "--sentinel_loss_factor",
                        type=float, default=10.0,
                        help="trip when loss exceeds this multiple of "
                             "the recent healthy median (0 disables the "
                             "relative check; non-finite always trips)")
    parser.add_argument("--sentinel-grad-max", "--sentinel_grad_max",
                        type=float, default=0.0,
                        help="absolute grad-norm trip threshold "
                             "(0 disables)")
    parser.add_argument("--sentinel-max-retries", "--sentinel_max_retries",
                        type=int, default=3,
                        help="consecutive rollback retries before the "
                             "run fails with DivergenceError")
    parser.add_argument("--sentinel-lr-backoff", "--sentinel_lr_backoff",
                        type=float, default=0.5,
                        help="LR multiplier applied on every sentinel "
                             "trip (1.0 = no backoff)")
    parser.add_argument("--sentinel-snapshot-every",
                        "--sentinel_snapshot_every", type=int, default=25,
                        help="epochs between in-memory last-good "
                             "snapshots the sentinel rolls back to")
    parser.add_argument("--sentinel-no-flush", "--sentinel_no_flush",
                        action="store_false", dest="sentinel_flush",
                        help="keep the stale pipelined halo carry on "
                             "rollback instead of flushing it to zeros")
    parser.set_defaults(sentinel_flush=True)
    parser.add_argument("--fault-plan", "--fault_plan", type=str,
                        default="",
                        help="deterministic chaos injection: comma-"
                             "separated kind@epoch[:rN] entries "
                             "(nan-loss, nan-grad, sigterm, crash, "
                             "corrupt-ckpt, desync, hang, overflow, "
                             "kernel-crash, graph-delta, plus the "
                             "storage kinds enospc, torn-write, ro-dir, "
                             "slow-fs@E:<ms> — armed at the boundary of "
                             "E, disarmed at the next checkpoint "
                             "boundary), e.g. "
                             "'nan-loss@5:r1,sigterm@8,enospc@4'; each "
                             "fires once, host-side only; :rN targets "
                             "one rank (process index) in multi-host "
                             "runs")
    # ---- streaming graphs (docs/STREAMING.md) ----
    parser.add_argument("--stream-plan", "--stream_plan", type=str,
                        default="",
                        help="graph delta schedule: comma-separated "
                             "FILE@epoch[:everyN] entries — batch j of "
                             "FILE (CRC-guarded JSONL or npz, "
                             "stream/deltas.py) applies at the boundary "
                             "of epoch+j*N. Edges/nodes land in the "
                             "existing partition through reserved "
                             "headroom (--stream-slack), so compiled "
                             "shapes stay static across deltas")
    parser.add_argument("--stream-slack", "--stream_slack", type=float,
                        default=0.10,
                        help="fractional headroom reserved in every "
                             "padded dimension (rows, edges, send "
                             "slots) of the sharded build for streamed "
                             "growth; exhausting it re-pads loudly "
                             "(one recompile) instead of failing")
    parser.add_argument("--journal-dir", "--journal_dir", type=str,
                        default="",
                        help="write-ahead delta journal directory "
                             "(stream/journal.py): every applied delta "
                             "batch is made durable before it mutates "
                             "the topology, and --resume replays the "
                             "journal to the checkpoint's watermark. "
                             "Defaults to <checkpoint-dir>/journal "
                             "when streaming with --checkpoint-dir; "
                             "set explicitly to journal without "
                             "checkpoints")
    # ---- numerics guardrails (docs/RESILIENCE.md "Numerics") ----
    parser.add_argument("--loss-scale", "--loss_scale", type=str,
                        default="off",
                        help="mixed-precision loss scaling: 'auto' "
                             "(dynamic — backoff on overflow, regrow "
                             "after a clean streak), a positive number "
                             "(static scale), or 'off'. Non-'off' also "
                             "arms in-graph overflow-skip: an epoch "
                             "whose reduced gradient is non-finite "
                             "keeps params unchanged (skips counted in "
                             "the metrics JSONL as 'numerics' records)")
    parser.add_argument("--rem-amax", "--rem_amax", action="store_true",
                        help="amax-clamped fp8 transport cast: scale "
                             "each gathered tensor by a power of two "
                             "from its amax so the e4m3/e5m2 cast lands "
                             "mid-range instead of saturating or "
                             "flushing to zero (only with --rem-dtype "
                             "float8)")
    parser.add_argument("--no-numerics-tripwire", "--no_numerics_tripwire",
                        action="store_false", dest="numerics_tripwire",
                        help="drop the in-graph per-phase non-finite "
                             "tripwire from the step (fault records "
                             "then name no NaN birth phase)")
    parser.set_defaults(numerics_tripwire=True)
    # ---- cross-rank coordination (docs/RESILIENCE.md multi-host) ----
    parser.add_argument("--watchdog-timeout", "--watchdog_timeout",
                        type=float, default=60.0,
                        help="multi-host heartbeat watchdog: a peer "
                             "rank silent on the shared partition "
                             "filesystem for this many seconds raises "
                             "PeerLost -> crash checkpoint -> resumable "
                             "exit 75 instead of hanging the pod in a "
                             "collective (0 disables; single-process "
                             "runs never arm it)")
    parser.add_argument("--watchdog-dir", "--watchdog_dir", type=str,
                        default="",
                        help="shared directory for heartbeat files and "
                             "desync resync states (default: "
                             "<partition-dir>/coord-<master-addr>-"
                             "<port>, the filesystem multi-host runs "
                             "already share)")
    parser.add_argument("--desync-check-every", "--desync_check_every",
                        type=int, default=0,
                        help="epochs between cross-rank agreement "
                             "checks of per-leaf CRC32 param digests "
                             "through the consensus channel "
                             "(0 disables; mismatch emits a 'desync' "
                             "fault and aborts resumably unless "
                             "--desync-resync)")
    parser.add_argument("--integrity-check-every",
                        "--integrity_check_every", type=int, default=0,
                        help="epochs between SDC integrity checks "
                             "(resilience/integrity.py): fletcher-"
                             "digest scrub of static device tables and "
                             "Freivalds verification of the production "
                             "SpMM at this cadence, cheap params/carry "
                             "digest compares at every boundary, and "
                             "the halo wire-checksum lane in the "
                             "pipelined step; 0 disables (and keeps "
                             "the compiled step byte-identical)")
    parser.add_argument("--desync-resync", "--desync_resync",
                        action="store_true",
                        help="on a detected cross-rank desync, resync "
                             "every rank from rank 0's state (via the "
                             "shared coordination dir) instead of "
                             "aborting with the resumable exit 75")
    parser.add_argument("--no-signal-handlers", "--no_signal_handlers",
                        action="store_true",
                        help="do not install SIGTERM/SIGINT handlers "
                             "(nested launchers that own their signals; "
                             "PIPEGCN_NO_SIGNAL_HANDLERS=1 does the "
                             "same)")
    parser.add_argument("--profile-dir", "--profile_dir", type=str,
                        default="",
                        help="write a jax.profiler trace of a few epochs "
                             "to this directory (TensorBoard format); "
                             "the captured trace is folded into a "
                             "'profile' metrics record with MEASURED "
                             "per-phase device time and comm/compute "
                             "overlap (docs/OBSERVABILITY.md)")
    parser.add_argument("--profile-epochs", "--profile_epochs", type=str,
                        default="",
                        help="'A:B' — capture the device trace around "
                             "epochs [A, B) instead of the default "
                             "auto-window; requires --profile-dir")
    parser.add_argument("--staleness-probe-every",
                        "--staleness_probe_every", type=int, default=0,
                        help="every N epochs measure the per-layer "
                             "relative drift between the stale halo "
                             "features the pipelined step consumed and "
                             "the fresh ones it shipped (emits "
                             "'staleness' records; pipelined mode "
                             "only; 0 disables)")
    parser.add_argument("--anatomy", action="store_true",
                        help="emit an 'anatomy' record before training: "
                             "the compiled step's FLOPs/bytes "
                             "attributed per phase from the optimized "
                             "HLO + XLA cost analysis "
                             "(docs/OBSERVABILITY.md)")
    parser.add_argument("--metrics-out", "--metrics_out", type=str,
                        default="",
                        help="append structured JSONL telemetry (run "
                             "header + per-epoch/eval/summary records; "
                             "schema in pipegcn_tpu/obs/schema.py, see "
                             "docs/OBSERVABILITY.md) to this file; "
                             "summarize with python -m "
                             "pipegcn_tpu.cli.report")
    parser.add_argument("--no-train-traces", "--no_train_traces",
                        action="store_true",
                        help="disable the always-on training-span plane "
                             "(per-block compute/halo_exchange/"
                             "bgrad_return/grad_reduce/checkpoint/eval "
                             "spans + tracesync clock anchors in the "
                             "metrics stream; obs/trainspan.py, "
                             "docs/OBSERVABILITY.md 'Training traces'). "
                             "Spans are host-side only and inert "
                             "without --metrics-out")
    parser.add_argument("--sharded-eval", "--sharded_eval",
                        action="store_true",
                        help="evaluate through the training mesh instead "
                             "of one device (for graphs larger than a "
                             "single device's memory)")
    parser.add_argument("--sync-eval", "--sync_eval", action="store_true",
                        help="block the epoch loop on each evaluation "
                             "instead of the default async dispatch+"
                             "harvest (reference-thread analogue)")
    return parser
