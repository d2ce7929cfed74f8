"""Online serving entrypoint: `python -m pipegcn_tpu.cli.serve`.

Loads (or awaits) the partition artifact, builds the Trainer purely as
the host of the mesh + tuned kernel tables + sharded data, optionally
restores trained params from --checkpoint-dir, then hands everything to
the serve/ runtime: compiled-once ServingEngine, micro-batched query
path, incremental halo freshness, and a synthetic open-loop load
generator emitting schema-v5 `serving` records (docs/SERVING.md).

SIGTERM/SIGINT request a graceful stop: the loop drains every accepted
query, emits a hard-flushed final `serving` record (`final: true`), and
exits 0 — the contract the scripts/chaos.sh serving lane kills a live
process to verify.
"""

from __future__ import annotations

import json
import os
import signal
import sys

from .parser import create_parser


def build_parser():
    p = create_parser()
    g = p.add_argument_group("serving")
    g.add_argument("--serve-duration", "--serve_duration", type=float,
                   default=10.0,
                   help="seconds of open-loop load to serve")
    g.add_argument("--serve-qps", "--serve_qps", type=float, default=50.0,
                   help="target query arrival rate (open-loop Poisson)")
    g.add_argument("--serve-max-batch", "--serve_max_batch", type=int,
                   default=64, help="top of the padded batch ladder")
    g.add_argument("--serve-max-delay-ms", "--serve_max_delay_ms",
                   type=float, default=5.0,
                   help="max queueing delay before a partial batch "
                        "flushes (latency-vs-fill tradeoff)")
    g.add_argument("--serve-ladder-min", "--serve_ladder_min", type=int,
                   default=8, help="bottom of the padded batch ladder")
    g.add_argument("--serve-report-every", "--serve_report_every",
                   type=float, default=2.0,
                   help="seconds between `serving` metric records")
    g.add_argument("--serve-refresh-every", "--serve_refresh_every",
                   type=float, default=0.5,
                   help="seconds between logits recomputes (bounded-"
                        "staleness window)")
    g.add_argument("--serve-update-every", "--serve_update_every",
                   type=float, default=0.0,
                   help="seconds between synthetic feature-update "
                        "churn batches (0 disables)")
    g.add_argument("--serve-update-rows", "--serve_update_rows",
                   type=int, default=32,
                   help="rows per synthetic update batch")
    g.add_argument("--serve-artifact-timeout", "--serve_artifact_timeout",
                   type=float, default=600.0,
                   help="seconds to wait for a missing partition "
                        "artifact before giving up")
    g.add_argument("--serve-build", "--serve_build", action="store_true",
                   help="build the partition artifact locally when "
                        "missing instead of awaiting it")
    g.add_argument("--serve-max-queue", "--serve_max_queue", type=int,
                   default=0,
                   help="bound on queued query rows; overload sheds "
                        "tickets (counted as `shed`) instead of "
                        "growing the queue. 0 = unbounded")
    g.add_argument("--serve-ticket-deadline-ms",
                   "--serve_ticket_deadline_ms", type=float, default=0.0,
                   help="shed tickets that waited past this deadline "
                        "at flush time. 0 = no deadline")
    g.add_argument("--traffic", type=str, default="",
                   help="shaped open-loop arrival schedule "
                        "(serve/loadgen.RateShape): constant | "
                        "diurnal[:period[:floor]] | "
                        "flash-crowd[:mult[:t0[:t1]]] | trace:<path>. "
                        "Empty = legacy constant-rate Poisson")
    g.add_argument("--update-fraction", "--update_fraction",
                   type=float, default=0.0,
                   help="fraction of arrivals that are feature UPDATES "
                        "instead of queries (mixed workload; seeded "
                        "per arrival). 0 = query-only")
    g.add_argument("--trace-sample-rate", "--trace_sample_rate",
                   type=float, default=0.0,
                   help="fraction of submitted queries that mint a "
                        "trace id and land per-hop `span` records "
                        "(queue/dispatch, rpc, replica, engine) in "
                        "the metrics stream; cli.timeline renders "
                        "them as Perfetto flows. 0 = tracing off")
    return p


def _load_partition(args):
    """Resolve the partition artifact exactly like training's
    prepare(), but a missing path AWAITS (the shared-filesystem backoff
    poll) or builds under --serve-build — a serving replica must not
    crash because it raced the partitioner."""
    from ..partition.halo import ShardedGraph
    from .main import _await_partition_artifact, derive_graph_name

    graph_name = args.graph_name or derive_graph_name(args)
    from ..partition.partitioner import cluster_suffix

    csuf = "-c" + cluster_suffix(args.cluster_size) \
        if args.local_reorder == "cluster" else ""
    part_path = os.path.join(args.partition_dir, graph_name + csuf)

    if ShardedGraph.exists(part_path):
        sg = ShardedGraph.load(part_path)
        if sg.num_parts != args.n_partitions:
            raise ValueError(
                f"partition artifact at {part_path} has {sg.num_parts} "
                f"parts, requested {args.n_partitions}")
        return sg
    if args.serve_build:
        from ..graph.datasets import load_data
        from ..partition.partitioner import (locality_clusters,
                                             partition_graph)

        g = load_data(args.dataset, args.data_root)
        seed = args.seed if args.fix_seed else 0
        parts = partition_graph(g, args.n_partitions,
                                method=args.partition_method,
                                obj=args.partition_obj, seed=seed)
        cluster = None
        if args.local_reorder == "cluster":
            cluster = locality_clusters(
                g, target_size=args.cluster_size, seed=seed)
        sg = ShardedGraph.build(g, parts, n_parts=args.n_partitions,
                                cluster=cluster)
        os.makedirs(args.partition_dir, exist_ok=True)
        sg.save(part_path)
        sg.cache_dir = part_path
        return sg
    return _await_partition_artifact(
        part_path, args.n_partitions,
        timeout_s=args.serve_artifact_timeout)


def build_serving_engine(args, log=print):
    """Everything between parsed args and a warm ServingEngine —
    shared by this entrypoint and each fleet replica process
    (cli/fleet.py --replica-id K). Returns (trainer, engine, epoch)
    where epoch is the restored checkpoint generation (-1 when serving
    freshly-initialized params); the engine's parameter-generation
    axis is already set to it."""
    if args.model not in ("graphsage", "gcn", "gat"):
        raise ValueError(f"unknown model: {args.model}")
    if args.model in ("gcn", "gat") and args.use_pp:
        raise ValueError("--use-pp is a GraphSAGE-only optimization")

    from ..backend import device_line, place_compile_cache
    from .main import _maybe_init_distributed

    place_compile_cache()
    _maybe_init_distributed(args)
    log(device_line())

    from ..models.sage import ModelConfig
    from ..parallel.trainer import TrainConfig, Trainer
    from ..serve import ServingEngine
    from ..utils.checkpoint import checkpoint_exists, load_checkpoint

    sg = _load_partition(args)
    n_feat = args.n_feat or sg.n_feat
    n_class = args.n_class or sg.n_class
    layer_sizes = (n_feat,) + (args.n_hidden,) * (args.n_layers - 1) \
        + (n_class,)
    cfg = ModelConfig(
        layer_sizes=layer_sizes,
        model=args.model,
        n_heads=args.n_heads,
        n_linear=args.n_linear,
        use_pp=args.use_pp,
        norm=None if args.norm == "none" else args.norm,
        dropout=args.dropout,
        train_size=args.n_train or sg.n_train_global,
        spmm_chunk=args.spmm_chunk or None,
        spmm_impl=args.spmm_impl,
        block_tile=args.block_tile,
        block_nnz=args.block_nnz or None,
        block_group=args.block_group,
        bucket_merge=args.bucket_merge,
        tune=args.tune,
        tuner_samples=args.tuner_samples,
        rem_dtype=args.rem_dtype,
        rem_amax=args.rem_amax,
        dropout_bits=args.dropout_bits,
        dtype=args.dtype,
    )
    # the trainer is only the serving substrate here: mesh, tuned kernel
    # tables, sharded data, params template. No epochs run.
    tcfg = TrainConfig(lr=args.lr, n_epochs=0,
                       enable_pipeline=False, seed=args.seed,
                       eval=False, halo_dtype=args.halo_dtype)
    trainer = Trainer(sg, cfg, tcfg)

    epoch = -1
    if args.checkpoint_dir and checkpoint_exists(args.checkpoint_dir):
        host_state, epoch = load_checkpoint(args.checkpoint_dir,
                                            trainer.host_state())
        trainer.restore_state(host_state)
        log(f"serving params restored from {args.checkpoint_dir} "
            f"(epoch {epoch})")
    elif args.checkpoint_dir:
        log(f"WARNING: no checkpoint in {args.checkpoint_dir!r}; "
            f"serving freshly-initialized params")

    engine = ServingEngine.for_trainer(
        trainer, max_batch=args.serve_max_batch,
        ladder_min=args.serve_ladder_min)
    engine.param_generation = int(epoch)
    warm_s = engine.warmup()
    log(f"serve: engine warm in {warm_s:.2f}s "
        f"(ladder {engine.ladder}, {engine.num_global_nodes} nodes, "
        f"{trainer.P} partitions)")
    return trainer, engine, epoch


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    from ..serve import run_serving_loop

    trainer, engine, _epoch = build_serving_engine(args)

    ml = None
    if args.metrics_out:
        from ..obs import MetricsLogger, device_info, mesh_info

        ml = MetricsLogger(args.metrics_out)
        ml.run_header(config=vars(args), device=device_info(),
                      mesh={"n_parts": args.n_partitions,
                            **mesh_info(trainer.mesh)})

    stop_flag = {"stop": False}

    def _on_signal(signum, frame):  # noqa: ARG001
        stop_flag["stop"] = True

    old = [signal.signal(s, _on_signal)
           for s in (signal.SIGTERM, signal.SIGINT)]
    try:
        summary = run_serving_loop(
            engine,
            duration_s=args.serve_duration,
            qps=args.serve_qps,
            max_delay_ms=args.serve_max_delay_ms,
            report_every_s=args.serve_report_every,
            refresh_every_s=args.serve_refresh_every,
            update_every_s=args.serve_update_every,
            update_rows=args.serve_update_rows,
            seed=args.seed,
            ml=ml,
            traffic=args.traffic or None,
            update_fraction=args.update_fraction,
            max_queue=args.serve_max_queue or None,
            ticket_deadline_ms=args.serve_ticket_deadline_ms or None,
            trace_sample_rate=args.trace_sample_rate,
            stop=lambda: stop_flag["stop"],
        )
    finally:
        for s, h in zip((signal.SIGTERM, signal.SIGINT), old):
            signal.signal(s, h)
        if ml is not None:
            ml.close()
    print(json.dumps({"serve": True, **summary}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
