"""Launcher + training driver.

The analogue of the reference's `main.py` (load/partition/spawn) and
`train.py run()` (per-rank epoch loop) collapsed into one entry point:
there is no process spawning — the SPMD mesh replaces it — so "launch"
means: resolve config, load + partition the graph (cached on disk like
the reference's partition JSON, helper/utils.py:137 / --skip-partition),
build the Trainer, run the epoch loop with reference-format logging, and
save the best model.

Log-line format parity (reference train.py:369-371):
  Process 000 | Epoch 00009 | Time(s) ... | Comm(s) ... | Reduce(s) ... | Loss ...
Result-file format parity (train.py:33-39, 54-60):
  Epoch 00009 | Accuracy 95.00%                  (inductive)
  Epoch 00009 | Validation Accuracy ... | Test Accuracy ...   (trans)

Multi-host: when n_partitions spans multiple hosts (ceil(n_partitions /
parts_per_node) > 1), `jax.distributed.initialize` is called with the
coordinator at --master-addr:--port and process id --node-rank, after
which jax.devices() covers all hosts and the same SPMD program runs
(ICI intra-slice, DCN across hosts).
"""

from __future__ import annotations

import math
import os
import random
import time
import warnings


from ..graph.datasets import inductive_split, load_data
from ..models.sage import ModelConfig
from ..obs.trace import recorder, span
from ..partition.halo import ShardedGraph
from ..partition.partitioner import locality_clusters, partition_graph
from ..utils.checkpoint import (checkpoint_exists, load_checkpoint,
                                peek_watermark, save_pytree)


def derive_graph_name(args) -> str:
    mode = "induc" if args.inductive else "trans"
    return (f"{args.dataset}-{args.n_partitions}-{args.partition_method}-"
            f"{args.partition_obj}-{mode}")


def result_file_name(args) -> str:
    suffix = ""
    if args.grad_corr and args.feat_corr:
        suffix = "_grad_feat"
    elif args.grad_corr:
        suffix = "_grad"
    elif args.feat_corr:
        suffix = "_feat"
    return os.path.join(
        args.results_dir,
        f"{args.dataset}_n{args.n_partitions}_p{int(args.enable_pipeline)}"
        f"{suffix}.txt",
    )


def _maybe_init_distributed(args) -> None:
    import jax

    n_nodes = math.ceil(args.n_partitions / args.parts_per_node)
    if n_nodes <= 1:
        return
    addr = f"{args.master_addr}:{args.port}"
    timeout = int(getattr(args, "coordinator_timeout", 300))
    try:
        jax.distributed.initialize(
            coordinator_address=addr,
            num_processes=n_nodes,
            process_id=args.node_rank,
            initialization_timeout=timeout,
        )
    except Exception as exc:
        # without this, an unreachable coordinator used to hang the
        # process forever (or die with a bare RPC error no operator
        # could act on)
        raise RuntimeError(
            f"could not join the multi-host coordination service at "
            f"{addr} as process {args.node_rank}/{n_nodes} within "
            f"{timeout}s ({exc}). Check --master-addr/--port, that the "
            f"rank-0 process is up and the port is reachable from this "
            f"host, and raise --coordinator-timeout for slow pod "
            f"bring-up.") from exc


def _local_parts(args):
    """Global partition ids this process will own under the contiguous
    block assignment (node i gets [i*k, (i+1)*k)), or None when a
    single process owns everything. Passed to ShardedGraph.load so an
    elastic relaunch with a REDISTRIBUTED assignment validates its
    per-rank artifact slices at load time (partition/halo.py), not
    mid-epoch."""
    n_nodes = math.ceil(args.n_partitions / args.parts_per_node)
    if n_nodes <= 1:
        return None
    lo = args.node_rank * args.parts_per_node
    hi = min(lo + args.parts_per_node, args.n_partitions)
    return list(range(lo, hi))


def _pad_text(tables_pad) -> str:
    """Trainer.tables_pad for the set-up line: per direction the row
    buckets' widths and gather slots an edge (bucket_spmm.pad_stats;
    under the block kernel, the remainder's, and what the dense half
    stores: block_spmm.dense_pad_stats), how many tables its source
    rows are cut into for the gathers (bucket_spmm.source_parts), and
    the float32 bytes one call writes for its sums
    (bucket_spmm.result_bytes)."""
    return "".join(
        f" | {d}: widths {t['widths']}, pad_ratio {t['pad_ratio']} "
        f"({t['slots']} slots / {t['edges']} edges), parts {t['parts']} "
        f"of up to {t['part_rows']} rows, result_bytes "
        f"{t['result_bytes']}"
        + (f", dense_pad {t['dense_pad']} ({t['dense_slots']} slots / "
           f"{t['dense_blocks']} blocks, a_bytes {t['a_bytes']})"
           if "dense_pad" in t else "")
        for d, t in (tables_pad or {}).items())


def prepare(args):
    """Load, partition (or reuse artifact), and return
    (sharded_graph, eval_graphs or None), under a `setup/graph` span."""
    with span("setup/graph"):
        return _prepare(args)


def _prepare(args):
    graph_name = args.graph_name or derive_graph_name(args)
    # the local-id ordering is part of the artifact's identity: a
    # cluster-reordered layout and a plain one are both valid but not
    # interchangeable (--skip-partition must never silently reuse the
    # other kind), so the ordering choice gets its own cache key suffix
    # non-default cluster granularity changes the layout, so it gets its
    # own artifact identity (like the "-c" ordering suffix itself)
    from ..partition.partitioner import cluster_suffix

    csuf = "-c" + cluster_suffix(args.cluster_size) \
        if args.local_reorder == "cluster" else ""
    part_name = graph_name + csuf
    part_path = os.path.join(args.partition_dir, part_name)

    g = None
    eval_graphs = None
    if args.eval or not (args.skip_partition and ShardedGraph.exists(part_path)):
        g = load_data(args.dataset, args.data_root)
        if args.inductive:
            train_g, val_g, test_g = inductive_split(g)
            eval_graphs = {"val": (val_g, "val_mask"),
                           "test": (test_g, "test_mask")}
        else:
            train_g = g
            eval_graphs = {"val": (g, "val_mask"), "test": (g, "test_mask")}
        if not args.eval:
            eval_graphs = None

    if args.skip_partition and ShardedGraph.exists(part_path):
        sg = ShardedGraph.load(part_path, parts=_local_parts(args))
        if sg.num_parts != args.n_partitions:
            raise ValueError(
                f"partition artifact at {part_path} has "
                f"{sg.num_parts} parts, requested {args.n_partitions}"
            )
    else:
        import jax

        if jax.process_count() > 1 and jax.process_index() != 0:
            # multi-host: only process 0 partitions (the reference
            # partitions on node_rank 0 only, main.py:32-40); peers poll
            # the shared filesystem for the finished artifact so every
            # process trains on the SAME partition (the partitioner is
            # deterministic per host but not across toolchains)
            sg = _await_partition_artifact(part_path, args.n_partitions,
                                           parts=_local_parts(args))
        else:
            assert g is not None
            # inductive mode partitions the train subgraph only
            # (reference main.py:34-35)
            pg = train_g if args.inductive else g
            seed = args.seed if args.fix_seed else 0
            parts = partition_graph(
                pg, args.n_partitions, method=args.partition_method,
                obj=args.partition_obj, seed=seed,
            )
            cluster = None
            if args.local_reorder == "cluster":
                cluster = locality_clusters(
                    pg, target_size=args.cluster_size, seed=seed)
            # papers100M-class edge lists: the RAM-bounded chunked build
            # (bit-identical output) keeps the O(E) int64 scratch of the
            # plain build from crowding host memory
            build = (ShardedGraph.build_chunked
                     if pg.num_edges > 200_000_000 else ShardedGraph.build)
            sg = build(pg, parts, n_parts=args.n_partitions,
                       cluster=cluster)
            os.makedirs(args.partition_dir, exist_ok=True)
            sg.save(part_path)
            # first runs cache their derived kernel tables too
            sg.cache_dir = part_path
    return sg, eval_graphs


def _prepare_streaming(args):
    """Streaming-mode prepare (--stream-plan / graph-delta faults):
    always builds the sharded graph in memory — the patcher mutates the
    HOST graph and partition arrays in lockstep with the device state,
    which a reloaded artifact would not share — and reserves
    --stream-slack headroom in every padded dimension so scheduled
    deltas land without recompiling. Returns
    (sg, eval_graphs, host_graph, parts)."""
    if args.local_reorder != "none":
        raise ValueError(
            "--stream-plan / graph-delta faults require --local-reorder "
            "none: the patcher appends new nodes in plain local-id "
            "order, and cluster renumbering would break the "
            "patched-vs-rebuilt bit-identity contract")
    if args.use_pp:
        raise ValueError(
            "streaming deltas are incompatible with --use-pp (the "
            "layer-0 precompute bakes in the pre-delta topology)")
    if args.inductive:
        raise ValueError(
            "streaming deltas support transductive runs only (the "
            "inductive split would diverge from the patched graph)")
    if math.ceil(args.n_partitions / args.parts_per_node) > 1:
        raise ValueError(
            "streaming deltas are single-process only (the patcher "
            "owns the full host-side partition state)")
    g = load_data(args.dataset, args.data_root)
    eval_graphs = ({"val": (g, "val_mask"), "test": (g, "test_mask")}
                   if args.eval else None)
    seed = args.seed if args.fix_seed else 0
    parts = partition_graph(
        g, args.n_partitions, method=args.partition_method,
        obj=args.partition_obj, seed=seed)
    sg = ShardedGraph.build(g, parts, n_parts=args.n_partitions,
                            slack=args.stream_slack)
    return sg, eval_graphs, g, parts


def _await_partition_artifact(part_path: str, n_partitions: int,
                              timeout_s: float = 3600.0,
                              poll_s: float = 2.0,
                              max_poll_s: float = 30.0,
                              parts=None):
    """Poll the shared filesystem for process 0's finished artifact.

    Exponential backoff with jitter: a 64-host pod polling a shared
    filesystem in lockstep every 2 s is a thundering herd for the whole
    multi-hour partition build; backing off to `max_poll_s` (desynced
    by the jitter) costs at most one extra poll interval of startup
    latency. A progress line keeps long waits diagnosable from the
    rank's log."""
    start = time.monotonic()
    deadline = start + timeout_s
    poll = poll_s
    next_report = start
    while not ShardedGraph.exists(part_path):
        now = time.monotonic()
        if now > deadline:
            raise TimeoutError(
                f"timed out waiting for partition artifact at {part_path} "
                f"(is the partition dir on a shared filesystem?)"
            )
        if now >= next_report:
            print(f"waiting for partition artifact at {part_path} "
                  f"({int(now - start)}s elapsed, poll {poll:.1f}s)")
            next_report = now + 30.0
        time.sleep(min(poll + random.uniform(0, poll * 0.25),
                       max(deadline - time.monotonic(), 0.1)))
        poll = min(poll * 1.6, max_poll_s)
    sg = ShardedGraph.load(part_path, parts=parts)
    if sg.num_parts != n_partitions:
        raise ValueError(
            f"partition artifact at {part_path} has {sg.num_parts} parts, "
            f"requested {n_partitions}"
        )
    return sg


def run(args) -> dict:
    """Full training run; returns a result dict (accuracies, timings).
    Its set-up is recorded as spans (obs/trace.py): setup/process (the
    process's first run only), setup/runtime, setup/services,
    setup/graph, setup/trainer, then fit's own."""
    recorder().begin_run()
    # seed semantics: random unless --fix-seed (reference main.py:11-14)
    if not args.fix_seed:
        if args.parts_per_node < args.n_partitions:
            warnings.warn("Please enable `--fix-seed` for multi-node training.")
        args.seed = random.randint(0, 1 << 31)

    if args.model not in ("graphsage", "gcn", "gat"):
        raise ValueError(f"unknown model: {args.model}")
    if args.model in ("gcn", "gat") and args.use_pp:
        raise ValueError("--use-pp is a GraphSAGE-only optimization")
    if args.backend in ("nccl", "mpi"):
        raise NotImplementedError(
            f"backend {args.backend!r} is not supported; use 'xla'"
        )
    if args.backend not in ("xla", "gloo"):
        raise ValueError(f"unknown backend: {args.backend}")
    if args.resume and not args.checkpoint_dir:
        # fail BEFORE the partition/trainer build: a silent no-op
        # resume restarted multi-day runs from epoch 0 unnoticed
        raise ValueError(
            "--resume requires --checkpoint-dir (there is nothing to "
            "resume from)")
    # validate the loss-scale spec BEFORE the partition/trainer build
    # (a typo'd flag must not burn a multi-minute setup)
    from ..resilience.numerics import LossScaleConfig

    LossScaleConfig.parse(getattr(args, "loss_scale", "off"))
    profile_epochs = None
    if getattr(args, "profile_epochs", ""):
        # parse BEFORE the partition/trainer build: a malformed window
        # must not burn a multi-minute setup
        from ..obs.profiler import parse_profile_epochs

        profile_epochs = parse_profile_epochs(args.profile_epochs)
        if not args.profile_dir:
            raise ValueError(
                "--profile-epochs needs --profile-dir (there is "
                "nowhere to write the trace)")
    # parse the delta schedule BEFORE the partition/trainer build: a
    # missing or corrupt delta file must not burn a multi-minute setup
    # (parse() CRC-checks every batch up front)
    stream_plan = None
    streaming = bool(getattr(args, "stream_plan", "")) or \
        "graph-delta" in getattr(args, "fault_plan", "")
    if getattr(args, "stream_plan", ""):
        from ..stream import StreamPlan

        stream_plan = StreamPlan.parse(args.stream_plan)

    with span("setup/runtime"):
        # deferred jax import so the parser works without initializing
        # backends
        import jax

        from ..backend import device_line, place_compile_cache

        recorder().install()
        cache_dir = place_compile_cache()
        _maybe_init_distributed(args)
        # what this process actually runs on: a run where JAX came up
        # on the CPU must be visible in the log, not only in the
        # metrics header
        print(device_line())
        print(f"compile cache: {cache_dir}")

    from ..parallel.trainer import TrainConfig, Trainer
    from ..resilience import CoordConfig, Coordinator

    with span("setup/services"):
        # cross-rank coordination: inactive (pure no-ops) in single-process
        # runs, so fit() keeps one code path. Built BEFORE the partition
        # build and started immediately: heartbeats must flow while this
        # rank spends minutes partitioning / compiling, or its
        # already-training-blocked peers would mistake the silence for
        # death. The shared coordination dir (heartbeats + desync resync)
        # defaults under the partition dir — the filesystem multi-host runs
        # already share — keyed by the rendezvous endpoint so concurrent
        # runs never cross-talk. The consensus channel itself needs the
        # training mesh and is attached after the trainer build.
        coord_dir = args.watchdog_dir or os.path.join(
            args.partition_dir,
            f"coord-{args.master_addr}-{args.port}")
        # under elastic supervision (cli.elastic) the membership generation
        # keys the heartbeat filenames, so a relaunched fleet never sees a
        # previous incarnation's files (resilience/elastic.py)
        try:
            membership_gen = int(os.environ.get("PIPEGCN_MEMBERSHIP_GEN", -1))
        except ValueError:
            membership_gen = -1
        if membership_gen >= 0:
            print(f"elastic membership generation {membership_gen}")
        coord = Coordinator(
            cfg=CoordConfig(
                dir=coord_dir,
                watchdog_timeout=args.watchdog_timeout,
                desync_every=args.desync_check_every,
                desync_resync=args.desync_resync,
                generation=membership_gen,
            ),
            log=print)
        coord.start()

        # ---- flight recorder (obs/flight.py): on by default, breadcrumbs
        # from here on dump to blackbox-r<k>.json in the coordination dir
        # on fault / unhandled exception / preemption / watchdog trip, and
        # on demand via SIGQUIT (kill -QUIT <pid>) ----
        from ..obs import flight as flightrec

        flightrec.configure(rank=jax.process_index(), dump_dir=coord_dir)
        flightrec.install_signal_dump()
        flightrec.crumb("run-start", dataset=args.dataset,
                        n_partitions=args.n_partitions,
                        node_rank=args.node_rank)

    with span("setup/graph") as graph_span:
        if streaming:
            # streaming needs the live host graph + parts the artifact
            # path discards, so it always builds in memory (with slack
            # headroom)
            sg, eval_graphs, host_g, host_parts = _prepare_streaming(args)
        else:
            sg, eval_graphs = _prepare(args)
    prepare_s = graph_span.dur_s
    # partition-size report (reference prints each rank's node count at
    # setup, train.py:267-268)
    sizes = ", ".join(str(int(c)) for c in sg.inner_count)
    print(f"partition sizes (inner nodes per device): {sizes}")

    n_feat = args.n_feat or sg.n_feat
    n_class = args.n_class or sg.n_class
    n_train = args.n_train or sg.n_train_global
    layer_sizes = (n_feat,) + (args.n_hidden,) * (args.n_layers - 1) + (n_class,)
    cfg = ModelConfig(
        layer_sizes=layer_sizes,
        model=args.model,
        n_heads=args.n_heads,
        n_linear=args.n_linear,
        use_pp=args.use_pp,
        norm=None if args.norm == "none" else args.norm,
        dropout=args.dropout,
        train_size=n_train,
        spmm_chunk=args.spmm_chunk or None,
        spmm_impl=args.spmm_impl,
        block_tile=args.block_tile,
        block_nnz=args.block_nnz or None,
        block_group=args.block_group,
        bucket_merge=args.bucket_merge,
        tune=args.tune,
        tuner_samples=args.tuner_samples,
        rem_dtype=args.rem_dtype,  # 'none' normalized by ModelConfig
        rem_amax=args.rem_amax,
        dropout_bits=args.dropout_bits,
        dtype=args.dtype,
    )
    tcfg = TrainConfig(
        lr=args.lr,
        weight_decay=args.weight_decay,
        n_epochs=args.n_epochs,
        enable_pipeline=args.enable_pipeline,
        feat_corr=args.feat_corr,
        grad_corr=args.grad_corr,
        corr_momentum=args.corr_momentum,
        log_every=args.log_every,
        seed=args.seed,
        eval=args.eval,
        fused_epochs=args.fused_epochs,
        rng_impl=args.rng_impl,
        dropout_reuse=args.dropout_reuse,
        halo_dtype=args.halo_dtype,
        epoch_block=args.epoch_block,
        comm_prefetch=args.comm_prefetch,
        numerics_tripwire=args.numerics_tripwire,
        loss_scale=args.loss_scale,
        integrity_check_every=args.integrity_check_every,
        train_traces=not args.no_train_traces,
    )
    with span("setup/trainer"):
        trainer = Trainer(sg, cfg, tcfg)
    # set-up is paid by every run and is larger than a short run's
    # training: say where it went (host wall clock, not a device metric)
    setup_s = {"graph_partition": round(prepare_s, 2),
               **{k: round(v, 2) for k, v in trainer.setup_s.items()}}
    print("setup seconds: " + ", ".join(
        f"{k}={v}" for k, v in setup_s.items())
        + f" | partition artifact: {sg.source}"
        + f" | kernel tables: {trainer.tables_source or 'none'}"
        + _pad_text(trainer.tables_pad))

    patcher = None
    journal = None
    if streaming:
        from ..stream import DeltaJournal, GraphPatcher

        patcher = GraphPatcher(host_g, sg, host_parts,
                               slack=args.stream_slack)
        trainer.enable_stream(patcher)
        n_due = stream_plan.remaining() if stream_plan is not None else 0
        print(f"streaming enabled: {n_due} delta batch(es) scheduled, "
              f"slack={args.stream_slack:.0%}, "
              f"headroom={patcher.slack_remaining()}")
        # write-ahead delta journal: defaults under the checkpoint dir
        # so the elastic supervisor / soak harness inherit durability
        # with zero extra plumbing; --journal-dir overrides
        journal_dir = getattr(args, "journal_dir", "") or (
            os.path.join(args.checkpoint_dir, "journal")
            if args.checkpoint_dir else "")
        if journal_dir:
            journal = DeltaJournal(journal_dir)
            print(f"delta journal at {journal_dir} "
                  f"(last durable seq {journal.last_seq()})")

    graph_name = args.graph_name or derive_graph_name(args)
    os.makedirs(args.results_dir, exist_ok=True)
    rfile = result_file_name(args)

    start_epoch = 0
    replay_stats = None
    wm_seq, wm_gen = -1, 0
    if args.resume:
        if checkpoint_exists(args.checkpoint_dir):
            if journal is not None:
                # crash-consistent streaming resume: the graph below is
                # NOMINAL (checkpoints never hold topology), so replay
                # every journaled seq <= the checkpoint's watermark
                # BEFORE loading state — the params must meet the graph
                # they trained against (a replayed re-pad also restores
                # the carry shapes the checkpoint was saved with). Seqs
                # past the watermark are uncommitted: truncated here,
                # re-delivered by the plan at their scheduled epochs.
                from ..stream import replay_for_resume

                wm_seq, wm_gen = peek_watermark(args.checkpoint_dir)
                replay_stats = replay_for_resume(
                    journal, wm_seq, trainer.apply_graph_deltas,
                    plan=stream_plan)
                if stream_plan is not None:
                    stream_plan.skip_journaled(wm_seq)
                print(f"journal replay to watermark seq={wm_seq}: "
                      f"{replay_stats['replayed']} replayed, "
                      f"{replay_stats['rederived']} re-derived from "
                      f"the plan, {replay_stats['truncated']} "
                      f"uncommitted entr(ies) rolled back; "
                      f"topo_generation={trainer.topo_generation}"
                      + (f" (checkpoint says {wm_gen})"
                         if trainer.topo_generation != wm_gen else ""))
            # host_state() (not device_get): the sharded comm carry is
            # not process-addressable in multi-host runs; every process
            # resumes together, so the allgather inside is lockstep
            host_state, start_epoch = load_checkpoint(
                args.checkpoint_dir, trainer.host_state()
            )
            trainer.restore_state(host_state)
            print(f"resumed from {args.checkpoint_dir} "
                  f"at epoch {start_epoch}")
        else:
            warnings.warn(
                f"--resume: no checkpoint found in "
                f"{args.checkpoint_dir!r}; starting a FRESH run from "
                f"epoch 0 (first checkpoint will be written there)")
            print(f"WARNING: --resume found no checkpoint in "
                  f"{args.checkpoint_dir!r}; training from scratch")

    with span("setup/services"):
        metrics = None
        sink_prev = None
        if args.metrics_out:
            from ..obs import MetricsLogger, device_info, mesh_info

            metrics = MetricsLogger(args.metrics_out)
            # args-level header (richer than the trainer's fallback): the
            # exact CLI invocation that produced the numbers
            metrics.run_header(
                config=vars(args),
                device=device_info(),
                mesh={"n_parts": args.n_partitions,
                      **mesh_info(trainer.mesh)},
                setup_s=setup_s, tables_pad=trainer.tables_pad,
                **trainer.dropout_mask_stats(),
            )
            if replay_stats is not None:
                # the resume replay ran before the sink existed; its audit
                # records land here (soak invariant #9 + the topo-rollback
                # postmortem rule read them)
                metrics.journal(
                    op="replay", seq=wm_seq,
                    topo_generation=int(trainer.topo_generation),
                    n_records=replay_stats["replayed"], source="resume",
                    rederived=replay_stats["rederived"],
                    watermark_generation=wm_gen)
                if replay_stats["truncated"]:
                    metrics.journal(
                        op="truncate", seq=wm_seq,
                        topo_generation=int(trainer.topo_generation),
                        n_records=replay_stats["truncated"],
                        source="resume")
            if not args.no_train_traces:
                # the set-up spans recorded so far, and every later one
                sink_prev = recorder().attach(
                    metrics, f"r{jax.process_index()}")

        # ---- fault tolerance (docs/RESILIENCE.md) ----
        from ..resilience import (DivergenceSentinel, FaultPlan,
                                  PreemptionHandler, SentinelConfig)

        sentinel = None
        if getattr(args, "sentinel", True):
            sentinel = DivergenceSentinel(SentinelConfig(
                loss_factor=args.sentinel_loss_factor,
                grad_norm_max=args.sentinel_grad_max,
                max_retries=args.sentinel_max_retries,
                lr_backoff=args.sentinel_lr_backoff,
                snapshot_every=args.sentinel_snapshot_every,
                flush_on_trip=args.sentinel_flush,
            ))
        fault_plan = FaultPlan.parse(args.fault_plan,
                                     rank=jax.process_index()) \
            if args.fault_plan else None
        preemption = PreemptionHandler()
        # the coordinator has been heartbeating since before the partition
        # build; now that the mesh and metrics sink exist, complete it
        coord.attach_mesh(trainer.mesh)
        coord.metrics = metrics

    if getattr(args, "anatomy", False):
        # compiled-step anatomy: FLOPs/bytes per phase from the
        # optimized HLO (obs/anatomy.py). Costs one single-epoch
        # compile up front — opt-in for that reason.
        from ..obs.anatomy import step_anatomy

        rec = step_anatomy(trainer)
        frac = rec.get("attributed_flops_fraction")
        print(f"epoch anatomy: {rec['n_ops']} HLO ops, "
              f"{rec['est_flops']:.3e} est FLOPs"
              + (f", {frac:.1%} attributed to named phases"
                 if frac is not None else ""))
        if metrics is not None:
            extras = {k: v for k, v in rec.items()
                      if k not in ("phases", "est_flops", "flops",
                                   "attributed_flops_fraction")}
            metrics.anatomy(rec["phases"], rec["est_flops"],
                            rec["flops"],
                            rec["attributed_flops_fraction"], **extras)

    try:
        with preemption.installed(enabled=not args.no_signal_handlers):
            fit_res = trainer.fit(
                eval_graphs,
                start_epoch=start_epoch,
                reference_logs=True,
                result_file=rfile,
                inductive=args.inductive,
                checkpoint_dir=args.checkpoint_dir or None,
                checkpoint_every=args.checkpoint_every,
                checkpoint_keep=args.checkpoint_keep,
                checkpoint_fallback_dir=getattr(
                    args, "checkpoint_fallback_dir", "") or None,
                profile_dir=args.profile_dir or None,
                profile_epochs=profile_epochs,
                staleness_probe_every=args.staleness_probe_every,
                measure_comm_cost=True,
                sharded_eval=args.sharded_eval,
                async_eval=not args.sync_eval,
                metrics=metrics,
                sentinel=sentinel,
                preemption=preemption,
                fault_plan=fault_plan,
                stream_plan=stream_plan,
                journal=journal,
                coord=coord,
            )
            if journal is not None and args.resume:
                # prove the replayed topology: the patched tables must
                # digest-match a from-scratch rebuild of the post-delta
                # graph (the PR-13 bit-identity oracle as a runtime
                # check; soak invariant #9 reads this record)
                from ..stream import verify_against_rebuild

                v = verify_against_rebuild(patcher)
                print(f"journal verify: tables_match="
                      f"{v['tables_match']}, topo_generation="
                      f"{trainer.topo_generation}"
                      + (f", mismatched tables: {v['mismatch']}"
                         if v["mismatch"] else ""))
                if metrics is not None:
                    metrics.journal(
                        op="verify", seq=int(patcher.last_seq),
                        topo_generation=int(trainer.topo_generation),
                        n_records=0, source="resume",
                        tables_match=bool(v["tables_match"]),
                        mismatch=list(v["mismatch"]))
    finally:
        coord.stop()
        # every record is already flushed; close releases the handle
        # even when training crashes mid-run
        if metrics is not None:
            recorder().restore(sink_prev)
            metrics.close()

    result = {
        "graph_name": graph_name,
        "epoch_time": fit_res["epoch_time"],
        "best_val": fit_res["best_val"],
        "best_epoch": fit_res["best_epoch"],
        "setup_s": setup_s,
        # in-process callers (chip_smoke.py) inspect placement, the
        # tuner's decision and the fallback list on the live object
        "trainer": trainer,
    }
    if args.metrics_out:
        result["metrics_out"] = args.metrics_out
    if args.eval and fit_res["best_params"] is not None:
        os.makedirs(args.model_dir, exist_ok=True)
        model_path = os.path.join(args.model_dir, f"{graph_name}_final.npz")
        # multi-host: identical replicated params on every process;
        # process 0 writes (save_pytree's pid-temp makes a stray
        # concurrent writer harmless, but N copies are pure waste)
        import jax

        if jax.process_index() == 0:
            save_pytree(model_path, fit_res["best_params"])
        print("model saved")
        print("Validation accuracy {:.2%}".format(fit_res["best_val"]))
        print("Test Result | Accuracy {:.2%}".format(fit_res["test_acc"]))
        result["test_acc"] = fit_res["test_acc"]
        result["model_path"] = model_path
    return result


def cli_entry() -> None:
    import sys

    from ..resilience import EXIT_PREEMPTED, PeerLost, Preempted
    from .parser import create_parser

    args = create_parser().parse_args()
    print(args)
    try:
        run(args)
    except Preempted as p:
        # distinct resumable status (EX_TEMPFAIL): a supervisor retries
        # with --resume on 75, treats anything else as a real failure
        print(f"preempted at epoch {p.epoch} ({p.reason}); resumable — "
              f"rerun with --resume --checkpoint-dir "
              f"{args.checkpoint_dir!r} [exit {EXIT_PREEMPTED}]")
        import jax

        if jax.process_count() > 1:
            # a ONE-SIDED preemption (an SDC quarantine asks only the
            # striking rank to leave) strands the peers mid-epoch: the
            # graceful exit's distributed-shutdown barrier can never
            # complete once they watchdog out, and the coordination
            # client would SIGABRT over the resumable status — same
            # reasoning as the PeerLost branch below
            sys.stdout.flush()
            sys.stderr.flush()
            os._exit(EXIT_PREEMPTED)
        sys.exit(EXIT_PREEMPTED)
    except PeerLost as p:
        # a dead peer is the platform's problem, not this state's: the
        # crash checkpoint is valid, so the supervisor reschedules the
        # whole pod and resumes — same contract as preemption. Exit via
        # os._exit: a graceful sys.exit runs jax's atexit distributed
        # shutdown, whose barrier can never complete with a dead peer —
        # the coordination client then hard-aborts the process (SIGABRT)
        # and the resumable status is lost.
        print(f"peer lost ({p}); resumable — restart the pod with "
              f"--resume --checkpoint-dir {args.checkpoint_dir!r} "
              f"[exit {EXIT_PREEMPTED}]")
        sys.stdout.flush()
        sys.stderr.flush()
        # os._exit skips atexit AND io teardown; the metrics sink was
        # closed (flushed) by run()'s finally, and fault records are
        # fsynced at write time (MetricsLogger.hard_flush), so the
        # final peer-lost record is already durable here
        os._exit(EXIT_PREEMPTED)
    except (Exception, KeyboardInterrupt) as exc:
        # unhandled exception: leave a black box beside the run before
        # the traceback propagates (skipped when the recorder was
        # never pointed at a run dir — the failure predates setup).
        # fit()'s own crash handler already dumped in-training
        # failures; re-dumping the same path with the newest crumbs is
        # idempotent.
        from ..obs import flight as flightrec

        if flightrec.get_recorder().dump_dir:
            flightrec.dump_blackbox(
                "exception",
                error=f"{type(exc).__name__}: {exc}"[:200])
        raise


if __name__ == "__main__":
    cli_entry()
