"""Serving-fleet entrypoint: `python -m pipegcn_tpu.cli.fleet`.

Two modes sharing one parser:

  driver (default)       resolves the partition artifact once, launches
                         --replicas N replica subprocesses (each a full
                         CPU/TPU mesh), waits for their readiness
                         files, fronts them with the failover Router,
                         and drives the open-loop fleet load loop
                         (serve/fleet.py). SIGTERM/SIGINT drain: every
                         accepted ticket is served by a survivor or
                         explicitly shed before the final record.

  replica (--replica-id K)  builds the ServingEngine exactly like
                         cli/serve.py (same flags — the driver forwards
                         its own argv) and serves it over TCP with
                         heartbeats + the zero-downtime checkpoint
                         hot-swap watcher. Its metrics land in
                         <fleet-dir>/replica-mK-iI-metrics.jsonl.

The replica-kill@W[:mK] entries of --fault-plan fire at serving-window
boundaries in the driver (SIGKILL replica K at window W), which is how
scripts/chaos.sh's fleet lane drills the failover path.
"""

from __future__ import annotations

import json
import os
import signal
import sys

from .serve import build_parser as _serve_build_parser


def build_parser():
    p = _serve_build_parser()
    g = p.add_argument_group("fleet")
    g.add_argument("--replicas", type=int, default=1,
                   help="number of serving replicas (each its own "
                        "process + mesh)")
    g.add_argument("--replica-id", "--replica_id", type=int, default=-1,
                   help="INTERNAL: run as replica K instead of the "
                        "driver")
    g.add_argument("--incarnation", type=int, default=0,
                   help="INTERNAL: relaunch count of this replica slot")
    g.add_argument("--fleet-dir", "--fleet_dir", type=str, default="",
                   help="shared directory for readiness files, "
                        "heartbeats, and per-replica logs "
                        "(default: <partition-dir>/fleet)")
    g.add_argument("--fleet-policy", "--fleet_policy", type=str,
                   default="least-queue", choices=("least-queue", "hash"),
                   help="router placement: least in-flight rows, or "
                        "consistent-hash on the batch's first node id")
    g.add_argument("--fleet-swap-poll", "--fleet_swap_poll", type=float,
                   default=0.5,
                   help="seconds between replica checkpoint-watcher "
                        "polls (zero-downtime hot-swap cadence)")
    g.add_argument("--fleet-heartbeat-timeout",
                   "--fleet_heartbeat_timeout", type=float, default=3.0,
                   help="replica heartbeat silence that counts as death")
    g.add_argument("--fleet-retry-timeout", "--fleet_retry_timeout",
                   type=float, default=5.0,
                   help="per-batch failover retry budget before the "
                        "batch is shed")
    g.add_argument("--fleet-max-restarts", "--fleet_max_restarts",
                   type=int, default=4,
                   help="lifetime relaunch cap per replica slot")
    g.add_argument("--fleet-ready-timeout", "--fleet_ready_timeout",
                   type=float, default=180.0,
                   help="seconds to wait for a replica's readiness file")
    a = p.add_argument_group("autoscale")
    a.add_argument("--autoscale", action="store_true",
                   help="close the loop: feed each window's telemetry "
                        "(+ alert fire edges) to the scale policy "
                        "(serve/autoscale.py) and let it spawn/retire "
                        "replicas between --autoscale-min/max; turns "
                        "on the graceful-degradation admission ladder")
    a.add_argument("--autoscale-min", "--autoscale_min", type=int,
                   default=1, help="replica floor under scale-down")
    a.add_argument("--autoscale-max", "--autoscale_max", type=int,
                   default=0,
                   help="replica ceiling under scale-up "
                        "(0 = max(4, --replicas))")
    a.add_argument("--autoscale-queue-high", "--autoscale_queue_high",
                   type=int, default=0,
                   help="queue rows that count as sustained pressure "
                        "(0 = half of --serve-max-queue, else 64)")
    a.add_argument("--autoscale-queue-low", "--autoscale_queue_low",
                   type=int, default=0,
                   help="queue rows below which a window counts as "
                        "idle (0 = an eighth of --serve-max-queue, "
                        "else 8)")
    a.add_argument("--autoscale-shed-high", "--autoscale_shed_high",
                   type=float, default=0.01,
                   help="window shed fraction that triggers an "
                        "immediate scale-up")
    a.add_argument("--autoscale-p99-slo", "--autoscale_p99_slo",
                   type=float, default=0.0,
                   help="p99 latency SLO in ms; sustained violation "
                        "triggers scale-up (0 = no latency trigger)")
    a.add_argument("--autoscale-cooldown", "--autoscale_cooldown",
                   type=float, default=10.0,
                   help="seconds between executed scale actions (the "
                        "anti-flap brake and the ramp rate)")
    a.add_argument("--degrade-ladder", "--degrade_ladder",
                   action="store_true",
                   help="graceful-degradation admission ladder without "
                        "autoscaling: tighten the effective queue "
                        "bound and ticket deadline as pressure rises "
                        "(brownout before blackout)")
    return p


def _replica_main(args) -> int:
    """Child mode: one serving replica process."""
    from ..obs import MetricsLogger
    from ..serve.fleet import ReplicaServer
    from .serve import build_serving_engine

    if not args.fleet_dir:
        raise ValueError("--replica-id requires --fleet-dir")
    os.makedirs(args.fleet_dir, exist_ok=True)
    rid, inc = args.replica_id, args.incarnation

    def log(msg):
        print(f"[replica {rid} i{inc}] {msg}", flush=True)

    # replicas never build the artifact (the driver did; N builders
    # would race) — they await it like any late-joining server
    args.serve_build = False
    trainer, engine, _epoch = build_serving_engine(args, log=log)

    ml = MetricsLogger(os.path.join(
        args.fleet_dir, f"replica-m{rid}-i{inc}-metrics.jsonl"))
    ml.run_header(config={"replica": rid, "incarnation": inc,
                          "n_partitions": args.n_partitions})

    # crash-consistent streaming: when a durable delta journal exists
    # (the trainer's WAL, stream/journal.py), the replica replays every
    # journaled topology delta against its freshly-loaded NOMINAL
    # artifact BEFORE publishing readiness — the fleet never routes to
    # a replica serving a stale graph. The callable runs inside
    # serve_forever, after the port binds but before the ready file.
    journal_dir = getattr(args, "journal_dir", "") or (
        os.path.join(args.checkpoint_dir, "journal")
        if args.checkpoint_dir else "")
    replay = None
    if journal_dir and os.path.isdir(journal_dir):
        def replay():
            import numpy as np

            from ..graph.datasets import load_data
            from ..stream import DeltaJournal, GraphPatcher

            journal = DeltaJournal(journal_dir)
            entries = journal.entries()
            if not entries:
                return 0
            # the patcher needs the host graph + partition assignment
            # the artifact was built from: reload the dataset (replicas
            # share the driver's flags, so this is the same graph) and
            # derive the assignment from the shard's global-id rows
            g = load_data(args.dataset, args.data_root)
            sg = trainer.sg
            parts = np.zeros(g.num_nodes, np.int32)
            for p in range(sg.num_parts):
                n = int(sg.inner_count[p])
                parts[np.asarray(sg.global_nid[p, :n])] = p
            patcher = GraphPatcher(
                g, sg, parts,
                slack=getattr(args, "stream_slack", 0.10))
            trainer.enable_stream(patcher)
            for _gen, batch in entries:
                rep = trainer.apply_graph_deltas(batch)
                engine.apply_graph_deltas(rep)
            engine.refresh_boundary()
            return len(entries)

    server = ReplicaServer(
        engine, args.fleet_dir, rid, incarnation=inc, ml=ml,
        checkpoint_dir=args.checkpoint_dir or None,
        swap_poll_s=args.fleet_swap_poll,
        report_every_s=args.serve_report_every, replay=replay, log=log)

    def _on_signal(signum, frame):  # noqa: ARG001
        server.request_stop()

    for s in (signal.SIGTERM, signal.SIGINT):
        signal.signal(s, _on_signal)
    try:
        server.serve_forever()
    finally:
        ml.close()
    return 0


def _driver_main(args, argv) -> int:
    from ..resilience.faults import FaultPlan
    from ..serve.fleet import FleetManager, run_fleet_loop
    from ..serve.router import Router
    from .serve import _load_partition

    import numpy as np

    if args.replicas < 1:
        raise ValueError("--replicas must be >= 1")
    fleet_dir = args.fleet_dir or os.path.join(
        args.partition_dir, "fleet")
    os.makedirs(fleet_dir, exist_ok=True)

    # resolve (and, under --serve-build, build) the artifact ONCE
    # before any replica launches — the replicas then just load it
    sg = _load_partition(args)
    num_nodes = int((np.asarray(sg.global_nid) >= 0).sum())

    ml = None
    if args.metrics_out:
        from ..obs import MetricsLogger

        ml = MetricsLogger(args.metrics_out)
        ml.run_header(config=vars(args),
                      mesh={"n_parts": args.n_partitions,
                            "replicas": args.replicas})

    # children inherit the environment; make sure the virtual-device
    # trick covers the mesh when nobody set XLA_FLAGS explicitly
    env = dict(os.environ)
    if "xla_force_host_platform_device_count" not in \
            env.get("XLA_FLAGS", ""):
        env["XLA_FLAGS"] = (
            env.get("XLA_FLAGS", "") +
            f" --xla_force_host_platform_device_count="
            f"{args.n_partitions}").strip()

    manager = FleetManager(
        fleet_dir, args.replicas, child_args=list(argv), ml=ml,
        env=env, heartbeat_timeout_s=args.fleet_heartbeat_timeout,
        ready_timeout_s=args.fleet_ready_timeout,
        max_restarts=args.fleet_max_restarts)
    clients = manager.launch_all()

    def on_fault(rid, reason):
        # one replica-dead + one kind="fleet" fault per death edge,
        # whether the router's dispatch or the supervisor saw it first
        if ml is not None:
            ml.fleet("replica-dead", rid, window=manager.window,
                     reason=reason)
            ml.fault("fleet", epoch=max(manager.window, 0), rank=rid,
                     reason=reason)

    def on_failover(to_rid, n_rows, n_attempts):
        if ml is not None:
            ml.fleet("failover", to_rid, window=manager.window,
                     n_retried=n_rows, attempts=n_attempts)

    router = Router(clients, policy=args.fleet_policy,
                    retry_timeout_s=args.fleet_retry_timeout,
                    on_fault=on_fault, on_failover=on_failover)

    fault_plan = None
    if getattr(args, "fault_plan", None):
        fault_plan = FaultPlan.parse(args.fault_plan)

    # ---- the closed loop: telemetry -> policy -> fleet actuation ----
    autoscaler = None
    ladder = None
    alerts_fn = None
    if args.autoscale or args.degrade_ladder:
        from ..serve.batcher import AdmissionLadder

        ladder = AdmissionLadder()
    if args.autoscale:
        from ..serve.autoscale import AutoscalePolicy

        max_q = args.serve_max_queue or 0
        q_high = args.autoscale_queue_high or (max_q // 2 if max_q
                                               else 64)
        q_low = args.autoscale_queue_low or max(1, (max_q // 8
                                                    if max_q else 8))
        autoscaler = AutoscalePolicy(
            min_replicas=args.autoscale_min,
            max_replicas=args.autoscale_max or max(4, args.replicas),
            queue_high=q_high, queue_low=q_low,
            shed_high=args.autoscale_shed_high,
            p99_slo_ms=args.autoscale_p99_slo or None,
            cooldown_s=args.autoscale_cooldown)
        if ml is not None:
            # the AlertEngine leg of the loop: tail the driver's own
            # metrics stream (plus any sibling streams in its dir) and
            # surface fire edges as policy evidence
            import time as _time

            from ..obs.health import AlertEngine
            from ..obs.live import LiveAggregator

            _agg = LiveAggregator(
                os.path.dirname(os.path.abspath(args.metrics_out))
                or ".", clock=_time.time)
            _alert_engine = AlertEngine(clock=_time.time)

            def alerts_fn():
                _agg.poll()
                edges = _alert_engine.evaluate(_agg)
                return [e["rule"] for e in edges
                        if e.get("state") == "fire"]

    stop_flag = {"stop": False}

    def _on_signal(signum, frame):  # noqa: ARG001
        stop_flag["stop"] = True

    old = [signal.signal(s, _on_signal)
           for s in (signal.SIGTERM, signal.SIGINT)]
    try:
        summary = run_fleet_loop(
            manager, router,
            num_nodes=num_nodes,
            duration_s=args.serve_duration,
            qps=args.serve_qps,
            max_batch=args.serve_max_batch,
            max_delay_ms=args.serve_max_delay_ms,
            ladder_min=args.serve_ladder_min,
            report_every_s=args.serve_report_every,
            max_queue=args.serve_max_queue or None,
            ticket_deadline_ms=args.serve_ticket_deadline_ms or None,
            seed=args.seed,
            ml=ml,
            fault_plan=fault_plan,
            traffic=args.traffic or None,
            update_fraction=args.update_fraction,
            ladder=ladder,
            autoscaler=autoscaler,
            alerts_fn=alerts_fn,
            trace_sample_rate=args.trace_sample_rate,
            stop=lambda: stop_flag["stop"],
        )
    finally:
        for s, h in zip((signal.SIGTERM, signal.SIGINT), old):
            signal.signal(s, h)
        manager.stop_all()
        if ml is not None:
            ml.close()
    print(json.dumps({"fleet": True, "replicas": args.replicas,
                      **summary}))
    return 0


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else list(argv)
    args = build_parser().parse_args(argv)
    if args.replica_id >= 0:
        return _replica_main(args)
    return _driver_main(args, argv)


if __name__ == "__main__":
    sys.exit(main())
