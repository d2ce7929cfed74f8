"""Serving fleet (pipegcn_tpu/serve/fleet.py + router.py,
docs/SERVING.md "Fleet").

These tests pin the round-12 fleet contracts:
  - router placement (least in-flight rows with id tiebreak; the
    consistent-hash ring's stability/spread and dead-arc-only remap),
    edge-triggered mark_down/mark_up, failover retry against
    survivors, and FleetUnavailable when nobody answers;
  - MicroBatcher's take/complete/shed split (the threaded dispatch
    path) and the conservation invariant
    submitted == served + shed + queue_depth;
  - the replica-kill@W[:mK] fault-plan grammar: parse, default member,
    single-shot due_member, boundary retirement on resume, rejection
    of malformed entries;
  - ReplicaServer over real TCP in-process: readiness file,
    incarnation-keyed heartbeat, query/health/stop ops, the final
    hard-flushed serving record;
  - the checkpoint hot-swap watcher: poll_checkpoint's hot-swap /
    swap-rejected fleet records, and ServingEngine.load_from_checkpoint
    against a real mesh — walk-back past a corrupt newest generation,
    per-generation fault dedupe, staleness bookkeeping;
  - run_fleet_loop end to end on fakes (hash policy, fake clock): a
    scripted replica-kill mid-load, failover to the survivor, zero
    accepted tickets lost, schema-valid serving records;
  - the two-process replica-kill drill (slow, chaos lane): SIGKILL a
    live replica subprocess mid-load; the router routes to the
    survivor, the supervisor relaunches + rejoins it, and the driver
    exits 0 with the conservation invariant intact.
"""

import json
import os
import signal
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from pipegcn_tpu.obs.metrics import MetricsLogger, read_metrics
from pipegcn_tpu.obs.schema import validate_record
from pipegcn_tpu.resilience import FaultPlan, corrupt_latest_checkpoint
from pipegcn_tpu.serve.batcher import MicroBatcher
from pipegcn_tpu.serve.fleet import (
    ReplicaError,
    ReplicaServer,
    TcpReplicaClient,
    _heartbeat_path,
    _read_ready,
    run_fleet_loop,
)
from pipegcn_tpu.serve.router import FleetUnavailable, Router

pytestmark = pytest.mark.fleet


# ---------------- fakes ------------------------------------------------


class FakeTime:
    """Injectable clock whose sleep() advances it (no real waiting)."""

    def __init__(self, t=0.0):
        self.t = float(t)

    def clock(self):
        return self.t

    def sleep(self, s):
        self.t += max(float(s), 0.0)


class FakeClient:
    """Replica client double: answers [ids, 2*ids] until killed."""

    def __init__(self, rid):
        self.rid = rid
        self.alive = True

    def query(self, ids):
        if not self.alive:
            raise ConnectionError(f"replica {self.rid} is dead")
        ids = np.asarray(ids)
        return np.stack([ids, ids * 2], axis=1).astype(np.float32)


class FakeManager:
    """The run_fleet_loop-facing surface of FleetManager, minus the
    subprocesses: kill_replica flips the fake client dead and the
    supervision poll is a no-op (no rejoin)."""

    def __init__(self, clients):
        self.n_replicas = len(clients)
        self.replicas = {rid: None for rid in clients}
        self.window = -1
        self._clients = clients

    def log(self, msg):
        pass

    def poll(self, router=None):
        pass

    def kill_replica(self, rid):
        self._clients[rid].alive = False


# ---------------- router: placement ------------------------------------


def test_router_least_queue_placement_and_counters():
    c = {0: FakeClient(0), 1: FakeClient(1)}
    r = Router(c, sleep=lambda s: None)
    out, rid = r.dispatch(np.array([5, 6]))
    assert rid == 0  # empty queues tie; ties break by replica id
    np.testing.assert_array_equal(out[:, 0], [5, 6])
    # the shallower queue wins
    with r._lock:
        r._inflight[0] = 10
    _, rid = r.dispatch(np.array([1]))
    assert rid == 1
    with r._lock:
        r._inflight[0] = 0
    assert r.n_dispatched == {0: 2, 1: 1}
    assert r.queue_depths() == {0: 0, 1: 0}
    assert r.n_failovers == 0 and r.n_retried_rows == 0


def test_router_hash_ring_stability_spread_and_remap():
    c = {0: FakeClient(0), 1: FakeClient(1), 2: FakeClient(2)}
    r = Router(c, policy="hash", sleep=lambda s: None)
    keys = list(range(200))
    owner = {k: r._hash_pick(k, set()) for k in keys}
    counts = {rid: sum(1 for v in owner.values() if v == rid)
              for rid in c}
    # 64 vnodes/replica keep the arcs reasonably even
    assert all(n > 20 for n in counts.values()), counts
    # a death remaps ONLY the dead replica's keys
    r.mark_down(1)
    owner2 = {k: r._hash_pick(k, set()) for k in keys}
    for k in keys:
        if owner[k] == 1:
            assert owner2[k] in (0, 2)
        else:
            assert owner2[k] == owner[k]
    # rejoin restores the original map exactly (stability)
    r.mark_up(1)
    assert {k: r._hash_pick(k, set()) for k in keys} == owner
    # dispatch routes by the batch's first node id
    _, rid = r.dispatch(np.array([17, 3]))
    assert rid == owner[17]


# ---------------- router: failover -------------------------------------


def test_router_failover_marks_down_retries_and_rejoins():
    ft = FakeTime()
    faults, fos = [], []
    c = {0: FakeClient(0), 1: FakeClient(1)}
    r = Router(c, retry_timeout_s=5.0, backoff_s=0.01,
               on_fault=lambda rid, reason: faults.append((rid, reason)),
               on_failover=lambda rid, n, att: fos.append((rid, n, att)),
               clock=ft.clock, sleep=ft.sleep)
    c[0].alive = False
    out, rid = r.dispatch(np.array([7]))  # picks 0, fails over to 1
    assert rid == 1
    np.testing.assert_array_equal(out[:, 1], [14])
    assert r.up_replicas() == [1]
    assert len(faults) == 1 and faults[0][0] == 0
    assert "dead" in faults[0][1]
    assert fos == [(1, 1, 2)]  # succeeded on attempt 2 with 1 row
    assert r.n_failovers == 1 and r.n_retried_rows == 1
    # mark_down is edge-triggered: no second fault for the same death
    assert r.mark_down(0, "again") is False
    assert len(faults) == 1
    # rejoin puts it back into rotation (up edge only once)
    c[0].alive = True
    assert r.mark_up(0) is True
    assert r.mark_up(0) is False
    assert r.up_replicas() == [0, 1]
    _, rid = r.dispatch(np.array([9]))
    assert rid == 0  # least-queue sees it again


def test_router_fleet_unavailable_when_all_down():
    ft = FakeTime()
    c = {0: FakeClient(0)}
    c[0].alive = False
    r = Router(c, retry_timeout_s=0.5, backoff_s=0.01,
               clock=ft.clock, sleep=ft.sleep)
    with pytest.raises(FleetUnavailable, match="no up replicas"):
        r.dispatch(np.array([1]))
    assert r.up_replicas() == []
    with pytest.raises(ValueError, match="unknown policy"):
        Router(c, policy="round-robin")
    with pytest.raises(ValueError, match="at least one"):
        Router({})


# ---------------- batcher: threaded dispatch split ---------------------


def test_batcher_take_complete_shed_conservation():
    now = [0.0]
    mb = MicroBatcher(run=None, max_batch=8, max_delay_ms=5.0,
                      ladder_min=2, clock=lambda: now[0])
    t1 = mb.submit(np.array([1, 2]))
    t2 = mb.submit(np.array([3]))
    assert mb.take_batch(now[0]) is None  # not due, not forced
    now[0] += 0.006
    take, ids = mb.take_batch(now[0])
    assert take == [t1, t2]
    np.testing.assert_array_equal(ids, [1, 2, 3])
    assert mb.queue_depth == 0 and not t1.done  # taken, not answered
    mb.complete_batch(take, np.stack([ids, ids], 1).astype(np.float32),
                      t_done=now[0])
    assert t1.done and t2.done and not t2.shed
    np.testing.assert_array_equal(t2.result[:, 0], [3])
    assert mb.n_served_rows == 3
    # a taken batch the fleet cannot answer is shed EXPLICITLY
    t3 = mb.submit(np.array([4, 5]))
    take, _ = mb.take_batch(now[0], force=True)
    mb.shed_batch(take, "fleet-down")
    assert t3.done and t3.shed and t3.shed_reason == "fleet-down"
    assert t3.result is None
    assert mb.n_shed_rows == 2 and mb.n_shed_tickets == 1
    # zero tickets silently lost, checkable from outside
    assert mb.n_submitted_rows == (mb.n_served_rows + mb.n_shed_rows
                                   + mb.queue_depth)


# ---------------- fault-plan grammar -----------------------------------


def test_fault_plan_replica_kill_grammar():
    fp = FaultPlan.parse("replica-kill@2:m1,replica-kill@4,kill@5:r1")
    assert "replica-kill@2:m1" in fp.remaining()
    assert "replica-kill@4" in fp.remaining()
    assert "kill@5:r1" in fp.remaining()
    # not due before its window
    assert fp.due_member("replica-kill", 1) is None
    # due at-or-after; consumed single-shot
    assert fp.due_member("replica-kill", 2) == 1
    assert fp.due_member("replica-kill", 3) is None
    # unqualified entry defaults to member 0
    assert fp.due_member("replica-kill", 4) == 0
    assert fp.due_member("replica-kill", 99) is None
    # the kill@E:rN entry is a different axis entirely
    assert "kill@5:r1" in fp.remaining()


def test_fault_plan_replica_kill_boundary_retired():
    fp = FaultPlan.parse("replica-kill@2:m1")
    fp.skip_before(2)  # a resume at window 2 already lived through it
    assert fp.due_member("replica-kill", 99) is None
    assert fp.remaining() == []


def test_fault_plan_replica_kill_rejects_malformed():
    with pytest.raises(ValueError, match="bad fault-plan entry"):
        FaultPlan.parse("replica-kill@x")
    with pytest.raises(ValueError, match="bad fault-plan entry"):
        FaultPlan.parse("replica-kill@2:m1:m2")
    with pytest.raises(ValueError, match="unknown fault kind"):
        FaultPlan.parse("replica-nuke@2")


# ---------------- replica server over real TCP -------------------------


class FakeEngine:
    """ServingEngine double for transport tests: logits [ids, 2*ids]."""

    fully_fresh = True
    staleness_age = 0

    def __init__(self):
        self.param_generation = 3
        self.param_staleness = 1

    def query(self, ids, stats=None):
        ids = np.asarray(ids)
        if stats is not None:
            stats.note_serve(int(ids.size), True, 0)
        return np.stack([ids, ids * 2], axis=1).astype(np.float32)


def test_replica_server_tcp_roundtrip(tmp_path):
    mpath = tmp_path / "replica.jsonl"
    ml = MetricsLogger(str(mpath))
    srv = ReplicaServer(FakeEngine(), str(tmp_path), 0, incarnation=5,
                        ml=ml, heartbeat_interval_s=0.05,
                        swap_poll_s=30.0, report_every_s=30.0,
                        log=lambda m: None)
    th = threading.Thread(target=srv.serve_forever, daemon=True)
    th.start()
    deadline = time.monotonic() + 30
    info = None
    while info is None and time.monotonic() < deadline:
        info = _read_ready(str(tmp_path), 0)
        time.sleep(0.01)
    assert info is not None, "replica never published readiness"
    assert info["incarnation"] == 5 and info["pid"] == os.getpid()
    cl = TcpReplicaClient("127.0.0.1", info["port"], 0)
    try:
        out, meta = cl.query(np.array([1, 2, 3]))
        assert out.dtype == np.float32 and out.shape == (3, 2)
        np.testing.assert_array_equal(out[:, 1], [2, 4, 6])
        assert meta["incarnation"] == 5
        assert meta["param_generation"] == 3
        assert meta["param_staleness"] == 1
        assert meta["hit"] is True
        h = cl.health()
        assert h["ok"] and h["replica"] == 0 and h["n_queries"] == 3
        # protocol errors surface as ReplicaError, connection survives
        with pytest.raises(ReplicaError, match="unknown op"):
            cl._rpc({"op": "bogus"})
        assert cl.health()["ok"]
        # the incarnation-keyed heartbeat is beating
        hb = _heartbeat_path(str(tmp_path), 0, 5)
        deadline = time.monotonic() + 10
        while not os.path.exists(hb) and time.monotonic() < deadline:
            time.sleep(0.01)
        assert os.path.exists(hb)
        cl.stop()
        th.join(timeout=10)
        assert not th.is_alive()
    finally:
        srv.request_stop()
        cl.close()
        ml.close()
    recs = read_metrics(mpath)
    serving = [r for r in recs if r.get("event") == "serving"]
    assert serving and serving[-1].get("final") is True
    assert serving[-1]["replica"] == 0
    assert serving[-1]["incarnation"] == 5
    for r in serving:
        validate_record(r)


# ---------------- checkpoint hot-swap watcher --------------------------


def test_poll_checkpoint_emits_hot_swap_records(tmp_path):
    reports = [
        {"swapped": True, "param_generation": 2, "param_staleness": 0,
         "swap_ms": 12.5},
        {"swapped": False, "reason": "no-newer-generation",
         "param_generation": 2, "param_staleness": 0},
        {"swapped": False, "reason": "newer-generation-corrupt",
         "param_generation": 2, "param_staleness": 1},
    ]

    class Eng:
        fully_fresh = True
        staleness_age = 0
        param_generation = -1
        param_staleness = 0

        def load_from_checkpoint(self, directory, ml=None):
            return reports.pop(0)

    mpath = tmp_path / "m.jsonl"
    with MetricsLogger(str(mpath)) as ml:
        srv = ReplicaServer(Eng(), str(tmp_path), 1, incarnation=2,
                            ml=ml, checkpoint_dir=str(tmp_path / "ckpt"),
                            log=lambda m: None)
        rep = srv.poll_checkpoint()
        assert rep is not None and rep["swapped"]
        assert srv.stats.param_generation == 2
        assert srv.poll_checkpoint() is None  # no-newer: silent
        assert srv.poll_checkpoint() is None  # corrupt: record, no swap
        # without a checkpoint dir the watcher is inert
        srv2 = ReplicaServer(Eng(), str(tmp_path), 3, ml=ml,
                             checkpoint_dir=None, log=lambda m: None)
        assert srv2.poll_checkpoint() is None
    fleet = [r for r in read_metrics(mpath) if r.get("event") == "fleet"]
    assert [r["kind"] for r in fleet] == ["hot-swap", "swap-rejected"]
    assert fleet[0]["replica"] == 1 and fleet[0]["incarnation"] == 2
    assert fleet[0]["param_generation"] == 2
    assert fleet[0]["swap_ms"] == pytest.approx(12.5)
    assert fleet[1]["reason"] == "newer-generation-corrupt"
    for r in fleet:
        validate_record(r)


@pytest.fixture(scope="module")
def swap_engine():
    """One small real mesh engine for the load_from_checkpoint tests
    (the only jax-compiling fixture in this module — keep it tiny)."""
    from pipegcn_tpu.graph import synthetic_graph
    from pipegcn_tpu.models import ModelConfig
    from pipegcn_tpu.parallel import Trainer, TrainConfig
    from pipegcn_tpu.partition import ShardedGraph, partition_graph
    from pipegcn_tpu.serve import ServingEngine

    g = synthetic_graph(num_nodes=240, avg_degree=6, n_feat=12,
                        n_class=4, seed=11)
    parts = partition_graph(g, 4, seed=0)
    sg = ShardedGraph.build(g, parts, n_parts=4)
    cfg = ModelConfig(layer_sizes=(sg.n_feat, 16, sg.n_class),
                      model="graphsage", norm="layer", dropout=0.0,
                      train_size=sg.n_train_global)
    t = Trainer(sg, cfg, TrainConfig(seed=3, n_epochs=0,
                                     enable_pipeline=False, eval=False))
    eng = ServingEngine.for_trainer(t, max_batch=16, ladder_min=8)
    return t, eng


def test_engine_hot_swap_walk_back_and_fault_dedupe(tmp_path,
                                                    swap_engine):
    from pipegcn_tpu.utils.checkpoint import save_checkpoint

    t, eng = swap_engine
    ckdir = str(tmp_path / "ckpt")
    mpath = tmp_path / "m.jsonl"
    ml = MetricsLogger(str(mpath))
    state = {"params": t.state["params"], "norm": t.state["norm"]}

    # empty directory: explicit no-checkpoint, nothing emitted
    rep = eng.load_from_checkpoint(ckdir, ml=ml)
    assert rep == {"swapped": False, "reason": "no-checkpoint",
                   "param_generation": -1, "param_staleness": 0}

    for e in (1, 2, 3):
        save_checkpoint(ckdir, state, epoch=e)
    corrupt_latest_checkpoint(ckdir)  # generation 3 is now garbage

    # walk-back: the newest generation fails verification, the newest
    # GOOD one (2) swaps in, and the walked-back fault is emitted
    with pytest.warns(UserWarning):
        rep = eng.load_from_checkpoint(ckdir, ml=ml)
    assert rep["swapped"] and rep["param_generation"] == 2
    assert rep["param_staleness"] == 1  # gen 3 published, not served
    assert rep["swap_ms"] >= 0.0
    assert eng.param_generation == 2

    # re-poll: nothing newer is READABLE; no re-swap, and the fault is
    # deduped per bad generation (not re-emitted every poll)
    with pytest.warns(UserWarning):
        rep = eng.load_from_checkpoint(ckdir, ml=ml)
    assert not rep["swapped"]
    assert rep["reason"] == "newer-generation-corrupt"
    assert rep["param_staleness"] == 1
    assert eng.param_generation == 2

    # a fresh good generation swaps in and clears the staleness
    save_checkpoint(ckdir, state, epoch=4)
    rep = eng.load_from_checkpoint(ckdir, ml=ml)
    assert rep["swapped"] and rep["param_generation"] == 4
    assert rep["param_staleness"] == 0
    ml.close()

    faults = [r for r in read_metrics(mpath) if r.get("event") == "fault"]
    assert [f["kind"] for f in faults] == ["serve-ckpt-corrupt"]
    assert faults[0]["epoch"] == 3
    validate_record(faults[0])


# ---------------- the fleet load loop (in-process, fakes) --------------


def test_run_fleet_loop_replica_kill_failover_conservation(tmp_path):
    ft = FakeTime()
    clients = {0: FakeClient(0), 1: FakeClient(1)}
    # hash placement spreads deterministically over both replicas; the
    # router keeps the real clock (only its failure backoff sleeps)
    router = Router(clients, policy="hash", retry_timeout_s=5.0,
                    backoff_s=0.001)
    mgr = FakeManager(clients)
    fp = FaultPlan.parse("replica-kill@2:m1")
    mpath = tmp_path / "loop.jsonl"
    with MetricsLogger(str(mpath)) as ml:
        summary = run_fleet_loop(
            mgr, router, num_nodes=100, duration_s=2.0, qps=300.0,
            max_batch=16, ladder_min=4, report_every_s=0.5,
            seed=1, ml=ml, fault_plan=fp,
            clock=ft.clock, sleep=ft.sleep)
    # the scripted kill fired at window 2 against replica 1
    assert summary["kills"] == [{"window": 2, "replica": 1}]
    # zero accepted tickets lost: served or explicitly shed, queue empty
    assert summary["conserved"] is True
    assert summary["drained"] is True
    assert summary["n_submitted"] == (summary["n_served"]
                                      + summary["n_shed"])
    assert summary["n_served"] > 0
    # batches that hashed to the dead replica failed over to survivor 0
    assert summary["n_failovers"] >= 1
    assert summary["n_retried_rows"] >= 1
    assert summary["replicas_up"] == 1
    assert summary["per_replica_dispatched"]["0"] > 0
    assert summary["per_replica_dispatched"]["1"] > 0
    assert set(summary["per_replica_queue_depth_max"]) == {"0", "1"}
    assert not summary["stopped_early"]
    # the aggregated serving records are schema-valid and accounted
    recs = [r for r in read_metrics(mpath)
            if r.get("event") == "serving"]
    assert len(recs) == summary["n_records"]
    assert recs[-1].get("final") is True
    for r in recs:
        validate_record(r)
        assert r["replicas_up"] in (1, 2)
    assert sum(r["shed"] for r in recs) == summary["n_shed"]


# ---------------- two-process replica-kill drill (chaos lane) ----------


@pytest.mark.slow
@pytest.mark.faults
def test_fleet_cli_replica_kill_drill(tmp_path):
    """SIGKILL one of two live replica meshes mid-load: the router must
    route to the survivor, the supervisor must relaunch + rejoin the
    dead slot (fleet fault + recovery records), and on SIGTERM the
    driver must drain with zero accepted tickets lost and exit 0."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    mpath = tmp_path / "metrics.jsonl"
    env = {
        **os.environ,
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
        "PYTHONPATH": repo,
    }
    proc = subprocess.Popen(
        [sys.executable, "-m", "pipegcn_tpu.cli.fleet",
         "--dataset", "synthetic:600:8:16:4", "--n-partitions", "4",
         "--n-hidden", "16", "--n-layers", "2", "--fix-seed",
         "--partition-dir", str(tmp_path / "parts"), "--serve-build",
         "--metrics-out", str(mpath),
         "--replicas", "2",
         # hash placement: with near-zero CPU query latency the
         # least-queue tiebreak would starve replica 1; the ring
         # guarantees both replicas own arcs of the keyspace
         "--fleet-policy", "hash",
         "--serve-duration", "600", "--serve-qps", "60",
         "--serve-report-every", "0.5",
         "--fault-plan", "replica-kill@3:m1",
         "--fleet-retry-timeout", "15",
         "--fleet-ready-timeout", "240"],
        env=env, cwd=repo, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True)

    def fleet_kinds():
        kinds = []
        if not mpath.exists():
            return kinds
        with open(mpath) as fh:
            for line in fh:
                try:
                    r = json.loads(line)
                except json.JSONDecodeError:
                    continue  # mid-write line
                if r.get("event") == "fleet":
                    kinds.append(r.get("kind"))
        return kinds

    try:
        deadline = time.monotonic() + 420
        while "replica-rejoin" not in fleet_kinds():
            assert proc.poll() is None, (
                "fleet driver exited before the rejoin:\n"
                + proc.communicate()[0][-3000:])
            assert time.monotonic() < deadline, (
                f"no replica-rejoin within the deadline "
                f"(fleet kinds so far: {fleet_kinds()})")
            time.sleep(0.5)
        proc.send_signal(signal.SIGTERM)
        out, _ = proc.communicate(timeout=180)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.communicate()
    assert proc.returncode == 0, out[-3000:]

    tail = [ln for ln in out.splitlines() if '"fleet": true' in ln]
    assert tail, out[-3000:]
    summ = json.loads(tail[-1])
    # zero accepted tickets lost across a replica SIGKILL
    assert summ["conserved"] is True
    assert summ["drained"] is True
    assert summ["n_submitted"] == summ["n_served"] + summ["n_shed"]
    assert summ["n_served"] > 0
    assert summ["replicas"] == 2
    assert summ["kills"] and summ["kills"][0]["replica"] == 1
    # both replicas actually served load
    assert summ["per_replica_dispatched"]["0"] > 0
    assert summ["per_replica_dispatched"]["1"] > 0
    # the survivor absorbed retried rows, and the slot rejoined
    assert summ["replicas_up"] == 2

    recs = read_metrics(mpath)  # post-exit: every line complete
    kinds = [r["kind"] for r in recs if r.get("event") == "fleet"]
    for expect in ("replica-dead", "relaunch", "replica-rejoin",
                   "fleet-stop"):
        assert expect in kinds, kinds
    faults = [r for r in recs if r.get("event") == "fault"
              and r.get("kind") == "fleet"]
    assert faults and faults[0]["rank"] == 1
    recov = [r for r in recs if r.get("event") == "recovery"
             and r.get("kind") == "fleet"]
    assert recov and recov[0]["rank"] == 1
    for r in recs:
        if r.get("event") in ("fleet", "serving"):
            validate_record(r)
