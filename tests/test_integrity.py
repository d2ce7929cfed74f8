"""Silent-data-corruption defense (resilience/integrity.py,
docs/RESILIENCE.md "Silent data corruption").

Pins the round-18 contracts:
  - fletcher digests: host numpy and the jitted device program agree
    bit-exactly on every dtype width, any single flipped bit changes
    the digest with certainty, and the construction is order
    independent (so XLA's reduction order never matters);
  - the ``bitflip@E[:rN]:<class>`` fault grammar: required target
    class, one-shot consumption, rank gating, loud rejection of
    malformed entries;
  - quarantine request markers: durable round-trip, fail-closed on an
    unreadable marker, operator clear;
  - the v13 ``integrity`` record kind validates against the schema;
  - the IntegrityPlane in isolation: static-table scrub attributes the
    dirty shard and the dirty-shard rebuild clears it; the dynamic
    params digest catches a boundary flip; Freivalds passes clean on
    both SpMM families;
  - the seeded bitflip-detection matrix THROUGH fit(): every target
    class x kernel family is injected, detected within the cadence,
    attributed to the right class in a contracted record, and the run
    still completes (recovery worked);
  - the serving wire guard: with --integrity-check-every armed the
    dirty-row exchange stays bit-identical to a full re-exchange and
    never recompiles (the checksum lane is a trace-time choice);
  - ``pipegcn-debug scrub``: exit 0 on a clean run dir, exit 2 when a
    checkpoint or ledger generation is tampered;
  - the elastic supervisor honors quarantine markers (member excluded
    at the next replan) and the explicit-rejoin release valve (marker
    cleared, member folded back in);
  - the slow two-member drill: recurring SDC on rank 1 writes the
    marker, the supervisor relaunches without it, and training
    completes on the survivor.
"""

import io
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from pipegcn_tpu.graph import synthetic_graph
from pipegcn_tpu.models import ModelConfig
from pipegcn_tpu.obs import (
    SCHEMA_VERSION,
    MetricsLogger,
    read_metrics,
    validate_record,
)
from pipegcn_tpu.parallel import Trainer, TrainConfig
from pipegcn_tpu.partition import ShardedGraph, partition_graph
from pipegcn_tpu.resilience import (
    EXIT_PREEMPTED,
    ElasticConfig,
    ElasticSupervisor,
    FaultPlan,
    MembershipLedger,
)
from pipegcn_tpu.resilience.integrity import (
    QUARANTINE_STRIKES,
    SDC_CODES,
    TARGETS,
    IntegrityPlane,
    clear_quarantine,
    digest_tree,
    flip_bit,
    host_digest,
    quarantine_marker_path,
    read_quarantines,
    request_quarantine,
    shard_digests,
)

pytestmark = pytest.mark.integrity

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def sharded():
    g = synthetic_graph(num_nodes=300, avg_degree=6, n_feat=8, n_class=3,
                        seed=1)
    parts = partition_graph(g, 2, seed=0)
    return ShardedGraph.build(g, parts, n_parts=2)


def _trainer(sg, impl="xla", **tkw):
    cfg = ModelConfig(layer_sizes=(sg.n_feat, 16, sg.n_class),
                      dropout=0.0, train_size=sg.n_train_global,
                      spmm_impl=impl)
    tkw.setdefault("n_epochs", 8)
    tkw.setdefault("log_every", 50)
    return Trainer(sg, cfg, TrainConfig(**tkw))


# ---------------- fletcher digests ------------------------------------


@pytest.mark.parametrize("arr", [
    np.linspace(-3, 3, 97).astype(np.float32),
    np.arange(-40, 40, dtype=np.int32).reshape(8, 10),
    np.arange(256, dtype=np.uint8),
    (np.arange(30) % 2 == 0),
    np.linspace(0, 1, 64).astype(np.float16),
], ids=["f32", "i32", "u8", "bool", "f16"])
def test_digest_host_device_bit_parity(arr):
    """The host numpy digest and the jitted device digest must agree
    bit-exactly for every dtype width — that equality is what lets the
    scrubber compare device state against host-built references."""
    import jax.numpy as jnp

    from pipegcn_tpu.resilience.integrity import device_digest

    h = host_digest(arr)
    d = np.asarray(device_digest(jnp.asarray(arr)))
    assert h.dtype == np.uint32 and h.shape == (2,)
    assert np.array_equal(h, d), (h, d)
    # 8-byte dtypes never exist on the CPU mesh (jax x64 is off), so
    # the parity contract stops at 4 bytes; the host digest still
    # folds them (checkpoint-side references)
    h64 = host_digest(np.linspace(-1, 1, 33))
    assert h64.shape == (2,) and not np.array_equal(
        h64, host_digest(flip_bit(np.linspace(-1, 1, 33), bit=9)))


def test_digest_single_flip_sensitivity_and_involution():
    rng = np.random.default_rng(0)
    a = rng.normal(size=(50, 4)).astype(np.float32)
    ref = host_digest(a)
    for bit, index in [(0, 0), (11, 37), (31, 199), (23, 73)]:
        b = flip_bit(a, bit=bit, index=index)
        assert not np.array_equal(host_digest(b), ref), (bit, index)
        # flipping the same bit twice is the identity
        c = flip_bit(b, bit=bit, index=index)
        assert np.array_equal(c, a)
    # the chaos lane's params flip (bit 11, mid-mantissa) stays finite:
    # wrong-but-finite is the SDC model, not a NaN the tripwire catches
    assert np.isfinite(flip_bit(a, bit=11, index=5)).all()


def test_wire_sum_order_independent_and_flip_sensitive():
    import jax.numpy as jnp

    from pipegcn_tpu.parallel.halo import wire_sum

    rng = np.random.default_rng(1)
    a = rng.normal(size=257).astype(np.float32)
    s = np.asarray(wire_sum(jnp.asarray(a)))
    # integer wraparound addition commutes: any permutation agrees
    p = rng.permutation(a)
    assert np.array_equal(np.asarray(wire_sum(jnp.asarray(p))), s)
    bad = flip_bit(a, bit=7, index=100)
    assert not np.array_equal(np.asarray(wire_sum(jnp.asarray(bad))), s)
    # digest matches the integrity plane's plain sum (shared construction)
    assert int(s) == int(host_digest(a)[0])


def test_shard_digests_attribute_the_dirty_shard():
    import jax.numpy as jnp

    a = np.arange(3 * 20, dtype=np.float32).reshape(3, 20)
    ref = shard_digests(jnp.asarray(a))
    assert ref.shape == (3, 2)
    b = flip_bit(a, bit=3, index=25)  # flat 25 -> shard 1
    cur = shard_digests(jnp.asarray(b))
    changed = np.nonzero(np.any(cur != ref, axis=-1))[0]
    assert changed.tolist() == [1]


def test_digest_tree_names_leaves():
    import jax.numpy as jnp

    tree = {"w": jnp.ones((4, 4)), "b": {"inner": jnp.zeros(3)}}
    d = digest_tree(tree)
    assert len(d) == 2
    assert all(v.shape == (2,) and v.dtype == np.uint32
               for v in d.values())
    assert any("w" in k for k in d) and any("inner" in k for k in d)


# ---------------- fault grammar ---------------------------------------


def test_bitflip_grammar_one_shot_and_rank_gating():
    p = FaultPlan.parse("bitflip@3:params,bitflip@5:r1:tables")
    assert p.due_str_arg("bitflip", 3) == "params"
    assert p.due_str_arg("bitflip", 3) is None  # consumed
    # the r1 entry never fires on rank 0
    assert p.due_str_arg("bitflip", 5) is None
    q = FaultPlan.parse("bitflip@5:r1:tables", rank=1)
    assert q.due_str_arg("bitflip", 5) == "tables"
    # the class argument is REQUIRED and must be a legal class
    with pytest.raises(ValueError, match="target class"):
        FaultPlan.parse("bitflip@3")
    with pytest.raises(ValueError, match="target class"):
        FaultPlan.parse("bitflip@3:meteor")
    # word arguments are bitflip-only
    with pytest.raises(ValueError, match="word argument"):
        FaultPlan.parse("sigterm@3:params")


def test_sdc_codes_cover_targets():
    assert set(SDC_CODES) == set(TARGETS)
    assert sorted(SDC_CODES.values()) == [1, 2, 3, 4]  # 0 = none


# ---------------- quarantine markers ----------------------------------


def test_quarantine_marker_roundtrip(tmp_path):
    d = str(tmp_path)
    path = request_quarantine(d, 3, reason="recurring SDC", strikes=2,
                              targets=["params", "params"])
    assert path == quarantine_marker_path(d, 3)
    q = read_quarantines(d)
    assert set(q) == {3}
    assert q[3]["reason"] == "recurring SDC"
    assert q[3]["strikes"] == 2 and q[3]["targets"] == ["params"]
    # an unreadable marker still quarantines (fail-closed)
    with open(quarantine_marker_path(d, 7), "w") as f:
        f.write("{torn")
    q = read_quarantines(d)
    assert set(q) == {3, 7}
    assert "unreadable" in q[7]["reason"]
    assert clear_quarantine(d, 3) and not clear_quarantine(d, 3)
    assert set(read_quarantines(d)) == {7}


# ---------------- schema contract -------------------------------------


def test_integrity_record_validates_and_schema_pin():
    assert SCHEMA_VERSION >= 15
    buf = io.StringIO()
    ml = MetricsLogger(buf)
    ml.run_header(config={}, device={}, mesh={})
    ml.integrity(epoch=4, check="scrub", outcome="mismatch",
                 target="tables", cadence=2, overhead_s=0.001,
                 detail="digest mismatch in spmm_rows",
                 dirty_shards=[1])
    ml.close()
    recs = [json.loads(line) for line in buf.getvalue().splitlines()]
    rec = [r for r in recs if r["event"] == "integrity"][0]
    validate_record(rec)
    assert rec["target"] == "tables" and rec["outcome"] == "mismatch"
    assert rec["cadence"] == 2 and rec["dirty_shards"] == [1]


# ---------------- the plane, in isolation -----------------------------


def test_plane_scrub_detects_table_flip_and_rebuild_clears(sharded):
    t = _trainer(sharded, n_epochs=2)
    integ = IntegrityPlane(1, log=lambda s: None)
    integ.baseline(t)
    assert integ.scrub_static(t).outcome == "ok"
    assert t._inject_bitflip("tables", 0, lambda s: None)
    res = integ.scrub_static(t)
    assert res.outcome == "mismatch" and res.target == "tables"
    assert res.dirty_shards  # attribution names the rotten shard(s)
    assert "digest mismatch" in res.detail
    # recovery: rebuild the dirty shard's tables from the host artifact
    t._rebuild_static_data(res.dirty_shards)
    assert integ.scrub_static(t).outcome == "ok"


def test_plane_dynamic_digest_catches_params_flip(sharded):
    t = _trainer(sharded, n_epochs=2)
    integ = IntegrityPlane(1, log=lambda s: None)
    integ.note_dynamic(t)
    assert all(r.outcome == "ok" for r in integ.verify_dynamic(t))
    assert t._inject_bitflip("params", 0, lambda s: None)
    results = integ.verify_dynamic(t)
    bad = [r for r in results if r.outcome == "mismatch"]
    assert [r.target for r in bad] == ["params"]
    assert "digest mismatch" in bad[0].detail
    # rollback/restore legitimately replaces state: drop the baselines
    integ.drop_dynamic()
    assert integ.verify_dynamic(t) == []


@pytest.mark.parametrize("impl", ["xla", "bucket"])
def test_freivalds_passes_clean(sharded, impl):
    t = _trainer(sharded, impl=impl, n_epochs=2)
    t.train_epoch(0)
    integ = IntegrityPlane(1, log=lambda s: None)
    res = integ.freivalds(t, 1)
    assert res is not None
    assert res.check == "freivalds" and res.outcome == "ok"


# ---------------- detection matrix through fit() ----------------------


def _assert_detected(sg, impl, targets):
    """One trainer per kernel family (compiles once), one fit per
    target class: the flip at epoch 3 must be injected, detected no
    later than epoch 3 + cadence with the right attribution, and the
    run must still reach n_epochs (recovery worked)."""
    cadence = 2
    t = _trainer(sg, impl=impl, enable_pipeline=True,
                 integrity_check_every=cadence, n_epochs=8)
    for target in targets:
        buf = io.StringIO()
        res = t.fit(eval_graphs=None, log_fn=lambda s: None,
                    metrics=MetricsLogger(buf),
                    fault_plan=FaultPlan.parse(f"bitflip@3:{target}"))
        recs = [json.loads(line)
                for line in buf.getvalue().splitlines()]
        injected = [r for r in recs if r["event"] == "fault"
                    and r.get("kind") == "injected"
                    and r.get("reason") == f"bitflip:{target}"]
        assert injected and injected[0]["epoch"] == 3, (impl, target)
        hits = [r for r in recs if r["event"] == "integrity"
                and r["outcome"] == "mismatch"
                and r.get("target") == target]
        assert hits, (impl, target,
                      [r for r in recs if r["event"] == "integrity"])
        assert all(3 <= r["epoch"] <= 3 + cadence for r in hits)
        for r in hits:
            validate_record(r)
            assert r["cadence"] == cadence
        # recovery let the run finish with finite numbers
        assert t.last_epoch == t.tcfg.n_epochs, (impl, target)
        if res["history"]:
            assert np.isfinite(res["history"][-1][1])


def test_bitflip_detection_matrix_xla(sharded):
    _assert_detected(sharded, "xla", TARGETS)


def test_bitflip_detection_matrix_bucket(sharded):
    # the full four-class sweep rides the xla family; bucket pins the
    # table-heavy classes its gather plans add (plus params for the
    # consensus-rollback path under a different kernel)
    _assert_detected(sharded, "bucket", ("tables", "params"))


# ---------------- serving wire guard ----------------------------------


def test_serving_wire_guard_bit_identical_and_no_recompile(sharded):
    """With --integrity-check-every armed the serving engine's dirty
    row exchange carries the checksum lane: results stay bit-identical
    to a full re-exchange, no mismatches fire on a clean wire, and the
    guarded program still traces exactly once (trace-time choice)."""
    from pipegcn_tpu.serve import ServingEngine, trace_counts

    t = _trainer(sharded, enable_pipeline=True, integrity_check_every=1,
                 n_epochs=2)
    t.train_epoch(0)
    eng = ServingEngine.for_trainer(t)
    assert eng._wire_guard
    eng.warmup()
    rng = np.random.default_rng(3)
    c0 = None
    for round_i in range(3):
        ids = rng.integers(0, eng.num_global_nodes, 12).astype(np.int64)
        vals = rng.normal(size=(12, eng.n_feat_raw)).astype(np.float32)
        eng.apply_updates(ids, vals)
        eng.refresh_boundary()
        ref = np.asarray(eng.full_boundary_exchange())
        got = np.asarray(eng._halo0)
        assert np.array_equal(ref, got), round_i
        if c0 is None:
            c0 = dict(trace_counts())  # steady state after round 0
    assert dict(trace_counts()) == c0, (
        "wire guard recompiled a serving program")
    assert eng.wire_bad_total == 0


# ---------------- debug scrub CLI -------------------------------------


def test_debug_scrub_clean_then_tampered(tmp_path):
    from pipegcn_tpu.cli.debug import EXIT_CORRUPT, main
    from pipegcn_tpu.resilience import plan_assignment
    from pipegcn_tpu.utils.checkpoint import save_checkpoint

    run = tmp_path / "run"
    ck = run / "ck"
    state = {"params": {"w": np.arange(8, dtype=np.float32)}}
    save_checkpoint(str(ck), state, 4)
    led = MembershipLedger(str(run / "coord-elastic"))
    led.append(generation=0, members=[0, 1],
               assignment=plan_assignment(2, [0, 1]), trigger="start")
    assert main(["scrub", str(run)]) == 0
    assert main(["scrub", str(run), "--json"]) == 0
    # tamper a checkpoint byte: scrub must exit 2, not crash
    npz = sorted(ck.glob("state-*.npz"))[0]
    blob = bytearray(npz.read_bytes())
    blob[len(blob) // 2] ^= 0xFF
    npz.write_bytes(bytes(blob))
    assert main(["scrub", str(run)]) == EXIT_CORRUPT
    # heal the checkpoint, rot the ledger payload instead
    save_checkpoint(str(ck), state, 6)
    npz.unlink()
    assert main(["scrub", str(run)]) == 0
    path = led.path_for(0)
    rec = json.load(open(path))
    rec["payload"]["trigger"] = "tampered"
    json.dump(rec, open(path, "w"))
    assert main(["scrub", str(run)]) == EXIT_CORRUPT


# ---------------- supervisor: quarantine + release valve ---------------


class _FakeHandle:
    def __init__(self, rc):
        self.returncode = None
        self._rc = rc

    def poll(self):
        self.returncode = self._rc
        return self._rc

    def send_signal(self, sig):
        pass


class _FakeFleet:
    def __init__(self, rcs):
        self.rcs = list(rcs)
        self.launches = []

    def popen(self, cmd, env, log_path):
        self.launches.append(
            {"cmd": list(cmd), "env": dict(env), "log": log_path})
        return _FakeHandle(self.rcs.pop(0))


def _train_argv(tmp_path, n_parts=4, ppn=2):
    return [
        "--dataset", "synthetic:300:6:8:3",
        "--n-partitions", str(n_parts),
        "--parts-per-node", str(ppn),
        "--n-epochs", "6", "--n-hidden", "8", "--dropout", "0.0",
        "--no-eval", "--fix-seed", "--seed", "7",
        "--partition-dir", str(tmp_path / "parts"),
        "--checkpoint-dir", str(tmp_path / "ck"),
        "--metrics-out", str(tmp_path / "metrics.jsonl"),
    ]


def _fast_cfg(**kw):
    kw.setdefault("backoff_base_s", 0.0)
    kw.setdefault("backoff_max_s", 0.0)
    kw.setdefault("poll_s", 0.01)
    kw.setdefault("storm_threshold", 1000)
    return ElasticConfig(**kw)


def test_supervisor_excludes_quarantined_then_rejoin_releases(tmp_path):
    """A pre-existing quarantine marker keeps member 1 out of gen 0
    (trigger 'quarantine', sole survivor owns everything); the pending
    explicit rejoin request is the operator release valve — at the
    next membership event it clears the marker and folds 1 back in."""
    coord = str(tmp_path / "parts" / "coord-elastic")
    request_quarantine(coord, 1, reason="recurring silent data "
                       "corruption", strikes=QUARANTINE_STRIKES,
                       targets=["params"])
    MembershipLedger(coord).request_rejoin(1)
    # gen 0: member 0 alone -> 75 (resumable); gen 1: members 0+1 -> 0
    fleet = _FakeFleet([EXIT_PREEMPTED, 0, 0])
    logs = []
    sup = ElasticSupervisor(_train_argv(tmp_path), _fast_cfg(),
                            popen=fleet.popen, log=logs.append)
    assert sup.run() == 0
    assert len(fleet.launches) == 3
    led = MembershipLedger(coord)
    assert led.generations() == [0, 1]
    g0, g1 = led.read(0), led.read(1)
    assert g0["trigger"] == "quarantine" and g0["members"] == [0]
    assert g0["assignment"]["parts"] == {"0": [0, 1, 2, 3]}
    assert g1["trigger"] == "rejoin" and g1["members"] == [0, 1]
    # the release valve consumed both the marker and the request
    assert not os.path.exists(quarantine_marker_path(coord, 1))
    assert led.pending_rejoins() == []
    assert any("quarantine" in line for line in logs)
    assert any("released from quarantine" in line for line in logs)


def test_supervisor_never_quarantines_everyone(tmp_path):
    """Quarantining EVERY member keeps the full set (training on
    nothing helps nobody) with a loud log."""
    coord = str(tmp_path / "parts" / "coord-elastic")
    for m in (0, 1):
        request_quarantine(coord, m, reason="sdc", strikes=2,
                           targets=["tables"])
    fleet = _FakeFleet([0, 0])
    logs = []
    sup = ElasticSupervisor(_train_argv(tmp_path), _fast_cfg(),
                            popen=fleet.popen, log=logs.append)
    assert sup.run() == 0
    led = MembershipLedger(coord)
    assert led.read(0)["trigger"] == "start"
    assert led.read(0)["members"] == [0, 1]
    assert any("every member" in line for line in logs)
    # markers survive: an operator must clear them explicitly
    assert set(read_quarantines(coord)) == {0, 1}


# ---------------- the two-member quarantine drill (slow) ---------------


@pytest.mark.slow
@pytest.mark.faults
def test_recurring_sdc_quarantines_rank_and_fleet_recovers(tmp_path):
    """Acceptance: rank 1 suffers two scheduled bit flips (cadence 1,
    so each is detected immediately -> QUARANTINE_STRIKES reached), it
    writes the quarantine marker and exits resumable; the supervisor
    replans WITHOUT it and the survivor finishes all 10 epochs owning
    both partitions."""
    env = {
        **os.environ,
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
        "PYTHONPATH": REPO,
        "PYTHONUNBUFFERED": "1",
    }
    ck = str(tmp_path / "ck")
    cmd = [
        sys.executable, "-m", "pipegcn_tpu.cli.elastic",
        "--max-restarts", "3", "--backoff-base", "0.1",
        "--metrics-out", str(tmp_path / "sup.jsonl"),
        "--",
        "--dataset", "synthetic:300:6:8:3",
        "--n-partitions", "2", "--parts-per-node", "1",
        "--n-epochs", "10", "--n-hidden", "8", "--dropout", "0.0",
        "--log-every", "1000", "--fix-seed", "--seed", "7", "--no-eval",
        "--partition-dir", str(tmp_path / "parts"),
        "--checkpoint-dir", ck, "--checkpoint-every", "2",
        "--integrity-check-every", "1",
        "--fault-plan", "bitflip@3:r1:params,bitflip@5:r1:params",
        "--metrics-out", str(tmp_path / "metrics.jsonl"),
    ]
    proc = subprocess.run(cmd, env=env, cwd=REPO, timeout=560,
                          capture_output=True, text=True)
    tail = (proc.stdout + proc.stderr)[-4000:]
    assert proc.returncode == 0, tail
    coord = str(tmp_path / "parts" / "coord-elastic")
    # the marker is durable evidence — it outlives the run
    q = read_quarantines(coord)
    assert 1 in q, (q, tail)
    assert q[1]["strikes"] >= QUARANTINE_STRIKES
    led = MembershipLedger(coord)
    gens = led.generations()
    assert len(gens) >= 2, tail
    quarantined = [led.read(g) for g in gens
                   if led.read(g)["trigger"] == "quarantine"]
    assert quarantined and quarantined[0]["members"] == [0], tail
    # the survivor really trained: detection records from rank 1's
    # generation-0 stream name the params class
    mfiles = [os.path.join(tmp_path, f) for f in os.listdir(tmp_path)
              if f.startswith("metrics.")]
    hits = []
    for mf in mfiles:
        hits += [r for r in read_metrics(mf)
                 if r.get("event") == "integrity"
                 and r.get("outcome") == "mismatch"
                 and r.get("target") == "params"]
    assert hits, tail
