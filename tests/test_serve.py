"""Online serving runtime (pipegcn_tpu/serve/, docs/SERVING.md).

These tests pin the round-10 serving contracts:
  - micro-batcher policy + power-of-two padding ladder (pure host unit
    tests on a fake clock);
  - compiled-once query engine: served logits match the single-device
    full-graph eval oracle, and steady-state traffic across every
    ladder bucket replays compiled code (trace-time compile counter —
    a jit cache hit never increments it);
  - incremental halo freshness: the dirty-row-only send-list replay is
    BIT-IDENTICAL to a full boundary re-exchange (graphsage AND the
    gcn pre-scaled send view);
  - layer-0 cache invalidation off the send-lists vs a brute-force
    slot enumeration;
  - the staleness ledger (age = update batches not yet in served
    logits) and the use_pp guard;
  - end-to-end: run_serving_loop emits schema-valid `serving` records
    and drains; the SIGTERM kill drill (marked slow, chaos lane) pins
    that a live `python -m pipegcn_tpu.cli.serve` drains and lands a
    hard-flushed final record before exiting 0.
"""

import json
import os
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

from pipegcn_tpu.graph import synthetic_graph
from pipegcn_tpu.models import ModelConfig
from pipegcn_tpu.parallel import Trainer, TrainConfig
from pipegcn_tpu.partition import ShardedGraph, partition_graph
from pipegcn_tpu.serve import (
    Layer0Cache,
    MicroBatcher,
    OpenLoopGenerator,
    ServingEngine,
    ServingStats,
    bucket_for,
    bucket_ladder,
    run_serving_loop,
    trace_counts,
)

pytestmark = pytest.mark.serving


def _trainer(model="graphsage", use_pp=False, n_parts=4, seed=31,
             epochs=2):
    g = synthetic_graph(num_nodes=400, avg_degree=8, n_feat=12,
                        n_class=5, seed=seed)
    parts = partition_graph(g, n_parts, seed=0)
    sg = ShardedGraph.build(g, parts, n_parts=n_parts)
    cfg = ModelConfig(
        layer_sizes=(sg.n_feat, 16, 16, sg.n_class), model=model,
        norm="layer", dropout=0.0, train_size=sg.n_train_global,
        use_pp=use_pp,
    )
    t = Trainer(sg, cfg, TrainConfig(seed=3, enable_pipeline=True))
    for e in range(epochs):
        t.train_epoch(e)
    return t, g


@pytest.fixture(scope="module")
def served():
    """Read-only trainer+engine shared by the oracle/recompile tests.
    Tests that MUTATE features (apply_updates) must use `mutable`."""
    t, g = _trainer()
    eng = ServingEngine.for_trainer(t, max_batch=64, ladder_min=8)
    eng.warmup()
    return t, g, eng


@pytest.fixture(scope="module")
def mutable():
    """Engine the freshness/loop tests may patch features on (one
    trainer build amortized across them; the tests only rely on
    invariants — bit-identity, ledger deltas, finiteness — never on
    specific pre-update feature values)."""
    t, _ = _trainer(epochs=1)
    eng = ServingEngine.for_trainer(t)
    eng.warmup()
    return eng


# ---------------- padding ladder + micro-batcher (host-only) ----------


def test_bucket_ladder_semantics():
    assert bucket_ladder(8, 64) == [8, 16, 32, 64]
    assert bucket_ladder(8, 100) == [8, 16, 32, 64, 128]
    assert bucket_for(1, [8, 16]) == 8
    assert bucket_for(8, [8, 16]) == 8
    assert bucket_for(9, [8, 16]) == 16
    with pytest.raises(ValueError):
        bucket_for(17, [8, 16])


def test_microbatcher_policy_fake_clock():
    now = [0.0]
    batches = []

    def run(ids):
        batches.append(np.asarray(ids).copy())
        return np.stack([ids, ids * 2], axis=1).astype(np.float32)

    fills = []
    mb = MicroBatcher(run, max_batch=8, max_delay_ms=5.0, ladder_min=2,
                      clock=lambda: now[0],
                      observer=lambda b, n, lats: fills.append((b, n)))
    t1 = mb.submit(np.array([3, 4]))
    assert mb.queue_depth == 2
    # below max_batch and under the delay: not flushed yet
    assert mb.pump(now[0]) == 0
    assert not t1.done
    now[0] += 0.006  # past max_delay
    assert mb.pump(now[0]) == 1
    assert t1.done and mb.queue_depth == 0
    np.testing.assert_array_equal(t1.result[:, 0], [3, 4])
    assert t1.latency_s == pytest.approx(0.006)
    # a full batch flushes immediately, no waiting
    t2 = mb.submit(np.arange(8))
    assert mb.due(now[0])
    assert mb.pump(now[0]) == 1
    assert t2.done
    # two tickets coalesce into one run() call
    ta = mb.submit(np.array([1]))
    tb = mb.submit(np.array([2, 3]))
    now[0] += 0.010
    assert mb.pump(now[0]) == 1
    assert ta.done and tb.done
    np.testing.assert_array_equal(ta.result[:, 0], [1])
    np.testing.assert_array_equal(tb.result[:, 0], [2, 3])
    assert len(batches) == 3 and batches[-1].size == 3
    # drain flushes leftovers regardless of the clock
    tc = mb.submit(np.array([5]))
    mb.drain()
    assert tc.done and mb.queue_depth == 0
    # observer saw (bucket, valid-rows) per batch
    assert fills == [(2, 2), (8, 8), (4, 3), (2, 1)]
    # oversized submissions are rejected (callers chunk upstream)
    with pytest.raises(ValueError):
        mb.submit(np.arange(9))


def test_microbatcher_load_shedding_fake_clock():
    """Bounded-queue + deadline load shedding (docs/SERVING.md "Load
    shedding"): overload is answered 'no' immediately, expired tickets
    are shed at flush time, and the conservation invariant
    submitted == served + shed + queue_depth holds throughout."""
    now = [0.0]
    stats = ServingStats(clock=lambda: now[0])
    served = []

    def run(ids):
        served.append(np.asarray(ids).copy())
        return np.stack([ids, ids], axis=1).astype(np.float32)

    mb = MicroBatcher(run, max_batch=8, max_delay_ms=5.0, ladder_min=2,
                      clock=lambda: now[0], max_queue=4,
                      ticket_deadline_ms=20.0,
                      observer=stats.note_batch, on_shed=stats.note_shed)
    t1 = mb.submit(np.array([1, 2, 3]))
    assert not t1.shed
    # a submit that lands exactly AT the bound is accepted...
    t2 = mb.submit(np.array([4]))
    assert not t2.shed and mb.queue_depth == 4
    # ...one row past it is shed immediately with an explicit reason
    t3 = mb.submit(np.array([5]))
    assert t3.done and t3.shed and t3.shed_reason == "queue-full"
    assert t3.result is None and mb.queue_depth == 4
    # tickets that outwait the deadline are shed at flush, not served
    now[0] += 0.021
    assert mb.pump(now[0], force=True) == 0
    assert t1.shed and t1.shed_reason == "deadline"
    assert t2.shed and t2.shed_reason == "deadline"
    assert served == []  # nothing uselessly late ever ran
    # a fresh ticket inside the deadline still serves normally
    t4 = mb.submit(np.array([6, 7]))
    now[0] += 0.006
    assert mb.pump(now[0]) == 1
    assert t4.done and not t4.shed
    np.testing.assert_array_equal(t4.result[:, 0], [6, 7])
    # conservation: every submitted row is served, shed, or queued
    assert mb.n_submitted_rows == 7
    assert mb.n_served_rows == 2 and mb.n_shed_rows == 5
    assert mb.n_shed_tickets == 3
    assert mb.n_submitted_rows == (mb.n_served_rows + mb.n_shed_rows
                                   + mb.queue_depth)
    # the shed count lands in the serving record via on_shed
    assert stats.snapshot()["shed"] == 5


def test_serving_stats_snapshot():
    now = [100.0]
    st = ServingStats(clock=lambda: now[0])
    st.note_batch(8, 4, [0.001, 0.001, 0.002, 0.010])
    st.note_serve(4, hit=True, staleness_age=0)
    now[0] += 2.0
    rec = st.snapshot(queue_depth=3)
    assert rec["queries"] == 4
    assert rec["qps"] == pytest.approx(2.0)
    assert rec["batch_fill"] == pytest.approx(0.5)
    assert rec["queue_depth"] == 3
    assert rec["p50_ms"] == pytest.approx(1.5)
    assert rec["p99_ms"] <= 10.0 and rec["p99_ms"] > rec["p50_ms"]
    assert rec["cache_hit_rate"] == pytest.approx(1.0)
    assert rec["staleness_age"] == 0
    # snapshot(reset=True) starts a fresh window
    now[0] += 1.0
    empty = st.snapshot()
    assert empty["queries"] == 0 and empty["p50_ms"] is None
    assert empty["batch_fill"] is None and empty["cache_hit_rate"] is None


def test_open_loop_generator_deterministic():
    a = OpenLoopGenerator(100, qps=50, duration_s=2.0, seed=7)
    b = OpenLoopGenerator(100, qps=50, duration_s=2.0, seed=7)
    np.testing.assert_array_equal(a.arrivals, b.arrivals)
    np.testing.assert_array_equal(a.queries, b.queries)
    assert np.all(np.diff(a.arrivals) >= 0)  # open loop: fixed up front
    assert a.arrivals[-1] <= 2.0
    assert a.queries.min() >= 0 and a.queries.max() < 100


# ---------------- query engine ----------------------------------------


def test_query_matches_full_eval_oracle(served):
    t, g, eng = served
    handle = t.eval_dispatch(g, "val_mask")
    assert handle[0] == "full"
    full = np.asarray(handle[2])
    ids = np.arange(g.num_nodes, dtype=np.int64)
    out = eng.query(ids)
    assert out.shape == (g.num_nodes, eng.n_class)
    np.testing.assert_allclose(out, full[ids], atol=1e-5)


def test_zero_recompiles_after_warmup(served):
    t, _, eng = served
    # warmup ran in the fixture; the engine is cached per-trainer
    assert ServingEngine.for_trainer(t, max_batch=64, ladder_min=8) \
        is eng
    c0 = dict(trace_counts())
    rng = np.random.default_rng(0)
    for n in (1, 3, 8, 17, 33, 64, 200):  # 200 chunks over the top
        ids = rng.integers(0, eng.num_global_nodes, n).astype(np.int64)
        out = eng.query(ids)
        assert out.shape == (n, eng.n_class)
        assert np.isfinite(out).all()
    assert dict(trace_counts()) == c0, (
        "steady-state queries recompiled a serving program")


def test_tracing_adds_zero_recompiles_and_conserves_spans(
        served, tmp_path):
    """--trace-sample-rate 1.0 through the full serving loop: every
    query mints a trace id, spans land in the metrics stream with one
    terminal (dispatch|shed) each, and NO serving program retraces —
    the tracing is host-side clock arithmetic only. At rate 0 the
    sampler mints nothing."""
    from pipegcn_tpu.obs.metrics import MetricsLogger, read_metrics
    from pipegcn_tpu.obs.schema import validate_record
    from pipegcn_tpu.serve.tracing import TraceSampler

    _, _, eng = served
    eng.warmup()
    c0 = dict(trace_counts())
    mpath = tmp_path / "traced.jsonl"
    with MetricsLogger(mpath) as ml:
        ml.run_header(config={}, device={}, mesh={})
        summary = run_serving_loop(
            eng, duration_s=0.8, qps=60.0, max_delay_ms=2.0,
            report_every_s=0.4, refresh_every_s=0.0,
            update_every_s=0.0, seed=0, ml=ml,
            trace_sample_rate=1.0)
    assert dict(trace_counts()) == c0, (
        "tracing recompiled a serving program")
    assert summary["n_traced"] == summary["n_queries"] > 0
    assert summary["n_spans"] > 0
    spans = [r for r in read_metrics(mpath) if r.get("event") == "span"]
    assert len(spans) == summary["n_spans"]
    by_trace = {}
    for s in spans:
        validate_record(s)
        assert s["dur_ms"] >= 0 and s["t_start"] > 0
        by_trace.setdefault(s["trace_id"], []).append(s["op"])
    assert len(by_trace) == summary["n_traced"]
    for tid, ops in by_trace.items():
        term = [op for op in ops if op in ("dispatch", "shed")]
        assert len(term) == 1, (tid, ops)
    # rate 0 is the default and mints nothing
    assert TraceSampler(0.0).sample() is None


def test_query_rejects_out_of_range(served):
    _, _, eng = served
    with pytest.raises(ValueError, match="out of range"):
        eng.query(np.array([eng.num_global_nodes], dtype=np.int64))
    with pytest.raises(ValueError, match="out of range"):
        eng.query(np.array([-1], dtype=np.int64))


# ---------------- incremental freshness --------------------------------


def _assert_incremental_bit_identical(eng, model):
    rng = np.random.default_rng(1)
    before = None
    for round_i in range(3):  # repeated update/refresh cycles stay exact
        n = 10 + 5 * round_i
        ids = rng.integers(0, eng.num_global_nodes, n).astype(np.int64)
        vals = rng.normal(size=(n, eng.n_feat_raw)).astype(np.float32)
        if before is None:
            before = eng.query(ids[:4])
            probe = ids[:4]
        eng.apply_updates(ids, vals)
        assert eng.staleness_age >= 1 and not eng.fully_fresh
        eng.refresh_boundary()
        ref = np.asarray(eng.full_boundary_exchange())
        got = np.asarray(eng._halo0)
        assert got.dtype == ref.dtype and got.shape == ref.shape
        assert np.array_equal(ref, got), (
            f"{model}: incremental refresh != full re-exchange "
            f"(round {round_i})")
    # updates actually reach served logits after refresh()
    eng.refresh()
    assert eng.fully_fresh
    after = eng.query(probe)
    assert np.isfinite(after).all()
    assert not np.allclose(before, after)


def test_incremental_freshness_bit_identical(mutable):
    """The dirty-row send-list replay must land boundary slots
    BIT-IDENTICAL to rebuilding the whole halo from scratch."""
    _assert_incremental_bit_identical(mutable, "graphsage")


def test_incremental_freshness_bit_identical_gcn():
    """Same contract for gcn, whose send view pre-scales features by
    1/sqrt(deg) before shipping — the exchange input is NOT the raw
    feature row, so the patch/exchange op ordering must match the
    training forward exactly."""
    t, _ = _trainer(model="gcn", epochs=1)
    eng = ServingEngine.for_trainer(t)
    eng.warmup()
    _assert_incremental_bit_identical(eng, "gcn")


def test_refresh_boundary_noop_when_clean(served):
    _, _, eng = served  # never dirtied: no dispatch, returns 0
    assert eng.refresh_boundary() == 0


def test_staleness_ledger_and_use_pp_guard(mutable):
    eng = mutable
    # the bit-identity test (runs earlier in this file) leaves the
    # engine fully refreshed; independent of ordering, settle it first
    eng.refresh_boundary()
    eng.refresh()
    assert eng.staleness_age == 0 and eng.fully_fresh
    rng = np.random.default_rng(2)
    ids = rng.integers(0, eng.num_global_nodes, 8).astype(np.int64)
    vals = rng.normal(size=(8, eng.n_feat_raw)).astype(np.float32)
    eng.apply_updates(ids, vals)
    assert eng.staleness_age == 1
    eng.apply_updates(ids, vals)
    assert eng.staleness_age == 2
    eng.refresh_boundary()
    eng.refresh()
    assert eng.staleness_age == 0 and eng.fully_fresh
    # refresh() WITHOUT a boundary refresh leaves the halo lag visible
    eng.apply_updates(ids, vals)
    eng.refresh()
    assert eng.staleness_age == eng._halo_lag
    # shape/range validation
    with pytest.raises(ValueError, match="values must be"):
        eng.apply_updates(ids, vals[:, :2])
    with pytest.raises(ValueError, match="out of range"):
        eng.apply_updates(np.array([eng.num_global_nodes]), vals[:1])
    # use_pp folds raw features into the precompute: updates refused
    t_pp, _ = _trainer(use_pp=True, epochs=1, seed=37)
    eng_pp = ServingEngine.for_trainer(t_pp)
    eng_pp.warmup()
    assert np.isfinite(eng_pp.query(ids)).all()  # read path still fine
    with pytest.raises(ValueError, match="use_pp"):
        eng_pp.apply_updates(ids, vals)


# ---------------- layer-0 cache ---------------------------------------


def test_cache_invalidation_matches_brute_force():
    P, B = 4, 3
    rng = np.random.default_rng(0)
    send_idx = rng.integers(0, 50, (P, P - 1, B)).astype(np.int32)
    send_mask = rng.random((P, P - 1, B)) < 0.7
    cache = Layer0Cache(send_idx, send_mask)
    assert cache.n_stale == 0
    parts = np.array([0, 0, 2], dtype=np.int64)
    rows = np.array([send_idx[0, 0, 1], send_idx[0, 2, 0],
                     send_idx[2, 1, 2]], dtype=np.int64)
    touched = cache.invalidate_rows(parts, rows)
    # brute force: slot (d-1)*B+k on receiver q=(p+d)%P goes stale iff
    # partition p's send list at distance d ships a dirty row there
    expect = np.zeros((P, (P - 1) * B), bool)
    dirty = {(int(p), int(r)) for p, r in zip(parts, rows)}
    for p in range(P):
        for d in range(1, P):
            q = (p + d) % P
            for k in range(B):
                if send_mask[p, d - 1, k] and \
                        (p, int(send_idx[p, d - 1, k])) in dirty:
                    expect[q, (d - 1) * B + k] = True
    np.testing.assert_array_equal(cache.stale, expect)
    assert touched == int(expect.sum()) and cache.n_stale == touched
    for q in range(P):
        np.testing.assert_array_equal(cache.stale_slots(q),
                                      np.nonzero(expect[q])[0])
    cache.mark_fresh()
    assert cache.n_stale == 0
    # interior (never-sent) rows invalidate nothing
    interior = np.array([49], dtype=np.int64)
    masked = send_idx[3][send_mask[3]]
    if 49 not in masked:
        assert cache.invalidate_rows(np.array([3]), interior) == 0
    # hit accounting
    cache.record_queries(8, hit=True)
    cache.record_queries(2, hit=False)
    assert cache.hit_rate == pytest.approx(0.8)


# ---------------- end-to-end loop + records ----------------------------


def test_serving_loop_emits_valid_records(tmp_path, mutable):
    from pipegcn_tpu.obs.metrics import MetricsLogger, read_metrics
    from pipegcn_tpu.obs.schema import validate_record

    eng = mutable
    mpath = tmp_path / "serve.jsonl"
    with MetricsLogger(mpath) as ml:
        ml.run_header(config={}, device={}, mesh={})
        summary = run_serving_loop(
            eng, duration_s=1.2, qps=80.0, max_delay_ms=2.0,
            report_every_s=0.4, refresh_every_s=0.2,
            update_every_s=0.3, update_rows=8, seed=0, ml=ml)
    assert summary["n_queries"] > 0
    assert summary["qps"] > 0
    assert summary["p50_ms"] is not None and summary["p50_ms"] > 0
    assert summary["drained"] is True
    assert not summary["stopped_early"]
    recs = [r for r in read_metrics(mpath) if r.get("event") == "serving"]
    assert len(recs) == summary["n_records"] and recs
    for r in recs:
        validate_record(r)
        assert r["queries"] >= 0 and r["queue_depth"] >= 0
    assert recs[-1].get("final") is True
    total = sum(r["queries"] for r in recs)
    assert total == summary["n_queries"]


def test_serving_loop_stop_flag_drains(mutable):
    eng = mutable
    calls = [0]

    def stop():
        calls[0] += 1
        return calls[0] > 10  # stop almost immediately

    summary = run_serving_loop(eng, duration_s=30.0, qps=50.0,
                               report_every_s=1.0, seed=0, stop=stop)
    assert summary["stopped_early"] is True
    assert summary["drained"] is True
    assert summary["duration_s"] < 30.0


# ---------------- cli preflight + kill drill ---------------------------


def test_serve_cli_artifact_preflight_times_out(tmp_path):
    """Without --serve-build and without an artifact, cli.serve waits
    (bounded) for process 0's partition build instead of crashing with
    FileNotFoundError — and raises TimeoutError at the deadline."""
    from pipegcn_tpu.cli.serve import _load_partition, build_parser

    args = build_parser().parse_args([
        "--dataset", "synthetic:200:6:8:3", "--n-partitions", "4",
        "--partition-dir", str(tmp_path),
        "--serve-artifact-timeout", "0.3",
    ])
    t0 = time.monotonic()
    with pytest.raises(TimeoutError, match="partition artifact"):
        _load_partition(args)
    assert time.monotonic() - t0 < 30.0


@pytest.mark.slow
def test_serve_cli_kill_drill(tmp_path):
    """Chaos-lane drill: SIGTERM a live serve process mid-load; it must
    drain accepted queries and land a hard-flushed final `serving`
    record (final: true) before exiting 0."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    mpath = tmp_path / "metrics.jsonl"
    env = {
        **os.environ,
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
        "PYTHONPATH": repo,
    }
    proc = subprocess.Popen(
        [sys.executable, "-m", "pipegcn_tpu.cli.serve",
         "--dataset", "synthetic:600:8:16:4", "--n-partitions", "4",
         "--n-hidden", "16", "--n-layers", "2", "--fix-seed",
         "--partition-dir", str(tmp_path / "parts"), "--serve-build",
         "--metrics-out", str(mpath),
         "--serve-duration", "300", "--serve-qps", "40",
         "--serve-report-every", "0.5", "--serve-refresh-every", "0.5",
         "--serve-update-every", "0.4"],
        env=env, cwd=repo, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True)

    def n_serving_records():
        if not mpath.exists():
            return 0
        n = 0
        with open(mpath) as fh:
            for line in fh:
                try:
                    if json.loads(line).get("event") == "serving":
                        n += 1
                except json.JSONDecodeError:
                    pass  # mid-write line
        return n

    try:
        deadline = time.monotonic() + 240
        while n_serving_records() < 1:
            assert proc.poll() is None, (
                "serve exited before first record:\n"
                + proc.communicate()[0][-2000:])
            assert time.monotonic() < deadline, "no serving record"
            time.sleep(0.5)
        proc.send_signal(signal.SIGTERM)
        out, _ = proc.communicate(timeout=120)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.communicate()
    assert proc.returncode == 0, out[-2000:]
    recs = []
    with open(mpath) as fh:
        for line in fh:
            r = json.loads(line)  # post-exit: every line complete
            if r.get("event") == "serving":
                recs.append(r)
    assert recs and recs[-1].get("final") is True
    # the stdout summary reports a clean drain
    tail = [ln for ln in out.splitlines() if '"serve": true' in ln]
    assert tail, out[-2000:]
    summ = json.loads(tail[-1])
    assert summ["drained"] is True and summ["stopped_early"] is True
    # no silently dropped tickets: every accepted row was served or
    # explicitly shed before the final record landed
    assert summ["conserved"] is True
    assert summ["n_submitted"] == summ["n_served"] + summ["n_shed"]
