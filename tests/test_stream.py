"""Streaming-graph subsystem tests (docs/STREAMING.md).

The load-bearing contract is BIT-IDENTITY: after any sequence of delta
batches, the patched ShardedGraph (CSR slabs, send-lists, halo slots,
padded tables) must equal a from-scratch ``ShardedGraph.build`` of the
post-delta graph at the same padded dims — patching is an optimization,
never an approximation. On top of that: slack exhaustion must re-pad
LOUDLY (never silently corrupt), steady-state deltas must not recompile
anything, the pipelined comm carry must flush exactly the changed rows,
the serving topology-refresh path must reproduce a full boundary
exchange bitwise, and tampered delta files must be rejected at load.
"""

import os

import jax
import numpy as np
import pytest

from pipegcn_tpu.graph.synthetic import (synthetic_delta_schedule,
                                         synthetic_graph)
from pipegcn_tpu.models import ModelConfig
from pipegcn_tpu.parallel import Trainer, TrainConfig
from pipegcn_tpu.partition.halo import ShardedGraph
from pipegcn_tpu.partition.partitioner import partition_graph
from pipegcn_tpu.stream import (DeltaBatch, GraphPatcher, SlackExhausted,
                                StreamPlan, load_deltas, save_deltas)
from pipegcn_tpu.stream.patch import flush_masks

pytestmark = pytest.mark.stream

P = 4


def _stack(seed=6, n=240, slack=0.25, spmm="xla", model="graphsage",
           pipeline=False, n_epochs=6):
    g = synthetic_graph(num_nodes=n, avg_degree=6, n_feat=10, n_class=4,
                        seed=seed)
    parts = partition_graph(g, P)
    sg = ShardedGraph.build(g, parts, n_parts=P, slack=slack)
    cfg = ModelConfig(layer_sizes=(10, 12, 4), norm="layer",
                      dropout=0.0, model=model,
                      train_size=sg.n_train_global, spmm_impl=spmm)
    tcfg = TrainConfig(seed=3, enable_pipeline=pipeline,
                      n_epochs=n_epochs, log_every=10_000,
                      fused_epochs=1)
    t = Trainer(sg, cfg, tcfg)
    patcher = GraphPatcher(g, sg, parts, slack=slack)
    t.enable_stream(patcher)
    return g, parts, sg, cfg, tcfg, t, patcher


def _fresh_rebuild(patcher, sg, cfg, tcfg):
    """From-scratch oracle at the SAME padded dims as the patched
    state (bit-identity needs identical shapes)."""
    sg2 = ShardedGraph.build(
        patcher.g, patcher.parts, n_parts=P,
        min_n_max=patcher.sg.n_max, min_b_max=patcher.sg.b_max,
        min_e_max=patcher.sg.e_max)
    return Trainer(sg2, cfg, tcfg), sg2


def _assert_data_bit_identical(t, t2, but=()):
    """Every device table equal bit for bit, but those whose key starts
    with one of `but`."""
    d1 = {k: v for k, v in jax.device_get(t.data).items()
          if not k.startswith(tuple(but))}
    d2 = {k: v for k, v in jax.device_get(t2.data).items()
          if not k.startswith(tuple(but))}
    assert set(d1) == set(d2)
    for k in sorted(d1):
        a, b = np.asarray(d1[k]), np.asarray(d2[k])
        assert a.shape == b.shape, (k, a.shape, b.shape)
        assert a.dtype == b.dtype, (k, a.dtype, b.dtype)
        assert np.array_equal(a, b), (
            k, np.argwhere(a != b)[:5] if a.shape else (a, b))


# ---------------- bit-identity oracle --------------------------------


@pytest.mark.parametrize("spmm", ["xla", "bucket"])
def test_patched_tables_bit_identical_to_rebuild(spmm):
    """Every device table (CSR slabs, send-lists, halo routing, feats,
    masks) after two delta batches == a from-scratch build of the
    post-delta graph, on the raw-gather AND the dirty-shard incremental
    bucket-table path. The bucket kernel's tables keep the ladder they
    had (a fitted ladder moves with the histogram, and with it every
    shape: bucket_spmm.build_sharded_bucket_tables), so they are held
    to what they SUM: every row's neighbours, both directions."""
    g, parts, sg, cfg, tcfg, t, patcher = _stack(spmm=spmm)
    n0 = g.num_nodes
    for b in synthetic_delta_schedule(g, n_batches=2, edges_per_batch=5,
                                      dels_per_batch=3,
                                      nodes_per_batch=2, seed=21):
        rep = t.apply_graph_deltas(b)
        assert not rep.repadded
        assert rep.touched_parts
    # new nodes landed: host graph grew in place, sg identity kept
    assert patcher.g.num_nodes == n0 + 4
    assert patcher.sg is t.sg
    t2, _ = _fresh_rebuild(patcher, sg, cfg, tcfg)
    _assert_data_bit_identical(t, t2, but=("bkt_",))
    if spmm == "bucket":
        _assert_bucket_tables_sum_the_same(t, t2)
    # eval parity on the patched graph: identical params through both
    # stacks must score identically (the forward pass IS the tables)
    t2.state = dict(t2.state)
    t2.state["params"] = t.state["params"]
    t2.state["norm"] = t.state["norm"]
    a1 = t.evaluate(patcher.g, "val_mask", sharded=True)
    a2 = t2.evaluate(patcher.g, "val_mask", sharded=True)
    assert a1 == a2
    # ...and training continues finite on the patched tables
    assert np.isfinite(t.train_epoch(0))


def _assert_bucket_tables_sum_the_same(t, t2):
    from test_bucket_spmm import _table_neighbours

    n_src_rows = t.sg.n_max + t.sg.halo_size
    for stem, sentinel in (("bkt_fwd", n_src_rows), ("bkt_bwd", t.sg.n_max)):
        assert _table_neighbours(t._bucket_tables, stem, sentinel) == \
            _table_neighbours(t2._bucket_tables, stem, sentinel)


def _bucket_widths_of(t):
    return {k: v.shape[1] for k, v in t._bucket_tables.items()
            if not k.endswith("inv")}


def test_bucket_delta_keeps_the_ladder_and_a_grown_row_refits():
    """Under the bucket kernel a within-slack delta is a DIRTY rebuild:
    the fitted widths it was compiled for stay (same jitted step, same
    table widths, from the first delta on), and the tables sum what
    a from-scratch build of the patched graph sums. A delta that grows
    a row past the ladder's top width refits, and still sums the same."""
    g, parts, sg, cfg, tcfg, t, patcher = _stack(slack=0.30,
                                                 spmm="bucket")
    assert np.isfinite(t.train_epoch(0))
    step_before, widths = t._step, _bucket_widths_of(t)
    ladder = t._bucket_plan_cache["widths"]
    # the cache keeps a ladder a part; these directions are one part
    for d, fitted in zip(("bkt_fwd", "bkt_bwd"), ladder):
        assert [w for k, w in sorted(widths.items())
                if k.startswith(d)] == list(*fitted)
    b = synthetic_delta_schedule(g, n_batches=1, edges_per_batch=6,
                                 dels_per_batch=2, nodes_per_batch=1,
                                 seed=3)[0]
    rep = t.apply_graph_deltas(b)
    assert not rep.repadded and rep.touched_parts
    assert t._step is step_before
    assert t._bucket_plan_cache["widths"] == ladder
    assert _bucket_widths_of(t) == widths
    t2, _ = _fresh_rebuild(patcher, sg, cfg, tcfg)
    _assert_bucket_tables_sum_the_same(t, t2)
    assert np.isfinite(t.train_epoch(1))
    # the node of the most in-edges gets more than the top width holds,
    # from nodes of its own partition that are no neighbours yet
    deg = np.bincount(patcher.g.dst, minlength=patcher.g.num_nodes)
    v = int(np.argmax(deg))
    nbrs = set(patcher.g.src[patcher.g.dst == v].tolist())
    mates = [u for u in np.nonzero(patcher.parts == patcher.parts[v])[0]
             if u not in nbrs]
    need = ladder[0][0][-1] - int(deg[v]) + 2
    assert 0 < need <= len(mates)
    rep = t.apply_graph_deltas(DeltaBatch.make(
        seq=1, add_edges=[(u, v) for u in mates[:need]]))
    assert not rep.repadded
    refit = t._bucket_plan_cache["widths"]
    assert refit != ladder and refit[0][0][-1] >= ladder[0][0][-1] + 2
    t3, _ = _fresh_rebuild(patcher, sg, cfg, tcfg)
    _assert_bucket_tables_sum_the_same(t, t3)
    assert _bucket_widths_of(t) == _bucket_widths_of(t3)
    assert np.isfinite(t.train_epoch(2))


# ---------------- slack exhaustion -----------------------------------


def test_slack_exhaustion_is_loud_then_repads():
    """A batch past the reserved headroom raises SlackExhausted when
    re-padding is off, and re-pads LOUDLY (repadded=True, grown dims,
    still bit-identical) when it is allowed."""
    g, parts, sg, cfg, tcfg, t, patcher = _stack(slack=0.0)
    # a star of brand-new nodes wired to node 0 overflows any 0-slack
    # padding in one shot
    m = 12
    rng = np.random.default_rng(0)
    feats = rng.normal(size=(m, 10)).astype(np.float32)
    labels = np.zeros(m, dtype=np.int64)
    nbrs = tuple(np.array([0], dtype=np.int64) for _ in range(m))
    big = DeltaBatch(seq=0, add_edges=np.zeros((0, 2), np.int64),
                     del_edges=np.zeros((0, 2), np.int64),
                     node_feat=feats, node_label=labels, node_nbrs=nbrs)
    with pytest.raises(SlackExhausted):
        patcher.apply(big, allow_repad=False)
    rep = t.apply_graph_deltas(big)  # allow_repad=True path
    assert rep.repadded
    assert t.sg.n_max > sg.n_max or t.sg.e_max > sg.e_max \
        or t.sg.b_max > sg.b_max
    t2, _ = _fresh_rebuild(patcher, sg, cfg, tcfg)
    _assert_data_bit_identical(t, t2)
    assert np.isfinite(t.train_epoch(0))


# ---------------- zero-recompile pin ---------------------------------


def test_steady_state_delta_does_not_recompile():
    """A within-slack delta must leave the compiled step untouched:
    same jitted step object, every device-table shape/dtype unchanged
    (shape-stability + same callable == cache hit, no retrace)."""
    g, parts, sg, cfg, tcfg, t, patcher = _stack(slack=0.30)
    assert np.isfinite(t.train_epoch(0))
    step_before = t._step
    shapes_before = {k: (v.shape, str(v.dtype))
                     for k, v in t.data.items()}
    b = synthetic_delta_schedule(g, n_batches=1, edges_per_batch=6,
                                 dels_per_batch=2, nodes_per_batch=1,
                                 seed=3)[0]
    rep = t.apply_graph_deltas(b)
    assert not rep.repadded
    assert t._step is step_before
    shapes_after = {k: (v.shape, str(v.dtype))
                    for k, v in t.data.items()}
    assert shapes_after == shapes_before
    assert np.isfinite(t.train_epoch(1))


# ---------------- pipelined carry flush ------------------------------


def test_carry_flush_zeroes_exactly_the_changed_rows():
    """After a delta under the pipelined trainer, comm-carry rows whose
    send-list entries changed are zeroed (receiver side for halo/favg,
    sender side for bgrad/bavg) and every untouched row is bitwise
    preserved — a stale carry for a re-routed slot would inject another
    node's features."""
    g, parts, sg, cfg, tcfg, t, patcher = _stack(pipeline=True)
    for e in range(3):  # populate the staleness-1 carry
        assert np.isfinite(t.train_epoch(e))
    before = jax.device_get(t.state["comm"])
    b = synthetic_delta_schedule(g, n_batches=1, edges_per_batch=6,
                                 dels_per_batch=3, nodes_per_batch=1,
                                 seed=11)[0]
    rep = t.apply_graph_deltas(b)
    assert rep.changed_send is not None and rep.changed_send.any()
    recv, send = flush_masks(rep.changed_send, P, t.sg.b_max)
    masks = {"halo": recv, "favg": recv, "bgrad": send, "bavg": send}
    after = jax.device_get(t.state["comm"])
    flushed = 0
    for grp, bufs in after.items():
        if grp not in masks:
            continue
        m = masks[grp]
        for k, v in bufs.items():
            v = np.asarray(v)
            old = np.asarray(before[grp][k])
            assert np.all(v[m] == 0), (grp, k)
            assert np.array_equal(v[~m], old[~m]), (grp, k)
            flushed += int(m.sum())
    assert flushed > 0
    assert np.isfinite(t.train_epoch(3))


# ---------------- fit() integration ----------------------------------


def test_fit_applies_stream_plan_and_fault_grammar(tmp_path):
    """End to end through fit(): scheduled deltas land at their epochs,
    the graph-delta fault kind injects an unscheduled batch, every
    application emits a contracted v8 `stream` record with forced-probe
    drift, and the plan is fully consumed."""
    from pipegcn_tpu.obs.metrics import MetricsLogger, read_metrics
    from pipegcn_tpu.resilience.faults import FaultPlan

    g, parts, sg, cfg, tcfg, t, patcher = _stack(pipeline=True,
                                                 n_epochs=10)
    batches = synthetic_delta_schedule(g, n_batches=2,
                                       edges_per_batch=4,
                                       dels_per_batch=2,
                                       nodes_per_batch=1, seed=9)
    dpath = str(tmp_path / "deltas.jsonl")
    save_deltas(dpath, batches)
    plan = StreamPlan.parse(f"{dpath}@4:3")  # epochs 4, 7
    fp = FaultPlan.parse("graph-delta@9")
    mpath = str(tmp_path / "metrics.jsonl")
    with MetricsLogger(mpath) as m:
        t.fit(None, log_fn=lambda *_: None, metrics=m,
              stream_plan=plan, fault_plan=fp)
    recs = read_metrics(mpath)
    stream = [r for r in recs if r["event"] == "stream"]
    assert [r["epoch"] for r in stream] == [4, 7, 9]
    assert [r["seq"] for r in stream] == [0, 1, 2]
    assert all(r["drift"] is not None for r in stream)
    assert all(not r["repadded"] for r in stream)
    faults = [r for r in recs if r["event"] == "fault"]
    assert any(r.get("reason") == "graph-delta" for r in faults)
    assert plan.remaining() == 0


def test_fit_rebuild_after_repad_writes_a_load_span(tmp_path):
    """A delta past the slack re-pads and rebuilds the step inside fit():
    the next dispatch compiles it again, and its `fit/load` span (a
    child of that block's compute span) names the rebuilt step."""
    from pipegcn_tpu.obs.metrics import MetricsLogger, read_metrics

    g, parts, sg, cfg, tcfg, t, patcher = _stack(slack=0.0, n_epochs=4)
    m = 12
    rng = np.random.default_rng(0)
    big = DeltaBatch(seq=0, add_edges=np.zeros((0, 2), np.int64),
                     del_edges=np.zeros((0, 2), np.int64),
                     node_feat=rng.normal(size=(m, 10)).astype(np.float32),
                     node_label=np.zeros(m, dtype=np.int64),
                     node_nbrs=tuple(np.array([0], dtype=np.int64)
                                     for _ in range(m)))
    dpath = str(tmp_path / "deltas.jsonl")
    save_deltas(dpath, [big])
    mpath = str(tmp_path / "metrics.jsonl")
    step_before = t._step
    with MetricsLogger(mpath) as ml:
        t.fit(None, log_fn=lambda *_: None, metrics=ml,
              stream_plan=StreamPlan.parse(f"{dpath}@2"))
    assert t._step is not step_before
    recs = read_metrics(mpath)
    assert [r["repadded"] for r in recs if r["event"] == "stream"] == [True]
    loads = {r["epoch"]: r for r in recs
             if r["event"] == "span" and r["op"] == "fit/load"}
    assert sorted(loads) == [0, 2]      # the first step, the rebuilt one
    assert loads[2]["compiles"] >= 1
    assert "jit(step)" in loads[0]["programs"]
    assert loads[2]["programs"] == ["jit(step)"]
    compute = {r["span_id"]: r for r in recs
               if r["event"] == "span" and r["op"] == "compute"}
    assert compute[loads[2]["parent"]]["epoch"] == 2


# ---------------- serving topology refresh ---------------------------


@pytest.mark.serving
@pytest.mark.parametrize("model", ["graphsage", "gcn"])
def test_serving_topology_delta_freshness_oracle(model):
    """The serving oracle: after a topology delta, the incremental path
    (changed-slot flush + dirty-row exchange) must reproduce a full
    boundary exchange BITWISE, with zero retraces, and query logits
    over every node (including new ones) must equal a from-scratch
    trainer+engine stack — for the SAGE and the GCN (in-deg pre-scale)
    send views."""
    from pipegcn_tpu.serve.engine import ServingEngine, trace_counts

    g, parts, sg, cfg, tcfg, t, patcher = _stack(model=model, n=260)
    eng = ServingEngine.for_trainer(t)
    eng.warmup()
    # a plain feature update first: both update paths coexist
    eng.apply_updates([3, 17], np.ones((2, 10), np.float32))
    eng.refresh_boundary()

    batches = synthetic_delta_schedule(g, n_batches=2,
                                       edges_per_batch=5,
                                       dels_per_batch=3,
                                       nodes_per_batch=2, seed=21)
    tc0 = dict(trace_counts())
    gen0 = eng.topo_generation
    for b in batches:
        rep = t.apply_graph_deltas(b)
        assert not rep.repadded
        eng.apply_graph_deltas(rep)
        eng.refresh_boundary()
        inc = np.asarray(eng._halo0)
        full = np.asarray(eng.full_boundary_exchange())
        assert np.array_equal(inc, full), np.argwhere(inc != full)[:5]
        eng.refresh()
    assert eng.topo_generation == gen0 + len(batches)
    assert dict(trace_counts()) == tc0, "topology deltas retraced"

    # fresh-stack logits oracle, every node incl. the 4 new ones
    sg2 = ShardedGraph.build(patcher.g, patcher.parts, n_parts=P,
                             min_n_max=sg.n_max, min_b_max=sg.b_max,
                             min_e_max=sg.e_max)
    t2 = Trainer(sg2, cfg, tcfg)
    eng2 = ServingEngine.for_trainer(t2)
    eng2._params, eng2._norm = eng._params, eng._norm
    eng2.apply_updates([3, 17], np.ones((2, 10), np.float32))
    eng2.refresh_boundary()
    eng2.refresh()
    q = np.arange(eng.num_global_nodes, dtype=np.int64)
    assert eng.num_global_nodes == g.num_nodes  # g mutated in place
    a = eng.query(q)
    b = eng2.query(q)
    assert np.array_equal(a, b)


def test_serving_repad_invalidates_engine():
    """A re-padding delta changes compiled shapes: the engine must
    refuse to limp along (RuntimeError directing a rebuild) and the
    trainer's engine cache must be cleared."""
    from pipegcn_tpu.serve.engine import ServingEngine

    g, parts, sg, cfg, tcfg, t, patcher = _stack(slack=0.0)
    eng = ServingEngine.for_trainer(t)
    eng.warmup()
    m = 12
    rng = np.random.default_rng(0)
    big = DeltaBatch(
        seq=0, add_edges=np.zeros((0, 2), np.int64),
        del_edges=np.zeros((0, 2), np.int64),
        node_feat=rng.normal(size=(m, 10)).astype(np.float32),
        node_label=np.zeros(m, dtype=np.int64),
        node_nbrs=tuple(np.array([0], np.int64) for _ in range(m)))
    rep = t.apply_graph_deltas(big)
    assert rep.repadded
    with pytest.raises(RuntimeError, match="rebuild"):
        eng.apply_graph_deltas(rep)
    assert not getattr(t, "_serving_engines", {})
    # a rebuilt engine serves the grown graph
    eng2 = ServingEngine.for_trainer(t)
    eng2.warmup()
    out = eng2.query(np.arange(g.num_nodes, dtype=np.int64))
    assert np.all(np.isfinite(out))


# ---------------- delta format guards --------------------------------


def test_delta_file_roundtrip_and_crc_tamper_rejected(tmp_path):
    """save/load round-trips both formats bit-exactly; a tampered
    payload (JSONL field edit, npz array bit-flip) fails CRC at load —
    a half-written or corrupted delta file must never patch a graph."""
    g = synthetic_graph(num_nodes=120, avg_degree=5, n_feat=6,
                        n_class=3, seed=1)
    batches = synthetic_delta_schedule(g, n_batches=3,
                                       edges_per_batch=4,
                                       dels_per_batch=2,
                                       nodes_per_batch=1, seed=2)
    for ext in ("jsonl", "npz"):
        path = str(tmp_path / f"d.{ext}")
        save_deltas(path, batches)
        loaded = load_deltas(path)
        assert [b.seq for b in loaded] == [b.seq for b in batches]
        for a, b in zip(loaded, batches):
            assert np.array_equal(a.add_edges, b.add_edges)
            assert np.array_equal(a.del_edges, b.del_edges)
            assert np.array_equal(a.node_feat, b.node_feat)

    # JSONL tamper: flip one digit inside a batch record
    jpath = str(tmp_path / "d.jsonl")
    with open(jpath) as f:
        lines = f.read().splitlines()
    import json as _json

    rec = _json.loads(lines[1])
    rec["add_edges"][0][0] += 1
    lines[1] = _json.dumps(rec)
    tampered = str(tmp_path / "tampered.jsonl")
    with open(tampered, "w") as f:
        f.write("\n".join(lines) + "\n")
    with pytest.raises(ValueError, match="CRC"):
        load_deltas(tampered)

    # npz tamper: rewrite one payload array, keep the stored CRC
    npath = str(tmp_path / "d.npz")
    z = dict(np.load(npath, allow_pickle=False))
    key = next(k for k in z if k.endswith("add_edges") and z[k].size)
    z[key] = z[key] + 1
    tampered_n = str(tmp_path / "tampered.npz")
    np.savez(tampered_n, **z)
    with pytest.raises(ValueError, match="CRC"):
        load_deltas(tampered_n)


def test_stream_plan_grammar_errors(tmp_path):
    """Malformed --stream-plan specs fail loudly at parse time."""
    g = synthetic_graph(num_nodes=60, avg_degree=4, n_feat=4,
                        n_class=2, seed=0)
    batches = synthetic_delta_schedule(g, n_batches=1,
                                       edges_per_batch=2,
                                       dels_per_batch=1,
                                       nodes_per_batch=0, seed=0)
    path = str(tmp_path / "d.jsonl")
    save_deltas(path, batches)
    with pytest.raises((ValueError, FileNotFoundError)):
        StreamPlan.parse(str(tmp_path / "missing.jsonl") + "@3")
    with pytest.raises(ValueError):
        StreamPlan.parse(f"{path}@notanepoch")
    with pytest.raises(ValueError):
        StreamPlan.parse(path)  # no @epoch
    plan = StreamPlan.parse(f"{path}@2")
    assert plan.remaining() == 1
    assert plan.due(1) == []
    assert len(plan.due(2)) == 1
    assert plan.remaining() == 0
