"""Hybrid block-dense SpMM: unit parity vs dense reference (dense tiles
AND sparse remainder exercised), gradient parity vs the XLA path, and
trainer-level parity vs gather+segment-sum."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from pipegcn_tpu.graph import synthetic_graph
from pipegcn_tpu.models import ModelConfig
from pipegcn_tpu.ops.block_spmm import (
    BlockPlan,
    make_block_spmm_fn,
    plan_to_arrays,
)
from pipegcn_tpu.ops.spmm import spmm_mean
from pipegcn_tpu.parallel import Trainer, TrainConfig
from pipegcn_tpu.partition import ShardedGraph, partition_graph


@pytest.fixture(scope="module")
def edges():
    rng = np.random.default_rng(9)
    n_out, n_src = 96, 130
    e = 1200
    src = rng.integers(0, n_src, e).astype(np.int64)
    dst = rng.integers(0, n_out, e).astype(np.int64)
    # concentrate edges into one (dst-tile, src-tile) block so the dense
    # path has real work at tile=16
    dst[:300] = rng.integers(0, 16, 300)
    src[:300] = rng.integers(16, 32, 300)
    mask = dst != 5  # row 5 has no edges
    return src[mask], dst[mask], n_out, n_src


def _ref_mean(src, dst, n_out, fbuf, deg):
    out = np.zeros((n_out, fbuf.shape[1]), np.float32)
    for s, d in zip(src, dst):
        out[d] += np.asarray(fbuf, np.float32)[s]
    return out / np.asarray(deg)[:, None]


def _dense_a(tables, direction="fwd"):
    """The stored A arrays of one direction's dense classes."""
    return [v for k, v in sorted(tables.items())
            if k.startswith(f"blk_{direction}_g") and k.endswith("a")]


def _make_fn(src, dst, n_out, n_src, deg, tile, nnz_threshold):
    plan = BlockPlan(src, dst, n_out, n_src, n_feat=8, tile=tile,
                     nnz_threshold=nnz_threshold)
    arrs = {k: jnp.asarray(v) for k, v in plan_to_arrays(plan).items()}
    return plan, make_block_spmm_fn(arrs, deg, n_out, n_src, tile)


@pytest.mark.parametrize("nnz_threshold", [4, 10**9])
def test_block_mean_matches_dense(edges, nnz_threshold):
    """Low threshold → dense tiles carry most edges; huge threshold →
    everything goes through the remainder (bucket) path. Both must agree
    with the dense reference."""
    src, dst, n_out, n_src = edges
    rng = np.random.default_rng(0)
    fbuf = rng.standard_normal((n_src, 8)).astype(np.float32)
    deg = jnp.asarray(
        np.maximum(np.bincount(dst, minlength=n_out), 1).astype(np.float32)
    )
    plan, fn = _make_fn(src, dst, n_out, n_src, deg, 16, nnz_threshold)
    if nnz_threshold == 4:
        assert plan.a_blocks.shape[0] > 0  # dense path actually exercised
        assert plan.rem_count < src.shape[0]
    else:
        assert plan.a_blocks.shape[0] == 0
    out = fn(jnp.asarray(fbuf))
    np.testing.assert_allclose(
        np.asarray(out), _ref_mean(src, dst, n_out, fbuf, deg),
        rtol=1e-5, atol=1e-5)
    assert np.abs(np.asarray(out)[5]).max() == 0.0  # zero-degree row


def test_block_fn_grad_matches_reference(edges):
    src, dst, n_out, n_src = edges
    rng = np.random.default_rng(2)
    fbuf = jnp.asarray(rng.standard_normal((n_src, 8)).astype(np.float32))
    deg = jnp.asarray(
        np.maximum(np.bincount(dst, minlength=n_out), 1).astype(np.float32)
    )
    _, fn = _make_fn(src, dst, n_out, n_src, deg, 16, 4)
    order = np.argsort(dst, kind="stable")
    es = jnp.asarray(src[order].astype(np.int32))
    ed = jnp.asarray(dst[order].astype(np.int32))

    v_a, g_a = jax.value_and_grad(lambda f: (fn(f) ** 2).sum())(fbuf)
    v_b, g_b = jax.value_and_grad(
        lambda f: (spmm_mean(f, es, ed, deg, n_out, None, True) ** 2).sum()
    )(fbuf)
    np.testing.assert_allclose(float(v_a), float(v_b), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(g_a), np.asarray(g_b),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("spmm_chunk,rem_dtype",
                         [(None, None), (40, None), (40, "float8")])
def test_trainer_block_matches_xla(spmm_chunk, rem_dtype, monkeypatch):
    """Under shard_map on four devices; an edge budget of 40 cuts the
    remainder's buckets of more than 32 rows into chunks. Under fp8
    transport the remainder's messages are gathered as 16-bit words:
    the losses are those of the same trainer gathering element by
    element, to the bit, and the float32 kernel's within fp8's
    rounding."""
    from pipegcn_tpu.ops import bucket_spmm as bs

    g = synthetic_graph(num_nodes=300, avg_degree=7, n_feat=10, n_class=4,
                        seed=21)
    parts = partition_graph(g, 4, seed=0)
    sg = ShardedGraph.build(g, parts, n_parts=4)
    losses = {}
    for impl in ("xla", "block") + (("elements",) if rem_dtype else ()):
        if impl == "elements":
            monkeypatch.setattr(bs, "_rides_as_words", lambda dt, f: False)
        cfg = ModelConfig(layer_sizes=(10, 16, 4), norm="layer",
                          dropout=0.0, train_size=sg.n_train_global,
                          spmm_impl="xla" if impl == "xla" else "block",
                          spmm_chunk=spmm_chunk,
                          rem_dtype=None if impl == "xla" else rem_dtype)
        t = Trainer(sg, cfg, TrainConfig(seed=4, enable_pipeline=True))
        losses[impl] = [t.train_epoch(e) for e in range(6)]
        if rem_dtype and impl != "xla":
            # the compiled step holds 16-bit words, or none at all
            assert ("u16[" in t.step_compiled_text(1)) == (
                impl != "elements")
    if spmm_chunk:
        assert max(v.shape[-1] for k, v in t._block_tables.items()
                   if k.startswith("blkrem_")
                   and not k.endswith("inv")) > 32
    np.testing.assert_allclose(losses["xla"], losses["block"],
                               rtol=5e-2 if rem_dtype else 2e-4)
    if rem_dtype:
        assert losses["block"] == losses["elements"]
        assert losses["block"] != losses["xla"]


def test_block_budget_spill_and_wide_counts_stay_exact():
    """A tight byte budget forces dense-block spills, and >127-fold
    duplicate edges force the wider A dtype's smaller cap (the rebuild
    path): every edge must still be aggregated exactly once — spilled
    blocks' high-degree rows must not overflow a stale remainder
    ladder."""
    import ml_dtypes

    from pipegcn_tpu.graph import synthetic_graph
    from pipegcn_tpu.graph.csr import Graph
    from pipegcn_tpu.ops.block_spmm import (
        build_sharded_block_tables,
        make_device_block_spmm_fn,
    )

    base = synthetic_graph(num_nodes=256, avg_degree=12, n_feat=6,
                           n_class=3, homophily=0.9, seed=11)
    # multigraph: repeat one hub edge 200x (forces bf16 A, isz=2)
    rng = np.random.default_rng(0)
    rep_src = np.full(200, int(base.src[0]), np.int64)
    rep_dst = np.full(200, int(base.dst[0]), np.int64)
    g = Graph(base.num_nodes,
              np.concatenate([base.src, rep_src]),
              np.concatenate([base.dst, rep_dst]),
              ndata={k: v for k, v in base.ndata.items()
                     if k != "in_deg"})
    parts = partition_graph(g, 1, seed=0)
    sg = ShardedGraph.build(g, parts, n_parts=1)

    # budget of ONE int8 tile at tile=16 -> heavy spills; wide counts
    # then halve the cap during the dtype rebuild
    tables, tile = build_sharded_block_tables(
        sg, tile=16, n_feat_hint=6, byte_budget=16 * 16 * 2)
    # the wide-dtype path ran, in both directions' arrangements
    assert {a.dtype for d in ("fwd", "bwd") for a in _dense_a(tables, d)} \
        == {np.dtype(ml_dtypes.bfloat16)}

    fbuf_rows = sg.n_max + sg.halo_size
    fbuf = rng.standard_normal((fbuf_rows, 6)).astype(np.float32)
    d = {k: jnp.asarray(v[0]) for k, v in tables.items()}
    f = make_device_block_spmm_fn(
        d, jnp.asarray(sg.in_deg[0]), sg.n_max, fbuf_rows, tile)
    out = np.asarray(f(jnp.asarray(fbuf)))

    # dense reference over the padded edge list
    e = sg.edge_count[0]
    src, dst = sg.edge_src[0][:e], sg.edge_dst[0][:e]
    ref = np.zeros((sg.n_max, 6), np.float32)
    np.add.at(ref, dst, fbuf[src])
    ref /= sg.in_deg[0][:, None]
    np.testing.assert_allclose(out[:sg.n_max], ref, rtol=2e-2, atol=2e-2)


def test_trainer_block_clustered_matches_xla():
    """The intended production path: cluster-renumbered local ids feed
    the block-dense plan real dense tiles; training must still match the
    raw-edge XLA trainer loss-for-loss on the same layout."""
    from pipegcn_tpu.partition import locality_clusters

    g = synthetic_graph(num_nodes=600, avg_degree=10, n_feat=12,
                        n_class=4, homophily=0.9, seed=25)
    parts = partition_graph(g, 4, seed=0)
    cluster = locality_clusters(g, target_size=64, seed=0)
    sg = ShardedGraph.build(g, parts, n_parts=4, cluster=cluster)
    losses = {}
    for impl in ("xla", "block"):
        cfg = ModelConfig(layer_sizes=(12, 16, 4), norm="layer",
                          dropout=0.0, train_size=sg.n_train_global,
                          spmm_impl=impl, block_tile=32)
        t = Trainer(sg, cfg, TrainConfig(seed=4, enable_pipeline=True))
        losses[impl] = [t.train_epoch(e) for e in range(6)]
        if impl == "block":
            # the clustered layout must actually produce dense blocks
            assert _dense_a(t._block_tables)
    np.testing.assert_allclose(losses["xla"], losses["block"], rtol=2e-4)


def test_trainer_block_bf16_fused():
    g = synthetic_graph(num_nodes=300, avg_degree=7, n_feat=10, n_class=4,
                        seed=23)
    parts = partition_graph(g, 4, seed=0)
    sg = ShardedGraph.build(g, parts, n_parts=4)
    cfg = ModelConfig(layer_sizes=(10, 16, 16, 4), norm="layer",
                      dropout=0.2, train_size=sg.n_train_global,
                      spmm_impl="block", dtype="bfloat16", use_pp=True)
    t = Trainer(sg, cfg, TrainConfig(seed=4, enable_pipeline=True,
                                     feat_corr=True, grad_corr=True))
    losses = list(t.train_epochs(0, 4)) + list(t.train_epochs(4, 16))
    assert np.isfinite(losses).all()
    assert np.mean(losses[-4:]) < np.mean(losses[:4])


def test_bitpacked_a_parity_and_selection():
    """Simple graphs (0/1 edge multiplicity) ship A bit-packed: the
    sharded builder must emit both directions' A as uint8 with the
    contracted axis packed 8 to a byte, the cap must reflect the 8x
    cheaper encoding, and the device unpack must be numerically
    identical to the unpacked plan."""
    from pipegcn_tpu.ops.block_spmm import (
        build_sharded_block_tables,
        make_device_block_spmm_fn,
        pack_a_blocks,
    )

    rng = np.random.default_rng(3)
    n = 256
    # simple clustered graph: unique (src, dst) pairs only
    src = rng.integers(0, n, 4000)
    dst = rng.integers(0, n, 4000)
    src[:3000] = rng.integers(0, 64, 3000)
    dst[:3000] = rng.integers(0, 64, 3000)
    pairs = np.unique(np.stack([src, dst], 1), axis=0)
    src, dst = pairs[:, 0], pairs[:, 1]

    from pipegcn_tpu.graph.csr import Graph

    feat = rng.standard_normal((n, 8)).astype(np.float32)
    g = Graph(n, src, dst, ndata={
        "feat": feat,
        "label": np.zeros(n, np.int64),
        "train_mask": np.ones(n, bool),
        "val_mask": np.zeros(n, bool),
        "test_mask": np.zeros(n, bool),
    })
    parts = partition_graph(g, 1, seed=0)
    sg = ShardedGraph.build(g, parts, n_parts=1)

    tables, tile = build_sharded_block_tables(
        sg, tile=16, n_feat_hint=8, byte_budget=1 << 16)
    for d in ("fwd", "bwd"):
        assert _dense_a(tables, d)
        for k in (k for k in tables if k.startswith(f"blk_{d}_g")
                  and k.endswith("a")):
            a_bits, width = tables[k], tables[k[:-1] + "t"].shape[-1]
            assert a_bits.dtype == np.uint8
            assert a_bits.shape[-3:] == (width, tile // 8, tile)

    fbuf_rows = sg.n_max + sg.halo_size
    fbuf = rng.standard_normal((fbuf_rows, 8)).astype(np.float32)
    d = {k: jnp.asarray(v[0]) for k, v in tables.items()}
    fn = make_device_block_spmm_fn(
        d, jnp.asarray(sg.in_deg[0]), sg.n_max, fbuf_rows, tile)
    out = np.asarray(fn(jnp.asarray(fbuf)))

    e = sg.edge_count[0]
    ref = _ref_mean(sg.edge_src[0][:e], sg.edge_dst[0][:e], sg.n_max,
                    fbuf, sg.in_deg[0])
    np.testing.assert_allclose(out[:sg.n_max], ref, rtol=1e-5, atol=1e-5)

    # pack/unpack round-trip on a raw block tensor: the device unpacks
    # along the second-minor axis, the one the kernel contracts
    a = (rng.random((3, 16, 16)) < 0.3).astype(np.float32)
    packed = pack_a_blocks(a, axis=1)
    assert packed.shape == (3, 2, 16)
    from pipegcn_tpu.ops.block_spmm import _unpack_bits

    unpacked = np.asarray(_unpack_bits(jnp.asarray(packed), jnp.float32))
    np.testing.assert_array_equal(unpacked, a)


def test_group_union_extends_short_ladder():
    """An explicitly passed union-width ladder that tops out below the
    device's max union size is extended, not a hard failure — direct
    BlockPlan callers may reuse a group=1 layout's K-class ladder."""
    from pipegcn_tpu.ops.block_spmm import _group_union

    # one group of 4 key tiles referencing 6 distinct other-tiles:
    # union size 6 > ladder max 2
    keys = np.array([0, 1, 2, 3, 0, 1], np.int64)
    others = np.array([0, 1, 2, 3, 4, 5], np.int64)
    classes, inv, counts, widths = _group_union(
        keys, others, n_key_tiles=4, n_other_tiles=6, group=4,
        n_blocks_pad=6, widths=[1, 2])
    assert widths[-1] >= 6  # ladder extended to cover the union
    total_rows = sum(c for c in counts)
    assert total_rows == 1  # the single group landed in some class
    # every block is placed: the widest class holds all 6 union slots
    a_idx, t_mat = classes[-1]
    assert (t_mat[0] != 6).sum() == 6


@pytest.mark.parametrize("group", [2, 4])
def test_block_grouped_union_matches_dense(edges, group):
    """Union-gather layout (block_group > 1): consecutive dst tiles
    share one gathered source-tile union. Must agree exactly with the
    dense reference — and with the per-tile (group=1) path's gradients."""
    src, dst, n_out, n_src = edges
    rng = np.random.default_rng(3)
    fbuf = jnp.asarray(rng.standard_normal((n_src, 8)).astype(np.float32))
    deg = jnp.asarray(
        np.maximum(np.bincount(dst, minlength=n_out), 1).astype(np.float32)
    )
    plan = BlockPlan(src, dst, n_out, n_src, n_feat=8, tile=16,
                     nnz_threshold=4, group=group)
    assert plan.a_blocks.shape[0] > 0
    arrs = {k: jnp.asarray(v) for k, v in plan_to_arrays(plan).items()}
    # grouped layout actually emitted: `group` output tiles a row
    assert {a.shape[-1] for a in _dense_a(arrs)} == {group * 16}
    fn = make_block_spmm_fn(arrs, deg, n_out, n_src, 16)
    out = fn(fbuf)
    np.testing.assert_allclose(
        np.asarray(out),
        _ref_mean(src, dst, n_out, np.asarray(fbuf), deg),
        rtol=1e-5, atol=1e-5)

    _, ref_fn = _make_fn(src, dst, n_out, n_src, deg, 16, 4)
    g_u = jax.grad(lambda f: (fn(f) ** 2).sum())(fbuf)
    g_r = jax.grad(lambda f: (ref_fn(f) ** 2).sum())(fbuf)
    np.testing.assert_allclose(np.asarray(g_u), np.asarray(g_r),
                               rtol=1e-5, atol=1e-6)


def test_trainer_block_grouped_matches_xla():
    """Trainer-level: the union-gather block kernel trains loss-for-loss
    with the raw-edge XLA path on a clustered layout, across devices
    (shared-cap padding + cross-device inv reoffsetting exercised)."""
    from pipegcn_tpu.partition import locality_clusters

    g = synthetic_graph(num_nodes=600, avg_degree=10, n_feat=12,
                        n_class=4, homophily=0.9, seed=25)
    parts = partition_graph(g, 4, seed=0)
    cluster = locality_clusters(g, target_size=64, seed=0)
    sg = ShardedGraph.build(g, parts, n_parts=4, cluster=cluster)
    losses = {}
    for impl, grp in (("xla", 1), ("block", 4)):
        cfg = ModelConfig(layer_sizes=(12, 16, 4), norm="layer",
                          dropout=0.0, train_size=sg.n_train_global,
                          spmm_impl=impl, block_tile=32, block_group=grp)
        t = Trainer(sg, cfg, TrainConfig(seed=4, enable_pipeline=True))
        losses[impl] = [t.train_epoch(e) for e in range(6)]
        if impl == "block":
            assert {a.shape[-1] for a in _dense_a(t._block_tables)} \
                == {4 * 32}
    np.testing.assert_allclose(losses["xla"], losses["block"], rtol=2e-4)


@pytest.mark.parametrize("group", [1, 4])
def test_chunked_scan_path_matches(edges, group, monkeypatch):
    """Force the builder's chunking (tiny element budget): a class
    stored as the xs of a lax.scan, its tail zero slots, must not
    change results in either dense layout."""
    import pipegcn_tpu.ops.block_spmm as bsp

    src, dst, n_out, n_src = edges
    rng = np.random.default_rng(5)
    fbuf = jnp.asarray(rng.standard_normal((n_src, 8)).astype(np.float32))
    deg = jnp.asarray(
        np.maximum(np.bincount(dst, minlength=n_out), 1).astype(np.float32)
    )
    plan = BlockPlan(src, dst, n_out, n_src, n_feat=8, tile=16,
                     nnz_threshold=4, group=group)
    arrs = {k: jnp.asarray(v) for k, v in plan_to_arrays(plan).items()}
    fn = make_block_spmm_fn(arrs, deg, n_out, n_src, 16)
    assert all(a.ndim == 4 for a in _dense_a(arrs))
    ref = np.asarray(fn(fbuf))
    g_ref = jax.grad(lambda f: (fn(f) ** 2).sum())(fbuf)
    # the chunking is the BUILDER's: the kernel scans what is stored
    monkeypatch.setattr(bsp, "_DENSE_CHUNK_ELEMS", 2048)
    arrs_c = {k: jnp.asarray(v) for k, v in plan_to_arrays(plan).items()}
    assert any(a.ndim == 5 for a in _dense_a(arrs_c))
    fn_c = make_block_spmm_fn(arrs_c, deg, n_out, n_src, 16)
    np.testing.assert_allclose(np.asarray(fn_c(fbuf)), ref,
                               rtol=1e-6, atol=1e-6)
    g_c = jax.grad(lambda f: (fn_c(f) ** 2).sum())(fbuf)
    np.testing.assert_allclose(np.asarray(g_c), np.asarray(g_ref),
                               rtol=1e-5, atol=1e-6)


def test_trainer_headline_stack_fused():
    """The exact benchmark-headline configuration in one run: block
    kernel, union-gather group 4, fp8 remainder transport, bf16
    compute, use_pp, pipelined + corrections, fused-epoch scan."""
    from pipegcn_tpu.partition import locality_clusters

    g = synthetic_graph(num_nodes=600, avg_degree=10, n_feat=12,
                        n_class=4, homophily=0.9, seed=25)
    parts = partition_graph(g, 4, seed=0)
    cluster = locality_clusters(g, target_size=64, seed=0)
    sg = ShardedGraph.build(g, parts, n_parts=4, cluster=cluster)
    cfg = ModelConfig(layer_sizes=(12, 16, 16, 4), norm="layer",
                      dropout=0.2, train_size=sg.n_train_global,
                      spmm_impl="block", block_tile=32, block_group=4,
                      rem_dtype="float8", dtype="bfloat16", use_pp=True)
    t = Trainer(sg, cfg, TrainConfig(seed=4, enable_pipeline=True,
                                     feat_corr=True, grad_corr=True))
    # the grouped union-gather tables must actually be in play — zero
    # dense tiles would silently reduce this to a remainder-only run
    assert {a.shape[-1] for a in _dense_a(t._block_tables)} == {4 * 32}
    losses = list(t.train_epochs(0, 4)) + list(t.train_epochs(4, 16))
    assert np.isfinite(losses).all()
    assert np.mean(losses[-4:]) < np.mean(losses[:4])


# ---------------- named scopes inside the kernel ---------------------------

@pytest.mark.parametrize("kernel", [
    dict(),
    dict(block_group=4, rem_dtype="float8"),
], ids=["block", "block-u4-f8"])
def test_scan_names_the_kernels_work(kernel):
    """In the compiled 2-epoch scan, what runs under `spmm` names a
    second-level scope (unpack, tile, rem_gather, rem_reduce, cast,
    unpermute, ...), forward and under `bwd`: at least 95% of the bytes
    its instructions move."""
    from pipegcn_tpu.obs.anatomy import scope_coverage
    from pipegcn_tpu.obs.profiler import hlo_op_map, scope_path
    from pipegcn_tpu.partition import locality_clusters

    g = synthetic_graph(num_nodes=600, avg_degree=10, n_feat=12,
                        n_class=4, homophily=0.9, seed=25)
    parts = partition_graph(g, 4, seed=0)
    cluster = locality_clusters(g, target_size=64, seed=0)
    sg = ShardedGraph.build(g, parts, n_parts=4, cluster=cluster)
    cfg = ModelConfig(layer_sizes=(12, 16, 16, 4), norm="layer",
                      dropout=0.2, train_size=sg.n_train_global,
                      spmm_impl="block", block_tile=32, dtype="bfloat16",
                      use_pp=True, **kernel)
    t = Trainer(sg, cfg, TrainConfig(seed=4, enable_pipeline=True))
    assert {a.shape[-1] for a in _dense_a(t._block_tables)} == {
        32 * kernel.get("block_group", 1)}
    txt = t.step_compiled_text(2)
    cov = scope_coverage(txt)
    for direction in ("fwd", "bwd"):
        assert cov[direction]["n_ops"] > 0
        assert cov[direction]["fraction"] >= 0.95, cov
    paths = {scope_path(op) for op, _ in hlo_op_map(txt).values()}
    want = {"unpack", "tile", "unpermute", "rem_gather", "rem_reduce",
            "rem_unpermute", "scale"}
    if "rem_dtype" in kernel:
        want.add("cast")             # fp8 e4m3 forward, e5m2 backward
    assert {"spmm/" + w for w in want} <= paths
    assert {"spmm/bwd/" + w for w in want} <= paths
    below = {tok for p in paths if p.startswith("spmm/")
             for tok in p.split("/")[1:]}
    assert below <= want | {"bwd", "cast", "rem_relayout", "tripwire"}, \
        below


# ---------------- the remainder's slot-major gather stream ------------------

@pytest.mark.parametrize("group", [1, 4])
@pytest.mark.parametrize("rem_dtype", [None, "bfloat16", "float8"])
def test_remainder_is_slot_major_and_matches_dense(edges, rem_dtype, group):
    """The block kernel's remainder runs bucket_aggregate under the
    `rem_` scope over slot-major tables ([P, w, cap], cap % 32 == 0):
    forward and gradient against the dense mean within the transport's
    rounding, and in the jaxpr every `rem_reduce` sum reads the
    gathered stream in the transport dtype."""
    from types import SimpleNamespace

    from pipegcn_tpu.ops.block_spmm import (
        build_sharded_block_tables,
        make_device_block_spmm_fn,
    )
    from pipegcn_tpu.ops.bucket_spmm import ROW_TILE, transport_dtypes
    from test_bucket_spmm import assert_reduce_reads_transport

    src, dst, n_out, n_src = edges
    order = np.argsort(dst, kind="stable")
    sg = SimpleNamespace(
        num_parts=1, n_max=n_out, halo_size=n_src - n_out,
        edge_src=src[order][None].astype(np.int32),
        edge_dst=dst[order][None].astype(np.int32))
    f = 8
    tabs, tile = build_sharded_block_tables(sg, tile=16, n_feat_hint=f,
                                            nnz_threshold=4, group=group)
    rem = [k for k in tabs if k.startswith("blkrem_")
           and not k.endswith("inv")]
    assert {k[:10] for k in rem} == {"blkrem_fwd", "blkrem_bwd"}
    for k in rem:
        p, w, cap = tabs[k].shape
        assert p == 1 and cap % ROW_TILE == 0 and cap > 0
    deg = np.maximum(np.bincount(dst, minlength=n_out), 1)
    d = {k: jnp.asarray(v[0]) for k, v in tabs.items()}

    def fn(x):
        return make_device_block_spmm_fn(
            d, jnp.asarray(deg, jnp.float32), n_out, n_src, tile,
            rem_dtype=rem_dtype)(x)

    rng = np.random.default_rng(6)
    x = jnp.asarray(rng.standard_normal((n_src, f)), jnp.float32)
    c = jnp.asarray(rng.standard_normal((n_out, f)), jnp.float32)
    out, vjp = jax.vjp(fn, x)
    a = np.zeros((n_out, n_src))
    np.add.at(a, (dst, src), 1.0)
    a /= deg[:, None]
    tol = {None: 1e-5, "bfloat16": 2e-2, "float8": 0.3}[rem_dtype]
    np.testing.assert_allclose(np.asarray(out), a @ np.asarray(x),
                               rtol=tol, atol=tol)
    np.testing.assert_allclose(np.asarray(vjp(c)[0]), a.T @ np.asarray(c),
                               rtol=tol, atol=tol)

    fwd_dt, bwd_dt = transport_dtypes(rem_dtype)
    seen = assert_reduce_reads_transport(
        jax.make_jaxpr(fn)(x).jaxpr, fwd_dt or jnp.float32, f)
    assert sorted(seen) == sorted(
        tabs[k].shape[1:] for k in rem if k.startswith("blkrem_fwd"))
    seen = assert_reduce_reads_transport(
        jax.make_jaxpr(vjp)(c).jaxpr, bwd_dt or jnp.float32, f)
    assert sorted(seen) == sorted(
        tabs[k].shape[1:] for k in rem if k.startswith("blkrem_bwd"))


def test_remainder_scopes_carry_the_rem_prefix(edges):
    """plan_to_arrays hands the single-device remainder tables over as
    the builder made them (slot-major), and the lowered kernel names the
    remainder's work rem_gather / rem_reduce / rem_unpermute."""
    import re

    src, dst, n_out, n_src = edges
    deg = jnp.asarray(
        np.maximum(np.bincount(dst, minlength=n_out), 1).astype(np.float32))
    plan, fn = _make_fn(src, dst, n_out, n_src, deg, 16, 4)
    arrs = plan_to_arrays(plan)
    rem = plan.rem_fwd.whole()
    for b, m in enumerate(rem.mats):
        assert m.shape[0] == rem.widths[b] and m.shape[1] % 32 == 0
        assert (f"blkrem_fwd_{b:02d}" in arrs) == bool(m.shape[1])
    txt = jax.jit(fn).lower(jnp.ones((n_src, 8), jnp.float32)).as_text(
        debug_info=True)
    names = set(re.findall(r"rem_(?:gather|reduce|unpermute)(?=/)", txt))
    assert names == {"rem_gather", "rem_reduce", "rem_unpermute"}


# ---------------- the remainder's widths are fitted; the K classes' are not --

def _clustered_shards(n_parts, seed=3):
    """Shards with a dense diagonal of (dst-tile, src-tile) blocks and
    a remainder whose degrees differ by shard."""
    from types import SimpleNamespace

    rng = np.random.default_rng(seed)
    n_max, halo, tile = 128, 32, 16
    srcs, dsts = [], []
    for r in range(n_parts):
        s, d = [], []
        for t in range(n_max // tile):       # 60 edges a diagonal block
            d.append(rng.integers(0, tile, 60) + t * tile)
            s.append(rng.integers(0, tile, 60) + t * tile)
        degs = rng.poisson(3 + 4 * r, n_max)
        d.append(np.repeat(np.arange(n_max), degs))
        s.append(rng.integers(0, n_max + halo, degs.sum()))
        srcs.append(np.concatenate(s))
        dsts.append(np.concatenate(d))
    e_max = max(a.size for a in srcs)
    pad = [e_max - a.size for a in srcs]
    return SimpleNamespace(
        num_parts=n_parts, n_max=n_max, halo_size=halo,
        edge_count=np.asarray([a.size for a in srcs]),
        edge_src=np.stack([np.concatenate([a, np.zeros(p, np.int64)])
                           for a, p in zip(srcs, pad)]).astype(np.int32),
        edge_dst=np.stack([np.concatenate([a, np.full(p, n_max)])
                           for a, p in zip(dsts, pad)]).astype(np.int32))


@pytest.mark.parametrize("group", [1, 2])
@pytest.mark.parametrize("n_parts", [1, 4])
def test_sharded_block_tables_fit_the_remainder_only(n_parts, group):
    """build_sharded_block_tables fits ONE remainder ladder a direction
    to the remainder histograms of all the shards (known after the
    dense selection); the dense K classes keep the x1.5 ladder; every
    device's kernel equals the dense mean, forward and VJP; and
    bucket_pad_stats reads the remainder's padding off the tables."""
    from pipegcn_tpu.ops.block_spmm import (
        build_sharded_block_tables,
        make_device_block_spmm_fn,
    )
    from pipegcn_tpu.ops.bucket_spmm import (bucket_pad_stats, degree_hist,
                                             fit_widths, ladder_prefix)

    sg = _clustered_shards(n_parts)
    n_src = sg.n_max + sg.halo_size
    f, tile = 8, 16
    tabs, _ = build_sharded_block_tables(sg, tile=tile, n_feat_hint=f,
                                         nnz_threshold=20, group=group)
    plans = [BlockPlan(sg.edge_src[r], sg.edge_dst[r], sg.n_max, n_src, f,
                       tile=tile, nnz_threshold=20, group=group)
             for r in range(n_parts)]
    assert all(p.a_blocks.shape[0] and p.rem_count for p in plans)
    pad = bucket_pad_stats(tabs, sg.n_max, n_src, stem="blkrem")
    for d, degs in (("fwd", [p.rem_fwd.degs[0] for p in plans]),
                    ("bwd", [p.rem_bwd.degs[0] for p in plans])):
        want = fit_widths(degree_hist(degs))
        keys = sorted(k for k in tabs if k.startswith(f"blkrem_{d}_")
                      and not k.endswith("inv"))
        assert [tabs[k].shape[1] for k in keys] == want
        assert pad[d]["widths"] == [want]
        assert pad[d]["edges"] == sum(p.rem_count for p in plans)
        assert pad[d]["slots"] == sum(tabs[k].size for k in keys)
        # the dense classes: rungs of the x1.5 ladder, as before
        k_widths = [tabs[k].shape[-1] for k in sorted(tabs)
                    if k.startswith(f"blk_{d}_g") and k.endswith("t")]
        assert k_widths and set(k_widths) <= set(ladder_prefix(12))
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((n_src, f)), jnp.float32)
    c = jnp.asarray(rng.standard_normal((sg.n_max, f)), jnp.float32)
    for r in range(n_parts):
        real = sg.edge_dst[r] < sg.n_max
        src, dst = sg.edge_src[r][real], sg.edge_dst[r][real]
        deg = np.maximum(np.bincount(dst, minlength=sg.n_max), 1)
        fn = make_device_block_spmm_fn(
            {k: jnp.asarray(v[r]) for k, v in tabs.items()},
            jnp.asarray(deg, jnp.float32), sg.n_max, n_src, tile)
        out, vjp = jax.vjp(fn, x)
        a = np.zeros((sg.n_max, n_src))
        np.add.at(a, (dst, src), 1.0)
        a /= deg[:, None]
        np.testing.assert_allclose(np.asarray(out), a @ np.asarray(x),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(vjp(c)[0]),
                                   a.T @ np.asarray(c),
                                   rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("n_parts", [1, 4])
def test_remainder_cut_into_parts_matches_dense(n_parts, monkeypatch):
    """With the part bound lowered under the shards' heights the
    remainder's directions are cut by source rows into parts (keys of
    their own, a ladder a part fitted over every shard, each part's
    indices under its own row count): every remainder edge is counted
    once over the parts, `plan_to_arrays` hands the one-device parts
    over as the builder made them, and every device's kernel is the
    dense mean, forward and VJP."""
    from pipegcn_tpu.ops import bucket_spmm
    from pipegcn_tpu.ops.block_spmm import (build_sharded_block_tables,
                                            make_device_block_spmm_fn)
    from pipegcn_tpu.ops.bucket_spmm import (_part_stems, bucket_pad_stats,
                                             source_parts)

    monkeypatch.setattr(bucket_spmm, "GATHER_PART_BYTES",
                        61 * bucket_spmm.SLAB_BYTES)
    sg = _clustered_shards(n_parts)
    n_src = sg.n_max + sg.halo_size
    f, tile = 8, 16
    tabs, _ = build_sharded_block_tables(sg, tile=tile, n_feat_hint=f,
                                         nnz_threshold=20)
    pad = bucket_pad_stats(tabs, sg.n_max, n_src, stem="blkrem")
    for d, rows in (("fwd", n_src), ("bwd", sg.n_max)):
        k = source_parts(rows)
        assert k > 1 and pad[d]["parts"] == k
        assert len(_part_stems(tabs, f"blkrem_{d}")) == k
        assert len(pad[d]["widths"]) == k
    plan = BlockPlan(sg.edge_src[0], sg.edge_dst[0], sg.n_max, n_src, f,
                     tile=tile, nnz_threshold=20)
    arrs = plan_to_arrays(plan)
    assert "blkrem_fwd_p1_inv" in arrs and "blkrem_bwd_p1_inv" in arrs
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((n_src, f)), jnp.float32)
    c = jnp.asarray(rng.standard_normal((sg.n_max, f)), jnp.float32)
    for r in range(n_parts):
        real = sg.edge_dst[r] < sg.n_max
        src, dst = sg.edge_src[r][real], sg.edge_dst[r][real]
        deg = np.maximum(np.bincount(dst, minlength=sg.n_max), 1)
        fn = make_device_block_spmm_fn(
            {k: jnp.asarray(v[r]) for k, v in tabs.items()},
            jnp.asarray(deg, jnp.float32), sg.n_max, n_src, tile)
        out, vjp = jax.vjp(fn, x)
        a = np.zeros((sg.n_max, n_src))
        np.add.at(a, (dst, src), 1.0)
        a /= deg[:, None]
        np.testing.assert_allclose(np.asarray(out), a @ np.asarray(x),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(vjp(c)[0]),
                                   a.T @ np.asarray(c),
                                   rtol=1e-5, atol=1e-5)


def test_set_remainder_widths_rebuilds_the_remainder_alone(edges):
    """A plan moved to another remainder ladder rebuilds those tables
    and nothing of the dense half; at its own widths it rebuilds
    nothing."""
    src, dst, n_out, n_src = edges
    plan = BlockPlan(src, dst, n_out, n_src, n_feat=8, tile=16,
                     nnz_threshold=4)
    a_blocks, block_dst = plan.a_blocks, plan.block_dst
    fwd, bwd = plan.rem_fwd.whole().mats, plan.rem_bwd.whole().mats
    fw, bw = plan.rem_fwd.whole().widths, plan.rem_bwd.whole().widths
    plan.set_remainder_widths(fw, bw)
    assert plan.rem_fwd.whole().mats is fwd
    assert plan.rem_bwd.whole().mats is bwd
    wider = fw[:-1] + [fw[-1] + 3]
    plan.set_remainder_widths(wider, bw)
    assert plan.rem_fwd.whole().mats is not fwd
    assert plan.rem_bwd.whole().mats is bwd
    assert [m.shape[0] for m in plan.rem_fwd.whole().mats] == wider
    assert plan.a_blocks is a_blocks and plan.block_dst is block_dst
    deg = jnp.asarray(np.maximum(np.bincount(dst, minlength=n_out), 1)
                      .astype(np.float32))
    arrs = {k: jnp.asarray(v) for k, v in plan_to_arrays(plan).items()}
    fbuf = np.random.default_rng(0).standard_normal(
        (n_src, 8)).astype(np.float32)
    out = make_block_spmm_fn(arrs, deg, n_out, n_src, 16)(
        jnp.asarray(fbuf))
    np.testing.assert_allclose(
        np.asarray(out), _ref_mean(src, dst, n_out, fbuf, deg),
        rtol=1e-5, atol=1e-5)


# ---------------- A is stored in the order the step reads it ----------------

def _tiled_shards(n_parts, encoding, seed=11):
    """Shards whose destination tiles hold 1 to 6 dense blocks each (so
    several K classes fill) over a sparse remainder. `encoding` decides
    the edge multiplicities and with them how A is stored: "bits" (a
    simple graph), "int8" (an edge three times) or "bf16" (one 200
    times)."""
    from types import SimpleNamespace

    rng = np.random.default_rng(seed)
    n_max, halo, tile = 160, 32, 16
    n_src, n_t = n_max + halo, n_max // tile
    srcs, dsts = [], []
    for r in range(n_parts):
        s, d = [], []
        for t in range(n_t):
            for o in rng.choice(n_src // tile, 1 + (t + r) % 6,
                                replace=False):
                cells = rng.choice(tile * tile, 40, replace=False)
                d.append(cells // tile + t * tile)
                s.append(cells % tile + o * tile)
        d.append(rng.integers(0, n_max, 300))
        s.append(rng.integers(0, n_src, 300))
        key = np.unique(np.concatenate(d) * n_src + np.concatenate(s))
        d, s = key // n_src, key % n_src
        reps = {"bits": 0, "int8": 2, "bf16": 199}[encoding]
        # the repeated edge sits in a dense block
        srcs.append(np.concatenate([s, np.full(reps, s[0])]))
        dsts.append(np.concatenate([d, np.full(reps, d[0])]))
    e_max = max(a.size for a in srcs)
    return SimpleNamespace(
        num_parts=n_parts, n_max=n_max, halo_size=halo,
        edge_count=np.asarray([a.size for a in srcs]),
        edge_src=np.stack([np.pad(a, (0, e_max - a.size))
                           for a in srcs]).astype(np.int32),
        edge_dst=np.stack([np.pad(a, (0, e_max - a.size),
                                  constant_values=n_max)
                           for a in dsts]).astype(np.int32))


def _gather_formulation(plan, direction, tiles, as_gathered=False):
    """The dense half as the kernel ran it before A was stored in
    reading order: ONE table in block-id order with a zero block
    appended, a class's blocks gathered by BlockPlan's index matrices
    ([R, G, U, T, S]), laid out again as the contraction's left operand
    ([R, U, S, G*T]; the backward contracts the other axis of the SAME
    blocks: [R, U, T, G*S]) and contracted: the gather and the relayout
    the chip ran every call. `as_gathered` contracts the gathered
    blocks through the einsum spec the kernel wrote instead (no
    relayout of ours; XLA's own, so the float sums may take another
    order). Kept as the reference of what is summed."""
    T, f = plan.tile, tiles.shape[-1]
    a_pad = jnp.concatenate([jnp.asarray(plan.a_blocks),
                             jnp.zeros((1, T, T), jnp.float32)])
    classes, inv, _, _ = plan.dense_classes(direction)
    spec = "rduts,rusf->rdtf" if direction == "fwd" else "rduts,rutf->rdsf"
    axes = (0, 2, 4, 1, 3) if direction == "fwd" else (0, 2, 3, 1, 4)
    outs = []
    for a_idx, t_mat in classes:
        r, grp, u = a_idx.shape
        if not r:
            continue
        blks = jnp.take(a_pad, jnp.asarray(a_idx), axis=0)
        tls = jnp.take(tiles, jnp.asarray(t_mat), axis=0)
        if as_gathered:
            out = jnp.einsum(spec, blks, tls,
                             preferred_element_type=jnp.float32)
        else:
            out = jnp.einsum(
                "rksm,rksf->rmf",
                blks.transpose(axes).reshape(r, u, T, grp * T), tls,
                preferred_element_type=jnp.float32)
        outs.append(out.reshape(-1, T, f))
    outs.append(jnp.zeros((1, T, f), jnp.float32))
    return jnp.take(jnp.concatenate(outs), jnp.asarray(inv),
                    axis=0).reshape(-1, f)


@pytest.mark.parametrize("n_parts", [1, 2])
@pytest.mark.parametrize("chunked", [False, True],
                         ids=["whole", "scanned"])
@pytest.mark.parametrize("encoding", ["bits", "int8", "bf16"])
@pytest.mark.parametrize("group", [1, 4])
def test_stored_a_sums_what_the_gathers_summed(group, encoding, chunked,
                                               n_parts, monkeypatch):
    """build_sharded_block_tables stores A per direction and class in
    the order the kernel reads it; forward and backward, on every
    stacked device, the dense half gives BIT FOR BIT what gathering
    block-id-ordered A by the plan's index matrices gave (the backward
    from the transposes' own copy what the transposed contraction
    gave), whatever the encoding and whether or not a class is stored
    as a scan's chunks."""
    import ml_dtypes

    import pipegcn_tpu.ops.block_spmm as bsp

    tile, f = 16, 8
    if chunked:
        # 4 [tile, tile] slots a chunk: every class of more rows scans
        monkeypatch.setattr(bsp, "_DENSE_CHUNK_ELEMS", 4 * tile * tile)
    sg = _tiled_shards(n_parts, encoding)
    n_src = sg.n_max + sg.halo_size
    tabs, _ = bsp.build_sharded_block_tables(
        sg, tile=tile, n_feat_hint=f, nnz_threshold=20, group=group)
    want_dt = {"bits": np.uint8, "int8": np.int8,
               "bf16": ml_dtypes.bfloat16}[encoding]
    a_all = _dense_a(tabs, "fwd") + _dense_a(tabs, "bwd")
    assert a_all and {a.dtype for a in a_all} == {np.dtype(want_dt)}
    assert any(a.ndim == 6 for a in a_all) == chunked  # [P, n, rows, ...]
    assert len({a.shape[-3] for a in a_all}) > 1       # several K classes
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.standard_normal((n_src, f)), jnp.float32)
    g = jnp.asarray(rng.standard_normal((sg.n_max, f)), jnp.float32)
    for r in range(n_parts):
        plan = BlockPlan(sg.edge_src[r], sg.edge_dst[r], sg.n_max, n_src,
                         f, tile=tile, nnz_threshold=20, group=group)
        assert plan.a_blocks.shape[0] > 20
        d = {k: jnp.asarray(v[r]) for k, v in tabs.items()}
        for direction, operand, n_key in (("fwd", x, plan.n_src_tiles),
                                          ("bwd", g, plan.n_dst_tiles)):
            tiles = jnp.pad(operand, ((0, (n_key + 1) * tile
                                       - operand.shape[0]), (0, 0))
                            ).reshape(n_key + 1, tile, f)
            stems = sorted(k[:-1] for k in d
                           if k.startswith(f"blk_{direction}_g")
                           and k.endswith("a"))
            n_rows = n_src if direction == "bwd" else sg.n_max
            got = bsp._dense_apply(
                [(d[k + "a"], d[k + "t"]) for k in stems],
                d[f"blk_{direction}_inv"], tiles, tile, n_rows, f,
                jnp.float32)
            ref = _gather_formulation(plan, direction, tiles)[:n_rows]
            assert np.abs(np.asarray(ref)).max() > 1.0
            np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
            np.testing.assert_allclose(
                np.asarray(got), np.asarray(_gather_formulation(
                    plan, direction, tiles, as_gathered=True))[:n_rows],
                rtol=1e-5, atol=1e-5)


def test_dense_edges_are_counted_once_a_direction(tmp_path):
    """Edge conservation for the dense half: each direction's A counts
    every edge the remainder does not hold, once, device by device —
    checked where the tables are built and again where a trainer loads
    them from its cache; a table that lost a block does not train."""
    from pipegcn_tpu.ops.block_spmm import (build_sharded_block_tables,
                                            validate_dense_tables)
    from pipegcn_tpu.ops.bucket_spmm import table_edges
    from pipegcn_tpu.partition import locality_clusters

    sg = _tiled_shards(2, "int8")
    n_src = sg.n_max + sg.halo_size
    tabs, tile = build_sharded_block_tables(sg, tile=16, n_feat_hint=8,
                                            nnz_threshold=20, group=2)
    real = [int(np.count_nonzero(d < sg.n_max)) for d in sg.edge_dst]
    dense = [n - e for n, e in zip(
        real, table_edges(tabs, "blkrem_fwd", n_src))]
    assert min(dense) > 1000
    stats = validate_dense_tables(tabs, tile, n_edges=dense)
    for d in ("fwd", "bwd"):
        assert stats[d]["dense_slots"] >= stats[d]["dense_blocks"] > 40
        assert stats[d]["a_bytes"] == sum(a[0].nbytes
                                          for a in _dense_a(tabs, d))
    assert stats["fwd"]["dense_blocks"] == stats["bwd"]["dense_blocks"]
    lost = dict(tabs)
    k = next(k for k in tabs if k.startswith("blk_bwd_g")
             and k.endswith("a") and tabs[k][1, 0].any())
    lost[k] = tabs[k].copy()
    lost[k][1, 0] = 0                      # device 1 loses a row's blocks
    with pytest.raises(ValueError, match="a block was lost"):
        validate_dense_tables(lost, tile, n_edges=dense)
    with pytest.raises(ValueError, match="a block was lost"):
        validate_dense_tables(lost, tile)  # the directions disagree

    # the same check guards a cache load
    g = synthetic_graph(num_nodes=600, avg_degree=10, n_feat=12,
                        n_class=4, homophily=0.9, seed=25)
    parts = partition_graph(g, 2, seed=0)
    ShardedGraph.build(
        g, parts, n_parts=2,
        cluster=locality_clusters(g, target_size=64, seed=0)
    ).save(str(tmp_path / "art"))
    cfg = None
    for corrupt in (False, True):
        sgl = ShardedGraph.load(str(tmp_path / "art"))
        cfg = cfg or ModelConfig(
            layer_sizes=(12, 16, 4), norm="layer", dropout=0.0,
            train_size=sgl.n_train_global, spmm_impl="block",
            block_tile=32)
        if not corrupt:
            t = Trainer(sgl, cfg, TrainConfig(seed=0))
            assert t.tables_source == "built in this run"
            pad = t.tables_pad
            fname, = tmp_path.glob("art/*_tables.npz")
            z = dict(np.load(fname))
            k = next(k for k in z if k.startswith("blk_fwd_g")
                     and k.endswith("a"))
            assert z[k].any()
            z[k] = np.zeros_like(z[k])
            t2 = Trainer(ShardedGraph.load(str(tmp_path / "art")), cfg,
                         TrainConfig(seed=0))
            assert t2.tables_source == f"loaded from {fname}"
            assert t2.tables_pad == pad and pad["fwd"]["dense_blocks"] > 0
            with open(fname, "wb") as fh:
                np.savez(fh, **z)
        else:
            with pytest.raises(ValueError, match="a block was lost"):
                Trainer(sgl, cfg, TrainConfig(seed=0))


@pytest.mark.parametrize("group", [1, 4])
def test_budget_bounds_what_is_stored(group):
    """DENSE_A_BYTE_BUDGET's rule on a small scale: under a budget that
    cannot hold every dense block, both directions' A AS STORED (pad
    slots, shared row caps and chunk tails included) fit it, the
    densest blocks are the ones kept, and estimate_block_coverage
    predicts the very split the builder makes."""
    from pipegcn_tpu.ops.block_spmm import (build_sharded_block_tables,
                                            dense_pad_stats,
                                            estimate_block_coverage)
    from pipegcn_tpu.ops.bucket_spmm import table_edges

    sg = _tiled_shards(2, "bits")
    n_src = sg.n_max + sg.halo_size
    real = sum(int(np.count_nonzero(d < sg.n_max)) for d in sg.edge_dst)
    stored, covered = {}, {}
    budgets = [1 << 30]
    while budgets:
        budget = budgets.pop(0)
        tabs, tile = build_sharded_block_tables(
            sg, tile=16, n_feat_hint=8, nnz_threshold=20, group=group,
            byte_budget=budget)
        stats = dense_pad_stats(tabs, tile)
        stored[budget] = stats["fwd"]["a_bytes"] + stats["bwd"]["a_bytes"]
        covered[budget] = 1 - int(table_edges(
            tabs, "blkrem_fwd", n_src).sum()) / real
        assert stored[budget] <= budget
        assert covered[budget] == pytest.approx(estimate_block_coverage(
            sg, 16, 8, nnz_threshold=20, byte_budget=budget, group=group))
        if budget == 1 << 30:  # then three fifths and a quarter of it all
            budgets = [stored[budget] * 3 // 5, stored[budget] // 4]
    full, most, some = sorted(stored, reverse=True)
    assert stored[full] > most >= stored[most] > stored[some] > 0
    assert covered[full] > covered[most] > covered[some] > 0
