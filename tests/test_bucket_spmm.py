"""Degree-bucketed scatter-free SpMM: unit parity vs dense reference and
trainer-level parity vs the XLA gather+segment-sum path."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from pipegcn_tpu.graph import synthetic_graph
from pipegcn_tpu.models import ModelConfig
from pipegcn_tpu.ops.bucket_spmm import (
    BucketPlan,
    bucket_aggregate,
    build_tables_for_edges,
    make_bucket_spmm_fn,
    _bucket_widths,
)
from pipegcn_tpu.parallel import Trainer, TrainConfig
from pipegcn_tpu.partition import ShardedGraph, partition_graph


@pytest.fixture(scope="module")
def edges():
    rng = np.random.default_rng(5)
    n_out, n_src = 120, 150
    e = 900
    src = rng.integers(0, n_src, e).astype(np.int64)
    dst = rng.integers(0, n_out, e).astype(np.int64)
    # a hub row and an isolated row to stress buckets
    dst[:100] = 7
    mask = dst != 11  # row 11 has no edges
    return src[mask], dst[mask], n_out, n_src


def _dense_sum(src, dst, n_out, n_src, fbuf):
    out = np.zeros((n_out, fbuf.shape[1]), np.float32)
    for s, d in zip(src, dst):
        out[d] += np.asarray(fbuf, np.float32)[s]
    return out


def test_bucket_aggregate_matches_dense(edges):
    src, dst, n_out, n_src = edges
    rng = np.random.default_rng(0)
    fbuf = rng.standard_normal((n_src, 16)).astype(np.float32)
    widths = _bucket_widths(int(np.bincount(dst, minlength=n_out).max()))
    mats, inv, counts = build_tables_for_edges(src, dst, n_out, n_src,
                                               widths)
    out = bucket_aggregate(jnp.asarray(fbuf),
                           [jnp.asarray(m) for m in mats],
                           jnp.asarray(inv))
    np.testing.assert_allclose(np.asarray(out),
                               _dense_sum(src, dst, n_out, n_src, fbuf),
                               rtol=1e-5, atol=1e-5)
    # zero-degree row stays zero
    assert np.abs(np.asarray(out)[11]).max() == 0.0


def test_bucket_aggregate_slabbed_matches(edges):
    # force the feature-slab path (production: F wider than 256 bytes /
    # itemsize; here slab=4 so F=10 spans 3 slabs incl. a partial one)
    src, dst, n_out, n_src = edges
    rng = np.random.default_rng(3)
    fbuf = rng.standard_normal((n_src, 10)).astype(np.float32)
    widths = _bucket_widths(int(np.bincount(dst, minlength=n_out).max()))
    mats, inv, counts = build_tables_for_edges(src, dst, n_out, n_src,
                                               widths)
    ref = _dense_sum(src, dst, n_out, n_src, fbuf)
    for chunk_edges in (None, 64):
        out = bucket_aggregate(jnp.asarray(fbuf),
                               [jnp.asarray(m) for m in mats],
                               jnp.asarray(inv), chunk_edges=chunk_edges,
                               slab=4)
        np.testing.assert_allclose(np.asarray(out), ref,
                                   rtol=1e-5, atol=1e-5)
    # default slab width activates on its own past 256 bytes per row
    wide = rng.standard_normal((n_src, 70)).astype(np.float32)
    out = bucket_aggregate(jnp.asarray(wide),
                           [jnp.asarray(m) for m in mats],
                           jnp.asarray(inv))
    np.testing.assert_allclose(
        np.asarray(out), _dense_sum(src, dst, n_out, n_src, wide),
        rtol=1e-5, atol=1e-5)


def test_bucket_aggregate_chunked_matches(edges):
    src, dst, n_out, n_src = edges
    rng = np.random.default_rng(1)
    fbuf = rng.standard_normal((n_src, 8)).astype(np.float32)
    widths = _bucket_widths(int(np.bincount(dst, minlength=n_out).max()))
    mats, inv, _ = build_tables_for_edges(src, dst, n_out, n_src, widths)
    jm = [jnp.asarray(m) for m in mats]
    a = bucket_aggregate(jnp.asarray(fbuf), jm, jnp.asarray(inv))
    b = bucket_aggregate(jnp.asarray(fbuf), jm, jnp.asarray(inv),
                         chunk_elems=64)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)


def test_bucket_mean_fn_grad_matches_reference(edges):
    """Forward and backward of the custom-VJP closure vs spmm_mean."""
    from pipegcn_tpu.ops.spmm import spmm_mean

    src, dst, n_out, n_src = edges
    rng = np.random.default_rng(2)
    fbuf = jnp.asarray(rng.standard_normal((n_src, 8)).astype(np.float32))
    deg = jnp.asarray(
        np.maximum(np.bincount(dst, minlength=n_out), 1).astype(np.float32)
    )
    plan = BucketPlan(src, dst, n_out, n_src)
    fn = make_bucket_spmm_fn(
        [jnp.asarray(m) for m in plan.fwd_mats], jnp.asarray(plan.fwd_inv),
        [jnp.asarray(m) for m in plan.bwd_mats], jnp.asarray(plan.bwd_inv),
        deg, n_src,
    )
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


def test_trainer_bucket_matches_xla():
    g = synthetic_graph(num_nodes=300, avg_degree=7, n_feat=10, n_class=4,
                        seed=21)
    parts = partition_graph(g, 4, seed=0)
    sg = ShardedGraph.build(g, parts, n_parts=4)
    losses = {}
    for impl in ("xla", "bucket"):
        cfg = ModelConfig(layer_sizes=(10, 16, 4), norm="layer",
                          dropout=0.0, train_size=sg.n_train_global,
                          spmm_impl=impl)
        t = Trainer(sg, cfg, TrainConfig(seed=4, enable_pipeline=True))
        losses[impl] = [t.train_epoch(e) for e in range(6)]
    np.testing.assert_allclose(losses["xla"], losses["bucket"], rtol=2e-4)


def test_trainer_bucket_bf16_fused():
    g = synthetic_graph(num_nodes=300, avg_degree=7, n_feat=10, n_class=4,
                        seed=22)
    parts = partition_graph(g, 4, seed=0)
    sg = ShardedGraph.build(g, parts, n_parts=4)
    cfg = ModelConfig(layer_sizes=(10, 16, 16, 4), norm="layer",
                      dropout=0.2, train_size=sg.n_train_global,
                      spmm_impl="bucket", dtype="bfloat16", use_pp=True)
    t = Trainer(sg, cfg, TrainConfig(seed=4, enable_pipeline=True,
                                     feat_corr=True, grad_corr=True))
    losses = list(t.train_epochs(0, 4)) + list(t.train_epochs(4, 16))
    assert np.isfinite(losses).all()
    assert np.mean(losses[-4:]) < np.mean(losses[:4])


def test_ladder_prefix_lockstep():
    """ladder_prefix and _bucket_widths must come from the same
    progression: the sharded builders regenerate shared ladders by
    length and silently corrupt tables if the two ever diverge."""
    from pipegcn_tpu.ops.bucket_spmm import _bucket_widths, ladder_prefix

    for md in (1, 2, 5, 17, 492, 65536, 1_000_000):
        w = _bucket_widths(md)
        assert w == ladder_prefix(len(w))
        assert w[-1] >= md
        if len(w) > 1:
            assert w[-2] < md
        assert all(b > a for a, b in zip(w, w[1:]))
        # padding bound: each rung at most 1.5x the previous
        assert all(b <= max(a + 1, (a * 3) // 2) for a, b in zip(w, w[1:]))


def test_float8_transport_tolerance_and_slab_width():
    """rem_dtype='float8': e4m3 transport packs F=256 into ONE 256-byte
    gather row (no slabbing) and stays within fp8 quantization error of
    the f32 result; e5m2 cotangent transport likewise."""
    rng = np.random.default_rng(7)
    n_out, n_src, e = 60, 80, 700
    src = rng.integers(0, n_src, e).astype(np.int64)
    dst = rng.integers(0, n_out, e).astype(np.int64)
    deg = jnp.asarray(
        np.maximum(np.bincount(dst, minlength=n_out), 1).astype(np.float32))
    plan = BucketPlan(src, dst, n_out, n_src)
    f32_fn = make_bucket_spmm_fn(
        [jnp.asarray(m) for m in plan.fwd_mats], jnp.asarray(plan.fwd_inv),
        [jnp.asarray(m) for m in plan.bwd_mats], jnp.asarray(plan.bwd_inv),
        deg, n_src)
    f8_fn = make_bucket_spmm_fn(
        [jnp.asarray(m) for m in plan.fwd_mats], jnp.asarray(plan.fwd_inv),
        [jnp.asarray(m) for m in plan.bwd_mats], jnp.asarray(plan.bwd_inv),
        deg, n_src, rem_dtype="float8")
    fbuf = jnp.asarray(rng.standard_normal((n_src, 256)).astype(np.float32))
    o32 = np.asarray(f32_fn(fbuf))
    o8 = np.asarray(f8_fn(fbuf))
    # e4m3 has a 3-bit mantissa (~6% element error); mean-of-degree
    # aggregation keeps the relative error of the same order
    err = np.abs(o8 - o32) / (np.abs(o32) + 1e-3)
    assert np.median(err) < 0.03
    # the mean is dragged by near-zero outputs where relative error
    # diverges; 15% bounds it without being noise-brittle
    assert err.mean() < 0.15
    g32 = np.asarray(jax.grad(lambda f: (f32_fn(f) ** 2).sum())(fbuf))
    g8 = np.asarray(jax.grad(lambda f: (f8_fn(f) ** 2).sum())(fbuf))
    gerr = np.abs(g8 - g32) / (np.abs(g32) + 1e-3)
    assert np.median(gerr) < 0.1  # e5m2: 2-bit mantissa
    # zero-degree/no-edge rows stay exactly zero
    no_edge = np.setdiff1d(np.arange(n_out), dst)
    if no_edge.size:
        assert np.abs(o8[no_edge]).max() == 0.0


def test_transport_dtypes_mapping():
    from pipegcn_tpu.ops.bucket_spmm import transport_dtypes

    assert transport_dtypes(None) == (None, None)
    assert transport_dtypes("none") == (None, None)
    f, b = transport_dtypes("float8")
    assert f == jnp.float8_e4m3fn and b == jnp.float8_e5m2
    f, b = transport_dtypes("bfloat16")
    assert f == jnp.bfloat16 and b == jnp.bfloat16
    with pytest.raises(ValueError):
        transport_dtypes("int4")


def test_transport_cast_saturates_not_nan():
    """fp8 has no inf: an overflowing astype yields NaN — transport_cast
    must clamp to the finite max instead (raw layer-0 features can
    exceed e4m3's +-448)."""
    from pipegcn_tpu.ops.bucket_spmm import transport_cast

    x = jnp.asarray([1e4, -1e4, 3.0], jnp.float32)
    y = np.asarray(
        transport_cast(x, jnp.float8_e4m3fn).astype(jnp.float32))
    assert np.isfinite(y).all()
    assert y[0] == 448.0 and y[1] == -448.0
    # identity when no transport dtype
    assert transport_cast(x, None) is x


# ---------------- named scopes inside the kernel ---------------------------

def _window_graph(n=256, deg=12, n_feat=12, n_class=4, seed=0):
    """Every node aggregates a contiguous id window below it: index
    runs long enough for streaming-slab plans (tests/test_reorder.py)."""
    from pipegcn_tpu.graph.csr import Graph

    src = [j for i in range(n) for j in range(max(0, i - deg), i)]
    dst = [i for i in range(n) for j in range(max(0, i - deg), i)]
    rng = np.random.default_rng(seed)
    ar = np.arange(n)
    return Graph(
        num_nodes=n, src=np.asarray(src, np.int64),
        dst=np.asarray(dst, np.int64),
        ndata={"feat": rng.normal(size=(n, n_feat)).astype(np.float32),
               "label": rng.integers(0, n_class, size=n).astype(np.int64),
               "train_mask": ar < n // 2,
               "val_mask": (ar >= n // 2) & (ar < 3 * n // 4),
               "test_mask": ar >= 3 * n // 4})


@pytest.mark.parametrize("slab", ["off", "on"])
def test_scan_names_the_kernels_work(slab):
    """In the compiled 2-epoch scan, what runs under `spmm` names a
    second-level scope (gather, reduce, unpermute, ...), forward and
    under `bwd`: at least 95% of the bytes its instructions move. A
    kernel change that leaves work unnamed shows here."""
    from pipegcn_tpu.obs.anatomy import scope_coverage
    from pipegcn_tpu.obs.profiler import hlo_op_map, scope_path

    g = _window_graph()
    sg = ShardedGraph.build(g, np.zeros(g.num_nodes, np.int32), n_parts=1)
    cfg = ModelConfig(layer_sizes=(12, 16, 16, 4), norm="layer",
                      dropout=0.2, train_size=sg.n_train_global,
                      spmm_impl="bucket", slab=slab, dtype="bfloat16",
                      use_pp=True)
    t = Trainer(sg, cfg, TrainConfig(seed=4, enable_pipeline=True))
    assert t._slab_active() == (slab == "on")
    txt = t.step_compiled_text(2)
    cov = scope_coverage(txt)
    for direction in ("fwd", "bwd"):
        assert cov[direction]["n_ops"] > 0
        assert cov[direction]["fraction"] >= 0.95, cov
    paths = {scope_path(op) for op, _ in hlo_op_map(txt).values()}
    assert {"spmm/gather", "spmm/reduce", "spmm/unpermute",
            "spmm/bwd/gather", "spmm/bwd/reduce",
            "spmm/bwd/unpermute"} <= paths
    # every path below spmm is made of the kernel's own names
    kernel = {"bwd", "gather", "reduce", "unpermute", "relayout", "cast",
              "scale"}
    below = {tok for p in paths if p.startswith("spmm/")
             for tok in p.split("/")[1:]}
    assert below <= kernel | {"tripwire"}, below


def test_slabbed_aggregate_names_its_relayout(edges):
    """Rows wider than a slab go through the feature-slab transposes:
    their copies are `relayout`, and a prefix (the block kernel's
    remainder) renames all four scopes."""
    import re

    src, dst, n_out, n_src = edges
    plan = BucketPlan(src, dst, n_out, n_src)
    mats = [jnp.asarray(m) for m in plan.fwd_mats]
    inv = jnp.asarray(plan.fwd_inv)

    def paths(scope):
        fn = jax.jit(lambda x: bucket_aggregate(x, mats, inv, slab=4,
                                                scope=scope))
        txt = fn.lower(jnp.ones((n_src, 10), jnp.float32)).as_text(
            debug_info=True)
        return set(re.findall(r"(?:rem_)?(?:gather|reduce|unpermute"
                              r"|relayout)(?=/)", txt))

    assert paths("") == {"gather", "reduce", "unpermute", "relayout"}
    assert paths("rem_") == {"rem_gather", "rem_reduce", "rem_unpermute",
                             "rem_relayout"}
