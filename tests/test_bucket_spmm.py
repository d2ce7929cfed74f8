"""Degree-bucketed scatter-free SpMM: unit parity vs dense reference and
trainer-level parity vs the XLA gather+segment-sum path."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from pipegcn_tpu.graph import synthetic_graph
from pipegcn_tpu.models import ModelConfig
from pipegcn_tpu.ops.bucket_spmm import (
    DEFAULT_CHUNK_ELEMS,
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
        *_as_operands(plan.fwd), *_as_operands(plan.bwd),
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


@pytest.mark.parametrize("spmm_chunk,rem_dtype",
                         [(None, None), (40, None), (40, "float8")])
def test_trainer_bucket_matches_xla(spmm_chunk, rem_dtype, monkeypatch):
    """Under shard_map on four devices; an edge budget of 40 cuts every
    bucket of more than 32 rows into chunks (the scan whose carry is
    the bucket's result). Under fp8 transport the chunks' messages are
    gathered as 16-bit words: the losses are those of the same trainer
    gathering element by element, to the bit, and the float32 kernel's
    within fp8's rounding."""
    from pipegcn_tpu.ops import bucket_spmm as bs

    g = synthetic_graph(num_nodes=300, avg_degree=7, n_feat=10, n_class=4,
                        seed=21)
    parts = partition_graph(g, 4, seed=0)
    sg = ShardedGraph.build(g, parts, n_parts=4)
    losses = {}
    for impl in ("xla", "bucket") + (("elements",) if rem_dtype else ()):
        if impl == "elements":
            monkeypatch.setattr(bs, "_rides_as_words", lambda dt, f: False)
        cfg = ModelConfig(layer_sizes=(10, 16, 4), norm="layer",
                          dropout=0.0, train_size=sg.n_train_global,
                          spmm_impl="xla" if impl == "xla" else "bucket",
                          spmm_chunk=spmm_chunk,
                          rem_dtype=None if impl == "xla" else rem_dtype)
        t = Trainer(sg, cfg, TrainConfig(seed=4, enable_pipeline=True))
        losses[impl] = [t.train_epoch(e) for e in range(6)]
        if rem_dtype and impl != "xla":
            # the compiled step holds 16-bit words, or none at all
            assert ("u16[" in t.step_compiled_text(1)) == (
                impl != "elements")
    if spmm_chunk:
        assert max(v.shape[-1] for k, v in t._bucket_tables.items()
                   if not k.endswith("inv")) > 32
    np.testing.assert_allclose(losses["xla"], losses["bucket"],
                               rtol=5e-2 if rem_dtype else 2e-4)
    if rem_dtype:
        assert losses["bucket"] == losses["elements"]
        assert losses["bucket"] != losses["xla"]


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
        *_as_operands(plan.fwd), *_as_operands(plan.bwd),
        deg, n_src)
    f8_fn = make_bucket_spmm_fn(
        *_as_operands(plan.fwd), *_as_operands(plan.bwd),
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

def _window_graph(n=1024, deg=12, n_feat=12, n_class=4, seed=0):
    """Every node aggregates a contiguous id window below it, of 1 to
    `deg` ids in turn: several buckets under the fitted widths too."""
    from pipegcn_tpu.graph.csr import Graph

    src = [j for i in range(n) for j in range(max(0, i - 1 - i % deg), i)]
    dst = [i for i in range(n) for j in range(max(0, i - 1 - i % deg), i)]
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


def test_scan_names_the_kernels_work():
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
                      spmm_impl="bucket", dtype="bfloat16", use_pp=True)
    t = Trainer(sg, cfg, TrainConfig(seed=4, enable_pipeline=True))
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
    """Rows wider than a slab and no whole number of slabs wide are
    padded with zero columns to whole slabs and cut back after the
    takes: those copies are `relayout`, and a prefix (the block
    kernel's remainder) renames all four scopes."""
    import re

    src, dst, n_out, n_src = edges
    plan = BucketPlan(src, dst, n_out, n_src)
    mats, inv = _as_operands(plan.fwd)

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


# ---------------- the slot-major gather stream ------------------------------

def _ladder_upto(top):
    from pipegcn_tpu.ops.bucket_spmm import _ladder_rungs

    out = []
    for w in _ladder_rungs():
        if w > top:
            return out
        out.append(w)


LADDER = _ladder_upto(211)
TRANSPORTS = {"float32": jnp.float32, "bfloat16": jnp.bfloat16,
              "e4m3": jnp.float8_e4m3fn, "e5m2": jnp.float8_e5m2}


def _bucket_edges(width, seed, n_rows=150, n_src=260, n_zero=9, n_low=20):
    """Edges whose destinations fill the bucket of `width`: n_rows
    destinations with a degree in (rung below, width] (at least one at
    each end), n_low of degree 1 or 2, n_zero with none."""
    rng = np.random.default_rng(seed)
    lo = max([w for w in LADDER if w < width], default=0) + 1
    degs = np.concatenate([
        [lo, width], rng.integers(lo, width + 1, n_rows - 2),
        rng.integers(1, 3, n_low), np.zeros(n_zero, np.int64)])
    degs = degs[rng.permutation(degs.size)]
    dst = np.repeat(np.arange(degs.size), degs)
    src = np.concatenate([rng.choice(n_src, d, replace=False)
                          for d in degs]) if dst.size else dst
    return src.astype(np.int64), dst.astype(np.int64), degs.size, n_src


def _dense(src, dst, n_out, n_src):
    a = np.zeros((n_out, n_src), np.float64)
    np.add.at(a, (dst, src), 1.0)
    return a


def _assert_slot_major(mats, width_of=None):
    from pipegcn_tpu.ops.bucket_spmm import ROW_TILE

    for m in mats:
        assert m.ndim == 2 and m.shape[1] % ROW_TILE == 0, m.shape
    if width_of is not None:
        assert [m.shape[0] for m in mats] == list(width_of)


@pytest.mark.parametrize("transport", sorted(TRANSPORTS))
@pytest.mark.parametrize("width", LADDER)
def test_slot_major_forward_and_vjp_match_dense(width, transport):
    """Every x1.5 rung up to 211 as a bucket's width (the forward
    tables on that ladder, the transpose tables on widths fitted to
    their histogram), every transport dtype: the forward over the
    slot-major tables and its VJP (the same kernel over the transpose
    tables) are the dense float32 products of the operand AS
    TRANSPORTED, unchunked and over three balanced chunks, the last one
    moved back to the table's end; rows without an edge read zero."""
    dt = TRANSPORTS[transport]
    f = 8
    src, dst, n_out, n_src = _bucket_edges(width, seed=width)
    plan = BucketPlan(src, dst, n_out, n_src,
                      fwd_widths=_bucket_widths(int(np.bincount(dst).max())))
    fwd, bwd = plan.fwd.whole(), plan.bwd.whole()
    _assert_slot_major(fwd.mats, fwd.widths)
    _assert_slot_major(bwd.mats, bwd.widths)
    b = fwd.widths.index(width)
    # widths 1 and 2 also hold the low-degree rows: 150 rows, or a few more
    n_b = fwd.counts[b]
    assert 150 <= n_b <= 170 and fwd.mats[b].shape == (
        width, 160 if n_b <= 160 else 192)
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((n_src, f)), jnp.float32).astype(dt)
    g = jnp.asarray(rng.standard_normal((n_out, f)), jnp.float32).astype(dt)
    a = _dense(src, dst, n_out, n_src)
    want_f = a @ np.asarray(x.astype(jnp.float32), np.float64)
    want_b = a.T @ np.asarray(g.astype(jnp.float32), np.float64)
    fm = [jnp.asarray(m) for m in fwd.mats]
    bm = [jnp.asarray(m) for m in bwd.mats]
    # 64 rows of the bucket a chunk: 160 rows are chunks of 64, 64 and a
    # last one moved back to rows 96..160
    for chunk_elems in (1 << 30, 64 * width * f):
        out = bucket_aggregate(x, fm, jnp.asarray(fwd.inv),
                               chunk_elems=chunk_elems)
        assert out.dtype == jnp.float32 and out.shape == (n_out, f)
        np.testing.assert_allclose(np.asarray(out), want_f, rtol=2e-6,
                                   atol=1e-5)
        zero = np.bincount(dst, minlength=n_out) == 0
        assert zero.sum() == 9 and not np.asarray(out)[zero].any()
        back = bucket_aggregate(g, bm, jnp.asarray(bwd.inv),
                                chunk_elems=chunk_elems)
        np.testing.assert_allclose(np.asarray(back), want_b, rtol=2e-6,
                                   atol=1e-5)


@pytest.mark.parametrize("rem_dtype", [None, "bfloat16", "float8"])
@pytest.mark.parametrize("min_width", [0, 4, 19])
def test_slot_major_closure_with_min_width_merging(min_width, rem_dtype):
    """The differentiable closure over sharded tables whose narrow
    buckets are merged into the first rung >= min_width: forward and
    gradient against the dense mean, within the transport's rounding."""
    from pipegcn_tpu.ops.bucket_spmm import (
        build_sharded_bucket_tables,
        make_device_bucket_spmm_fn,
    )
    from types import SimpleNamespace

    src, dst, n_out, n_src = _bucket_edges(28, seed=min_width + 3,
                                           n_src=200)
    order = np.argsort(dst, kind="stable")
    sg = SimpleNamespace(
        num_parts=1, n_max=n_out, halo_size=n_src - n_out,
        edge_src=src[order][None].astype(np.int32),
        edge_dst=dst[order][None].astype(np.int32))
    tabs = build_sharded_bucket_tables(sg, min_width=min_width)
    fwd = sorted(k for k in tabs if k.startswith("bkt_fwd_")
                 and not k.endswith("inv"))
    assert min(tabs[k].shape[1] for k in fwd) >= max(min_width, 1)
    _assert_slot_major([tabs[k][0] for k in fwd])
    deg = np.maximum(np.bincount(dst, minlength=n_out), 1)
    fn = make_device_bucket_spmm_fn(
        {k: jnp.asarray(v[0]) for k, v in tabs.items()},
        jnp.asarray(deg, jnp.float32), n_src, rem_dtype=rem_dtype)
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.standard_normal((n_src, 16)), jnp.float32)
    c = jnp.asarray(rng.standard_normal((n_out, 16)), jnp.float32)
    out, vjp = jax.vjp(fn, x)
    a = _dense(src, dst, n_out, n_src) / deg[:, None]
    tol = {None: 1e-5, "bfloat16": 2e-2, "float8": 0.3}[rem_dtype]
    np.testing.assert_allclose(np.asarray(out), a @ np.asarray(x),
                               rtol=tol, atol=tol)
    np.testing.assert_allclose(np.asarray(vjp(c)[0]), a.T @ np.asarray(c),
                               rtol=tol, atol=tol)


def _walk_eqns(jaxpr):
    """Every equation of a jaxpr, those of its sub-jaxprs (scan bodies,
    pjit calls) included."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _walk_eqns(inner)


# what stands between the gathered words and a byte plane's sum in
# _widen_sum: the split, the narrowing and the view as the fp8 dtype
_WORD_SPLIT = ("and", "shift_right_logical", "convert_element_type",
               "bitcast_convert_type")


def assert_reduce_reads_transport(jaxpr, dt, f):
    """In `jaxpr` every sum under the `reduce` / `rem_reduce` scope (a
    bucket's width) reads the gathered stream as the gather left it,
    rows a multiple of 32: [w, rows, f] in the transport dtype `dt`,
    widened at most by the convert feeding it, or (one-byte `dt`, even
    f) uint16 [w, rows, f/2] words, split and widened a byte plane at a
    time on the way into two sums. No f32 widening of a message tensor
    feeds a reshape. Returns the [w, rows] of each stream seen."""
    from pipegcn_tpu.ops.bucket_spmm import ROW_TILE, _rides_as_words

    words = _rides_as_words(dt, f)
    eqns = list(_walk_eqns(jaxpr))
    made_by = {id(o): e for e in eqns for o in e.outvars}
    seen = {}
    for e in eqns:
        scope = str(e.source_info.name_stack).split("/")[-1]
        if e.primitive.name == "reduce_sum" and \
                scope in ("reduce", "rem_reduce"):
            assert tuple(e.params["axes"]) == (0,), e
            src = e.invars[0]
            maker = made_by.get(id(src))
            if maker is not None and \
                    maker.primitive.name == "convert_element_type":
                src = maker.invars[0]
            assert src.aval.dtype == dt, (src.aval, dt)
            # back through the byte split to the words as gathered
            while words and id(src) in made_by and \
                    made_by[id(src)].primitive.name in _WORD_SPLIT:
                src = made_by[id(src)].invars[0]
            w, rows, ff = src.aval.shape
            assert src.aval.dtype == (jnp.uint16 if words else dt), src.aval
            assert ff == (f // 2 if words else f), src.aval
            assert rows % ROW_TILE == 0, src.aval
            assert e.outvars[0].aval.dtype == jnp.float32
            seen.setdefault(id(src), []).append((w, rows))
        if e.primitive.name == "reshape":
            maker = made_by.get(id(e.invars[0]))
            widened = (maker is not None
                       and maker.primitive.name == "convert_element_type"
                       and maker.outvars[0].aval.dtype == jnp.float32
                       and maker.invars[0].aval.dtype != jnp.float32)
            assert not (widened and e.invars[0].aval.size >= 32 * f), e
    # a word stream feeds exactly two sums, one a byte plane
    assert all(len(v) == (2 if words else 1) for v in seen.values()), seen
    return [v[0] for v in seen.values()]


@pytest.mark.parametrize("chunked", [False, True])
@pytest.mark.parametrize("transport", sorted(TRANSPORTS))
def test_reduce_operand_is_the_transported_stream(transport, chunked):
    """The jaxpr of the kernel: the reduction's operand is the gathered
    [width, rows, F] stream in its transport dtype (rows % 32 == 0),
    and no float32 copy of rows * width * F elements is reshaped."""
    dt = TRANSPORTS[transport]
    f = 8
    src, dst, n_out, n_src = _bucket_edges(63, seed=2)
    plan = BucketPlan(src, dst, n_out, n_src, fwd_widths=_bucket_widths(63))
    mats, inv = _as_operands(plan.fwd)
    chunk = 64 * 63 * f if chunked else 1 << 30
    jaxpr = jax.make_jaxpr(
        lambda x: bucket_aggregate(x, mats, inv, chunk_elems=chunk)
    )(jnp.zeros((n_src, f), dt)).jaxpr
    seen = assert_reduce_reads_transport(jaxpr, dt, f)
    live = [m.shape for m in mats if m.shape[1]]
    want = [(w, 64 if chunked and w == 63 else n) for w, n in live]
    assert sorted(seen) == sorted(want)


# ---------------- fp8 rows ride the gather as 16-bit words -------------------

FP8 = {"e4m3": jnp.float8_e4m3fn, "e5m2": jnp.float8_e5m2}


def _gather_operands(fn, *args):
    """(dtype, shape) of the table every row take under a `gather` scope
    reads, in the jaxpr of fn(*args)."""
    return [(e.invars[0].aval.dtype, e.invars[0].aval.shape)
            for e in _walk_eqns(jax.make_jaxpr(fn)(*args).jaxpr)
            if e.params.get("name") == "_take" and
            str(e.source_info.name_stack).split("/")[-1] == "gather"]


@pytest.mark.parametrize("chunked", [False, True])
@pytest.mark.parametrize("f", [256, 512])
@pytest.mark.parametrize("fmt", sorted(FP8))
def test_word_path_equals_element_path_bit_for_bit(fmt, f, chunked,
                                                   monkeypatch):
    """An fp8 operand of F = 256 (one slab) or 512 (two) is gathered as
    uint16 [R + 1, 128] words, and the sums over the forward tables and
    over the transpose tables (the VJP's direction) equal, bit for bit,
    those of the same operand gathered element by element: the widening
    is exact and the sums are the same sums in the same order.
    Unchunked, and in chunks of 64 rows with a ragged last one; then the
    differentiable closure under `rem_dtype='float8'`, forward and VJP."""
    from pipegcn_tpu.ops import bucket_spmm as bs

    dt = FP8[fmt]
    width = 13
    src, dst, n_out, n_src = _bucket_edges(width, seed=31)
    plan = BucketPlan(src, dst, n_out, n_src)
    (fm, finv), (bm, binv) = _as_operands(plan.fwd), _as_operands(plan.bwd)
    chunk = 64 * width * 256 if chunked else 1 << 30
    rng = np.random.default_rng(f)
    # small and large magnitudes: subnormals of both formats are sent
    scale = np.exp2(rng.integers(-12, 5, (n_src, 1)))
    x = jnp.asarray(rng.standard_normal((n_src, f)) * scale,
                    jnp.float32).astype(dt)
    g = jnp.asarray(rng.standard_normal((n_out, f)) * scale[:n_out],
                    jnp.float32).astype(dt)
    deg = jnp.asarray(np.maximum(np.bincount(dst, minlength=n_out), 1),
                      jnp.float32)

    def forward(a):
        return bucket_aggregate(a, fm, finv, chunk_elems=chunk)

    def run():
        fn = make_bucket_spmm_fn(fm, finv, bm, binv, deg, n_src,
                                 chunk_elems=chunk, rem_dtype="float8")
        out, vjp = jax.vjp(fn, x.astype(jnp.float32))
        return [np.asarray(a) for a in (
            forward(x), bucket_aggregate(g, bm, binv, chunk_elems=chunk),
            out, vjp(g.astype(jnp.float32))[0])]

    # (a fresh lambda each time: a function's trace is cached)
    live = sum(1 for m in fm if m.shape[1])
    assert _gather_operands(lambda a: forward(a), x) == [
        (jnp.uint16, (n_src + 1, 128))] * live
    words = run()
    monkeypatch.setattr(bs, "_rides_as_words", lambda dtype, f: False)
    assert _gather_operands(lambda a: forward(a), x) == [
        (dt, (n_src + 1, 256))] * live
    for a, b in zip(words, run()):
        assert a.any() and np.array_equal(a, b)


@pytest.mark.parametrize("fmt", sorted(FP8))
def test_every_byte_widens_as_astype(fmt):
    """All 256 byte values of the format come out of the packed words
    as `astype(float32)` gives them, from either byte of a word. None
    is excepted: the NaN patterns (e4m3fn 0x7F and 0xFF; e5m2 0x7D to
    0x7F and 0xFD to 0xFF) read NaN and e5m2's 0x7C / 0xFC read
    infinity, since each byte goes back through the dtype's convert."""
    from pipegcn_tpu.ops.bucket_spmm import _pack_words, _widen_sum

    dt = FP8[fmt]
    every = jax.lax.bitcast_convert_type(jnp.arange(256, dtype=jnp.uint8),
                                         dt)
    want = np.asarray(every.astype(jnp.float32))
    assert np.isnan(want).sum() == {"e4m3": 2, "e5m2": 6}[fmt]
    table = jnp.stack([every, every[::-1]], axis=1)     # [256, 2]
    words = _pack_words(table)
    assert words.dtype == jnp.uint16 and words.shape == (256, 1)
    # one slot: no sum; the two halves of the row, low bytes first
    got = np.concatenate(_widen_sum(words[None], dt), axis=-1)
    np.testing.assert_array_equal(got[:, 0], want)
    np.testing.assert_array_equal(got[:, 1], want[::-1])
    # unsigned and byte for byte: the sentinel's zero row stays zero
    assert not np.asarray(_pack_words(jnp.zeros((3, 8), dt))).any()


@pytest.mark.parametrize("case", ["e4m3-odd", "e5m2-odd", "bfloat16",
                                  "float32"])
def test_other_operands_are_gathered_as_they_are(case):
    """An odd width has no whole number of words, and two- and four-byte
    elements need none: the gather reads the operand itself."""
    dt, f = {"e4m3-odd": (jnp.float8_e4m3fn, 255),
             "e5m2-odd": (jnp.float8_e5m2, 7),
             "bfloat16": (jnp.bfloat16, 128),
             "float32": (jnp.float32, 64)}[case]
    src, dst, n_out, n_src = _bucket_edges(6, seed=8)
    plan = BucketPlan(src, dst, n_out, n_src)
    fm, inv = _as_operands(plan.fwd)
    x = jnp.asarray(np.random.default_rng(0).standard_normal((n_src, f)),
                    jnp.float32).astype(dt)
    ops = _gather_operands(
        lambda a: bucket_aggregate(a, fm, inv), x)
    assert ops and all(o == (dt, (n_src + 1, f)) for o in ops), ops
    out = bucket_aggregate(x, fm, inv)
    want = _dense(src, dst, n_out, n_src) @ np.asarray(
        x.astype(jnp.float32), np.float64)
    np.testing.assert_allclose(np.asarray(out), want, rtol=2e-6, atol=1e-5)


# ---------------- widths fitted to the degree histogram -----------------------

def _slots(hist, widths):
    from pipegcn_tpu.ops.bucket_spmm import _ladder_slots

    return _ladder_slots(np.asarray(hist, np.int64), widths)


def _hist(kind):
    rng = np.random.default_rng(7)
    if kind == "poisson":
        deg = rng.poisson(10, 200_000) + 1
    elif kind == "normal":      # between the rungs 94 and 141
        deg = np.clip(rng.normal(98, 14, 60_000).round(), 60, 140)
    elif kind == "power-law":
        deg = np.minimum(rng.zipf(1.7, 300_000), 5000)
    elif kind == "single-degree":
        deg = np.full(5000, 37)
    elif kind == "one-hub":
        deg = np.concatenate([rng.integers(1, 8, 4000), [100_000]])
    else:
        deg = np.zeros(0, np.int64)
    return np.bincount(deg.astype(np.int64), minlength=1)


@pytest.mark.parametrize("seed", range(6))
def test_fit_widths_is_the_optimum_on_small_histograms(seed):
    """Against every choice of at most K widths out of the degrees
    present, on small random histograms with gaps, of one shard or of
    two or three (slots then count every shard at the largest one's rows):
    the programme's slots are the least, within K, ascending, the last
    the largest degree, none below min_width."""
    import itertools

    from pipegcn_tpu.ops.bucket_spmm import fit_widths

    rng = np.random.default_rng(seed)
    for _ in range(40):
        n = int(rng.integers(1, 11))
        # one shard, or three whose rows pad to the largest's cap
        hist = np.zeros((int(rng.choice([1, 2, 3])), n + 1), np.int64)
        hist[:, 1:] = rng.integers(0, 200, hist[:, 1:].shape) * (
            rng.random(hist[:, 1:].shape) < 0.7)
        hist[0, int(rng.integers(1, n + 1))] += 5
        k, floor = int(rng.integers(1, 5)), int(rng.integers(0, 6))
        got = fit_widths(hist if seed % 2 else hist.squeeze(), k, floor)
        degs = np.nonzero(hist.sum(axis=0)[1:])[0] + 1
        tops = sorted({max(int(d), floor, 1) for d in degs})
        best = min(
            _slots(hist, list(combo) + [tops[-1]])[0]
            for j in range(min(k, len(tops)))
            for combo in itertools.combinations(tops[:-1], j))
        assert _slots(hist, got)[0] == best, (hist, k, floor, got)
        assert len(got) <= k and got == sorted(set(got))
        assert got[-1] >= degs[-1] and got[0] >= floor


@pytest.mark.parametrize("min_width", [0, 5])
@pytest.mark.parametrize("kind", ["poisson", "normal", "power-law",
                                  "single-degree", "one-hub", "empty"])
def test_fit_widths_never_worse_than_the_ladder(kind, min_width):
    """On a histogram of each family the fitted widths issue no more
    slots than the x1.5 ladder, in no more buckets than max(8, the
    ladder's non-empty rungs); the top width covers the largest degree
    and none is below min_width. Between two rungs (normal) and on
    Poisson degrees the fit is several points under the ladder."""
    from pipegcn_tpu.ops.bucket_spmm import FIT_MIN_BUCKETS, fit_widths

    hist = _hist(kind)
    got = fit_widths(hist, min_width=min_width)
    assert got == sorted(set(got)) and got[0] >= max(1, min_width)
    if kind == "empty":
        assert got == [max(1, min_width)]
        return
    top = hist.shape[0] - 1
    old = _bucket_widths(top, min_width)
    old_slots, old_filled = _slots(hist, old)
    slots, filled = _slots(hist, got)
    assert got[-1] >= top
    assert slots <= old_slots
    assert filled == len(got) <= max(FIT_MIN_BUCKETS, old_filled)
    edges = int((np.arange(top + 1) * hist).sum())
    if kind in ("poisson", "normal") and not min_width:
        assert old_slots / edges > 1.15 and slots / edges < 1.06
    if kind == "single-degree":
        assert got == [37]


def test_fit_widths_thins_a_hub_histogram_fast():
    """Thousands of distinct degrees up to a 100k hub: the candidates
    are thinned, the answer stays within the rule, and the host pays
    well under a second."""
    import time

    from pipegcn_tpu.ops.bucket_spmm import (_FIT_CANDIDATES,
                                             FIT_MIN_BUCKETS, fit_widths)

    rng = np.random.default_rng(3)
    deg = np.minimum(rng.zipf(1.5, 2_000_000), 100_000)
    deg[0] = 100_000
    hist = np.bincount(deg)
    assert np.count_nonzero(hist) > 4 * _FIT_CANDIDATES
    t0 = time.perf_counter()
    got = fit_widths(hist)
    assert time.perf_counter() - t0 < 1.0
    old_slots, old_filled = _slots(hist, _bucket_widths(100_000))
    assert got[-1] == 100_000
    assert _slots(hist, got)[0] <= old_slots
    assert len(got) <= max(FIT_MIN_BUCKETS, old_filled)


@pytest.mark.parametrize("transport", ["float32", "e4m3"])
def test_fitted_plan_between_two_rungs_matches_dense(transport):
    """Degrees concentrated between the old rungs 94 and 141 (Reddit's
    remainder): BucketPlan's own widths split them into several
    buckets of far fewer slots, and forward and VJP are the dense
    products."""
    dt = TRANSPORTS[transport]
    rng = np.random.default_rng(9)
    n_src, f = 400, 8
    degs = np.clip(rng.normal(106, 10, 700).round(), 95, 140).astype(int)
    degs = np.concatenate([degs, [0, 0, 1, 3]])
    dst = np.repeat(np.arange(degs.size), degs)
    src = np.concatenate([rng.choice(n_src, d, replace=False)
                          for d in degs])
    plan = BucketPlan(src, dst, degs.size, n_src)
    fwd, bwd = plan.fwd.whole(), plan.bwd.whole()
    hist = np.bincount(degs)
    assert 3 <= len(fwd.widths) <= 8
    assert fwd.widths[-1] == degs.max()
    assert _slots(hist, fwd.widths)[0] < 0.85 * _slots(
        hist, _bucket_widths(int(degs.max())))[0]
    _assert_slot_major(fwd.mats, fwd.widths)
    _assert_slot_major(bwd.mats, bwd.widths)
    x = jnp.asarray(rng.standard_normal((n_src, f)), jnp.float32).astype(dt)
    g = jnp.asarray(rng.standard_normal((degs.size, f)),
                    jnp.float32).astype(dt)
    a = _dense(src, dst, degs.size, n_src)
    for chunk_elems in (1 << 30, 96 * 100 * f):
        out = bucket_aggregate(
            x, [jnp.asarray(m) for m in fwd.mats],
            jnp.asarray(fwd.inv), chunk_elems=chunk_elems)
        np.testing.assert_allclose(
            np.asarray(out),
            a @ np.asarray(x.astype(jnp.float32), np.float64),
            rtol=2e-6, atol=1e-5)
        back = bucket_aggregate(
            g, [jnp.asarray(m) for m in bwd.mats],
            jnp.asarray(bwd.inv), chunk_elems=chunk_elems)
        np.testing.assert_allclose(
            np.asarray(back),
            a.T @ np.asarray(g.astype(jnp.float32), np.float64),
            rtol=2e-6, atol=1e-5)


# ---------------- balanced chunks --------------------------------------------

def test_chunk_rows_regathers_under_a_tile_a_chunk():
    """Over random widths, row counts and budgets: chunks are
    ROW_TILE-aligned, fit the budget, cover the table, and the rows
    gathered twice are under ROW_TILE * chunks (never more than an even
    spread over the fewest chunks re-gathers)."""
    from pipegcn_tpu.ops.bucket_spmm import ROW_TILE, chunk_rows, row_cap

    rng = np.random.default_rng(11)
    for _ in range(2000):
        w = int(rng.integers(1, 600))
        f = int(rng.choice([8, 64, 128, 256]))
        n_b = ROW_TILE * int(rng.integers(1, 20_000))
        budget = int(rng.choice([1 << 16, 1 << 20, 32 << 20]))
        rows, n_chunks = chunk_rows(w, n_b, f, budget)
        max_rows = max(ROW_TILE, budget // (w * f) // ROW_TILE * ROW_TILE)
        assert rows % ROW_TILE == 0 and 0 < rows <= max_rows
        assert rows <= n_b and rows * n_chunks >= n_b
        assert (n_chunks == 1) == (n_b <= max_rows)
        fewest = -(-n_b // max_rows)
        assert n_chunks < 2 * fewest + 1
        even = row_cap(-(-n_b // fewest)) * fewest
        assert rows * n_chunks <= even
        assert rows * n_chunks - n_b < ROW_TILE * n_chunks
    # Reddit's width-141 bucket as PR 32 cut it, and a fitted one
    assert chunk_rows(141, 135_488, 256, 32 << 20) == (928, 146)
    assert chunk_rows(100, 54_400, 256, 32 << 20) == (1088, 50)


@pytest.mark.parametrize("n_rows", [130, 200, 330])
def test_chunked_rows_are_written_once_or_with_equal_values(n_rows):
    """A bucket cut into several chunks, the last one moved back over
    its neighbour: every row of the result equals the unchunked one
    bit for bit (a row written twice is written the same)."""
    from pipegcn_tpu.ops.bucket_spmm import ROW_TILE, chunk_rows

    width, f = 28, 8
    src, dst, n_out, n_src = _bucket_edges(width, seed=n_rows,
                                           n_rows=n_rows, n_low=0)
    mats, inv, counts = build_tables_for_edges(src, dst, n_out, n_src,
                                               [width])
    x = jnp.asarray(np.random.default_rng(0).standard_normal((n_src, f)),
                    jnp.float32)
    whole = bucket_aggregate(x, [jnp.asarray(mats[0])], jnp.asarray(inv))
    budget = 96 * width * f
    rows, n_chunks = chunk_rows(width, mats[0].shape[1], f, budget)
    assert n_chunks > 1
    assert rows * n_chunks - mats[0].shape[1] < ROW_TILE * n_chunks
    cut = bucket_aggregate(x, [jnp.asarray(mats[0])], jnp.asarray(inv),
                           chunk_elems=budget)
    assert np.array_equal(np.asarray(whole), np.asarray(cut))


# ---------------- one ladder over the shards, sticky under deltas ------------

def _four_shards(seed=0, hub=0):
    """Four shards of unlike degrees (2, 6, 12 and 20 a row on average)
    as the sharded builders see them."""
    from types import SimpleNamespace

    rng = np.random.default_rng(seed)
    n_max, halo, e_max = 192, 32, 6000
    srcs, dsts = [], []
    for r, mean in enumerate((2, 6, 12, 20)):
        degs = np.minimum(rng.poisson(mean, n_max - 8), n_max)
        if hub and r == 1:
            degs[0] = hub
        dst = np.repeat(np.arange(degs.size), degs)
        src = np.concatenate([rng.choice(n_max + halo, d, replace=False)
                              for d in degs] + [np.zeros(0, np.int64)])
        pad = e_max - dst.size
        srcs.append(np.concatenate([src, np.zeros(pad, np.int64)]))
        dsts.append(np.concatenate([dst, np.full(pad, n_max)]))
    return SimpleNamespace(
        num_parts=4, n_max=n_max, halo_size=halo,
        edge_src=np.stack(srcs).astype(np.int32),
        edge_dst=np.stack(dsts).astype(np.int32))


def _table_neighbours(tables, stem, sentinel):
    """{(device, row): sorted neighbours} read back from stacked
    slot-major tables: what they sum, whatever their widths."""
    keys = sorted(k for k in tables if k.startswith(stem + "_")
                  and not k.endswith("inv"))
    out = {}
    for dev in range(tables[stem + "_inv"].shape[0]):
        cols = [tables[k][dev][:, c] for k in keys
                for c in range(tables[k].shape[2])]
        for row, pos in enumerate(tables[stem + "_inv"][dev]):
            if pos < len(cols):
                nb = cols[pos][cols[pos] != sentinel]
                out[dev, row] = sorted(nb.tolist())
    return out


def _shard_neighbours(sg, transpose=False):
    out = {}
    for r in range(sg.num_parts):
        real = sg.edge_dst[r] < sg.n_max
        a, b = sg.edge_src[r][real], sg.edge_dst[r][real]
        if transpose:
            a, b = b, a
        for s, d in zip(a.tolist(), b.tolist()):
            out.setdefault((r, d), []).append(s)
    return {k: sorted(v) for k, v in out.items()}


def test_one_fitted_ladder_serves_every_shard():
    """Four shards of unlike degree histograms: the builder fits ONE
    ladder a direction to all of them, every device's tables have those
    widths (one traced program), caps are the largest shard's, the
    stacked tables gather exactly the slots the fit minimised (no more
    than on the x1.5 ladder), and each device's tables hold exactly its
    own edges."""
    from pipegcn_tpu.ops.bucket_spmm import (bucket_pad_stats,
                                             build_sharded_bucket_tables,
                                             degree_hist, fit_widths)

    sg = _four_shards()
    n_src_rows = sg.n_max + sg.halo_size
    tabs = build_sharded_bucket_tables(sg)
    real = [sg.edge_dst[r] < sg.n_max for r in range(4)]
    for stem, arr, sentinel in (("bkt_fwd", sg.edge_dst, n_src_rows),
                                ("bkt_bwd", sg.edge_src, sg.n_max)):
        hist = degree_hist(np.bincount(arr[r][real[r]]) for r in range(4))
        want = fit_widths(hist)
        keys = sorted(k for k in tabs if k.startswith(stem + "_")
                      and not k.endswith("inv"))
        assert [tabs[k].shape[1] for k in keys] == want
        assert sum(tabs[k].size for k in keys) == _slots(hist, want)[0] \
            <= _slots(hist, _bucket_widths(hist.shape[1] - 1))[0]
        assert all(tabs[k].shape[0] == 4 and tabs[k].shape[2] % 32 == 0
                   for k in keys)
        assert _table_neighbours(tabs, stem, sentinel) == \
            _shard_neighbours(sg, transpose=stem == "bkt_bwd")
    pad = bucket_pad_stats(tabs, sg.n_max, n_src_rows)
    n_edges = int(sum(r.sum() for r in real))
    assert pad["fwd"]["edges"] == pad["bwd"]["edges"] == n_edges
    assert pad["fwd"]["slots"] == sum(
        4 * t.shape[1] * t.shape[2] for k, t in tabs.items()
        if k.startswith("bkt_fwd_") and not k.endswith("inv"))


def test_dirty_rebuild_keeps_the_ladder_until_it_is_outgrown():
    """With a plan cache and dirty shards the cached widths stay while
    their top covers the largest degree (clean shards' plans are
    reused; the tables hold the new edges at the OLD widths, where a
    cache-free build may choose others); a degree past the top refits
    and rebuilds every plan."""
    from pipegcn_tpu.ops.bucket_spmm import build_sharded_bucket_tables

    sg = _four_shards()
    n_src_rows = sg.n_max + sg.halo_size
    cache = {}
    build_sharded_bucket_tables(sg, plan_cache=cache)
    widths, plans = cache["widths"], list(cache["plans"])
    # shard 2 changes: its rows of degree 1 to 3 lose their edges
    deg = np.bincount(sg.edge_dst[2][sg.edge_dst[2] < sg.n_max],
                      minlength=sg.n_max)
    sg.edge_dst[2][np.isin(sg.edge_dst[2], np.nonzero(deg <= 3)[0])] = \
        sg.n_max
    tabs = build_sharded_bucket_tables(sg, plan_cache=cache, dirty=[2])
    assert cache["widths"] == widths
    assert [p is q for p, q in zip(cache["plans"], plans)] == \
        [True, True, False, True]
    assert _table_neighbours(tabs, "bkt_fwd", n_src_rows) == \
        _shard_neighbours(sg)
    assert _table_neighbours(tabs, "bkt_bwd", sg.n_max) == \
        _shard_neighbours(sg, transpose=True)
    # a row of shard 1 (and no other shard) outgrows the forward
    # ladder's top width (the cache keeps a ladder a part; this graph's
    # directions are one part each): its pad edges become edges of row 0
    top = widths[0][0][-1]
    grown = sg
    pad = np.nonzero(grown.edge_dst[1] == sg.n_max)[0][:top + 40]
    grown.edge_dst[1][pad] = 0
    grown.edge_src[1][pad] = np.arange(pad.size) % n_src_rows
    hub = int(np.count_nonzero(grown.edge_dst[1] == 0))
    assert hub > top
    tabs = build_sharded_bucket_tables(grown, plan_cache=cache, dirty=[1])
    assert cache["widths"][0][0][-1] == hub
    assert not any(p is q for p, q in zip(cache["plans"], plans))
    assert _table_neighbours(tabs, "bkt_fwd", n_src_rows) == \
        _shard_neighbours(grown)


def test_fit_widths_takes_the_stacked_objective_at_two_shards():
    """P = 2, one shard of low degrees and one of high: under shard_map
    every shard's bucket is padded to the larger one's rows, and the fit
    minimises THAT (never more than the ladder gives the stacked
    tables), which the sum of the two histograms does not describe."""
    from pipegcn_tpu.ops.bucket_spmm import fit_widths

    rng = np.random.default_rng(5)
    degs = [rng.poisson(6, 5000) + 1, rng.poisson(40, 5000) + 1]
    size = max(int(d.max()) for d in degs) + 1
    hist = np.stack([np.bincount(d, minlength=size) for d in degs])
    got = fit_widths(hist)
    ladder = _bucket_widths(size - 1)
    assert got[-1] == size - 1
    assert _slots(hist, got)[0] <= _slots(hist, ladder)[0]
    # the widths fitted to the summed histogram cost the stacked tables
    # at least as much as the ones fitted to them
    summed = fit_widths(hist.sum(axis=0), len(got))
    assert _slots(hist, got)[0] <= _slots(hist, summed)[0]


# ---------------- no edge is ever dropped silently ------------------------------

def test_widths_that_do_not_cover_a_degree_raise():
    """A width list whose top is under a row's degree would lose that
    row's tail: the builder refuses it, by name, in both directions of a
    plan; at the degree itself it builds."""
    src, dst, n_out, n_src = _bucket_edges(28, seed=1)
    top = int(np.bincount(dst).max())
    with pytest.raises(ValueError, match=f"end at {top - 1} but a row "
                                         f"has {top} edges"):
        build_tables_for_edges(src, dst, n_out, n_src, [4, top - 1])
    with pytest.raises(ValueError, match="but a row has"):
        BucketPlan(src, dst, n_out, n_src, bwd_widths=[1])
    mats, _, counts = build_tables_for_edges(src, dst, n_out, n_src,
                                             [4, top])
    assert sum(int((m != n_src).sum()) for m in mats) == src.size


@pytest.mark.parametrize("direction", ["fwd", "bwd"])
@pytest.mark.parametrize("kernel", ["bucket", "block remainder"])
def test_an_entry_overwritten_by_the_sentinel_is_counted(kernel, direction):
    """One edge of one device replaced by the sentinel (in bounds, and a
    change of one mean under any transport's noise): the validation
    counts each direction's entries against the edges the tables were
    built from and against each other, and names the direction."""
    from pipegcn_tpu.ops.block_spmm import build_sharded_block_tables
    from pipegcn_tpu.ops.bucket_spmm import (build_sharded_bucket_tables,
                                             table_edges,
                                             validate_bucket_tables)

    sg = _four_shards()
    n_src_rows = sg.n_max + sg.halo_size
    if kernel == "bucket":
        stem, tabs = "bkt", build_sharded_bucket_tables(sg)
        n_edges = [int((d < sg.n_max).sum()) for d in sg.edge_dst]
    else:
        stem = "blkrem"
        tabs, _ = build_sharded_block_tables(sg, tile=16, n_feat_hint=8,
                                             nnz_threshold=6)
        n_edges = table_edges(tabs, "blkrem_fwd", n_src_rows).tolist()
        assert 0 < sum(n_edges) < int((sg.edge_dst < sg.n_max).sum())
    validate_bucket_tables(tabs, sg.n_max, n_src_rows, n_edges, stem)
    sentinel = n_src_rows if direction == "fwd" else sg.n_max
    key = sorted(k for k in tabs if k.startswith(f"{stem}_{direction}_")
                 and not k.endswith("inv"))[1]
    bad = {k: np.array(v) for k, v in tabs.items()}
    dev, slot, col = np.argwhere(bad[key] != sentinel)[-1]
    bad[key][dev, slot, col] = sentinel
    for count in (n_edges, None):
        with pytest.raises(ValueError, match="dropped or overwritten"):
            validate_bucket_tables(bad, sg.n_max, n_src_rows, count, stem)


# ---------------- fitted tables sum what the ladder's tables sum ----------------

def _ladder_fit(hist, max_buckets=None, min_width=0):
    """fit_widths' signature, the x1.5 ladder's answer."""
    return _bucket_widths(np.atleast_2d(hist).shape[1] - 1, min_width)


def _sums_of(kernel, sg):
    """Forward output and every VJP of `kernel` over sg's tables, as
    float32 arrays (sequential: two epochs' losses, which carry both)."""
    n_src = sg.n_max + sg.halo_size
    rng = np.random.default_rng(0)
    if kernel == "sequential":
        from pipegcn_tpu.parallel import SequentialRunner

        cfg = ModelConfig(layer_sizes=(sg.n_feat, 16, 16, sg.n_class),
                          train_size=sg.n_train_global, dropout=0.0,
                          norm="layer", spmm_impl="bucket")
        run = SequentialRunner(sg, cfg, TrainConfig(
            lr=0.01, n_epochs=2, enable_pipeline=True, eval=False, seed=2))
        return [np.asarray([run.run_epoch(e) for e in range(2)])]
    out = []
    for r in range(sg.num_parts):
        deg = jnp.asarray(sg.in_deg[r], jnp.float32)
        x = jnp.asarray(rng.standard_normal((n_src, 8)), jnp.float32)
        c = jnp.asarray(rng.standard_normal((sg.n_max, 8)), jnp.float32)
        if kernel == "attention":
            from pipegcn_tpu.ops.gat_bucket import (build_sharded_gat_tables,
                                                    make_device_gat_fn)

            d = {k: jnp.asarray(v[r])
                 for k, v in build_sharded_gat_tables(sg).items()}
            fn = make_device_gat_fn(d, sg.n_max, n_src, 2, 0.2)
            args = (x.reshape(n_src, 2, 4),
                    jnp.asarray(rng.standard_normal((n_src, 2)),
                                jnp.float32),
                    jnp.asarray(rng.standard_normal((sg.n_max, 2)),
                                jnp.float32))
            c = c.reshape(sg.n_max, 2, 4)
        elif kernel == "block remainder":
            from pipegcn_tpu.ops.block_spmm import (
                build_sharded_block_tables, make_device_block_spmm_fn)

            tabs, tile = build_sharded_block_tables(
                sg, tile=16, n_feat_hint=8, nnz_threshold=6)
            fn = make_device_block_spmm_fn(
                {k: jnp.asarray(v[r]) for k, v in tabs.items()}, deg,
                sg.n_max, n_src, tile)
            args = (x,)
        else:
            from pipegcn_tpu.ops.bucket_spmm import (
                build_sharded_bucket_tables, make_device_bucket_spmm_fn)

            tabs = build_sharded_bucket_tables(sg)
            fn = make_device_bucket_spmm_fn(
                {k: jnp.asarray(v[r]) for k, v in tabs.items()}, deg, n_src)
            args = (x,)
        y, vjp = jax.vjp(fn, *args)
        out += [np.asarray(y)] + [np.asarray(g) for g in vjp(c)]
    return out


@pytest.mark.parametrize("kernel", ["bucket", "block remainder",
                                    "sequential", "attention"])
def test_fitted_tables_sum_what_the_ladder_tables_sum(kernel, monkeypatch):
    """Every builder of row buckets (the bucket kernel, the block
    kernel's remainder, the sequential runner, the attention tables),
    two shards: at the fitted widths the kernel's float32 forward and
    backward are what they are at the x1.5 ladder's widths (the same
    edges in other slots, so a sum in another order), and the widths
    do differ."""
    from pipegcn_tpu.ops import block_spmm, bucket_spmm, gat_bucket

    g = synthetic_graph(num_nodes=500, avg_degree=14, n_feat=12,
                        n_class=4, seed=3)
    sg = ShardedGraph.build(g, partition_graph(g, 2, seed=0), n_parts=2)
    fitted = _sums_of(kernel, sg)
    widths = bucket_spmm.build_sharded_bucket_tables(sg)
    for mod in (bucket_spmm, block_spmm, gat_bucket):
        monkeypatch.setattr(mod, "fit_widths", _ladder_fit)
    ladder = _sums_of(kernel, sg)
    assert {k: v.shape for k, v in widths.items()} != {
        k: v.shape
        for k, v in bucket_spmm.build_sharded_bucket_tables(sg).items()}
    assert len(fitted) == len(ladder)
    for a, b in zip(fitted, ladder):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)


# ---------------- a direction cut by source rows into parts -----------------

def _as_operands(direction):
    """A Direction's tables as bucket_aggregate takes them: one part's
    list and permutation, or a list of each."""
    parts = [([jnp.asarray(m) for m in p.mats], jnp.asarray(p.inv))
             for p in direction.parts]
    if len(parts) == 1:
        return parts[0]
    return [m for m, _ in parts], [i for _, i in parts]


PART_TRANSPORTS = {"float32": (jnp.float32, None),
                   "bfloat16": (jnp.bfloat16, "bfloat16"),
                   "fp8-words": (jnp.float8_e4m3fn, "float8")}


# (features, slab elements, chunk budget in elements) of the parity
# cases: three whole slabs, a width that is no whole number of slabs
# (padded to three), one slab; chunked buckets beside unchunked ones,
# or none chunked
PART_LAYOUTS = {"slabs": (24, 8, 8 * 32 * 3),
                "ragged-slabs": (20, 8, 8 * 32 * 3),
                "one-slab": (8, 8, 8 * 32 * 3),
                "unchunked": (24, 8, DEFAULT_CHUNK_ELEMS)}


@pytest.mark.parametrize("layout", sorted(PART_LAYOUTS))
@pytest.mark.parametrize("transport", sorted(PART_TRANSPORTS))
@pytest.mark.parametrize("k", [1, 2, 3])
def test_parts_sum_what_one_table_sums(k, transport, layout):
    """A direction cut by source rows into k parts (each its own table,
    zero row and fitted widths), forward and transpose, through feature
    slabs (whole ones, a width padded to whole ones, or one) and
    chunked buckets beside unchunked ones (or none chunked), with
    destination rows that have edges but none in some part: on
    integer-valued features every sum is the segment sum's and the
    dense one's, exactly (the same f32 sums in another order); the
    closure's VJP is the uncut one's within f32 tolerance. fp8 rides
    the gathers as 16-bit words."""
    from pipegcn_tpu.ops.bucket_spmm import _rides_as_words, chunk_rows

    src, dst, n_out, n_src = _bucket_edges(19, seed=k, n_rows=260,
                                           n_src=400)
    one = BucketPlan(src, dst, n_out, n_src, parts=(1, 1))
    cut = BucketPlan(src, dst, n_out, n_src, parts=(k, k))
    assert (cut.fwd.k, cut.bwd.k) == (k, k)
    dt, rem_dtype = PART_TRANSPORTS[transport]
    f, slab, chunk = PART_LAYOUTS[layout]
    assert _rides_as_words(dt, slab) == (transport == "fp8-words")
    chunked = [chunk_rows(m.shape[0], m.shape[1], slab, chunk)[1] > 1
               for p in cut.fwd.parts for m in p.mats if m.shape[1]]
    assert any(chunked) == (layout != "unchunked")
    if k > 1:
        assert not all(chunked)
        # rows with an edge, but none in some part: they read that
        # part's zero row
        assert any(((d == 0) & (np.bincount(dst, minlength=n_out) > 0))
                   .any() for d in cut.fwd.degs)
    rng = np.random.default_rng(k)
    x = rng.integers(-3, 4, (n_src, f)).astype(np.float32)
    g = rng.integers(-3, 4, (n_out, f)).astype(np.float32)
    a = _dense(src, dst, n_out, n_src)
    agg = jax.jit(lambda v, m, i: bucket_aggregate(
        v, m, i, chunk_elems=chunk, slab=slab))
    for plan_dir, feats, s, d, rows in ((cut.fwd, x, src, dst, n_out),
                                        (cut.bwd, g, dst, src, n_src)):
        got = agg(jnp.asarray(feats, dt), *_as_operands(plan_dir))
        want = jax.ops.segment_sum(jnp.asarray(feats)[s], d, rows)
        assert np.array_equal(np.asarray(got), np.asarray(want))
        assert np.array_equal(np.asarray(got),
                              (a if plan_dir is cut.fwd else a.T) @ feats)
    # the closures' VJP, once a part count and transport
    if layout != "slabs":
        return
    deg = jnp.asarray(np.maximum(np.bincount(dst, minlength=n_out), 1),
                      jnp.float32)
    outs = []
    for plan in (one, cut):
        fn = make_bucket_spmm_fn(*_as_operands(plan.fwd),
                                 *_as_operands(plan.bwd), deg, n_src,
                                 chunk_elems=chunk, rem_dtype=rem_dtype)
        y, vjp = jax.vjp(fn, jnp.asarray(x))
        outs.append((np.asarray(y), np.asarray(vjp(jnp.asarray(g))[0])))
    for u, v in zip(*outs):
        np.testing.assert_allclose(v, u, rtol=1e-6, atol=1e-6)


# Yelp's fwd tables (two parts of 358,424 source rows, its F of 512 two
# slabs of the fp8 transport) and Reddit's remainder (one part, one
# slab), as the compiled-program tests describe them: (table shapes a
# part, destination rows, F)
_RESULT_CASES = {
    "yelp": ([[(3, 137_472), (6, 115_168), (19, 2656)],
              [(3, 136_704), (7, 89_408), (19, 6784)]], 716_847, 512),
    "reddit": ([[(90, 55_552), (104, 59_008), (538, 416)]], 232_965, 256),
}


@pytest.mark.parametrize("case", sorted(_RESULT_CASES))
def test_result_bytes_is_what_the_kernel_writes(case):
    """`result_bytes`, read off a direction's tables as the builder keys
    them, is the float32 bytes bucket_aggregate's traced program
    allocates for its sums (one buffer of every part's bucket sums and
    zero row) and writes in its takes (one a part, [n_out, F]): Yelp's
    two parts of two slabs, Reddit's one of one. Nothing runs."""
    from pipegcn_tpu.ops.bucket_spmm import (direction_tables,
                                             part_heights, result_bytes)

    parts, n_out, f = _RESULT_CASES[case]
    sds = jax.ShapeDtypeStruct
    tables = {}
    for p, shapes in enumerate(parts):
        stem = "bkt_fwd" if p == 0 else f"bkt_fwd_p{p}"
        tables[f"{stem}_inv"] = sds((n_out,), jnp.int32)
        tables.update({f"{stem}_{b:02d}": sds(sh, jnp.int32)
                       for b, sh in enumerate(shapes)})
    mats, invs = direction_tables(tables, "bkt_fwd")
    jaxpr = jax.make_jaxpr(lambda x, m, i: bucket_aggregate(x, m, i))(
        sds((n_out, f), jnp.float8_e4m3fn), mats, invs)

    def f32_outs(jx, prims):
        for eqn in jx.eqns:
            if eqn.primitive.name in prims:
                yield from (v.aval for v in eqn.outvars
                            if v.aval.dtype == jnp.float32)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from f32_outs(sub, prims)

    sums = list(f32_outs(jaxpr.jaxpr, {"empty"}))
    takes = [a for a in f32_outs(jaxpr.jaxpr, {"gather"})
             if a.shape[0] == n_out]
    heights = part_heights(mats if len(parts) > 1 else [mats])
    assert [a.shape for a in sums] == [(sum(heights), f)]
    assert [a.shape for a in takes] == [(n_out, f)] * len(parts)
    made = sum(4 * int(np.prod(a.shape)) for a in sums + takes)
    assert result_bytes(tables, "bkt_fwd", f) == made
    assert heights == [sum(r for _, r in shapes) + 1 for shapes in parts]


@pytest.mark.parametrize("n_rows,k", [(232_966, 1), (393_215, 1),
                                      (393_216, 2), (716_848, 2),
                                      (786_431, 3)])
def test_part_count_follows_the_height(n_rows, k):
    """Reddit's 232,966 source rows make one table of 256-byte slab rows
    under GATHER_PART_BYTES, Yelp's 716,848 two; the parts are equal
    within a row, cover every row once, and none is taller than the
    bound holds (its rows and its zero row)."""
    from pipegcn_tpu.ops.bucket_spmm import (GATHER_PART_BYTES, SLAB_BYTES,
                                             part_bounds, source_parts)

    assert source_parts(n_rows) == k
    bounds = part_bounds(n_rows, k)
    assert bounds[0][0] == 0 and bounds[-1][1] == n_rows
    assert all(a[1] == b[0] for a, b in zip(bounds, bounds[1:]))
    rows = [hi - lo for lo, hi in bounds]
    assert max(rows) - min(rows) <= 1
    assert (max(rows) + 1) * SLAB_BYTES <= GATHER_PART_BYTES
    if k > 1:   # and one part fewer would make one too tall
        assert (-(-n_rows // (k - 1)) + 1) * SLAB_BYTES > GATHER_PART_BYTES


def _cut_shards(monkeypatch, rows_a_part=80):
    """_four_shards with the part bound lowered so that a direction's
    source rows are cut into parts of at most `rows_a_part` rows."""
    from pipegcn_tpu.ops import bucket_spmm

    monkeypatch.setattr(bucket_spmm, "GATHER_PART_BYTES",
                        (rows_a_part + 1) * bucket_spmm.SLAB_BYTES)
    return _four_shards()


def _part_neighbours(tables, stem, n_src_rows):
    """_table_neighbours over every part of a direction, the part's
    rows counted from the first source row again."""
    from pipegcn_tpu.ops.bucket_spmm import (_bucket_keys, _part_stems,
                                             part_bounds)

    stems = _part_stems(tables, stem)
    out = {}
    for s, (lo, hi) in zip(stems, part_bounds(n_src_rows, len(stems))):
        own = {k: tables[k] for k in _bucket_keys(tables, s) + [s + "_inv"]}
        for key, nb in _table_neighbours(own, s, hi - lo).items():
            out.setdefault(key, []).extend(lo + v for v in nb)
    return {key: sorted(v) for key, v in out.items() if v}


@pytest.mark.parametrize("direction", ["fwd", "bwd"])
def test_sharded_parts_hold_every_edge_once(direction, monkeypatch):
    """Four shards cut into three parts a direction: each part has its
    own keys, its own fitted ladder and its own inverse permutation over
    every destination row; a row with no edge in a part points at that
    part's zero row; over all parts each device's tables hold exactly
    its edges (validate_bucket_tables counts them), pad_stats reports
    the cut, and every device's closure is the dense mean, forward and
    VJP."""
    from pipegcn_tpu.ops.bucket_spmm import (
        _bucket_keys, _part_stems, bucket_pad_stats,
        build_sharded_bucket_tables, make_device_bucket_spmm_fn,
        part_bounds)

    sg = _cut_shards(monkeypatch)
    n_src_rows = sg.n_max + sg.halo_size
    tabs = build_sharded_bucket_tables(sg)
    stem = f"bkt_{direction}"
    src_rows = n_src_rows if direction == "fwd" else sg.n_max
    stems = _part_stems(tabs, stem)
    assert stems == [stem, f"{stem}_p1", f"{stem}_p2"]
    assert _part_neighbours(tabs, stem, src_rows) == \
        _shard_neighbours(sg, transpose=direction == "bwd")
    pad = bucket_pad_stats(tabs, sg.n_max, n_src_rows)[direction]
    bounds = part_bounds(src_rows, 3)
    assert pad["parts"] == 3 and pad["part_rows"] == max(
        hi - lo for lo, hi in bounds) <= 80
    assert pad["widths"] == [[tabs[k].shape[1] for k in _bucket_keys(tabs, s)]
                             for s in stems]
    real = [sg.edge_dst[r] < sg.n_max for r in range(4)]
    assert pad["edges"] == int(sum(m.sum() for m in real))
    gsrc = sg.edge_src if direction == "fwd" else sg.edge_dst
    gdst = sg.edge_dst if direction == "fwd" else sg.edge_src
    for s, (lo, hi) in zip(stems, bounds):
        zero_row = sum(tabs[k].shape[2] for k in _bucket_keys(tabs, s))
        for r in range(4):
            sel = real[r] & (gsrc[r] >= lo) & (gsrc[r] < hi)
            has = np.zeros(tabs[s + "_inv"].shape[1], bool)
            has[gdst[r][sel]] = True
            assert np.all((tabs[s + "_inv"][r] == zero_row) == ~has)
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.standard_normal((n_src_rows, 8)), jnp.float32)
    c = jnp.asarray(rng.standard_normal((sg.n_max, 8)), jnp.float32)
    for r in range(4):
        a = np.zeros((sg.n_max, n_src_rows))
        np.add.at(a, (sg.edge_dst[r][real[r]], sg.edge_src[r][real[r]]), 1)
        deg = np.maximum(a.sum(axis=1), 1)
        fn = make_device_bucket_spmm_fn(
            {k: jnp.asarray(v[r]) for k, v in tabs.items()},
            jnp.asarray(deg, jnp.float32), n_src_rows)
        y, vjp = jax.vjp(fn, x)
        np.testing.assert_allclose(np.asarray(y),
                                   (a / deg[:, None]) @ np.asarray(x),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(vjp(c)[0]),
                                   (a / deg[:, None]).T @ np.asarray(c),
                                   rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("part", [0, 1, 2])
def test_an_index_past_its_parts_sentinel_raises(part, monkeypatch):
    """An index that is in bounds for the whole source rows but past its
    own part's zero row (the part's row count) is refused by name, as
    is an inverse permutation past the part's own buckets; the part's
    sentinel itself over an edge is a dropped edge, counted."""
    from pipegcn_tpu.ops.bucket_spmm import (
        _bucket_keys, _part_stem, build_sharded_bucket_tables, part_bounds,
        validate_bucket_tables)

    sg = _cut_shards(monkeypatch)
    n_src_rows = sg.n_max + sg.halo_size
    tabs = build_sharded_bucket_tables(sg)
    lo, hi = part_bounds(n_src_rows, 3)[part]
    s = _part_stem("bkt_fwd", part)
    key = _bucket_keys(tabs, s)[0]
    assert hi - lo < n_src_rows
    bad = {k: np.array(v) for k, v in tabs.items()}
    bad[key][0, 0, 0] = hi - lo + 1
    with pytest.raises(ValueError, match=f"{key!r} holds out-of-bounds"):
        validate_bucket_tables(bad, sg.n_max, n_src_rows)
    bad[key][0, 0, 0] = hi - lo
    with pytest.raises(ValueError, match="dropped or overwritten"):
        validate_bucket_tables(bad, sg.n_max, n_src_rows)
    bad[key][0, 0, 0] = tabs[key][0, 0, 0]
    rows = sum(tabs[k].shape[2] for k in _bucket_keys(tabs, s))
    bad[s + "_inv"][0, 0] = rows + 1
    with pytest.raises(ValueError, match="out-of-bounds"):
        validate_bucket_tables(bad, sg.n_max, n_src_rows)


def test_dirty_rebuild_keeps_the_parts_and_the_edges(monkeypatch):
    """The streaming path over a cut direction: the cache keeps a ladder
    a part, a dirty rebuild reuses the clean shards' plans with the
    parts they have, and the tables hold the changed graph's edges."""
    from pipegcn_tpu.ops.bucket_spmm import (_part_stems,
                                             build_sharded_bucket_tables)

    sg = _cut_shards(monkeypatch)
    n_src_rows = sg.n_max + sg.halo_size
    cache = {}
    build_sharded_bucket_tables(sg, plan_cache=cache)
    widths, plans = cache["widths"], list(cache["plans"])
    assert [len(w) for w in widths] == [3, 3]
    deg = np.bincount(sg.edge_dst[2][sg.edge_dst[2] < sg.n_max],
                      minlength=sg.n_max)
    sg.edge_dst[2][np.isin(sg.edge_dst[2], np.nonzero(deg <= 3)[0])] = \
        sg.n_max
    tabs = build_sharded_bucket_tables(sg, plan_cache=cache, dirty=[2])
    assert cache["widths"] == widths
    assert [p is q for p, q in zip(cache["plans"], plans)] == \
        [True, True, False, True]
    assert all(p.fwd.k == p.bwd.k == 3 for p in cache["plans"])
    for d, rows in (("fwd", n_src_rows), ("bwd", sg.n_max)):
        assert len(_part_stems(tabs, f"bkt_{d}")) == 3
        assert _part_neighbours(tabs, f"bkt_{d}", rows) == \
            _shard_neighbours(sg, transpose=d == "bwd")


@pytest.mark.parametrize("rem_dtype", [None, "float8"])
def test_trainer_over_parts_matches_xla(rem_dtype, monkeypatch):
    """The trainer on four devices (halo rows among the forward's source
    rows) with the part bound lowered so that both directions are cut
    into parts: `tables_pad` says so, and the losses are the XLA
    path's, within the transport's rounding."""
    from pipegcn_tpu.ops import bucket_spmm as bs

    g = synthetic_graph(num_nodes=300, avg_degree=7, n_feat=10, n_class=4,
                        seed=21)
    sg = ShardedGraph.build(g, partition_graph(g, 4, seed=0), n_parts=4)
    monkeypatch.setattr(bs, "GATHER_PART_BYTES", 31 * bs.SLAB_BYTES)
    losses = {}
    for impl in ("xla", "bucket"):
        cfg = ModelConfig(layer_sizes=(10, 16, 4), norm="layer",
                          dropout=0.0, train_size=sg.n_train_global,
                          spmm_impl=impl, spmm_chunk=40,
                          rem_dtype=rem_dtype if impl == "bucket" else None)
        t = Trainer(sg, cfg, TrainConfig(seed=4, enable_pipeline=True))
        losses[impl] = [t.train_epoch(e) for e in range(6)]
    n_src_rows = sg.n_max + sg.halo_size
    assert t.tables_pad["fwd"]["parts"] == bs.source_parts(n_src_rows) > 1
    assert t.tables_pad["bwd"]["parts"] == bs.source_parts(sg.n_max) > 1
    np.testing.assert_allclose(losses["xla"], losses["bucket"],
                               rtol=5e-2 if rem_dtype else 2e-4)
