import numpy as np
import jax
import jax.numpy as jnp
import pytest

from pipegcn_tpu.graph import karate_club, synthetic_graph
from pipegcn_tpu.models import ModelConfig, forward, init_norm_state, init_params
from pipegcn_tpu.ops import spmm_mean, spmm_sum


@pytest.fixture(scope="module")
def small_graph():
    return karate_club(n_feat=8)


def _graph_arrays(g):
    """Full-graph edge arrays with one pad edge exercising the sentinel."""
    n = g.num_nodes
    src = np.concatenate([g.src, [0]]).astype(np.int32)
    dst = np.concatenate([g.dst, [n]]).astype(np.int32)  # sentinel
    return jnp.array(src), jnp.array(dst), jnp.array(
        g.ndata["in_deg"].astype(np.float32)
    )


def test_spmm_sum_matches_dense(small_graph):
    g = small_graph
    n = g.num_nodes
    src, dst, deg = _graph_arrays(g)
    x = jnp.array(np.random.default_rng(0).normal(size=(n, 8)).astype(np.float32))
    out = spmm_sum(x, src, dst, n)
    a = np.zeros((n, n), np.float32)
    np.add.at(a, (g.dst, g.src), 1.0)
    np.testing.assert_allclose(out, a @ np.asarray(x), rtol=1e-4, atol=1e-4)


def test_spmm_chunked_matches_unchunked(small_graph):
    g = small_graph
    n = g.num_nodes
    src, dst, deg = _graph_arrays(g)
    x = jnp.array(np.random.default_rng(1).normal(size=(n, 8)).astype(np.float32))
    full = spmm_mean(x, src, dst, deg, n)
    for chunk in (7, 64, 128):
        np.testing.assert_allclose(
            spmm_mean(x, src, dst, deg, n, chunk=chunk), full,
            rtol=1e-4, atol=1e-5,
        )


def test_spmm_gradient(small_graph):
    g = small_graph
    n = g.num_nodes
    src, dst, deg = _graph_arrays(g)
    x = jnp.ones((n, 4), jnp.float32)

    def f(x):
        return spmm_sum(x, src, dst, n).sum()

    grad = jax.grad(f)(x)
    # d/dx_u of sum over edges = out-degree of u (incl. pad edge's src 0
    # being dropped via the sentinel segment)
    np.testing.assert_allclose(
        np.asarray(grad)[:, 0], g.out_degrees().astype(np.float32), rtol=1e-5
    )


def _cfg(g, hidden=16, n_layers=3, **kw):
    n_class = int(g.ndata["label"].max()) + 1
    sizes = (g.ndata["feat"].shape[1],) + (hidden,) * (n_layers - 1) + (n_class,)
    kw.setdefault("train_size", int(g.ndata["train_mask"].sum()))
    return ModelConfig(layer_sizes=sizes, **kw)


def test_init_param_shapes_and_bounds(small_graph):
    cfg = _cfg(small_graph, norm="layer", n_linear=1)
    params = init_params(jax.random.PRNGKey(0), cfg)
    assert len(params["layers"]) == 3
    assert set(params["layers"][0]) == {"w1", "b1", "w2", "b2"}
    assert set(params["layers"][2]) == {"w", "b"}  # linear tail
    assert len(params["norms"]) == 2
    w1 = params["layers"][0]["w1"]
    bound = 1.0 / np.sqrt(w1.shape[0])
    assert float(jnp.abs(w1).max()) <= bound
    assert float(jnp.abs(w1).max()) > 0.5 * bound  # actually spread out


def test_train_eval_parity_no_dropout(small_graph):
    """With dropout=0 and a trivial comm (full graph as one shard), the
    training path must equal the eval path exactly."""
    g = small_graph
    n = g.num_nodes
    src, dst, deg = _graph_arrays(g)
    feat = jnp.array(g.ndata["feat"])
    cfg = _cfg(g, dropout=0.0, norm="layer")
    params = init_params(jax.random.PRNGKey(1), cfg)

    train_out, _ = forward(
        params, cfg, feat, src, dst, deg, n,
        training=True, rng=jax.random.PRNGKey(0),
        comm_update=lambda i, h: h,
    )
    eval_out, _ = forward(
        params, cfg, feat, src, dst, deg, n, training=False,
    )
    np.testing.assert_allclose(train_out, eval_out, rtol=1e-4, atol=1e-5)


def test_use_pp_parity(small_graph):
    """Training with precomputed concat input == eval recomputing the
    first-layer aggregation on the fly (module/layer.py:41-42 vs 58-60)."""
    g = small_graph
    n = g.num_nodes
    src, dst, deg = _graph_arrays(g)
    feat = jnp.array(g.ndata["feat"])
    cfg = _cfg(g, dropout=0.0, norm="layer", use_pp=True)
    params = init_params(jax.random.PRNGKey(2), cfg)

    ah = spmm_mean(feat, src, dst, deg, n)
    pp_input = jnp.concatenate([feat, ah], axis=1)
    train_out, _ = forward(
        params, cfg, pp_input, src, dst, deg, n,
        training=True, rng=jax.random.PRNGKey(0),
        comm_update=lambda i, h: h,
    )
    eval_out, _ = forward(
        params, cfg, feat, src, dst, deg, n, training=False,
        eval_pp_agg=True,
    )
    np.testing.assert_allclose(train_out, eval_out, rtol=1e-4, atol=1e-5)


def test_dropout_changes_output_and_is_seeded(small_graph):
    g = small_graph
    n = g.num_nodes
    src, dst, deg = _graph_arrays(g)
    feat = jnp.array(g.ndata["feat"])
    cfg = _cfg(g, dropout=0.5)
    params = init_params(jax.random.PRNGKey(3), cfg)

    def run(seed):
        out, _ = forward(
            params, cfg, feat, src, dst, deg, n,
            training=True, rng=jax.random.PRNGKey(seed),
            comm_update=lambda i, h: h,
        )
        return np.asarray(out)

    a, b, a2 = run(0), run(1), run(0)
    assert not np.allclose(a, b)
    np.testing.assert_array_equal(a, a2)


def test_sync_batch_norm_single_device(small_graph):
    """psum=identity SyncBN must match plain batch normalization when
    train_size equals the row count."""
    g = small_graph
    n = g.num_nodes
    src, dst, deg = _graph_arrays(g)
    feat = jnp.array(g.ndata["feat"])
    cfg = _cfg(g, dropout=0.0, norm="batch", train_size=n)
    params = init_params(jax.random.PRNGKey(4), cfg)
    state = init_norm_state(cfg)
    assert len(state) == 2

    out, new_state = forward(
        params, cfg, feat, src, dst, deg, n,
        training=True, rng=jax.random.PRNGKey(0),
        comm_update=lambda i, h: h, norm_state=state,
    )
    assert out.shape == (n, 2)
    # running stats moved toward the batch stats (momentum 0.1)
    assert not np.allclose(np.asarray(new_state[0]["mean"]), 0.0)
    # eval path consumes running stats without error
    eval_out, _ = forward(
        params, cfg, feat, src, dst, deg, n, training=False,
        norm_state=new_state,
    )
    assert eval_out.shape == (n, 2)


def test_gradients_flow_everywhere(small_graph):
    g = small_graph
    n = g.num_nodes
    src, dst, deg = _graph_arrays(g)
    feat = jnp.array(g.ndata["feat"])
    labels = jnp.array(g.ndata["label"])
    cfg = _cfg(g, dropout=0.0, norm="layer", n_linear=1)
    params = init_params(jax.random.PRNGKey(5), cfg)

    def loss_fn(p):
        logits, _ = forward(
            p, cfg, feat, src, dst, deg, n,
            training=True, rng=jax.random.PRNGKey(0),
            comm_update=lambda i, h: h,
        )
        onehot = jax.nn.one_hot(labels, logits.shape[-1])
        return -(jax.nn.log_softmax(logits) * onehot).sum()

    grads = jax.grad(loss_fn)(params)
    flat, _ = jax.tree_util.tree_flatten(grads)
    assert all(np.isfinite(np.asarray(x)).all() for x in flat)
    assert all(float(jnp.abs(x).max()) > 0 for x in flat)


def test_spmm_bf16_forward_and_grad_match_f32(small_graph):
    """bf16 spmm_mean: forward within bf16 tolerance of f32; the custom
    VJP accumulates the backward scatter in f32 (cotangents must closely
    match the f32 path, not bf16-accumulation error)."""
    import jax
    import jax.numpy as jnp
    from pipegcn_tpu.ops.spmm import spmm_mean

    g = small_graph
    n = g.num_nodes
    rng = np.random.default_rng(0)
    feat = rng.standard_normal((n, 8)).astype(np.float32)
    order = np.argsort(g.dst, kind="stable")
    es = jnp.asarray(g.src[order].astype(np.int32))
    ed = jnp.asarray(g.dst[order].astype(np.int32))
    deg = jnp.asarray(np.maximum(g.in_degrees(), 1).astype(np.float32))

    def loss32(f):
        return (spmm_mean(f, es, ed, deg, n, None, True) ** 2).sum()

    def loss16(f):
        return (spmm_mean(f.astype(jnp.bfloat16), es, ed, deg, n,
                          None, True) ** 2).sum()

    f32 = jnp.asarray(feat)
    v32, g32 = jax.value_and_grad(loss32)(f32)
    v16, g16 = jax.value_and_grad(loss16)(f32)
    np.testing.assert_allclose(v16, v32, rtol=0.03)
    np.testing.assert_allclose(np.asarray(g16), np.asarray(g32),
                               rtol=0.1, atol=0.02)

    # chunked path agrees with unchunked in bf16
    out_a = spmm_mean(f32.astype(jnp.bfloat16), es, ed, deg, n, None, True)
    out_b = spmm_mean(f32.astype(jnp.bfloat16), es, ed, deg, n, 7, True)
    np.testing.assert_allclose(np.asarray(out_a), np.asarray(out_b),
                               rtol=1e-6)


def test_spmm_bf16_in_deg_cotangent_matches_f32(small_graph):
    """Differentiating through the degrees must give the true cotangent
    -(out*g).sum(-1)/deg on the bf16 custom-VJP path, matching f32
    autodiff (it used to silently return zeros)."""
    import jax
    import jax.numpy as jnp
    from pipegcn_tpu.ops.spmm import spmm_mean

    g = small_graph
    n = g.num_nodes
    rng = np.random.default_rng(3)
    feat = jnp.asarray(rng.standard_normal((n, 8)).astype(np.float32))
    order = np.argsort(g.dst, kind="stable")
    es = jnp.asarray(g.src[order].astype(np.int32))
    ed = jnp.asarray(g.dst[order].astype(np.int32))
    deg0 = jnp.asarray(np.maximum(g.in_degrees(), 1).astype(np.float32))

    def loss32(deg):
        return (spmm_mean(feat, es, ed, deg, n, None, True) ** 2).sum()

    def loss16(deg):
        return (spmm_mean(feat.astype(jnp.bfloat16), es, ed, deg, n,
                          None, True) ** 2).sum()

    gd32 = jax.grad(loss32)(deg0)
    gd16 = jax.grad(loss16)(deg0)
    assert float(jnp.abs(gd32).max()) > 0
    np.testing.assert_allclose(np.asarray(gd16), np.asarray(gd32),
                               rtol=0.1, atol=0.02)


def _dropout_unpinned(rng, h, rate, bits):
    """Dropout as it was written before its mask was pinned: the draw
    and the select in one expression, for XLA to fuse where it likes."""
    if bits == 8:
        thresh = min(max(int(round(rate * 256.0)), 1), 255)
        keep = jax.random.bits(rng, h.shape, jnp.uint8) >= jnp.uint8(thresh)
        return jnp.where(keep, h / (1.0 - thresh / 256.0), 0.0)
    keep = jax.random.bernoulli(rng, 1.0 - rate, h.shape)
    return jnp.where(keep, h / (1.0 - rate), 0.0)


@pytest.mark.parametrize("bits", [32, 8])
@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("rate", [0.1, 0.5, 0.0])
def test_pinned_dropout_is_bit_identical(bits, dtype, rate):
    """The mask drawn once and kept behind a barrier selects the same
    elements and scales them by the same keep probability as the draw
    fused into its consumers: the outputs and the gradients of a
    matmul that reads them, into the input and the weight, are the
    same bits."""
    from pipegcn_tpu.models.sage import _dropout

    dt = jnp.dtype(dtype)
    r = np.random.default_rng(5)
    h = jnp.asarray(r.normal(size=(96, 40)), dt)
    w = jnp.asarray(r.normal(size=(40, 24)), dt)
    key = jax.random.PRNGKey(11)

    def loss(fn):
        def f(h, w):
            y = fn(key, h, rate, bits) if rate > 0 else h
            return jnp.sum(jnp.tanh(y @ w).astype(jnp.float32))
        return jax.jit(jax.value_and_grad(f, argnums=(0, 1)))

    got = loss(_dropout)(h, w)
    want = loss(_dropout_unpinned)(h, w)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(
            np.atleast_1d(np.asarray(a)).view(np.uint8),
            np.atleast_1d(np.asarray(b)).view(np.uint8))
    dropped = jax.jit(lambda h: _dropout(key, h, rate, bits))(h)
    np.testing.assert_array_equal(
        np.asarray(dropped).view(np.uint8),
        np.asarray(jax.jit(
            lambda h: _dropout_unpinned(key, h, rate, bits)
            if rate > 0 else h)(h)).view(np.uint8))


@pytest.mark.parametrize("shape", ["yelp", "reddit", "no-pp", "dropout0"])
def test_dropout_masks_counted(shape):
    """`dropout_masks` counts what `_dropout` pins while the forward
    traces: one mask a dropout layer that takes a gradient, a byte an
    element. Yelp's shape (use_pp, a dense tail of two): 3, layer 0's
    mask drawn inside the fusion that drops the features, layer 1's
    over inner and halo rows, the tail's over inner rows; Reddit's (use_pp, no tail): 3, the
    graph layers' masks over their inner and halo rows; without use_pp
    layer 0 pins too; none at dropout 0, and none in evaluation."""
    from pipegcn_tpu.models.sage import dropout_masks

    n, halo = 200, 24
    cfg = {
        "yelp": ModelConfig(layer_sizes=(30, 64, 64, 64, 10), n_linear=2,
                            use_pp=True, dropout=0.1, dtype="bfloat16"),
        "reddit": ModelConfig(layer_sizes=(60, 32, 32, 32, 8),
                              use_pp=True, dropout=0.5,
                              dtype="bfloat16"),
        "no-pp": ModelConfig(layer_sizes=(30, 32, 32, 8), dropout=0.5),
        "dropout0": ModelConfig(layer_sizes=(30, 64, 64, 64, 10),
                                n_linear=2, use_pp=True, dropout=0.0),
    }[shape]
    pp = 2 if cfg.use_pp else 1
    feat = jax.ShapeDtypeStruct((n, pp * cfg.layer_sizes[0]),
                                cfg.compute_dtype)
    want = {
        "yelp": (3, (n + halo) * 64 + 2 * n * 64),
        "reddit": (3, 3 * (n + halo) * 32),
        "no-pp": (3, (n + halo) * (30 + 32 + 32)),
        "dropout0": (0, 0),
    }[shape]
    assert dropout_masks(cfg, feat, n, halo) == want
    # every `fit` that writes a run header asks again: no second trace
    hits = dropout_masks.cache_info().hits
    assert dropout_masks(cfg, feat, n, halo) == want
    assert dropout_masks.cache_info().hits == hits + 1

    # evaluation draws nothing, whatever the rate
    g = karate_club(n_feat=8)
    src, dst, deg = _graph_arrays(g)
    ecfg = ModelConfig(layer_sizes=(8, 16, 4), dropout=0.5)
    masks = []
    forward(init_params(jax.random.PRNGKey(0), ecfg), ecfg,
            jnp.array(g.ndata["feat"]), src, dst, deg, g.num_nodes,
            training=False, masks=masks)
    assert masks == []
