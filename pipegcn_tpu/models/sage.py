"""GraphSAGE model family — pure-JAX functional implementation.

Behavioral parity with the reference (module/model.py:25-58,
module/layer.py:8-62, module/sync_bn.py:7-56), re-architected for TPU:
parameters are explicit pytrees, communication is an injected callback
(`comm_update`) instead of a process-global buffer singleton
(reference helper/context.py:4-5), and distributed normalization takes an
injected `psum` so the same code runs single-device (psum = identity) and
inside `shard_map` (psum over the mesh axis).

Layer stack (reference module/model.py:29-38): `n_layers - n_linear`
graph layers followed by `n_linear` plain dense layers; LayerNorm or
SyncBatchNorm + activation between all but the last layer. Per-layer
training order (module/model.py:43-57): comm update -> dropout -> layer
-> norm -> activation.

Graph layer semantics (module/layer.py:40-62):
  training:  ah = spmm(fbuf)/in_deg;  h = fbuf[:n_dst] @ W1 + ah @ W2 (+b)
             (first layer under use_pp: h = fbuf @ W (+b), input is the
             precomputed [feat, mean-neighbor-feat] concat of width 2F)
  eval:      same weights on a full homogeneous graph, degrees from the
             graph itself; use_pp layer computes concat(feat, ah) @ W.

Init (module/layer.py:24-36): U(-1/sqrt(fan_in), +1/sqrt(fan_in)) for all
weights and biases (the dense tail's torch default has the same bounds).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from ..ops.spmm import spmm_mean

Params = dict
PsumFn = Callable[[jax.Array], jax.Array]


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    layer_sizes: Tuple[int, ...]   # [in_feat, hidden..., n_class]
    # 'graphsage' (reference parity, module/layer.py) | 'gcn' | 'gat'
    # (framework extensions). GCN: symmetric-normalized convolution,
    # h_i = W Σ_j h_j / sqrt(d_i d_j) with the self-loop already in the
    # finalized graph; reuses every aggregation kernel unchanged — the
    # src-side 1/sqrt(d) scaling happens on the owner BEFORE the halo
    # exchange, the dst side folds into the mean kernel's output
    # (mean * sqrt(d)). GAT: multi-head edge-softmax attention
    # (n_heads); runs on the raw-edge formulation (attention weights
    # are per-edge, so the precomputed unweighted kernel tables do not
    # apply); halo sources attend with their (possibly stale) features,
    # exactly the staleness semantics of the mean path.
    model: str = "graphsage"
    n_heads: int = 4               # GAT attention heads
    leaky_slope: float = 0.2       # GAT LeakyReLU slope
    n_linear: int = 0              # dense tail layers (Yelp uses 2)
    use_pp: bool = False
    norm: Optional[str] = "layer"  # 'layer' | 'batch' | None
    dropout: float = 0.5
    train_size: int = 0            # global n_train (SyncBN divisor, loss)
    spmm_chunk: Optional[int] = None
    sorted_edges: bool = False     # edge_dst ascending (CSR order)
    # 'xla' | 'bucket' | 'block' | 'auto' — must stay in sync with
    # cli/parser.py --spmm-impl and Trainer._setup_spmm; 'auto'
    # resolves from the measured tuning table (ops/tuner.py)
    spmm_impl: str = "xla"
    block_tile: int = 256          # dense-tile edge for spmm_impl='block'
    # minimum edges for a (dst, src) tile to go dense; None = the
    # read-cost break-even tile*tile/n_feat (block_spmm.BlockPlan)
    block_nnz: Optional[int] = None
    # union-gather group size for the block kernel's dense path: that
    # many CONSECUTIVE dst tiles share one gathered source-tile union
    # (block_spmm._group_union; measured F-tile dedupe headroom in
    # docs/PERF_NOTES.md). 1 = per-tile K-class layout
    block_group: int = 1
    # bucket-merge lever (ops/bucket_spmm.fit_widths min_width): no
    # bucket narrower than this, so every lower-degree row joins the
    # first one, trading bounded padding for fewer per-bucket gather
    # launches/transients. 0 = the widths the histogram asks for.
    bucket_merge: int = 0
    # spmm_impl='auto' resolution (ops/tuner.py): True lets a cache
    # miss run the live micro-benchmark campaign; False restricts auto
    # to a persisted tuning table (falling back to the deterministic
    # default kernel when none exists — never a live measurement)
    tune: bool = True
    # edge budget of the tuner's sample of whole destination tile-rows
    # (ops/tuner.py DEFAULT_EDGE_BUDGET)
    tuner_samples: int = 4_000_000
    # gather-transport dtype for the bucket kernel / block remainder /
    # GAT attention kernel's wide value+cotangent gathers
    # (bucket_spmm.transport_dtypes): None = activation dtype;
    # 'float8' = e4m3 activations / e5m2 cotangents — halves gathered
    # rows at F=256 (the gather path is request-rate-bound at 256-byte
    # rows); accumulation stays f32. Casts SATURATE at the fp8 finite
    # max (transport_cast), so raw layer-0 features beyond +-448
    # (use_pp=False / gcn) clamp instead of going NaN; the one-shot
    # metric-bearing paths (pp precompute, sharded eval) are exempt.
    rem_dtype: Optional[str] = None
    # amax-clamped fp8 transport (resilience/numerics guardrail): scale
    # each gathered tensor by a power of two derived from its running
    # amax so the cast lands mid-range in e4m3/e5m2 instead of
    # saturating (or flushing to zero) at the static clamp; the inverse
    # scale is applied after the (linear) aggregation. No-op unless
    # rem_dtype is 'float8'.
    rem_amax: bool = False
    # dropout mask generation width (the RNG floor lever, with
    # --rng-impl): 32 = jax.random.bernoulli (uniform f32 compare,
    # reference parity); 8 = one random BYTE per element compared
    # against round(rate*256) — a quarter of the generated bits and no
    # f32 conversion, at the cost of quantizing the keep probability to
    # 1/256 (invisible at the usual 0.5). Masks differ from 32-bit mode
    # at the same seed (equally valid dropout noise).
    dropout_bits: int = 32
    dtype: str = "float32"         # compute dtype: 'float32' | 'bfloat16'

    def __post_init__(self):
        if self.model not in ("graphsage", "gcn", "gat"):
            raise ValueError(f"unknown model: {self.model}")
        if self.rem_dtype in ("", "none"):
            # ONE sentinel: every consumer sees None for "no transport
            # narrowing" (CLI/bench pass their 'none' strings through)
            object.__setattr__(self, "rem_dtype", None)
        if self.rem_dtype not in (None, "float8", "bfloat16"):
            raise ValueError(
                f"unknown rem_dtype: {self.rem_dtype!r} "
                "(none | bfloat16 | float8)")
        if self.bucket_merge < 0:
            raise ValueError(
                f"bucket_merge must be >= 0, got {self.bucket_merge}")
        if self.dropout_bits not in (8, 32):
            raise ValueError(
                f"dropout_bits must be 8 or 32, got {self.dropout_bits}")
        if self.model in ("gcn", "gat") and self.use_pp:
            # the pp precompute caches SAGE's mean-neighbor concat;
            # gcn/gat first layers aggregate like every other layer
            raise ValueError("use_pp is a GraphSAGE-only optimization")
        if self.model == "gat":
            if self.n_heads < 1:
                raise ValueError(f"n_heads must be >= 1, got "
                                 f"{self.n_heads}")
            if self.spmm_impl not in ("xla", "auto", "bucket"):
                # per-edge attention weights need the attention-bucket
                # kernel (ops/gat_bucket.py); the block tables
                # are unweighted and cannot express them
                raise ValueError(
                    f"spmm_impl={self.spmm_impl!r} does not apply to "
                    f"gat; use 'xla', 'bucket' or 'auto'")
            for i in range(self.n_layers - self.n_linear):
                if i < self.n_layers - 1 \
                        and self.layer_sizes[i + 1] % self.n_heads:
                    raise ValueError(
                        f"gat hidden width {self.layer_sizes[i + 1]} not "
                        f"divisible by n_heads={self.n_heads}")

    @property
    def n_layers(self) -> int:
        return len(self.layer_sizes) - 1

    @property
    def n_graph_layers(self) -> int:
        return self.n_layers - self.n_linear

    @property
    def compute_dtype(self):
        """Mixed precision, TPU style: activations, halo transport and
        SpMM messages flow in bfloat16 (halving HBM gather traffic and
        ICI volume; MXU-native matmuls); parameters, optimizer state,
        normalization statistics, SpMM accumulation and the loss stay
        float32. The reference has no analogue (torch fp32 throughout);
        dtype='float32' reproduces that exactly."""
        if self.dtype == "bfloat16":
            return jnp.bfloat16
        if self.dtype == "float32":
            return jnp.float32
        raise ValueError(f"unknown dtype: {self.dtype}")


def _uniform(rng, shape, bound):
    return jax.random.uniform(
        rng, shape, minval=-bound, maxval=bound, dtype=jnp.float32
    )


def init_params(rng: jax.Array, cfg: ModelConfig) -> Params:
    """Parameter pytree: {'layers': [...], 'norms': [...]}.

    Graph layers hold {'w1','b1','w2','b2'} (or {'w','b'} for the pp first
    layer); dense tail layers hold {'w','b'}; norm entries hold
    {'scale','bias'}. Weights are stored [in, out] (right-multiply).
    """
    layers: List[dict] = []
    norms: List[dict] = []
    use_pp = cfg.use_pp
    for i in range(cfg.n_layers):
        d_in, d_out = cfg.layer_sizes[i], cfg.layer_sizes[i + 1]
        rng, k1, k2, k3, k4 = jax.random.split(rng, 5)
        if i < cfg.n_graph_layers:
            if use_pp and i == 0:
                bound = 1.0 / (2 * d_in) ** 0.5
                layers.append({
                    "w": _uniform(k1, (2 * d_in, d_out), bound),
                    "b": _uniform(k2, (d_out,), bound),
                })
            elif cfg.model == "gcn":
                bound = 1.0 / d_in ** 0.5
                layers.append({
                    "w": _uniform(k1, (d_in, d_out), bound),
                    "b": _uniform(k2, (d_out,), bound),
                })
            elif cfg.model == "gat":
                # hidden layers concat H heads of d_out/H; a final graph
                # layer (producing logits) averages H heads of d_out
                h_ = cfg.n_heads
                dh = d_out if i == cfg.n_layers - 1 else d_out // h_
                bound = 1.0 / d_in ** 0.5
                layers.append({
                    "w": _uniform(k1, (d_in, h_ * dh), bound),
                    "b": _uniform(k2, (d_out,), bound),
                    "a_src": _uniform(k3, (h_, dh), 1.0 / dh ** 0.5),
                    "a_dst": _uniform(k4, (h_, dh), 1.0 / dh ** 0.5),
                })
            else:
                bound = 1.0 / d_in ** 0.5
                layers.append({
                    "w1": _uniform(k1, (d_in, d_out), bound),
                    "b1": _uniform(k2, (d_out,), bound),
                    "w2": _uniform(k3, (d_in, d_out), bound),
                    "b2": _uniform(k4, (d_out,), bound),
                })
        else:
            bound = 1.0 / d_in ** 0.5
            layers.append({
                "w": _uniform(k1, (d_in, d_out), bound),
                "b": _uniform(k2, (d_out,), bound),
            })
        if i < cfg.n_layers - 1 and cfg.norm is not None:
            norms.append({
                "scale": jnp.ones((d_out,), jnp.float32),
                "bias": jnp.zeros((d_out,), jnp.float32),
            })
    return {"layers": layers, "norms": norms}


def init_norm_state(cfg: ModelConfig) -> List[dict]:
    """Running mean/var for SyncBatchNorm (reference sync_bn.py:44-47);
    empty list unless norm == 'batch'."""
    if cfg.norm != "batch":
        return []
    return [
        {
            "mean": jnp.zeros((cfg.layer_sizes[i + 1],), jnp.float32),
            "var": jnp.ones((cfg.layer_sizes[i + 1],), jnp.float32),
        }
        for i in range(cfg.n_layers - 1)
    ]


def _layer_norm(h, scale, bias, eps=1e-5):
    # statistics in f32 even when activations flow in bf16
    hf = h.astype(jnp.float32)
    mu = hf.mean(axis=-1, keepdims=True)
    var = ((hf - mu) ** 2).mean(axis=-1, keepdims=True)
    out = (hf - mu) * jax.lax.rsqrt(var + eps) * scale + bias
    return out.astype(h.dtype)


def _sync_batch_norm_train(h, scale, bias, state, whole_size, psum,
                           row_mask=None, momentum=0.1, eps=1e-5):
    """Distributed BN over all rows across devices (reference
    sync_bn.py:13-22): statistics = psum of per-device sums divided by the
    global train size. `row_mask` excludes padded rows, whose values are
    nonzero layer outputs here (the reference has no padding; its rows are
    exactly the inner nodes).

    Intentional deviation: the reference all-reduces dweight/dbias inside
    the BN backward (sync_bn.py:35-36) AND again in the per-parameter
    reduce hook (reducer.py:30), making BN affine gradients P times the
    true distributed gradient. Here autodiff + the single grad psum yield
    the mathematically correct gradient (no double reduction).

    Returns (out, new_state)."""
    orig_dtype = h.dtype
    h = h.astype(jnp.float32)
    hm = h if row_mask is None else h * row_mask[:, None]
    sum_x = psum(hm.sum(axis=0))
    sum_x2 = psum((hm * hm).sum(axis=0))
    mean = sum_x / whole_size
    # Robustness deviation: the reference divides by the global TRAIN
    # size while summing over ALL local rows (sync_bn.py:19-20 with
    # model.py:38's train_size) — fine inductively (rows == train
    # nodes), but transductively rows > whole_size overscales `mean`,
    # and sum_x2 - mean*sum_x can then go NEGATIVE -> rsqrt(neg) -> NaN
    # (unexercised in the reference: no script selects --norm batch).
    # Clamping to >= 0 preserves exact parity whenever the reference
    # formula is well-posed and keeps training finite where it isn't.
    var = jnp.maximum((sum_x2 - mean * sum_x) / whole_size, 0.0)
    new_state = {
        "mean": state["mean"] * (1 - momentum) + mean * momentum,
        "var": state["var"] * (1 - momentum) + var * momentum,
    }
    x_hat = (h - mean) * jax.lax.rsqrt(var + eps)
    return (x_hat * scale + bias).astype(orig_dtype), new_state


def _sync_batch_norm_eval(h, scale, bias, state, eps=1e-5):
    hf = h.astype(jnp.float32)
    x_hat = (hf - state["mean"]) * jax.lax.rsqrt(state["var"] + eps)
    return (x_hat * scale + bias).astype(h.dtype)


def _gat_layer(fbuf, lp, edge_src, edge_dst, n_dst, n_heads, slope,
               is_last, out_dtype, chunk=None, gat_fn=None):
    """Multi-head edge-softmax attention aggregation.

    fbuf: [R, d_in] source rows (halo included). Returns [n_dst, d_out]
    — heads concatenated on hidden layers, averaged on a final (logits)
    layer. Attention statistics and all segment accumulations run in
    f32 regardless of the compute dtype; a final (logits) layer
    accumulates its matmul in f32 like dense() does.

    With `gat_fn` (the scatter-free attention-bucket kernel closure,
    ops/gat_bucket.make_device_gat_fn) the aggregation runs through
    precomputed bucket tables; otherwise over the raw edge list (pad
    edges carry dst == n_dst and fall into a discarded sentinel
    segment). `chunk` (cfg.spmm_chunk) bounds the raw path's per-pass
    edge intermediates the way spmm_mean's chunking does."""
    h_ = n_heads
    with jax.named_scope("dense"):
        z = jnp.matmul(fbuf, lp["w"].astype(fbuf.dtype),
                       preferred_element_type=jnp.float32 if is_last
                       else fbuf.dtype)
    dh = z.shape[-1] // h_
    z = z.reshape(-1, h_, dh)
    zf = z.astype(jnp.float32)
    el = (zf * lp["a_src"]).sum(-1)                    # [R, H]
    er = (zf[:n_dst] * lp["a_dst"]).sum(-1)            # [n_dst, H]

    if gat_fn is not None:
        with jax.named_scope("spmm"):
            out = gat_fn(z, el, er)                    # [n_dst, H, dh]
        out = out.mean(axis=1) if is_last \
            else out.reshape(n_dst, h_ * dh)
        return out.astype(out_dtype) + lp["b"].astype(out_dtype)

    er = jnp.concatenate([er, jnp.zeros((1, h_), jnp.float32)])
    n_seg = n_dst + 1
    e_cnt = edge_src.shape[0]

    # One code path: the unchunked case is a single chunk. Each pass
    # recomputes the cheap [E, H] logits; the expensive part (the
    # z[src] message gather) happens once, in the final pass.
    if not chunk or chunk >= e_cnt:
        chunk = max(e_cnt, 1)
    n_chunks = -(-e_cnt // chunk)
    pad = n_chunks * chunk - e_cnt
    # pad edges: dst -> sentinel segment, src -> row 0 (finite)
    es_p = jnp.pad(edge_src, (0, pad)).reshape(n_chunks, chunk)
    ed_p = jnp.pad(edge_dst, (0, pad),
                   constant_values=n_dst).reshape(n_chunks, chunk)

    def logits(es, ed):
        return jax.nn.leaky_relu(el[es] + er[ed], slope)  # [chunk, H]

    # carry inits must share the body outputs' device-varying type
    # under shard_map: a literal constant is 'unvarying' and scan
    # rejects the mismatch, so seed them with a varying zero
    vzero = el[:1].sum() * 0.0

    def max_body(m_acc, idx):
        m = jax.ops.segment_max(logits(*idx), idx[1], n_seg)
        return jnp.maximum(m_acc, m), None

    m, _ = jax.lax.scan(
        max_body, jnp.full((n_seg, h_), -jnp.inf, jnp.float32) + vzero,
        (es_p, ed_p))
    m = jnp.where(jnp.isfinite(m), m, 0.0)  # empty segments

    def sum_body(s_acc, idx):
        es, ed = idx
        ex = jnp.exp(logits(es, ed) - m[ed])
        return s_acc + jax.ops.segment_sum(ex, ed, n_seg), None

    s, _ = jax.lax.scan(
        sum_body, jnp.zeros((n_seg, h_), jnp.float32) + vzero,
        (es_p, ed_p))

    def out_body(o_acc, idx):
        es, ed = idx
        alpha = jnp.exp(logits(es, ed) - m[ed]) \
            / jnp.maximum(s[ed], 1e-16)
        msg = z[es].astype(jnp.float32) * alpha[..., None]
        return o_acc + jax.ops.segment_sum(msg, ed, n_seg), None

    out, _ = jax.lax.scan(
        out_body, jnp.zeros((n_seg, h_, dh), jnp.float32) + vzero,
        (es_p, ed_p))
    out = out[:n_dst]
    out = out.mean(axis=1) if is_last else out.reshape(n_dst, h_ * dh)
    return out.astype(out_dtype) + lp["b"].astype(out_dtype)


def _dropout(rng, h, rate, bits: int = 32, pin: bool = True,
             masks: Optional[List[int]] = None):
    """Inverted dropout of `h` at `rate`, the mask drawn from `rng`.

    With `pin` the mask is drawn ONCE and stored (a byte an element)
    behind an optimization barrier, and every consumer reads it: the
    forward's matmul or the aggregation's transport cast, and the
    backward's dW and dX. Without the barrier XLA fuses the threefry
    rounds into each consumer's fusion and re-derives the same bits
    there (docs/PERF_NOTES.md "A threefry mask is re-derived in every
    consumer fusion"). A mask with one consumer is cheaper drawn inside
    it: `pin=False`. Each pinned mask appends its bytes to `masks`,
    while the forward traces."""
    if rate <= 0.0:
        return h
    # named scope: the RNG + mask traffic show up as their own phase in
    # profiler traces / anatomy records (the floor terms --rng-impl rbg
    # and --dropout-bits 8 target)
    with jax.named_scope("dropout"):
        if bits == 8:
            # one random byte per element: keep iff byte >= thresh,
            # drop probability thresh/256 — the inverse scale uses the
            # QUANTIZED keep probability so the mask stays unbiased
            thresh = int(round(rate * 256.0))
            thresh = min(max(thresh, 1), 255)
            keep = jax.random.bits(rng, h.shape, jnp.uint8) >= jnp.uint8(
                thresh)
            keep_p = 1.0 - thresh / 256.0
        else:
            keep = jax.random.bernoulli(rng, 1.0 - rate, h.shape)
            keep_p = 1.0 - rate
        if pin:
            keep = jax.lax.optimization_barrier(keep)
            if masks is not None:
                masks.append(keep.size * keep.dtype.itemsize)
        return jnp.where(keep, h / keep_p, 0.0)


@functools.lru_cache(maxsize=32)
def dropout_masks(cfg: ModelConfig, feat: jax.ShapeDtypeStruct, n_dst: int,
                  halo_rows: int = 0) -> Tuple[int, int]:
    """(masks, bytes) of the dropout masks one training step draws once
    and keeps for its backward, as `_dropout` counts them while the
    forward traces at one device's shapes: `feat` its input, `n_dst`
    its rows, `halo_rows` the rows the exchange appends. Nothing
    compiles or runs (jax.eval_shape); the exchange and the aggregation
    stand in by the shapes they return. Cached: a trace costs the host
    about 10 ms, and every `fit` call that writes a run header asks."""
    masks: List[int] = []

    def trace(params, feat):
        return forward(
            params, cfg, feat, None, None,
            jnp.ones((n_dst,), jnp.float32), n_dst, training=True,
            rng=jax.random.PRNGKey(0),
            comm_update=lambda i, h: jnp.concatenate(
                [h, jnp.zeros((halo_rows, h.shape[1]), h.dtype)]),
            spmm_fn=lambda h: h[:n_dst].astype(jnp.float32),
            gat_fn=lambda z, el, er: z[:n_dst].astype(jnp.float32),
            masks=masks)

    params = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    jax.eval_shape(trace, params, feat)
    return len(masks), sum(masks)


def forward(
    params: Params,
    cfg: ModelConfig,
    h: jax.Array,
    edge_src: jax.Array,
    edge_dst: jax.Array,
    in_deg: jax.Array,
    n_dst: int,
    *,
    training: bool,
    rng: Optional[jax.Array] = None,
    comm_update: Optional[Callable[[int, jax.Array], jax.Array]] = None,
    norm_state: Optional[List[dict]] = None,
    psum: PsumFn = lambda x: x,
    eval_pp_agg: bool = False,
    row_mask: Optional[jax.Array] = None,
    spmm_fn: Optional[Callable[[jax.Array], jax.Array]] = None,
    gat_fn: Optional[Callable[..., jax.Array]] = None,
    halo_eval: bool = False,
    probe: Optional[Callable[[str, jax.Array], None]] = None,
    masks: Optional[List[int]] = None,
) -> Tuple[jax.Array, List[dict]]:
    """Run the GraphSAGE stack; returns (logits [n_dst, n_class],
    updated norm_state).

    Training (`training=True`): `comm_update(i, h)` must return the
    aggregation source buffer (inner rows + halo rows) for graph layer i;
    it is skipped for layer 0 under use_pp (reference model.py:45-46).
    `in_deg` are the precomputed full-graph degrees.

    Eval (`training=False`): the graph is the full homogeneous graph
    (edge_src == edge_dst space, no halo), `in_deg` its own degrees, no
    dropout, running stats for BN. `eval_pp_agg=True` makes the first
    layer compute concat(feat, ah) @ W (use_pp eval path,
    module/layer.py:58-60).

    Sharded eval (`training=False, halo_eval=True`): the reference
    evaluates the full graph on one host (train.py:20-61); this mode
    instead evaluates through the partitioned layout — `comm_update`
    provides the synchronous halo exchange (no staleness), the feature
    input is the per-device shard (under use_pp: the precomputed concat,
    so layer 0 is a plain dense like in training) — with eval semantics
    everywhere else (no dropout, BN running stats). No single device
    ever materializes the full graph.

    `probe(phase, array)` (optional) is the numerics tripwire hook
    (resilience/numerics.py PHASES): called with each phase's output
    tensor so the caller can fold cheap in-graph finiteness counts into
    the step metrics. Phases emitted here: input / halo_concat / spmm /
    dense / norm / logits; loss and grads are the caller's to probe.

    `masks` (optional) gets the bytes of each dropout mask the training
    forward draws once and keeps for its backward (`_dropout`).
    """
    if probe is None:
        probe = lambda _name, _x: None  # noqa: E731 — trivial no-op
    norm_state = norm_state if norm_state is not None else []
    new_norm_state: List[dict] = []
    use_norm = cfg.norm is not None
    cdt = cfg.compute_dtype
    h = h.astype(cdt)
    probe("input", h)

    def dense(x, w, b, out_dtype):
        # params live in f32; cast to the compute dtype at use so the
        # matmul runs on the MXU in bf16 (the cast's transpose returns
        # f32 parameter cotangents automatically). out_dtype=f32 (the
        # logits layer) accumulates AND emits f32 from the bf16 matmul
        # via preferred_element_type, then adds the f32 bias — the
        # product is never rounded to bf16.
        with jax.named_scope("dense"):
            y = jnp.matmul(x, w.astype(x.dtype),
                           preferred_element_type=out_dtype)
            return y + b.astype(out_dtype)

    def dense2(x, ah, lp, out_dtype, n_rows=None):
        # the SAGE update x[:n_rows] @ w1 + mean @ w2: the slice of the
        # inner rows, the cast of the mean and the sum of the two
        # products are dense work too
        with jax.named_scope("dense"):
            x = x if n_rows is None else x[:n_rows]
            y = (jnp.matmul(x, lp["w1"].astype(x.dtype),
                            preferred_element_type=out_dtype)
                 + lp["b1"].astype(out_dtype))
            ah = ah.astype(cdt)
            return y + (jnp.matmul(ah, lp["w2"].astype(ah.dtype),
                                   preferred_element_type=out_dtype)
                        + lp["b2"].astype(out_dtype))

    for i in range(cfg.n_layers):
      # named scope per layer: forward ops (and the backward ops XLA
      # derives from them) show up as "layer{i}/..." in profiler
      # traces instead of anonymous fusions (obs subsystem contract)
      with jax.named_scope(f"layer{i}"):
        is_graph = i < cfg.n_graph_layers
        # the network's last matmul produces logits in f32 for a stable
        # loss; hidden layers stay in the compute dtype
        out_dt = jnp.float32 if i == cfg.n_layers - 1 else cdt
        if training and cfg.dropout > 0:
            with jax.named_scope("dropout"):
                rng, sub = jax.random.split(rng)
        if is_graph:
            is_gcn = cfg.model == "gcn"
            is_gat = cfg.model == "gat"
            if is_gcn:
                # src-side symmetric normalization h_j / sqrt(d_j),
                # applied while every row is still on its owner (so the
                # halo exchange ships already-scaled values and halo
                # degrees are never needed); for full-graph eval the
                # rows ARE all the sources. d = full-graph in-degree of
                # A + I on both endpoints (the PyG gcn_norm convention).
                d_sqrt = jnp.sqrt(in_deg.astype(jnp.float32))
                h = (h.astype(jnp.float32)
                     / d_sqrt[: h.shape[0], None]).astype(cdt)
            if training or halo_eval:
                if (i > 0 or not cfg.use_pp) and comm_update is not None:
                    h = comm_update(i, h)
                    probe("halo_concat", h)
                if training and cfg.dropout > 0:
                    # under use_pp layer 0 drops the precomputed
                    # features, which take no gradient: XLA writes the
                    # dropped features once, from the draw's own fusion,
                    # and a stored mask would only add a write
                    h = _dropout(sub, h, cfg.dropout, cfg.dropout_bits,
                                 pin=not (cfg.use_pp and i == 0),
                                 masks=masks)
                lp = params["layers"][i]
                if cfg.use_pp and i == 0:
                    h = dense(h, lp["w"], lp["b"], out_dt)
                elif is_gat:
                    h = _gat_layer(h, lp, edge_src, edge_dst, n_dst,
                                   cfg.n_heads, cfg.leaky_slope,
                                   i == cfg.n_layers - 1, out_dt,
                                   chunk=cfg.spmm_chunk, gat_fn=gat_fn)
                else:
                    # spmm_fn (the bucket/block table kernels)
                    # returns the mean directly when injected
                    with jax.named_scope("spmm"):
                        if spmm_fn is not None:
                            ah = spmm_fn(h)
                        else:
                            ah = spmm_mean(h, edge_src, edge_dst,
                                           in_deg, n_dst,
                                           cfg.spmm_chunk,
                                           cfg.sorted_edges)
                    probe("spmm", ah)
                    if is_gcn:
                        # mean * sqrt(d_i) = (Σ_j h_j/sqrt(d_j))/sqrt(d_i)
                        ah = ah.astype(jnp.float32) * d_sqrt[:, None]
                        h = dense(ah.astype(cdt), lp["w"], lp["b"],
                                  out_dt)
                    else:
                        h = dense2(h, ah, lp, out_dt, n_dst)
            elif is_gat:
                lp = params["layers"][i]
                h = _gat_layer(h, lp, edge_src, edge_dst, n_dst,
                               cfg.n_heads, cfg.leaky_slope,
                               i == cfg.n_layers - 1, out_dt,
                               chunk=cfg.spmm_chunk)
            else:
                lp = params["layers"][i]
                with jax.named_scope("spmm"):
                    ah = spmm_mean(h, edge_src, edge_dst, in_deg, n_dst,
                                   cfg.spmm_chunk, cfg.sorted_edges)
                if is_gcn:
                    ah = ah.astype(jnp.float32) * d_sqrt[:, None]
                    h = dense(ah.astype(cdt), lp["w"], lp["b"], out_dt)
                elif cfg.use_pp and i == 0:
                    if not eval_pp_agg:
                        raise ValueError(
                            "use_pp model evaluated without eval_pp_agg"
                        )
                    h = dense(jnp.concatenate([h, ah.astype(cdt)], axis=1),
                              lp["w"], lp["b"], out_dt)
                else:
                    h = dense2(h, ah, lp, out_dt)
        else:
            if training and cfg.dropout > 0:
                h = _dropout(sub, h, cfg.dropout, cfg.dropout_bits,
                             masks=masks)
            lp = params["layers"][i]
            h = dense(h, lp["w"], lp["b"], out_dt)

        # one probe per layer output: the final layer's is the logits
        # phase, every other layer's the dense phase (aggregated over
        # layers by the collector — provenance wants the phase, the
        # per-layer split would only bloat the record)
        probe("logits" if i == cfg.n_layers - 1 else "dense", h)

        if i < cfg.n_layers - 1:
            if use_norm:
              with jax.named_scope("norm"):
                np_ = params["norms"][i]
                if cfg.norm == "layer":
                    h = _layer_norm(h, np_["scale"], np_["bias"])
                else:  # batch
                    if training:
                        h, ns = _sync_batch_norm_train(
                            h, np_["scale"], np_["bias"], norm_state[i],
                            cfg.train_size, psum, row_mask,
                        )
                        new_norm_state.append(ns)
                    else:
                        h = _sync_batch_norm_eval(
                            h, np_["scale"], np_["bias"], norm_state[i]
                        )
                probe("norm", h)
                # the activation is one elementwise pass with the norm
                h = jax.nn.relu(h)
            else:
                h = jax.nn.relu(h)

    if training and cfg.norm == "batch":
        return h, new_norm_state
    return h, norm_state
