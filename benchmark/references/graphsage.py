"""The plain reference of a training cell: GraphSAGE (mean aggregation,
optional precomputed first aggregation, dense tail, layer norm, dropout),
softmax or multi-label sigmoid cross-entropy summed over the training rows
and divided by their number, and Adam, in float32 `jax.numpy` with every
matrix product at `highest` precision. It follows the published PipeGCN
model (module/layer.py, module/model.py, train.py of the reference
repository) and imports nothing of the program under test.

It makes its own weights from the seed. What it shares with the program is
what the configuration and the seed fix: the initialisation scheme (uniform
+-1/sqrt(fan in), keys split five ways per layer from `PRNGKey(seed)`), the
dropout stream (`fold_in(fold_in(PRNGKey(seed + 17), epoch), 0)`, one split
per layer, one Bernoulli draw of the layer input's shape), and the order of
the rows the program feeds (a permutation of node ids, passed in as
`row_of_node`, so that row i of a mask lands on the same node).

Aggregation is a gather over a padded in-neighbour table, in row blocks so
that it fits beside the saved activations; its backward pass applies the
same table to the cotangent, which is exact because the edge set is its own
transpose (`graphgen.build_ell` refuses any other).

`quant` is the control's hook: the reference computed in the precision below
the configuration's (fp8 e4m3 with a per-tensor scale, for a bfloat16
configuration) rounds through it wherever the program rounds to bfloat16.
`train_rows` plants the half-batch fault for the tests and the readings.

`follow` is the interface the harness calls; a cell whose job names
another reference (`"reference": "<name>"` -> `references/<name>.py`)
brings a file with the same function.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class RefConfig:
    layer_sizes: Tuple[int, ...]   # in_feat, hidden..., n_class
    n_linear: int
    use_pp: bool
    dropout: float
    lr: float
    multilabel: bool
    norm: Optional[str] = "layer"

    @property
    def n_layers(self) -> int:
        return len(self.layer_sizes) - 1

    @property
    def n_graph_layers(self) -> int:
        return self.n_layers - self.n_linear


def _uniform(key, shape, bound):
    return jax.random.uniform(key, shape, minval=-bound, maxval=bound,
                              dtype=jnp.float32)


def init_params(seed: int, cfg: RefConfig) -> dict:
    rng = jax.random.PRNGKey(seed)
    layers, norms = [], []
    for i in range(cfg.n_layers):
        d_in, d_out = cfg.layer_sizes[i], cfg.layer_sizes[i + 1]
        rng, k1, k2, k3, k4 = jax.random.split(rng, 5)
        if i < cfg.n_graph_layers and cfg.use_pp and i == 0:
            bound = 1.0 / (2 * d_in) ** 0.5
            layers.append({"w": _uniform(k1, (2 * d_in, d_out), bound),
                           "b": _uniform(k2, (d_out,), bound)})
        elif i < cfg.n_graph_layers:
            bound = 1.0 / d_in ** 0.5
            layers.append({"w1": _uniform(k1, (d_in, d_out), bound),
                           "b1": _uniform(k2, (d_out,), bound),
                           "w2": _uniform(k3, (d_in, d_out), bound),
                           "b2": _uniform(k4, (d_out,), bound)})
        else:
            bound = 1.0 / d_in ** 0.5
            layers.append({"w": _uniform(k1, (d_in, d_out), bound),
                           "b": _uniform(k2, (d_out,), bound)})
        if i < cfg.n_layers - 1 and cfg.norm is not None:
            norms.append({"scale": jnp.ones((d_out,), jnp.float32),
                          "bias": jnp.zeros((d_out,), jnp.float32)})
    return {"layers": layers, "norms": norms}


def quant_fp8(x):
    """The control: fp8 e4m3 with a per-tensor scale to the largest
    magnitude, cotangent too, as a PR that drops a precision would write
    it. (A plain cast lets the cotangents of a loss averaged over 10^5
    rows underflow to 0, which reads as a step that changes nothing and
    not as a precision.)"""
    @jax.custom_vjp
    def q(x):
        return _q8(x)

    q.defvjp(lambda x: (_q8(x), None), lambda _, g: (_q8(g),))
    return q(x)


def _q8(x):
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def quant_bf16(x):
    """Round through bfloat16, cotangent too (used by the tests: the
    reference moved to the configuration's own precision must pass)."""
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def _block_rows(n_cols: int, width: int) -> int:
    rows = max((48 << 20) // max(n_cols * width, 1), 8)
    return 1 << (rows.bit_length() - 1)


def ell_sum(h, ell):
    """out[i] = sum over j of h[ell[i, j]], padding entries adding 0."""
    n, width = h.shape
    block = min(_block_rows(ell.shape[1], width), 1 << 14)
    pad = (-n) % block
    idx = jnp.pad(ell, ((0, pad), (0, 0)), constant_values=n)
    hx = jnp.concatenate([h, jnp.zeros((1, width), h.dtype)])
    out = jax.lax.map(lambda rows: hx[rows].sum(axis=1),
                      idx.reshape(-1, block, ell.shape[1]))
    return out.reshape(-1, width)[:n]


@jax.custom_vjp
def mean_agg(h, ell, deg):
    return ell_sum(h, ell) / deg[:, None]


def _mean_agg_fwd(h, ell, deg):
    return mean_agg(h, ell, deg), (ell, deg)


def _mean_agg_bwd(res, g):
    ell, deg = res
    return ell_sum(g / deg[:, None], ell), None, None


mean_agg.defvjp(_mean_agg_fwd, _mean_agg_bwd)


def precompute_input(feat, ell, deg):
    """use_pp: concat(feat, mean of the in-neighbours' feat), once."""
    return jnp.concatenate([feat, mean_agg(feat, ell, deg)], axis=1)


def _dense(x, w, b, quant):
    return jnp.matmul(x, quant(w), precision=HIGHEST) + b


def _layer_norm(h, scale, bias, eps=1e-5):
    mu = h.mean(axis=-1, keepdims=True)
    var = ((h - mu) ** 2).mean(axis=-1, keepdims=True)
    return (h - mu) * jax.lax.rsqrt(var + eps) * scale + bias


# saved activations above this many bytes are recomputed in the backward
# pass, layer by layer, so that the reference fits beside the graph
REMAT_ABOVE_BYTES = 6e9


def forward(params, cfg: RefConfig, x, ell, deg, rng, row_of_node,
            n_rows: int, quant: Callable = lambda v: v):
    """Logits [N, n_class] of one training pass. `rng` is the epoch's
    key; `n_rows` the number of rows of the program's feed (its padded
    row count), which fixes the dropout draw."""
    remat = (x.shape[0] * max(cfg.layer_sizes[1:]) * 4 * 5 * cfg.n_layers
             > REMAT_ABOVE_BYTES)
    h = x
    for i in range(cfg.n_layers):
        last = i == cfg.n_layers - 1

        def layer(h, lp, np_, sub, i=i, last=last):
            if cfg.dropout > 0:
                keep = jax.random.bernoulli(
                    sub, 1.0 - cfg.dropout, (n_rows, h.shape[1]))
                h = jnp.where(keep[row_of_node], h / (1.0 - cfg.dropout),
                              0.0)
            if i < cfg.n_graph_layers and not (cfg.use_pp and i == 0):
                ah = quant(mean_agg(h, ell, deg))
                h = (_dense(h, lp["w1"], lp["b1"], quant)
                     + _dense(ah, lp["w2"], lp["b2"], quant))
            else:
                h = _dense(h, lp["w"], lp["b"], quant)
            if last:
                return h
            h = quant(h)
            if cfg.norm is not None:
                h = quant(_layer_norm(h, np_["scale"], np_["bias"]))
            return jax.nn.relu(h)

        sub = None
        if cfg.dropout > 0:
            rng, sub = jax.random.split(rng)
        np_ = params["norms"][i] if (not last and cfg.norm) else None
        h = (jax.checkpoint(layer) if remat else layer)(
            h, params["layers"][i], np_, sub)
    return h


def loss_fn(params, cfg, x, label, train_mask, n_train, ell, deg, rng,
            row_of_node, n_rows, quant):
    logits = forward(params, cfg, x, ell, deg, rng, row_of_node, n_rows,
                     quant)
    if cfg.multilabel:
        per = (jnp.maximum(logits, 0.0) - logits * label
               + jnp.log1p(jnp.exp(-jnp.abs(logits)))).sum(axis=1)
    else:
        logp = jax.nn.log_softmax(logits, axis=-1)
        per = -jnp.take_along_axis(logp, label[:, None], axis=-1)[:, 0]
    return (per * train_mask).sum() / n_train


def adam_step(params, mu, nu, grads, t, lr, b1=0.9, b2=0.999, eps=1e-8):
    tm = jax.tree_util.tree_map
    mu = tm(lambda m, g: b1 * m + (1 - b1) * g, mu, grads)
    nu = tm(lambda v, g: b2 * v + (1 - b2) * g * g, nu, grads)
    bc1, bc2 = 1 - b1 ** t, 1 - b2 ** t
    params = tm(lambda p, m, v: p - lr * (m / bc1)
                / (jnp.sqrt(v / bc2) + eps), params, mu, nu)
    return params, mu, nu


def train(cfg: RefConfig, seed: int, graph: dict, row_of_node, n_rows: int,
          n_steps: int, quant: Callable = lambda v: v,
          train_rows: Optional[np.ndarray] = None,
          first_epoch: int = 0) -> dict:
    """Follow the first `n_steps` steps from the seed. Returns the loss
    and the gradient's global norm of each step, the first gradient, and
    the parameters, first and second moments after the last step, as
    numpy."""
    ell = jnp.asarray(graph["ell"])
    deg = jnp.asarray(graph["deg"])
    mask = graph["train_mask"] if train_rows is None else train_rows
    n_train = float(mask.sum())
    mask = jnp.asarray(mask.astype(np.float32))
    label = jnp.asarray(graph["label"] if cfg.multilabel
                        else graph["label"].astype(np.int32))
    row_of_node = jnp.asarray(np.asarray(row_of_node, np.int32))
    x = jnp.asarray(graph["feat"], jnp.float32)
    if cfg.use_pp:
        x = jax.jit(precompute_input)(x, ell, deg)
    x = jax.jit(quant)(x)

    # the graph rides as arguments: closed over, its gigabytes would be
    # baked into the lowered program as constants
    data = {"x": x, "label": label, "mask": mask, "ell": ell, "deg": deg,
            "row_of_node": row_of_node}

    @jax.jit
    def step(params, mu, nu, t, rng, d):
        loss, grads = jax.value_and_grad(loss_fn)(
            params, cfg, d["x"], d["label"], d["mask"], n_train, d["ell"],
            d["deg"], rng, d["row_of_node"], n_rows, quant)
        gnorm = jnp.sqrt(sum(jnp.sum(g * g)
                             for g in jax.tree_util.tree_leaves(grads)))
        new = adam_step(params, mu, nu, grads, t, cfg.lr)
        return new, loss, gnorm, grads

    params = init_params(seed, cfg)
    init = jax.device_get(params)
    zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
    mu, nu = zeros, zeros
    base = jax.random.PRNGKey(seed + 17)
    losses, gnorms, first_grad = [], [], None
    for k in range(n_steps):
        rng = jax.random.fold_in(jax.random.fold_in(base, first_epoch + k),
                                 0)
        (params, mu, nu), loss, gnorm, grads = step(
            params, mu, nu, jnp.float32(k + 1), rng, data)
        if k == 0:
            first_grad = jax.device_get(grads)
        losses.append(float(loss))
        gnorms.append(float(gnorm))
    return {"loss": losses, "grad_norm": gnorms, "first_grad": first_grad,
            "init": init, "params": jax.device_get(params),
            "mu": jax.device_get(mu), "nu": jax.device_get(nu)}


def follow(args, seed: int, facts: dict, graph: dict,
           quant: Callable = lambda v: v,
           train_rows: Optional[np.ndarray] = None) -> dict:
    """The first `facts["n_steps"]` steps of the cell whose program was
    built from `args` (the parsed flags of `main.py`), on the
    configuration's `graph`, fed in the program's row order."""
    if facts["num_parts"] != 1:
        raise ValueError("this reference follows one partition; a cell "
                         "across chips names its own in its job")
    cfg = RefConfig(
        layer_sizes=facts["layer_sizes"], n_linear=args.n_linear,
        use_pp=args.use_pp, dropout=args.dropout, lr=args.lr,
        multilabel=facts["multilabel"],
        norm=None if args.norm == "none" else args.norm)
    return train(cfg, seed, graph, facts["row_of_node"], facts["n_rows"],
                 facts["n_steps"], quant=quant, train_rows=train_rows)
