"""The benchmark's own copy of the data set generator.

A configuration names its graph as `synthetic:<nodes>:<deg>:<feat>:<cls>[:ml]`
with a graph seed. The program makes that graph with
`pipegcn_tpu/graph/synthetic.py synthetic_graph` + `graph/csr.py finalize`;
the reference may import nothing of the program, so the same generator is
kept here (copied from those two functions, PR 24) and the two must agree:
a program whose generator drifts trains another graph than the
configuration states, and `correct` then reads false.

What the reference needs of the graph is kept on disk after the first run
of a checkout (`partitions/bench/<config>/refgraph/`), because generating
the Reddit shape takes minutes and every later run would pay it again.
"""

from __future__ import annotations

import json
import os

import numpy as np


def parse_dataset(spec: str) -> dict:
    parts = spec.split(":")
    if parts[0] != "synthetic" or len(parts) < 5:
        raise ValueError(f"not a synthetic:<n>:<deg>:<feat>:<cls>[:ml] "
                         f"data set: {spec!r}")
    n, deg, feat, cls = (int(x) for x in parts[1:5])
    return {"num_nodes": n, "avg_degree": deg, "n_feat": feat,
            "n_class": cls,
            "multilabel": len(parts) > 5 and parts[5] == "ml"}


def synthetic_graph(num_nodes: int, avg_degree: int, n_feat: int,
                    n_class: int, multilabel: bool = False, seed: int = 0,
                    homophily: float = 0.8, train_frac: float = 0.6,
                    val_frac: float = 0.2, noise: float = 1.0) -> dict:
    """SBM-style graph with class-correlated features: symmetric edges,
    exactly one self loop per node. Returns src, dst (int64), feat
    [N, F] f32, label ([N] int64 or [N, C] f32), train_mask [N] bool."""
    rng = np.random.default_rng(seed)
    comm = rng.integers(0, n_class, size=num_nodes)
    n_edges = num_nodes * avg_degree // 2
    order = np.argsort(comm, kind="stable")
    sorted_comm = comm[order]
    starts = np.searchsorted(sorted_comm, np.arange(n_class))
    ends = np.searchsorted(sorted_comm, np.arange(n_class), side="right")

    def sample_pairs(k: int) -> np.ndarray:
        a = rng.integers(0, num_nodes, size=k)
        intra = rng.random(k) < homophily
        ca = comm[a]
        span = np.maximum(ends[ca] - starts[ca], 1)
        b_intra = order[starts[ca]
                        + (rng.integers(0, 1 << 62, size=k) % span)]
        b = np.where(intra, b_intra, rng.integers(0, num_nodes, size=k))
        lo, hi = np.minimum(a, b), np.maximum(a, b)
        return (lo * num_nodes + hi)[lo != hi]

    keys = np.unique(sample_pairs(n_edges))
    while keys.size < n_edges:
        extra = sample_pairs(2 * (n_edges - keys.size))
        merged = np.union1d(keys, extra)
        if merged.size == keys.size:
            break
        keys = merged
    if keys.size > n_edges:
        keys = rng.permutation(keys)[:n_edges]
    a, b = keys // num_nodes, keys % num_nodes
    loop = np.arange(num_nodes, dtype=np.int64)
    # pairs are canonical lo < hi, so no self pair survives: adding one
    # loop per node is the program's normalize_self_loops
    src = np.concatenate([a, b, loop]).astype(np.int64)
    dst = np.concatenate([b, a, loop]).astype(np.int64)

    protos = rng.normal(0.0, 1.0, size=(n_class, n_feat)).astype(np.float32)
    feat = protos[comm] + rng.normal(
        0.0, noise, size=(num_nodes, n_feat)).astype(np.float32)
    if multilabel:
        label = np.zeros((num_nodes, n_class), dtype=np.float32)
        label[np.arange(num_nodes), comm] = 1.0
        extra = rng.random((num_nodes, n_class)) < 0.1
        label = np.maximum(label, extra.astype(np.float32))
    else:
        label = comm.astype(np.int64)
    perm = rng.permutation(num_nodes)
    train_mask = np.zeros(num_nodes, dtype=bool)
    train_mask[perm[:int(train_frac * num_nodes)]] = True
    return {"src": src, "dst": dst, "feat": feat, "label": label,
            "train_mask": train_mask}


def build_ell(src: np.ndarray, dst: np.ndarray, n: int):
    """In-neighbour table [n, max in-degree] int32, padded with n (the
    reference appends one zero row there), and the in-degrees [n] f32.
    Raises unless the edge set is its own transpose: the reference's
    backward pass applies the same table to the cotangent."""
    fwd = np.sort(dst * n + src)
    if not np.array_equal(fwd, np.sort(src * n + dst)):
        raise ValueError("edge set is not symmetric")
    s_sorted = (fwd % n).astype(np.int32)
    d_sorted = fwd // n
    del fwd
    counts = np.bincount(d_sorted, minlength=n)
    indptr = np.zeros(n + 1, np.int64)
    np.cumsum(counts, out=indptr[1:])
    pos = np.arange(d_sorted.size, dtype=np.int64) - indptr[d_sorted]
    ell = np.full((n, int(counts.max())), n, np.int32)
    ell[d_sorted, pos] = s_sorted
    return ell, counts.astype(np.float32)


_FILES = ("ell", "deg", "feat", "label", "train_mask")


def reference_graph(dataset: str, graph_seed: int, cache_dir: str) -> dict:
    """ell, deg, feat, label, train_mask of the configuration's graph,
    from `cache_dir` when a finished copy for this (dataset, seed) is
    there, else generated and written."""
    meta_path = os.path.join(cache_dir, "meta.json")
    want = {"dataset": dataset, "graph_seed": graph_seed, "format": 1}
    if os.path.exists(meta_path):
        with open(meta_path) as f:
            if json.load(f) == want:
                return {k: np.load(os.path.join(cache_dir, k + ".npy"))
                        for k in _FILES}
    g = synthetic_graph(seed=graph_seed, **parse_dataset(dataset))
    n = g["feat"].shape[0]
    ell, deg = build_ell(g.pop("src"), g.pop("dst"), n)
    out = {"ell": ell, "deg": deg, **g}
    os.makedirs(cache_dir, exist_ok=True)
    for k in _FILES:
        np.save(os.path.join(cache_dir, k + ".npy"), out[k])
    tmp = meta_path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(want, f)
    os.replace(tmp, meta_path)  # meta last: a torn write is an absent one
    return out
