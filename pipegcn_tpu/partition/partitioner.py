"""Graph partitioner.

Replaces the reference's dependency on METIS via a customized DGL fork
(reference helper/utils.py:132-144; the fork exists only to pass
`objtype` through to METIS, README.md:62). Supported surface is the same:

    method = 'metis' | 'random'     (reference helper/parser.py:39-42)
    obj    = 'vol' | 'cut'

'metis' here is a self-contained locality-aware partitioner, fully
vectorized so it scales to 100M+ edge graphs on host:

    1. BFS ordering of the whole graph (random restart per connected
       component) — nodes close in the graph are close in the order;
    2. contiguous balanced blocks of that order as the initial partition;
    3. parallel greedy refinement sweeps moving boundary nodes to the
       neighboring partition with the best objective gain, subject to a
       balance cap (a vectorized, conflict-tolerant variant of
       Fiduccia–Mattheyses, in the spirit of parallel refiners like Jet).

It is not METIS, but fills the same role; partition quality affects
communication volume, not correctness.

When the native C++ multilevel partitioner (pipegcn_tpu.native:
heavy-edge-matching coarsening + FM refinement, the same algorithm
family as METIS itself) is buildable, 'metis' dispatches to it — it
produces substantially better cuts than the flat Python refiner and is
faster. PIPEGCN_NATIVE=0 forces the pure-numpy path.

Objectives:
    'cut' — minimize the number of edges crossing partitions.
    'vol' — minimize total communication volume: the number of distinct
            (node, foreign-partition) pairs, i.e. how many halo rows get
            exchanged per layer. This is the objective that matters for
            PipeGCN-style training.
"""

from __future__ import annotations

import numpy as np
import scipy.sparse as sp

from ..graph.csr import Graph


def partition_graph(
    g: Graph,
    n_parts: int,
    method: str = "metis",
    obj: str = "vol",
    seed: int = 0,
    refine_iters: int = 10,
    imbalance: float = 1.05,
    symmetric: bool = False,
) -> np.ndarray:
    """Assign each node to one of `n_parts` partitions.

    Returns an int32 array [num_nodes] of partition ids. Every partition is
    guaranteed non-empty (each device must own at least one node).

    `symmetric=True` asserts g's edge list is already mirrored (e.g.
    the papers100M finalized-edge cache): the adjacency is then built
    WITHOUT the doubling mirror — at billion-edge scale the difference
    is ~50 GB of transient.
    """
    if n_parts <= 0:
        raise ValueError(f"n_parts must be positive, got {n_parts}")
    if method not in ("metis", "random"):
        raise ValueError(f"unknown partition method: {method}")
    if obj not in ("vol", "cut"):
        raise ValueError(f"unknown partition objective: {obj}")
    if n_parts > g.num_nodes:
        raise ValueError(
            f"n_parts={n_parts} exceeds num_nodes={g.num_nodes}"
        )
    if n_parts == 1:
        return np.zeros(g.num_nodes, dtype=np.int32)

    rng = np.random.default_rng(seed)
    if method == "random":
        # Balanced random assignment (reference part_method='random').
        parts = np.repeat(
            np.arange(n_parts, dtype=np.int32), -(-g.num_nodes // n_parts)
        )[: g.num_nodes]
        rng.shuffle(parts)
        return parts

    from .. import native

    if symmetric or g.num_edges > _CHUNKED_ADJ_EDGES:
        # RAM-bounded path: counting-sort CSR build (no scipy COO,
        # whose doubled u/v int64 buffers alone cost ~100 GB at
        # papers100M scale). Duplicate/bidirectional edges stay as
        # parallel unit-weight entries — mutual pairs effectively weigh
        # 2 vs a one-way edge's 1 (an approximation vs _sym_adj's
        # dedup-to-1; exact when the input is uniformly mirrored, as
        # symmetric=True asserts)
        indptr, indices = _csr_adjacency_chunked(g, symmetric=symmetric)
        adj = None
    else:
        adj = _sym_adj(g)
        indptr = adj.indptr.astype(np.int64)
        indices = adj.indices.astype(np.int32)
    if native.available():
        return native.native_partition(
            indptr, indices, n_parts, obj=obj, seed=seed,
            imbalance=imbalance, refine_iters=refine_iters,
        )
    if adj is None:  # numpy fallback needs the scipy structure
        adj = sp.csr_matrix(
            (np.ones(indices.shape[0], np.int8), indices, indptr),
            shape=(g.num_nodes, g.num_nodes))

    order = _bfs_order(adj, rng)
    # contiguous balanced blocks of the BFS order
    parts = np.empty(g.num_nodes, dtype=np.int32)
    parts[order] = (
        np.arange(g.num_nodes, dtype=np.int64) * n_parts // g.num_nodes
    ).astype(np.int32)
    parts = _refine(adj, parts, n_parts, obj, refine_iters, imbalance, rng)
    return parts


# default locality-cluster granularity; artifact cache keys derive
# from it via cluster_suffix so every consumer shares ONE definition
# of "which layout is this". 1024 beat the earlier 4096 default on
# the chip on earlier code (1.5182 vs 1.5935 s/epoch; record removed
# in PR 21): same 80% dense coverage from 2.4x fewer, denser tiles.
DEFAULT_CLUSTER_SIZE = 1024


def cluster_suffix(target_size: int) -> str:
    """Artifact-name fragment identifying the cluster layout. Always
    encodes the size: identity must be self-describing, not relative
    to DEFAULT_CLUSTER_SIZE — a default-relative '' suffix silently
    re-mapped cached artifacts when the default moved 4096 -> 1024."""
    return f"s{target_size}"


def locality_clusters(
    g: Graph,
    target_size: int = DEFAULT_CLUSTER_SIZE,
    seed: int = 0,
) -> np.ndarray:
    """Cluster labels for locality-aware LOCAL renumbering.

    Orders of magnitude finer than the device partitioning: ~target_size
    nodes per cluster. ShardedGraph.build sorts each partition's inner
    nodes by these labels, so nodes of one community get contiguous
    local ids and the shard's adjacency concentrates into dense tiles —
    the structure ops/block_spmm.py's MXU path needs. (The reference
    inherits whatever order DGL's METIS emits; here locality is an
    explicit, separately-controlled step.)

    Uses the same partitioner machinery with k = ceil(n / target_size);
    returns zeros (single cluster, no-op ordering) for graphs at or
    below target_size.
    """
    k = max(1, -(-g.num_nodes // target_size))
    from .. import native

    if not native.available():
        # the pure-numpy refiner materializes dense [N, k] gain tables;
        # cap k so that stays ~256 MB instead of OOMing on large graphs
        # (coarser clusters = coarser locality, still valid ordering)
        k = min(k, max(1, (64 << 20) // max(g.num_nodes, 1)))
    if k == 1:
        return np.zeros(g.num_nodes, dtype=np.int32)
    # higher imbalance tolerance than device partitioning: clusters only
    # steer ordering, so balance is irrelevant — cut quality is all that
    # matters
    return partition_graph(g, k, method="metis", obj="cut", seed=seed,
                           refine_iters=6, imbalance=1.3)


# above this many edges the scipy COO symmetrize is replaced by the
# chunked counting-sort CSR build (RAM: ~3x edge bytes vs ~30x)
_CHUNKED_ADJ_EDGES = 50_000_000


# locality reorder modes for ShardedGraph local renumbering. "auto" is
# resolved by measurement (ops/tuner.choose_reorder), never stored: an
# artifact's layout tag is always one of these concrete modes.
REORDER_MODES = ("none", "degree", "bfs", "degree-bfs")


def reorder_suffix(mode: str) -> str:
    """Artifact-name fragment identifying the reorder layout. 'none'
    maps to '' so pre-reorder artifact names stay valid cache keys."""
    if mode not in REORDER_MODES:
        raise ValueError(f"unknown reorder mode: {mode!r} "
                         f"(expected one of {REORDER_MODES})")
    return "" if mode == "none" else f"-r{mode}"


def reorder_key(g: Graph, mode: str, seed: int = 0):
    """Per-node int64 sort key realizing the locality reordering.

    ShardedGraph.build inserts this key into its local-id lexsort below
    the (partition, train-segment) keys, so within each partition's
    train and non-train segments inner nodes are renumbered:

      'degree'     — degree-bucket-major (power-of-two in-degree
                     buckets, hubs first), global-id-minor;
      'bfs'        — BFS-locality order (graph neighbors get nearby
                     local ids, so neighbor-gather index streams of the
                     SpMM kernels collapse into contiguous runs);
      'degree-bfs' — degree-bucket-major, BFS-locality-minor: bucket
                     structure aligned with ops/bucket_spmm's ladder
                     AND run-friendly gather streams inside each bucket.

    Returns None for 'none' (layout unchanged). The key is a pure
    ordering choice — ShardedGraph permutes features/labels/masks/CSR/
    send-lists coherently, so training semantics are untouched.
    """
    if mode in (None, "none"):
        return None
    if mode not in REORDER_MODES:
        raise ValueError(f"unknown reorder mode: {mode!r} "
                         f"(expected one of {REORDER_MODES})")
    n = g.num_nodes
    minor = np.arange(n, dtype=np.int64)
    if mode in ("bfs", "degree-bfs"):
        rng = np.random.default_rng(seed)
        if g.num_edges > _CHUNKED_ADJ_EDGES:
            indptr, indices = _csr_adjacency_chunked(g)
            adj = sp.csr_matrix(
                (np.ones(indices.shape[0], np.int8), indices, indptr),
                shape=(n, n))
        else:
            adj = _sym_adj(g)
        order = _bfs_order(adj, rng)
        minor = np.empty(n, dtype=np.int64)
        minor[order] = np.arange(n, dtype=np.int64)
    if mode == "bfs":
        return minor
    # hubs first: the highest-degree rows are gathered most often, so
    # packing them into the lowest local ids concentrates the hot
    # working set into one compact, streamable id range
    deg = g.in_degrees().astype(np.int64)
    bucket = np.floor(np.log2(np.maximum(deg, 1))).astype(np.int64)
    return (int(bucket.max()) - bucket) * n + minor


def _csr_adjacency_chunked(g: Graph, symmetric: bool = False,
                           chunk: int = 32_000_000):
    """Self-loop-free CSR adjacency (indptr int64, indices int32) built
    by a two-pass chunked counting sort — peak transient is O(chunk),
    plus the output arrays themselves. With symmetric=False each edge
    is filled in both directions (no dedup: a bidirectional input pair
    contributes weight 2 per direction, uniformly — equivalent for the
    partition objectives); with symmetric=True the input is trusted to
    be mirrored already and filled as-is. Sources may be memmaps."""
    n = g.num_nodes
    counts = np.zeros(n, np.int64)
    E = g.src.shape[0]
    for i in range(0, E, chunk):
        s = np.asarray(g.src[i:i + chunk])
        d = np.asarray(g.dst[i:i + chunk])
        m = s != d
        s, d = s[m], d[m]
        counts += np.bincount(s, minlength=n)
        if not symmetric:
            counts += np.bincount(d, minlength=n)
    indptr = np.zeros(n + 1, np.int64)
    np.cumsum(counts, out=indptr[1:])
    del counts
    indices = np.empty(indptr[-1], np.int32)
    cursor = indptr[:-1].copy()

    def fill(s, d):
        if s.shape[0] == 0:
            return
        order = np.argsort(s, kind="stable")
        ss = s[order]
        dd = d[order]
        starts = np.concatenate(
            [[0], np.flatnonzero(np.diff(ss)) + 1])
        lens = np.diff(np.concatenate([starts, [ss.shape[0]]]))
        within = np.arange(ss.shape[0], dtype=np.int64) \
            - np.repeat(starts, lens)
        indices[cursor[ss] + within] = dd
        cursor[ss[starts]] += lens

    for i in range(0, E, chunk):
        s = np.asarray(g.src[i:i + chunk]).astype(np.int64, copy=False)
        d = np.asarray(g.dst[i:i + chunk]).astype(np.int64, copy=False)
        m = s != d
        s, d = s[m], d[m]
        fill(s, d)
        if not symmetric:
            fill(d, s)
    return indptr, indices


def _sym_adj(g: Graph) -> sp.csr_matrix:
    """Symmetric 0/1 adjacency without self loops."""
    non_loop = g.src != g.dst
    u = np.concatenate([g.src[non_loop], g.dst[non_loop]])
    v = np.concatenate([g.dst[non_loop], g.src[non_loop]])
    n = g.num_nodes
    a = sp.csr_matrix(
        (np.ones(u.shape[0], dtype=np.int32), (u, v)), shape=(n, n)
    )
    a.data[:] = 1  # collapse duplicate edges
    return a


def _bfs_order(adj: sp.csr_matrix, rng) -> np.ndarray:
    """Vectorized BFS ordering covering all components (restart at a random
    unvisited node per component)."""
    n = adj.shape[0]
    visited = np.zeros(n, dtype=bool)
    order = np.empty(n, dtype=np.int64)
    pos = 0
    # restart cursor over a fixed random permutation: amortized O(N) over
    # all components instead of an O(N) scan per component
    restart_perm = rng.permutation(n)
    cursor = 0
    while pos < n:
        while cursor < n and visited[restart_perm[cursor]]:
            cursor += 1
        start = int(restart_perm[cursor])
        frontier = np.array([start])
        visited[start] = True
        order[pos] = start
        pos += 1
        while frontier.size:
            # union of neighbors of the frontier, via one sparse matvec
            ind = np.unique(adj[frontier].indices)
            ind = ind[~visited[ind]]
            if ind.size == 0:
                break
            visited[ind] = True
            order[pos: pos + ind.size] = ind
            pos += ind.size
            frontier = ind
    return order


def _refine(
    adj: sp.csr_matrix,
    parts: np.ndarray,
    n_parts: int,
    obj: str,
    iters: int,
    imbalance: float,
    rng,
) -> np.ndarray:
    """Parallel greedy refinement. Each sweep computes, for every node, its
    neighbor count per partition (one sparse-dense matmul), derives move
    gains for the requested objective, and applies the highest-gain moves
    subject to the per-partition balance cap."""
    n = adj.shape[0]
    parts = parts.astype(np.int32).copy()
    cap = int(imbalance * (-(-n // n_parts)))
    arange = np.arange(n)

    for _ in range(iters):
        onehot = sp.csr_matrix(
            (np.ones(n, dtype=np.float32), (arange, parts)),
            shape=(n, n_parts),
        )
        counts = np.asarray((adj @ onehot).todense())  # [N, P]
        own = counts[arange, parts]
        if obj == "cut":
            gains = counts - own[:, None]
        else:  # vol: also count the halo pairs this node creates/removes
            gains = (
                counts
                - own[:, None]
                + (counts > 0).astype(np.float32)
                - (own > 0).astype(np.float32)[:, None]
            )
        gains[arange, parts] = -np.inf
        target = np.argmax(gains, axis=1).astype(np.int32)
        gain = gains[arange, target]
        movers = np.nonzero(gain > 0)[0]
        if movers.size == 0:
            break

        # enforce balance: admit the best movers into each target part up
        # to its remaining room, and never drain a part empty
        sizes = np.bincount(parts, minlength=n_parts)
        room = np.maximum(cap - sizes, 0)
        # sort movers by (target, -gain); rank within target group
        key = np.lexsort((-gain[movers], target[movers]))
        movers = movers[key]
        tgt = target[movers]
        grp_start = np.searchsorted(tgt, np.arange(n_parts))
        rank = arange[: movers.size] - grp_start[tgt]
        admitted = movers[rank < room[tgt]]
        if admitted.size == 0:
            break
        parts[admitted] = target[admitted]
        _fill_empty_parts(parts, n_parts)
    _fill_empty_parts(parts, n_parts)
    return parts


def _fill_empty_parts(parts: np.ndarray, n_parts: int) -> None:
    """Ensure every partition owns at least one node (each device must hold
    a shard); steal single nodes from the currently largest partition."""
    sizes = np.bincount(parts, minlength=n_parts)
    for p in np.nonzero(sizes == 0)[0]:
        donor = int(np.argmax(sizes))
        parts[np.nonzero(parts == donor)[0][0]] = p
        sizes[donor] -= 1
        sizes[p] += 1


def edge_cut(g: Graph, parts: np.ndarray) -> int:
    """Number of non-self-loop directed edges crossing partitions."""
    non_loop = g.src != g.dst
    return int((parts[g.src[non_loop]] != parts[g.dst[non_loop]]).sum())


def comm_volume(g: Graph, parts: np.ndarray) -> int:
    """Total halo pairs: distinct (node, foreign partition consuming it)."""
    non_loop = g.src != g.dst
    src, dst = g.src[non_loop], g.dst[non_loop]
    cross = parts[src] != parts[dst]
    pairs = np.unique(
        np.stack([src[cross], parts[dst[cross]].astype(np.int64)], 1), axis=0
    )
    return int(pairs.shape[0])
