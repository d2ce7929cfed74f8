"""Halo index pipeline: partitioned graph -> static-shaped device arrays.

This is the TPU-native replacement for the reference's entire per-rank
graph-construction stack — boundary discovery (helper/utils.py:154-188),
halo ordering + renumbering (train.py:84-131, 206-229), train-first
permutation (train.py:134-155), and recv-shape computation
(train.py:101-110) — done once on host in numpy, producing arrays whose
shapes are identical on every device so a single SPMD program can be
traced over them.

Layout per device r (P devices total):

  rows [0, N_max)           : inner (owned) nodes, train nodes first
                              (local ids of train nodes are [0, n_train_r)),
                              padded with zero rows up to N_max
  rows [N_max + (d-1)*B_max + k) for d in 1..P-1, k in [0, B_max):
                              halo slot k of ring distance d — after the
                              exchange step at distance d it holds entry k
                              of the send list of owner q = (r-d) mod P

The send list S[r][d-1] contains local indices of r's inner nodes needed
by the peer t = (r+d) mod P (nodes with an out-edge into t), sorted by
local id, padded to B_max. Keying halo blocks by ring *distance* instead
of owner rank (the reference sorts by owner rank, train.py:120-131) makes
the ppermute-based exchange's recv offsets identical across devices —
the property that lets one traced program serve all shards.

Local edges: every global edge (u, v) with part(v) == r appears exactly
once on device r as (src_local, dst_local); src_local is an inner id or a
halo slot. Edge arrays are padded to E_max with (src=0, dst=N_max); the
dst sentinel routes padded contributions into a dropped segment.
"""

from __future__ import annotations

import dataclasses
import json
import os
from typing import Dict, Optional

import numpy as np

from ..graph.csr import Graph


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m if m > 0 else x


# host-side edge-pass chunk: bounds O(E) int64 temporaries during
# checksums and the chunked build (6-7 per-edge int64 scratch arrays at
# a time -> ~0.9 GB per 16M-edge chunk instead of all-E at once)
_EDGE_CHUNK = 16 * 1024 * 1024


def _stable_argsort(keys: np.ndarray) -> np.ndarray:
    """Stable argsort of non-negative int64 fused keys — the difference
    between seconds and minutes at 114M edges. Thin alias of
    native.stable_argsort (kept for this module's call sites)."""
    from ..native import stable_argsort

    return stable_argsort(keys)


class _RaggedEdges:
    """Per-rank trimmed edge arrays of a trim_edges v3 artifact.

    Indexing by rank (`arr[r]`) memmaps that rank's trimmed 1-D file —
    slicing it `[:edge_count[r]]` is the identity, so per-rank code
    written against the padded [P, e_max] stack works unchanged.
    Whole-array operations (astype/reshape/...) are intentionally
    unsupported: the padded stack was not stored."""

    def __init__(self, adir: str, key: str, num_parts: int):
        self._adir = adir
        self._key = key
        self.num_parts = num_parts

    def __len__(self):
        return self.num_parts

    def __getitem__(self, r):
        if not isinstance(r, (int, np.integer)):
            raise TypeError(
                f"{self._key} is stored per-rank trimmed "
                "(trim_edges artifact); index by rank int only")
        if not 0 <= int(r) < self.num_parts:
            raise IndexError(
                f"rank {r} out of range [0, {self.num_parts})")
        return np.load(os.path.join(self._adir,
                                    f"{self._key}_r{int(r):03d}.npy"),
                       mmap_mode="r")

    def __array__(self, *a, **kw):
        # numpy coercion (np.asarray / zeros_like / iteration fallback)
        # must fail with the explanatory message, not a confusing
        # FileNotFoundError past the last rank or a silently unpadded
        # stack of equal-length ranks
        raise TypeError(
            f"{self._key} is a trim_edges per-rank view; the padded "
            "[P, e_max] stack was not stored — re-save without "
            "trim_edges for whole-array consumers")

    def __getattr__(self, name):
        raise AttributeError(
            f"{self._key} is a trim_edges per-rank view; the padded "
            f"[P, e_max] stack was not stored (re-save without "
            f"trim_edges for whole-array consumers like the mesh "
            f"Trainer) — attribute {name!r} unsupported")


@dataclasses.dataclass
class ShardedGraph:
    """Stacked per-device arrays (leading axis = device / partition).

    All integer index arrays are int32 (TPU-friendly); features float32.
    """

    num_parts: int
    n_max: int          # padded inner-node rows per device
    b_max: int          # padded send-list length (per peer distance)
    e_max: int          # padded edge count per device
    n_train_global: int
    n_feat: int
    n_class: int
    multilabel: bool

    inner_count: np.ndarray   # [P] real inner nodes per device
    train_count: np.ndarray   # [P] train nodes per device (local ids [0, t))
    edge_count: np.ndarray    # [P] real edges per device
    send_counts: np.ndarray   # [P, P-1] real send-list lengths

    edge_src: np.ndarray      # [P, E_max] int32 in [0, N_max + (P-1)*B_max)
    edge_dst: np.ndarray      # [P, E_max] int32 in [0, N_max]; N_max = pad
    send_idx: np.ndarray      # [P, P-1, B_max] int32 local inner ids
    send_mask: np.ndarray     # [P, P-1, B_max] bool

    feat: np.ndarray          # [P, N_max, F]
    label: np.ndarray         # [P, N_max] int64 or [P, N_max, C] float32
    train_mask: np.ndarray    # [P, N_max] bool (padding rows False)
    val_mask: np.ndarray      # [P, N_max] bool
    test_mask: np.ndarray     # [P, N_max] bool
    in_deg: np.ndarray        # [P, N_max] float32 (padding rows 1.0)
    global_nid: np.ndarray    # [P, N_max] int64 (padding rows -1)

    # wraparound-uint64 checksum of the source graph's global edge list
    # (identifies "is this sharded graph built from exactly graph g?" —
    # node-ID cover alone can't distinguish graphs sharing a node set);
    # -1 in artifacts saved before the field existed
    source_edge_checksum: int = -1

    # locality reorder layout (partitioner.REORDER_MODES): which node
    # renumbering this artifact's local ids follow. Pre-reorder
    # artifacts default to "none"/layout v1 on load; new builds stamp
    # LAYOUT_VERSION. reorder_perm[p, l] is the local id node (p, l)
    # would have under reorder="none" (the base layout), reorder_inv
    # its inverse; -1 on padding rows, None when reorder == "none".
    reorder: str = "none"
    layout_version: int = 1
    reorder_perm: Optional[np.ndarray] = None
    reorder_inv: Optional[np.ndarray] = None

    # set by load(): the artifact directory, which doubles as the cache
    # location for derived per-device kernel tables (bucket/block) so
    # repeat runs skip their O(E) host builds. Not serialized.
    cache_dir: Optional[str] = None

    # set by load(): the directory this graph was read from; None for a
    # graph built in this process (even once saved). Not serialized.
    loaded_from: Optional[str] = None

    # set by load(parts=...): the global partition ids THIS process
    # will own under the current elastic membership assignment
    # (resilience/elastic.py); None = unsupervised / owns everything.
    # Not serialized — the assignment is a property of the run, the
    # artifact stays world-size independent.
    local_parts: Optional[tuple] = None

    @property
    def halo_size(self) -> int:
        return (self.num_parts - 1) * self.b_max

    @property
    def source(self) -> str:
        """Where this graph came from, for set-up logs."""
        return (f"loaded from {self.loaded_from}" if self.loaded_from
                else "built in this run")

    @staticmethod
    def edge_checksum(g: Graph) -> int:
        # splitmix64-mix each fused (src, dst) pair BEFORE the order-free
        # sum: a plain sum of src*N + dst is linear (N*Σsrc + Σdst) and
        # collides for any re-pairing of the same endpoints — exactly the
        # rewired-graph case the checksum must detect. Chunked so the
        # uint64 temporaries stay bounded at papers100M scale (the sum
        # is order-free, so chunking cannot change the result).
        total = 0
        nn = np.uint64(g.num_nodes)
        for i0 in range(0, g.num_edges, _EDGE_CHUNK):
            sl = slice(i0, min(i0 + _EDGE_CHUNK, g.num_edges))
            x = g.src[sl].astype(np.uint64) * nn \
                + g.dst[sl].astype(np.uint64)
            x = (x ^ (x >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
            x = (x ^ (x >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
            x ^= x >> np.uint64(31)
            # explicit mod-2^64 accumulation (a np.uint64 scalar add
            # wraps identically but emits RuntimeWarning per chunk)
            total = (total + int(x.sum(dtype=np.uint64))) & ((1 << 64) - 1)
        return total

    # ------------------------------------------------------------------
    @staticmethod
    def _padded_dim(raw: int, pad_to: int, slack: float = 0.0,
                    floor: int = 0) -> int:
        """Padded size of a per-device dimension: the raw maximum grown
        by the streaming `slack` fraction (reserved headroom so
        stream/patch.py can add entries without changing compiled
        shapes), floored at `floor` (a bit-identity re-pad target), then
        rounded up to `pad_to`."""
        grown = int(np.ceil(raw * (1.0 + max(slack, 0.0))))
        return _round_up(max(grown, int(floor)), pad_to)

    @staticmethod
    def _send_structures(pair_fused: np.ndarray, parts: np.ndarray,
                         local_id: np.ndarray, num_parts: int, n: int,
                         pad_to: int, slack: float = 0.0,
                         min_b_max: int = 0) -> Dict[str, np.ndarray]:
        """Send lists + halo-slot lookup from the sorted unique
        (node, dest part) fused-pair array — the shared core of build()
        and build_chunked().

        Returns send_counts/b_max/send_idx/send_mask plus the pair->slot
        lookup pieces (`fused_sorted` = pair_fused itself, `dist`,
        `rank_in_group`, `order` = inverse of the send-list sort) used
        to localize cross-edge sources."""
        p_node = pair_fused // num_parts
        p_dest = (pair_fused % num_parts).astype(np.int32)
        p_owner = parts[p_node]
        # sort by (owner, dest, local id) -> grouped send lists in order
        skey = _stable_argsort(
            (p_owner.astype(np.int64) * num_parts + p_dest) * n
            + local_id[p_node]
        )
        p_node, p_dest, p_owner = p_node[skey], p_dest[skey], p_owner[skey]

        # group starts for each (owner, dest) combination
        combo = p_owner.astype(np.int64) * num_parts + p_dest
        send_counts = np.bincount(
            combo, minlength=num_parts * num_parts
        ).reshape(num_parts, num_parts)
        assert np.all(np.diag(send_counts) == 0)
        b_max = ShardedGraph._padded_dim(
            int(send_counts.max()), pad_to, slack, min_b_max
        ) if num_parts > 1 else 0

        combo_starts = np.zeros(num_parts * num_parts + 1, dtype=np.int64)
        np.cumsum(send_counts.reshape(-1), out=combo_starts[1:])
        rank_in_group = np.arange(p_node.shape[0]) - combo_starts[combo]

        # send_idx[r, d-1, k] = local id of k-th node r sends to (r+d)%P
        # (empty index arrays make these assignments no-ops, so the exact
        # shape works for P == 1 and b_max == 0 too)
        send_idx = np.zeros((num_parts, num_parts - 1, b_max),
                            dtype=np.int32)
        send_mask = np.zeros_like(send_idx, dtype=bool)
        dist = (p_dest - p_owner) % num_parts  # ring distance in 1..P-1
        send_idx[p_owner, dist - 1, rank_in_group] = \
            local_id[p_node].astype(np.int32)
        send_mask[p_owner, dist - 1, rank_in_group] = True

        # pair -> slot lookup via a dict-free merge: pair_fused is
        # already sorted by (node, dest) and p_* are its skey-
        # permutation, so the sorted key array IS pair_fused and the
        # sort order is skey's inverse — no third large sort needed
        fused_sorted_order = np.empty_like(skey)
        fused_sorted_order[skey] = np.arange(skey.size)
        return {
            "send_counts": send_counts,
            "b_max": b_max,
            "send_idx": send_idx,
            "send_mask": send_mask,
            "fused_sorted": pair_fused,
            # rank/dist in pair_fused order (hoisted out of the per-
            # chunk edge localization)
            "rank_by_pair": rank_in_group[fused_sorted_order],
            "dist_by_pair": dist[fused_sorted_order],
        }

    @staticmethod
    def _localize_edges(src: np.ndarray, dst: np.ndarray,
                        parts: np.ndarray, local_id: np.ndarray,
                        ss: Dict[str, np.ndarray], num_parts: int,
                        n_max: int, b_max: int):
        """(src_local, dst_local) int64 for a slice of global edges: an
        inner source maps to its local id, a cross source to its halo
        slot n_max + (dist-1)*b_max + rank in the owner's send list."""
        fused_sorted = ss["fused_sorted"]
        dst_local = local_id[dst].astype(np.int64)
        src_inner = parts[src] == parts[dst]
        edge_fused = src.astype(np.int64) * num_parts + parts[dst]
        loc = np.searchsorted(fused_sorted, edge_fused)
        # (only valid where cross; guard indices)
        loc = np.clip(loc, 0, max(fused_sorted.size - 1, 0))
        if fused_sorted.size:
            halo_rank = ss["rank_by_pair"][loc]
            halo_dist = ss["dist_by_pair"][loc]
        else:
            halo_rank = np.zeros_like(edge_fused)
            halo_dist = np.ones_like(edge_fused)
        src_local = np.where(
            src_inner,
            local_id[src],
            n_max + (halo_dist - 1) * b_max + halo_rank,
        ).astype(np.int64)
        return src_local, dst_local

    # ------------------------------------------------------------------
    @staticmethod
    def _local_ids(n: int, train_mask: np.ndarray, parts: np.ndarray,
                   num_parts: int, cluster: Optional[np.ndarray],
                   rkey: Optional[np.ndarray]):
        """Local-id assignment: sort nodes by (part, ~is_train
        [, reorder key][, cluster], global id) into contiguous per-part
        train-first blocks. Returns (local_id, part_sizes)."""
        keys = [np.arange(n)]
        if cluster is not None:
            keys.append(cluster.astype(np.int64))
        if rkey is not None:
            keys.append(np.asarray(rkey, dtype=np.int64))
        keys += [~train_mask, parts]
        order = np.lexsort(tuple(keys))
        part_sizes = np.bincount(parts, minlength=num_parts)
        part_starts = np.zeros(num_parts + 1, dtype=np.int64)
        np.cumsum(part_sizes, out=part_starts[1:])
        local_id = np.empty(n, dtype=np.int64)
        local_id[order] = np.arange(n) - part_starts[parts[order]]
        return local_id, part_sizes

    @staticmethod
    def _reorder_arrays(g: Graph, reorder: str, train_mask, parts,
                        num_parts, cluster, local_id, n_max):
        """(rkey-resolved reorder tag, perm, inv) for a build. The perm
        maps the reordered layout back to the base (reorder='none')
        layout so external consumers can translate local ids either
        way; both are [P, n_max] int32, -1 on padding rows."""
        if reorder in (None, "none"):
            return "none", None, None
        base_lid, _ = ShardedGraph._local_ids(
            g.num_nodes, train_mask, parts, num_parts, cluster, None)
        perm = np.full((num_parts, n_max), -1, np.int32)
        inv = np.full((num_parts, n_max), -1, np.int32)
        perm[parts, local_id] = base_lid.astype(np.int32)
        inv[parts, base_lid] = local_id.astype(np.int32)
        return reorder, perm, inv

    @staticmethod
    def build(
        g: Graph,
        parts: np.ndarray,
        n_parts: Optional[int] = None,
        pad_to: int = 8,
        cluster: Optional[np.ndarray] = None,
        reorder: str = "none",
        reorder_seed: int = 0,
        slack: float = 0.0,
        min_n_max: int = 0,
        min_b_max: int = 0,
        min_e_max: int = 0,
    ) -> "ShardedGraph":
        """Build the sharded layout from a graph and a partition assignment.

        `g` must be finalized (self loops + in_deg). `parts` is [N] int.
        `n_parts` is the intended device count; defaults to parts.max()+1
        but must be passed explicitly when trailing partitions could be
        empty (an empty shard is valid, just wasteful).

        `cluster` ([N] int, optional) adds a locality key to the local
        renumbering: within each partition's train and non-train
        segments, nodes sort by (cluster, global id) instead of global id
        alone, so community members get contiguous local ids and the
        shard adjacency concentrates into dense tiles (what
        ops/block_spmm.py exploits). Purely an ordering choice — every
        layout invariant (train-first, CSR edges, send lists) holds for
        any consistent order.

        `reorder` (partitioner.REORDER_MODES) adds the locality
        renumbering key BELOW the train segment and ABOVE the cluster
        key: within each partition's train/non-train segments inner
        nodes follow degree-bucket-major, BFS-locality-minor order so
        the SpMM gather index streams collapse into contiguous runs
        (ops/bucket_spmm slab plans). The base-layout permutation and
        its inverse are stored on the result (reorder_perm/reorder_inv)
        and ride the artifact.

        `slack` (streaming headroom, stream/patch.py) grows every padded
        per-device dimension (n_max, b_max, e_max) by that fraction over
        its raw maximum before rounding, reserving in-place growth room
        for delta patching without changing compiled shapes. The
        `min_*` floors force specific padded dimensions — the re-pad
        path and the patched-vs-rebuilt bit-identity oracle use them to
        rebuild a graph into the exact layout a patched ShardedGraph
        occupies.
        """
        n = g.num_nodes
        parts = parts.astype(np.int32)
        num_parts = int(n_parts) if n_parts is not None else int(parts.max()) + 1
        if num_parts < int(parts.max()) + 1:
            raise ValueError(
                f"n_parts={num_parts} smaller than max partition id "
                f"{int(parts.max())}"
            )
        train_mask = g.ndata["train_mask"]

        # ---- local ids: train-first within each partition ------------
        from .partitioner import reorder_key

        rkey = reorder_key(g, reorder, seed=reorder_seed)
        local_id, part_sizes = ShardedGraph._local_ids(
            n, train_mask, parts, num_parts, cluster, rkey)

        inner_count = part_sizes.astype(np.int32)
        train_count = np.bincount(
            parts[train_mask], minlength=num_parts
        ).astype(np.int32)

        n_max = ShardedGraph._padded_dim(
            int(part_sizes.max()), pad_to, slack, min_n_max)

        # ---- send lists ----------------------------------------------
        # cross edges define which (owner node, dest part) pairs exist;
        # fusing (node, dest) into one key makes the unique a cheap 1-D
        # sort instead of numpy's slow axis-0 row unique
        cross = parts[g.src] != parts[g.dst]
        cs, cd = g.src[cross], g.dst[cross]
        pair_fused = np.unique(
            cs.astype(np.int64) * num_parts + parts[cd]
        )  # sorted by (node, dest part), same order as the row unique
        ss = ShardedGraph._send_structures(pair_fused, parts, local_id,
                                           num_parts, n, pad_to,
                                           slack=slack,
                                           min_b_max=min_b_max)
        send_counts, b_max = ss["send_counts"], ss["b_max"]
        send_idx, send_mask = ss["send_idx"], ss["send_mask"]

        # ---- per-device edges ----------------------------------------
        edge_owner = parts[g.dst]  # device that owns each edge
        e_sizes = np.bincount(edge_owner, minlength=num_parts)
        e_max = ShardedGraph._padded_dim(
            int(e_sizes.max()), 128, slack, min_e_max)

        src_local_all, dst_local_all = ShardedGraph._localize_edges(
            g.src, g.dst, parts, local_id, ss, num_parts, n_max, b_max)

        # scatter edges into per-device padded arrays, sorted by local dst
        # within each device (CSR order — lets kernels rely on contiguous
        # destination segments; padding dst = n_max sorts to the tail)
        # THE hot host sort (E entries); fused single key + radix sort
        e_order = _stable_argsort(
            edge_owner.astype(np.int64) * (n_max + 1) + dst_local_all
        )
        e_starts = np.zeros(num_parts + 1, dtype=np.int64)
        np.cumsum(e_sizes, out=e_starts[1:])
        edge_src = np.zeros((num_parts, e_max), dtype=np.int32)
        edge_dst = np.full((num_parts, e_max), n_max, dtype=np.int32)
        pos_in_dev = np.arange(g.num_edges) - e_starts[edge_owner[e_order]]
        edge_src[edge_owner[e_order], pos_in_dev] = src_local_all[e_order]
        edge_dst[edge_owner[e_order], pos_in_dev] = dst_local_all[e_order]

        reo = ShardedGraph._reorder_arrays(
            g, reorder, train_mask, parts, num_parts, cluster,
            local_id, n_max)
        return ShardedGraph._assemble(
            g, parts, local_id, num_parts, n_max, b_max, e_max,
            e_sizes, inner_count, train_count, send_counts,
            edge_src, edge_dst, send_idx, send_mask, reorder=reo,
        )

    @staticmethod
    def _assemble(g, parts, local_id, num_parts, n_max, b_max, e_max,
                  e_sizes, inner_count, train_count, send_counts,
                  edge_src, edge_dst, send_idx, send_mask,
                  node_chunk: Optional[int] = None,
                  reorder=("none", None, None)) -> "ShardedGraph":
        """Per-device node-data scatter + dataclass construction — shared
        tail of build() and build_chunked(). `node_chunk` streams the
        feature scatter in row slices so a memmapped g.ndata['feat'] is
        never materialized whole."""
        n = g.num_nodes
        train_mask = np.asarray(g.ndata["train_mask"])

        def scatter_nodes(x: np.ndarray, fill) -> np.ndarray:
            shape = (num_parts, n_max) + x.shape[1:]
            out = np.full(shape, fill, dtype=x.dtype)
            out[parts, local_id] = x
            return out

        fsrc = g.ndata["feat"]
        if node_chunk:
            feat = np.zeros((num_parts, n_max) + fsrc.shape[1:],
                            np.float32)
            for i0 in range(0, n, node_chunk):
                sl = slice(i0, min(i0 + node_chunk, n))
                feat[parts[sl], local_id[sl]] = \
                    np.asarray(fsrc[sl], dtype=np.float32)
        else:
            feat = scatter_nodes(np.asarray(fsrc, np.float32), 0.0)
        label_arr = np.asarray(g.ndata["label"])
        multilabel = label_arr.ndim == 2
        if multilabel:
            label = scatter_nodes(label_arr.astype(np.float32), 0.0)
            n_class = int(label_arr.shape[1])
        else:
            label = scatter_nodes(label_arr.astype(np.int64), 0)
            n_class = int(label_arr.max()) + 1
        tm = scatter_nodes(train_mask.astype(bool), False)
        vm = scatter_nodes(
            np.asarray(g.ndata.get("val_mask", np.zeros(n, bool)),
                       bool), False
        )
        sm = scatter_nodes(
            np.asarray(g.ndata.get("test_mask", np.zeros(n, bool)),
                       bool), False
        )
        # degrees of the graph being partitioned (reference utils.py:142);
        # finalize()/node_subgraph keep ndata['in_deg'] consistent with the
        # attached graph, so prefer it over an O(E) recompute
        deg = g.ndata.get("in_deg")
        if deg is None:
            deg = g.in_degrees()
        in_deg = scatter_nodes(np.asarray(deg, np.float32), 1.0)
        in_deg[in_deg == 0] = 1.0
        gnid = scatter_nodes(np.arange(n, dtype=np.int64), -1)

        return ShardedGraph(
            num_parts=num_parts,
            n_max=n_max,
            b_max=b_max,
            e_max=e_max,
            n_train_global=int(train_mask.sum()),
            n_feat=int(feat.shape[-1]),
            n_class=n_class,
            multilabel=multilabel,
            inner_count=inner_count,
            train_count=train_count,
            edge_count=e_sizes.astype(np.int32),
            send_counts=send_counts[
                np.arange(num_parts)[:, None],
                (np.arange(num_parts)[:, None] + np.arange(1, max(num_parts, 2)))
                % num_parts,
            ].astype(np.int32) if num_parts > 1 else np.zeros((1, 0), np.int32),
            edge_src=edge_src,
            edge_dst=edge_dst,
            send_idx=send_idx,
            send_mask=send_mask,
            feat=feat,
            label=label,
            train_mask=tm,
            val_mask=vm,
            test_mask=sm,
            in_deg=in_deg,
            global_nid=gnid,
            source_edge_checksum=ShardedGraph.edge_checksum(g),
            reorder=reorder[0],
            # reorder="none" IS the v1 layout bit-for-bit: keep version 1
            # so existing tuning tables stay signature-valid for it
            layout_version=(ShardedGraph.LAYOUT_VERSION
                            if reorder[0] != "none" else 1),
            reorder_perm=reorder[1],
            reorder_inv=reorder[2],
        )

    # ------------------------------------------------------------------
    @staticmethod
    def build_chunked(
        g: Graph,
        parts: np.ndarray,
        n_parts: Optional[int] = None,
        pad_to: int = 8,
        cluster: Optional[np.ndarray] = None,
        reorder: str = "none",
        reorder_seed: int = 0,
        edge_chunk: int = _EDGE_CHUNK,
        node_chunk: int = 1 << 20,
    ) -> "ShardedGraph":
        """RAM-bounded build for papers100M-class graphs: bit-identical
        output to build(), with every O(E) pass chunked.

        build() materializes ~7 per-edge int64 scratch arrays at once
        (~180 GB at papers100M's 3.2B post-mirror edges — the regime the
        reference handles with a >=120 GB-RAM host, reference
        README.md:29-30); here the peak transient is O(edge_chunk) for
        the edge passes + one per-device argsort (E/P), so the resident
        set is dominated by the artifact itself. g.src/g.dst/g.ndata may
        be memmaps — every access is sliced.

        Equality with build() holds exactly: chunks preserve arrival
        order per device, and the final per-device stable dst sort
        reproduces build()'s global stable (owner, dst) order.
        """
        n = g.num_nodes
        parts = parts.astype(np.int32)
        num_parts = int(n_parts) if n_parts is not None \
            else int(parts.max()) + 1
        if num_parts < int(parts.max()) + 1:
            raise ValueError(
                f"n_parts={num_parts} smaller than max partition id "
                f"{int(parts.max())}"
            )
        train_mask = np.asarray(g.ndata["train_mask"])

        # ---- local ids (O(N), same as build) --------------------------
        from .partitioner import reorder_key

        rkey = reorder_key(g, reorder, seed=reorder_seed)
        local_id, part_sizes = ShardedGraph._local_ids(
            n, train_mask, parts, num_parts, cluster, rkey)
        inner_count = part_sizes.astype(np.int32)
        train_count = np.bincount(
            parts[train_mask], minlength=num_parts
        ).astype(np.int32)
        n_max = _round_up(int(part_sizes.max()), pad_to)

        # ---- pass 1 (chunked): owner counts + cross-pair uniques ------
        E = g.num_edges
        e_sizes = np.zeros(num_parts, np.int64)
        pair_chunks = []
        for i0 in range(0, E, edge_chunk):
            sl = slice(i0, min(i0 + edge_chunk, E))
            s = np.asarray(g.src[sl])
            d = np.asarray(g.dst[sl])
            pd = parts[d]
            e_sizes += np.bincount(pd, minlength=num_parts)
            cross = parts[s] != pd
            pair_chunks.append(np.unique(
                s[cross].astype(np.int64) * num_parts + pd[cross]))
        pair_fused = np.unique(np.concatenate(pair_chunks)) \
            if pair_chunks else np.zeros(0, np.int64)
        ss = ShardedGraph._send_structures(pair_fused, parts, local_id,
                                           num_parts, n, pad_to)
        send_counts, b_max = ss["send_counts"], ss["b_max"]
        e_max = _round_up(int(e_sizes.max()), 128)

        # ---- pass 2 (chunked): localize + scatter in arrival order ----
        edge_src = np.zeros((num_parts, e_max), dtype=np.int32)
        edge_dst = np.full((num_parts, e_max), n_max, dtype=np.int32)
        cursor = np.zeros(num_parts, np.int64)
        for i0 in range(0, E, edge_chunk):
            sl = slice(i0, min(i0 + edge_chunk, E))
            s = np.asarray(g.src[sl])
            d = np.asarray(g.dst[sl])
            src_l, dst_l = ShardedGraph._localize_edges(
                s, d, parts, local_id, ss, num_parts, n_max, b_max)
            owner = parts[d]
            o = _stable_argsort(owner.astype(np.int64))
            ow = owner[o]
            cnt = np.bincount(ow, minlength=num_parts)
            starts = np.zeros(num_parts + 1, np.int64)
            np.cumsum(cnt, out=starts[1:])
            pos = cursor[ow] + (np.arange(ow.size) - starts[ow])
            edge_src[ow, pos] = src_l[o]
            edge_dst[ow, pos] = dst_l[o]
            cursor += cnt

        # ---- per-device CSR sort (stable by local dst) ----------------
        for r in range(num_parts):
            e_r = int(e_sizes[r])
            if not e_r:
                continue
            o = _stable_argsort(edge_dst[r, :e_r].astype(np.int64))
            edge_src[r, :e_r] = edge_src[r, :e_r][o]
            edge_dst[r, :e_r] = edge_dst[r, :e_r][o]

        reo = ShardedGraph._reorder_arrays(
            g, reorder, train_mask, parts, num_parts, cluster,
            local_id, n_max)
        return ShardedGraph._assemble(
            g, parts, local_id, num_parts, n_max, b_max, e_max,
            e_sizes, inner_count, train_count, send_counts,
            edge_src, edge_dst, ss["send_idx"], ss["send_mask"],
            node_chunk=node_chunk, reorder=reo,
        )

    # ------------------------------------------------------------------
    # Partition artifact on disk (reference: dgl partition JSON + per-part
    # files, helper/utils.py:132-144 / 99-129; enables --skip-partition).

    _ARRAYS = [
        "inner_count", "train_count", "edge_count", "send_counts",
        "edge_src", "edge_dst", "send_idx", "send_mask", "feat", "label",
        "train_mask", "val_mask", "test_mask", "in_deg", "global_nid",
    ]

    # format history: v1 edges grouped by device only; v2 adds the per-
    # device dst-sorted (CSR) edge order that spmm's sorted path relies
    # on; v3 stores the same arrays as individual uncompressed .npy
    # files so loaders can mmap them (papers100M-class artifacts exceed
    # RAM as one decompressed npz; a v3 reader touches only the ranks
    # it slices — the per-rank loading the reference gets from dgl's
    # per-part files, helper/utils.py:132-144)
    FORMAT_VERSION = 2
    MMAP_FORMAT_VERSION = 3

    # layout contract version (orthogonal to the storage format above):
    # v1 = pre-reorder local-id contract; v2 = reorder-aware — the
    # manifest carries the reorder tag and, when reorder != "none", the
    # permutation arrays. v1 artifacts load as reorder="none".
    LAYOUT_VERSION = 2
    _REORDER_ARRAYS = ["reorder_perm", "reorder_inv"]

    def save(self, path: str, mmap: bool = False,
             trim_edges: bool = False) -> None:
        """trim_edges (v3/mmap only): store edge_src/edge_dst per rank,
        TRIMMED to each rank's real edge count, instead of the padded
        [P, e_max] stack — at papers100M scale the pareto-hub rank sets
        e_max ~2.7x the mean and the padded stack alone is ~69 GB on
        disk. load() then returns a _RaggedEdges view for those two
        keys; per-rank consumers (SequentialRunner, the ladder scan)
        index it exactly like the stacked array (`arr[r][:e]`), while
        whole-array consumers fail loudly (the mesh Trainer wants the
        padded stack — rebuild without trim_edges for that)."""
        if trim_edges and not mmap:
            raise ValueError("trim_edges requires mmap=True (v3)")
        os.makedirs(path, exist_ok=True)
        manifest = {
            "format_version": (self.MMAP_FORMAT_VERSION if mmap
                               else self.FORMAT_VERSION),
            "num_parts": self.num_parts,
            "n_max": self.n_max,
            "b_max": self.b_max,
            "e_max": self.e_max,
            "n_train_global": self.n_train_global,
            "n_feat": self.n_feat,
            "n_class": self.n_class,
            "multilabel": self.multilabel,
            "source_edge_checksum": self.source_edge_checksum,
            "reorder": self.reorder,
            "layout_version": self.layout_version,
        }
        if trim_edges:
            manifest["trimmed_edges"] = True
        # the permutation arrays exist only on reordered layouts, so
        # they are saved conditionally — pre-reorder readers of the
        # fixed _ARRAYS list stay compatible either way
        extra = [k for k in self._REORDER_ARRAYS
                 if getattr(self, k) is not None]
        # arrays first, manifest last: exists() keys off the manifest, so
        # a reader polling a shared filesystem (multi-host prepare) never
        # observes a half-written artifact
        if mmap:
            adir = os.path.join(path, "arrays")
            os.makedirs(adir, exist_ok=True)
            for k in self._ARRAYS + extra:
                if trim_edges and k in ("edge_src", "edge_dst"):
                    arr = getattr(self, k)
                    for r in range(self.num_parts):
                        e_r = int(self.edge_count[r])
                        np.save(os.path.join(adir, f"{k}_r{r:03d}.npy"),
                                np.asarray(arr[r][:e_r]))
                    continue
                np.save(os.path.join(adir, f"{k}.npy"), getattr(self, k))
        else:
            np.savez_compressed(
                os.path.join(path, "arrays.npz"),
                **{k: getattr(self, k) for k in self._ARRAYS + extra},
            )
        with open(os.path.join(path, "manifest.json"), "w") as f:
            json.dump(manifest, f, indent=2)

    @staticmethod
    def load(path: str, parts=None) -> "ShardedGraph":
        """Load an artifact; `parts` (optional) is the global partition
        ids this process will own under the current elastic membership
        assignment — validated immediately (validate_assignment) so a
        redistributed relaunch pointed at a half-synced or mismatched
        artifact fails AT LOAD, not mid-epoch inside a collective."""
        with open(os.path.join(path, "manifest.json")) as f:
            manifest = json.load(f)
        version = manifest.pop("format_version", 0)
        if version == ShardedGraph.MMAP_FORMAT_VERSION:
            trimmed = manifest.pop("trimmed_edges", False)
            adir = os.path.join(path, "arrays")
            arrays = {}
            for k in ShardedGraph._ARRAYS:
                if trimmed and k in ("edge_src", "edge_dst"):
                    arrays[k] = _RaggedEdges(adir, k,
                                             manifest["num_parts"])
                    continue
                arrays[k] = np.load(os.path.join(adir, f"{k}.npy"),
                                    mmap_mode="r")
            for k in ShardedGraph._REORDER_ARRAYS:
                p = os.path.join(adir, f"{k}.npy")
                if os.path.exists(p):
                    arrays[k] = np.load(p, mmap_mode="r")
            sg = ShardedGraph(**manifest, cache_dir=path, loaded_from=path,
                              **arrays)
            if sg.reorder != "none":
                sg.validate_layout()
            if parts is not None:
                sg.validate_assignment(parts)
            return sg
        if version != ShardedGraph.FORMAT_VERSION:
            raise ValueError(
                f"partition artifact at {path} has format v{version}, "
                f"expected v{ShardedGraph.FORMAT_VERSION} (or mmap "
                f"v{ShardedGraph.MMAP_FORMAT_VERSION}); re-partition "
                f"(delete the directory or drop --skip-partition)"
            )
        arrays = np.load(os.path.join(path, "arrays.npz"))
        keys = ShardedGraph._ARRAYS + [k for k in
                                       ShardedGraph._REORDER_ARRAYS
                                       if k in arrays.files]
        sg = ShardedGraph(**manifest, cache_dir=path, loaded_from=path,
                          **{k: arrays[k] for k in keys})
        if sg.reorder != "none":
            sg.validate_layout()
        if parts is not None:
            sg.validate_assignment(parts)
        return sg

    def validate_assignment(self, parts) -> None:
        """Assignment-aware artifact check for elastic membership
        (resilience/elastic.py): `parts` must be distinct in-range
        partition ids, and for a trim_edges v3 artifact every per-rank
        edge file those partitions need must actually exist on THIS
        host — after redistribution a process opens ranks it never
        touched before, and a partially-synced shared filesystem must
        fail loudly here instead of as a FileNotFoundError three
        layers down. Records the set on ``local_parts``."""
        ids = sorted(int(p) for p in parts)
        if len(set(ids)) != len(ids):
            raise ValueError(
                f"assignment validation: duplicate partition ids in "
                f"{list(parts)}")
        if ids and (ids[0] < 0 or ids[-1] >= self.num_parts):
            raise ValueError(
                f"assignment validation: partition ids {ids} out of "
                f"range [0, {self.num_parts}) — membership assignment "
                f"and artifact disagree (stale ledger or wrong "
                f"--n-partitions?)")
        for key in ("edge_src", "edge_dst"):
            arr = getattr(self, key)
            if isinstance(arr, _RaggedEdges):
                missing = [
                    r for r in ids
                    if not os.path.exists(os.path.join(
                        arr._adir, f"{key}_r{r:03d}.npy"))]
                if missing:
                    raise ValueError(
                        f"assignment validation: trimmed artifact is "
                        f"missing {key} files for newly-assigned "
                        f"partitions {missing} (half-synced artifact "
                        f"directory?)")
        self.local_parts = tuple(ids)

    def validate_layout(self) -> None:
        """Loud host-side boundary-slot / permutation validation (the
        same contract as ops.bucket_spmm.validate_bucket_tables): every
        send-list entry must name a real inner node of its sender, and
        a reordered layout's permutation arrays must be present and
        mutually inverse per rank. Raises a named ValueError on the
        first violated invariant — a silent mismatch here becomes
        garbage halo rows (wrong features exchanged), not a crash."""
        P = self.num_parts
        for r in range(P):
            ic = int(self.inner_count[r])
            for d in range(P - 1):
                c = int(self.send_counts[r, d])
                if not c:
                    continue
                idx = np.asarray(self.send_idx[r, d, :c])
                if idx.min() < 0 or idx.max() >= ic:
                    raise ValueError(
                        f"boundary-slot validation: send_idx[r={r}, "
                        f"dist={d + 1}] references local id "
                        f"{int(idx.min())}..{int(idx.max())} outside "
                        f"[0, {ic}) — send lists and node layout "
                        f"disagree (stale or mismatched reorder "
                        f"permutation?)")
        has_perm = self.reorder_perm is not None
        if (self.reorder != "none") != has_perm or \
                has_perm == (self.reorder_inv is None):
            raise ValueError(
                f"boundary-slot validation: reorder tag "
                f"{self.reorder!r} but permutation arrays "
                f"{'present' if has_perm else 'absent'} — layout "
                f"metadata is inconsistent (rebuild the artifact)")
        if not has_perm:
            return
        perm = np.asarray(self.reorder_perm)
        inv = np.asarray(self.reorder_inv)
        want = (P, self.n_max)
        if perm.shape != want or inv.shape != want:
            raise ValueError(
                f"boundary-slot validation: reorder permutation shape "
                f"{perm.shape}/{inv.shape} != {want} — permutation/"
                f"table mismatch (artifact built for another layout?)")
        ar = np.arange(self.n_max)
        for r in range(P):
            ic = int(self.inner_count[r])
            p_r, i_r = perm[r, :ic], inv[r, :ic]
            if not (np.array_equal(np.sort(p_r), ar[:ic])
                    and np.array_equal(i_r[p_r], ar[:ic])):
                raise ValueError(
                    f"boundary-slot validation: reorder_perm/"
                    f"reorder_inv of rank {r} are not mutually inverse "
                    f"permutations of [0, {ic}) — permutation/table "
                    f"mismatch")
            if ic < self.n_max and not (perm[r, ic:] == -1).all():
                raise ValueError(
                    f"boundary-slot validation: reorder_perm rank {r} "
                    f"padding rows not -1 — permutation/table mismatch")

    @staticmethod
    def exists(path: str) -> bool:
        return os.path.exists(os.path.join(path, "manifest.json"))
