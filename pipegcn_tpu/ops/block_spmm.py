"""Hybrid block-dense SpMM: community-dense tiles on the MXU, sparse
remainder through the scatter-free bucket kernel.

The third TPU-native replacement for DGL's SpMM (reference
module/layer.py:47-49), aimed at the regime that actually decides the
headline benchmark: large community-structured graphs (Reddit-like).
Such graphs concentrate most edges in dense (destination-tile,
source-tile) blocks; a gather-based SpMM re-reads each source row
once per edge (~degree times), while a block-dense formulation reads
each participating feature tile once per block and turns the
aggregation into batched [T,S] @ [S,F] matmuls — exactly what the MXU
is for. Edges outside dense blocks (the uniform "background") fall
back to ops/bucket_spmm.py's gather + dense-reduction.

Traffic comparison per layer at Reddit scale (114M edges, F=256,
bf16): pure gather moves ~59 GB; with SBM-like structure the hybrid
moves ~2-4 GB of A-blocks + feature tiles plus the remainder's
gathers — an order of magnitude less, with the dense part's FLOPs
(~1 TFLOP) costing single-digit milliseconds on one v5e chip.

Mechanics:
  - Host tiles the destination space into rows of `tile` (T) and the
    source space into `tile` (S); (bd, bs) blocks with
    nnz * F >= T * S ("the dense A block is cheaper to read than the
    gathers it replaces") are materialized as dense [T, S] matrices
    holding per-edge 1.0 (duplicate edges accumulate).
  - Forward: per destination tile, sum_k A[blk_k] @ fbuf_tile[src_k]
    via one batched einsum inside a lax.scan over destination tiles.
  - Backward: the same A blocks, transposed roles — per SOURCE tile,
    sum_k A[blk_k]^T @ g_tile[dst_k] — so no scatter anywhere; the
    remainder's backward is the bucket kernel's transpose tables.
  - Mean normalization (in_deg division) is applied once at the end,
    after dense + remainder parts are summed.

All shapes are static; per-device plans pad to shared maxima
(block count, per-tile block lists, bucket caps) so a single traced
program serves every device in shard_map.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .bucket_spmm import (
    _bucket_widths,
    bucket_aggregate,
    build_tables_for_edges,
    degree_hist,
    fit_widths,
    ladder_prefix,
    stack_to_caps,
    validate_bucket_tables,
)

# HBM budget for the per-device dense-A tensor (see
# build_sharded_block_tables) — shared with estimate_block_coverage and
# the multichip projection so every consumer predicts the same spill.
DENSE_A_BYTE_BUDGET = 2 << 30


def budget_block_cap(byte_budget: int, tile: int, bits: int = 1) -> int:
    """Max dense A-blocks that fit `byte_budget` at `bits` per entry
    (1 = the optimistic bit-packed encoding for 0/1 graphs)."""
    return max(1, (int(byte_budget) * 8) // (tile * tile * bits))


def _pad_rows(mat: np.ndarray, rows: int, fill) -> np.ndarray:
    if mat.shape[0] == rows:
        return mat
    return np.pad(mat, ((0, rows - mat.shape[0]),) +
                  ((0, 0),) * (mat.ndim - 1), constant_values=fill)


def pack_a_blocks(a_blocks: np.ndarray) -> np.ndarray:
    """Bit-pack 0/1-valued dense blocks [B, T, S] -> uint8 [B, T, S//8].

    On simple graphs (edge multiplicity <= 1 — the common case after
    self-loop normalization) every A entry is 0 or 1, so one bit per
    entry suffices: 8x less HBM than int8, which buys 8x more dense
    blocks under the same byte budget. Little-endian bit order matches
    the device-side unpack in _dense_apply."""
    assert a_blocks.shape[-1] % 8 == 0, a_blocks.shape
    assert a_blocks.max(initial=0.0) <= 1.0, "bit-packing needs 0/1 A"
    return np.packbits(a_blocks.astype(bool), axis=-1, bitorder="little")


def _unpack_bits(blks: jax.Array, s: int, compute_dtype) -> jax.Array:
    """Device-side inverse of pack_a_blocks on gathered [..., T, S//8]
    uint8 blocks -> [..., T, S] in the compute dtype."""
    shifts = jnp.arange(8, dtype=jnp.uint8)
    bits = (blks[..., None] >> shifts) & jnp.uint8(1)
    return bits.reshape(blks.shape[:-1] + (s,)).astype(compute_dtype)


def _max_group_count(keys: np.ndarray, n_groups: int) -> int:
    return max(int(np.bincount(keys, minlength=n_groups).max(initial=0)),
               1)


def _group_by_key(keys, vals_a, vals_b, n_groups, widths, pad_a, pad_b):
    """Bucket the (vals_a[i], vals_b[i]) pairs of each key into
    power-of-2 width classes by the key's pair count — the tile-level
    analogue of bucket_spmm's degree bucketing. A flat [n_groups, K_max]
    layout wastes (K_max - K_mean)/K_max of the dense path (measured 60%
    at Reddit scale: K_max 90 vs K_mean 36); per-width classes bound the
    padding at 2x and concentrate it in the cheap small-K classes.

    Returns (mats, inv, counts): mats[w] = (a_mat, b_mat), each
    [n_w, widths[w]] int32 padded with pad_a/pad_b; inv [n_groups] int32
    mapping each key to its row in the width-class concatenation (keys
    with no pairs -> sum(counts), the caller's zero sentinel row);
    counts[w] = real rows in class w."""
    order = np.argsort(keys, kind="stable")
    va, vb = vals_a[order], vals_b[order]
    cnt = np.bincount(keys, minlength=n_groups)
    # The fill mask truncates at each key's class width, so a ladder
    # whose top rung is below the max per-key count would silently drop
    # (A-block, tile) pairs. Fail loudly instead of aggregating wrong.
    max_cnt = int(cnt.max(initial=0))
    if max_cnt > widths[-1]:
        raise ValueError(
            f"width ladder {tuple(widths)} tops out below the max "
            f"per-key pair count {max_cnt}; pairs would be dropped")
    ptr = np.zeros(n_groups + 1, np.int64)
    np.cumsum(cnt, out=ptr[1:])
    widths_arr = np.asarray(widths, dtype=np.int64)
    wid = np.minimum(np.searchsorted(widths_arr, np.maximum(cnt, 1)),
                     len(widths) - 1)
    mats, counts = [], []
    inv = np.full(n_groups, -1, np.int64)
    offset = 0
    for w_i, w in enumerate(widths):
        rows = np.nonzero((wid == w_i) & (cnt > 0))[0]
        n_w = rows.shape[0]
        a_mat = np.full((n_w, w), pad_a, np.int32)
        b_mat = np.full((n_w, w), pad_b, np.int32)
        if n_w:
            j = np.arange(w)[None, :]
            mask = j < cnt[rows][:, None]
            pos = (ptr[rows][:, None] + j)[mask]
            r, c = np.nonzero(mask)
            a_mat[r, c] = va[pos]
            b_mat[r, c] = vb[pos]
            inv[rows] = offset + np.arange(n_w)
        mats.append((a_mat, b_mat))
        counts.append(n_w)
        offset += n_w
    inv[inv < 0] = offset
    return mats, inv.astype(np.int32), counts


def _group_union(keys: np.ndarray, others: np.ndarray, n_key_tiles: int,
                 n_other_tiles: int, group: int, n_blocks_pad: int,
                 widths: Optional[Sequence[int]] = None):
    """Union-gather grouping: `group` CONSECUTIVE key tiles share one
    gathered union of their blocks' other-tiles.

    Consecutive (cluster-ordered) destination tiles reference heavily
    overlapping source tiles — measured on the clustered Reddit shard,
    grouping 2/4/8 dst tiles dedupes the dense path's F-tile reads to
    0.56x/0.33x/0.22x (docs/PERF_NOTES.md). Here each group's union is
    gathered ONCE and consumed directly by one batched contraction over
    (union slot, in-tile) — the F-traffic per group drops from
    sum(K_d) tiles to U = |union| tiles.

    keys/others: [B] key-tile / other-tile id per dense block (key=dst
    for the forward, key=src for the transpose). Returns
    (classes, inv, counts, widths):
      classes[w] = (a_idx [R_w, group, widths[w]] int32 into the padded
        A tensor (pad -> n_blocks_pad, the zero block),
        t_mat [R_w, widths[w]] int32 other-tile ids (pad ->
        n_other_tiles, the zero tile));
      inv [n_key_tiles] int32 -> r * group + d flat position in the
        class-concatenated [sum R_w, group] output (key tiles whose
        whole group has no dense block -> sum(R_w) * group, the zero
        sentinel row);
      counts[w] = real rows in class w. Groups are bucketed into
      x1.5-ladder U-width classes (same padding bound as the bucket
      kernel's degree ladder)."""
    B = int(keys.shape[0])
    n_groups_max = -(-n_key_tiles // group)
    if B == 0:
        widths = list(widths) if widths is not None else [1]
        classes = [(np.full((0, group, w), n_blocks_pad, np.int32),
                    np.full((0, w), n_other_tiles, np.int32))
                   for w in widths]
        inv = np.zeros(n_key_tiles, np.int32)
        return classes, inv, [0] * len(widths), widths
    gid = keys // group
    order = np.lexsort((others, gid))
    g_o, o_o = gid[order], others[order]
    blk_o = np.arange(B, dtype=np.int64)[order]
    d_o = (keys[order] % group).astype(np.int64)
    ug, gcnt = np.unique(g_o, return_counts=True)
    grow = np.repeat(np.arange(ug.shape[0]), gcnt)  # block -> group row
    # union slot of each block within its group: blocks are sorted by
    # (group, other), so a block starts a new union slot iff its
    # (group, other) differs from the previous block's
    new_flag = np.ones(B, bool)
    new_flag[1:] = (g_o[1:] != g_o[:-1]) | (o_o[1:] != o_o[:-1])
    slot = np.cumsum(new_flag) - 1
    gstart = np.zeros(ug.shape[0], np.int64)
    gstart[1:] = np.cumsum(gcnt)[:-1]
    first = slot[gstart]
    u_idx = slot - first[grow]
    u_of_group = np.add.reduceat(new_flag, gstart).astype(np.int64)

    if widths is None:
        widths = _bucket_widths(int(u_of_group.max(initial=1)))
    widths = list(widths)
    widths_arr = np.asarray(widths, dtype=np.int64)
    max_u = int(u_of_group.max(initial=0))
    if max_u > widths[-1]:
        # an explicitly passed ladder (e.g. reused from a group=1
        # layout) may top out below this device's max union size;
        # extend it rather than dropping blocks
        widths += [w for w in _bucket_widths(max_u) if w > widths[-1]]
        widths_arr = np.asarray(widths, dtype=np.int64)
    wid = np.minimum(np.searchsorted(widths_arr, np.maximum(u_of_group, 1)),
                     len(widths) - 1)

    classes, counts = [], []
    concat_row = np.full(n_groups_max, -1, np.int64)
    offset = 0
    for w_i, w in enumerate(widths):
        gsel = np.nonzero(wid == w_i)[0]
        n_w = int(gsel.shape[0])
        a_idx = np.full((n_w, group, w), n_blocks_pad, np.int32)
        t_mat = np.full((n_w, w), n_other_tiles, np.int32)
        if n_w:
            cls_row = np.full(ug.shape[0], -1, np.int64)
            cls_row[gsel] = np.arange(n_w)
            bsel = cls_row[grow] >= 0
            r = cls_row[grow[bsel]]
            a_idx[r, d_o[bsel], u_idx[bsel]] = blk_o[bsel]
            nf = bsel & new_flag
            t_mat[cls_row[grow[nf]], u_idx[nf]] = o_o[nf]
            concat_row[ug[gsel]] = offset + cls_row[gsel]
        classes.append((a_idx, t_mat))
        counts.append(n_w)
        offset += n_w
    key_tiles = np.arange(n_key_tiles, dtype=np.int64)
    gr = concat_row[key_tiles // group]
    inv = np.where(gr >= 0, gr * group + key_tiles % group,
                   offset * group)
    return classes, inv.astype(np.int32), counts, widths


def estimate_block_coverage(sg, tile: int, n_feat_hint: int,
                            nnz_threshold: Optional[int] = None,
                            byte_budget: Optional[int] = DENSE_A_BYTE_BUDGET,
                            ) -> float:
    """Fraction of real edges lying in (dst-tile, src-tile) blocks dense
    enough for the MXU path (>= `nnz_threshold`, defaulting to
    BlockPlan's read-cost break-even).

    The cheap O(E) structural signal `auto` uses to choose between the
    hybrid block kernel and the pure bucket kernel without paying for a
    full plan build. High coverage means the layout (usually
    cluster-renumbered, partition/halo.py `cluster`) concentrates
    community edges into dense tiles. Counting goes through np.unique
    on the occupied block ids (O(E) memory) — a dense bincount over the
    n_dst_tiles x n_src_tiles id space would be tens of GB at
    10M-node-shard scale.

    `byte_budget` mirrors build_sharded_block_tables' HBM cap: without
    it the estimate counts dense blocks the real plan would spill, and
    `auto` could pick the block kernel at a realized coverage far below
    the threshold. The cap tracks the builder's A encoding: 1-bit
    packing when the graph is simple (no duplicate edges) and
    tile % 8 == 0, else the int8 cap (8x fewer blocks) — the bf16/f32
    ratchets (multiplicity > 127) are rare enough to leave optimistic."""
    thr = nnz_threshold if nnz_threshold is not None else max(
        1, (tile * tile) // max(n_feat_hint, 1))
    n_src_rows = sg.n_max + sg.halo_size
    n_src_tiles = -(-n_src_rows // tile)
    cap = None
    if byte_budget is not None:
        bits = 1 if tile % 8 == 0 else 8
        if bits == 1:
            for r in range(sg.num_parts):
                e = int(sg.edge_count[r])
                key = (sg.edge_dst[r][:e].astype(np.int64) * n_src_rows
                       + sg.edge_src[r][:e].astype(np.int64))
                if np.unique(key).shape[0] < key.shape[0]:
                    bits = 8  # duplicate edges -> builder can't bit-pack
                    break
        cap = budget_block_cap(byte_budget, tile, bits)
    dense = tot = 0
    for r in range(sg.num_parts):
        cov, _, d, t = _part_block_stats(sg, r, tile, n_src_tiles, thr,
                                         max_blocks=cap)
        dense += d
        tot += t
    return dense / max(tot, 1)


def _part_block_stats(sg, r: int, tile: int, n_src_tiles: int, thr: int,
                      max_blocks: Optional[int] = None):
    """(coverage, dense_block_count, dense_edges, real_edges) of one
    device's shard at the given tile/threshold — the single definition
    of the dense/remainder split shared by estimate_block_coverage and
    the multichip projection tool. `max_blocks` keeps only the densest
    blocks, matching BlockPlan's budget cutoff."""
    e = int(sg.edge_count[r])
    src = sg.edge_src[r][:e].astype(np.int64)
    dst = sg.edge_dst[r][:e].astype(np.int64)
    real = dst < sg.n_max
    src, dst = src[real], dst[real]
    _, counts = np.unique((dst // tile) * n_src_tiles + (src // tile),
                          return_counts=True)
    sel = counts >= thr
    if max_blocks is not None and int(sel.sum()) > max_blocks:
        kept = np.sort(counts[sel])[-max_blocks:]
        dense, n_dense = int(kept.sum()), int(kept.shape[0])
    else:
        dense, n_dense = int(counts[sel].sum()), int(sel.sum())
    tot = int(src.shape[0])
    return dense / max(tot, 1), n_dense, dense, tot


class BlockPlan:
    """Host-side hybrid plan for one device's edge list.

    Attributes (all numpy, static shapes):
      a_blocks:    [B, T, S] f32 — dense block values (1.0 per edge);
                   block B-1 is NOT special; a zero block is appended
                   on device as index B.
      fwd_groups/fwd_ginv/fwd_gcounts: destination tiles' (A-block,
                   source-tile) pair lists, K-bucketed into power-of-2
                   width classes (_group_by_key) so per-tile padding
                   never exceeds 2x; fwd_ginv restores tile order from
                   the class concatenation.
      bwd_groups/bwd_ginv/bwd_gcounts: the transpose — per source tile,
                   the A-block and destination-tile pairs.
      rem_*:       remainder edges' bucket tables (fwd + transpose).
    """

    def __init__(self, edge_src: np.ndarray, edge_dst: np.ndarray,
                 n_out: int, n_src_rows: int, n_feat: int,
                 tile: int = 256,
                 nnz_threshold: Optional[int] = None,
                 fwd_widths: Optional[Sequence[int]] = None,
                 bwd_widths: Optional[Sequence[int]] = None,
                 fwd_k_widths: Optional[Sequence[int]] = None,
                 bwd_k_widths: Optional[Sequence[int]] = None,
                 max_blocks: Optional[int] = None,
                 group: int = 1):
        T = S = tile
        self.tile = tile
        self.group = max(1, int(group))
        real = edge_dst < n_out
        src = edge_src[real].astype(np.int64)
        dst = edge_dst[real].astype(np.int64)
        n_dst_tiles = -(-n_out // T)
        n_src_tiles = -(-n_src_rows // S)
        self.n_out = n_out
        self.n_src_rows = n_src_rows
        self.n_dst_tiles = n_dst_tiles
        self.n_src_tiles = n_src_tiles

        if nnz_threshold is None:
            # dense block pays T*S A-reads + S*F tile-read amortized;
            # each replaced edge saves an F-wide gather
            nnz_threshold = max(1, (T * S) // max(n_feat, 1))
        bid = (dst // T) * n_src_tiles + (src // S)
        from ..native import stable_argsort

        order = stable_argsort(bid)
        src_o, dst_o, bid_o = src[order], dst[order], bid[order]
        uniq, starts, counts = np.unique(bid_o, return_index=True,
                                         return_counts=True)
        dense_sel = counts >= nnz_threshold
        if max_blocks is not None and int(dense_sel.sum()) > max_blocks:
            # HBM budget: keep only the densest blocks (best edges-
            # replaced-per-byte); the rest spill to the sparse remainder
            cutoff = np.sort(counts[dense_sel])[-max_blocks]
            dense_sel &= counts >= cutoff
            if int(dense_sel.sum()) > max_blocks:  # ties at the cutoff
                over = int(dense_sel.sum()) - max_blocks
                tie_idx = np.nonzero(dense_sel & (counts == cutoff))[0]
                dense_sel[tie_idx[:over]] = False

        # ---- dense blocks ----
        dense_ids = uniq[dense_sel]
        B = int(dense_ids.shape[0])
        # vectorized scatter-add over all dense-block edges (a per-block
        # Python loop is minutes at 100M-edge scale), chunked over block
        # ranges so the int64 bincount transient stays ~2 GB instead of
        # B*T*S*8 bytes (17 GB at Reddit scale)
        in_dense_o = dense_sel[np.searchsorted(uniq, bid_o)]
        k_of_edge = np.searchsorted(dense_ids, bid_o[in_dense_o])
        src_d = src_o[in_dense_o] % S
        dst_d = dst_o[in_dense_o] % T
        self.a_blocks = np.zeros((B, T, S), np.float32)
        blk_chunk = max(1, (1 << 28) // (T * S))  # ~2 GB int64 transient
        # k_of_edge is ascending (edges sorted by bid) -> one searchsorted
        # split per chunk boundary instead of boolean masks
        bounds = np.searchsorted(
            k_of_edge, np.arange(0, B + blk_chunk, blk_chunk))
        for ci in range(len(bounds) - 1):
            lo, hi = bounds[ci], bounds[ci + 1]
            if lo == hi:
                continue
            k0 = ci * blk_chunk
            n_blk = min(blk_chunk, B - k0)
            flat = ((k_of_edge[lo:hi] - k0) * (T * S)
                    + dst_d[lo:hi] * S + src_d[lo:hi])
            self.a_blocks[k0:k0 + n_blk] += np.bincount(
                flat, minlength=n_blk * T * S
            ).astype(np.float32).reshape(n_blk, T, S)
        bd = (dense_ids // n_src_tiles).astype(np.int64)
        bs = (dense_ids % n_src_tiles).astype(np.int64)

        blk_idx = np.arange(B, dtype=np.int64)
        if self.group > 1:
            # union-gather layout: `group` consecutive key tiles share
            # one gathered union of other-tiles (see _group_union)
            (self.fwd_u_classes, self.fwd_u_inv, self.fwd_u_counts,
             self.fwd_k_widths) = _group_union(
                bd, bs, n_dst_tiles, n_src_tiles, self.group, B,
                widths=fwd_k_widths)
            (self.bwd_u_classes, self.bwd_u_inv, self.bwd_u_counts,
             self.bwd_k_widths) = _group_union(
                bs, bd, n_src_tiles, n_dst_tiles, self.group, B,
                widths=bwd_k_widths)
        else:
            self.fwd_k_widths = list(
                fwd_k_widths if fwd_k_widths is not None
                else _bucket_widths(_max_group_count(bd, n_dst_tiles)))
            self.bwd_k_widths = list(
                bwd_k_widths if bwd_k_widths is not None
                else _bucket_widths(_max_group_count(bs, n_src_tiles)))
            self.fwd_groups, self.fwd_ginv, self.fwd_gcounts = \
                _group_by_key(bd, blk_idx, bs, n_dst_tiles,
                              self.fwd_k_widths, pad_a=B,
                              pad_b=n_src_tiles)
            self.bwd_groups, self.bwd_ginv, self.bwd_gcounts = \
                _group_by_key(bs, blk_idx, bd, n_src_tiles,
                              self.bwd_k_widths, pad_a=B,
                              pad_b=n_dst_tiles)

        # ---- sparse remainder (bucket tables both directions) ----
        # widths fitted to the remainder's own degree histograms
        # (bucket_spmm.fit_widths) unless given; the histograms and the
        # edges are kept so the sharded builder can fit ONE ladder over
        # every device's remainder and rebuild these tables alone
        self._rem_edges = (src_o[~in_dense_o], dst_o[~in_dense_o])
        r_src, r_dst = self._rem_edges
        self.rem_count = int(r_src.shape[0])
        self.rem_deg_in = np.bincount(r_dst)
        self.rem_deg_out = np.bincount(r_src)
        self.rem_fwd_widths = self.rem_bwd_widths = None
        self.set_remainder_widths(
            fwd_widths if fwd_widths is not None
            else fit_widths(degree_hist([self.rem_deg_in])),
            bwd_widths if bwd_widths is not None
            else fit_widths(degree_hist([self.rem_deg_out])))

    def set_remainder_widths(self, fwd_widths: Sequence[int],
                             bwd_widths: Sequence[int]) -> None:
        """(Re)build the remainder's bucket tables at the given widths;
        a direction already at them is left alone. The dense half is
        not touched: which edges are remainder is fixed by the block
        selection."""
        r_src, r_dst = self._rem_edges
        if list(fwd_widths) != self.rem_fwd_widths:
            self.rem_fwd_widths = list(fwd_widths)
            self.rem_fwd_mats, self.rem_fwd_inv, _ = \
                build_tables_for_edges(r_src, r_dst, self.n_out,
                                       self.n_src_rows,
                                       self.rem_fwd_widths)
        if list(bwd_widths) != self.rem_bwd_widths:
            self.rem_bwd_widths = list(bwd_widths)
            self.rem_bwd_mats, self.rem_bwd_inv, _ = \
                build_tables_for_edges(r_dst, r_src, self.n_src_rows,
                                       self.n_out, self.rem_bwd_widths)


# bound on one dense-apply chunk's materialized A elements (unpacked,
# compute dtype): 32M elems = 64 MB bf16
_DENSE_CHUNK_ELEMS = 32 * 1024 * 1024


def _apply_classes(classes, compute, per_row_elems, pads, inv, out_tile,
                   n_feat, out_rows):
    """Shared scaffold of the dense applies (per-tile and grouped): run
    `compute` over each class's index mats — chunked via a lax.scan
    over padded row blocks whenever the per-chunk transient would
    exceed _DENSE_CHUNK_ELEMS — then concatenate every class's output
    tiles (plus one zero sentinel row), restore output-tile order with
    `inv`, and flatten tiles to rows.

    classes: list of index-mat tuples (leading axis = class rows);
    compute(*mats) -> [rows, ..., out_tile, n_feat] f32 (extra middle
    axes are flattened into the tile axis); per_row_elems(mats) ->
    transient elements per row (the chunk divisor); pads: per-mat pad
    constants for the scan's padded tail (must point at zero
    blocks/tiles so pad rows compute zeros that get sliced away)."""
    outs = []
    for mats in classes:
        n_w = mats[0].shape[0]
        if n_w == 0:
            continue
        rpc = max(1, _DENSE_CHUNK_ELEMS // max(1, per_row_elems(mats)))
        if n_w <= rpc:
            out = compute(*mats)
        else:
            n_chunks = -(-n_w // rpc)
            pad_rows = n_chunks * rpc - n_w
            padded = tuple(
                jnp.pad(m, ((0, pad_rows),) + ((0, 0),) * (m.ndim - 1),
                        constant_values=p)
                for m, p in zip(mats, pads))

            def body(_, idx):
                return None, compute(*idx)

            _, chunks = jax.lax.scan(
                body, None,
                tuple(m.reshape((n_chunks, rpc) + m.shape[1:])
                      for m in padded))
            out = chunks.reshape((n_chunks * rpc,)
                                 + chunks.shape[2:])[:n_w]
        outs.append(out.reshape(-1, out_tile, n_feat))
    # mode='clip': indices in-bounds by construction (appended zero
    # rows are the sentinels) — fill-mode gathers are the one path
    # that can mint NaN from valid data (bucket_spmm rationale)
    with jax.named_scope("unpermute"):
        outs.append(jnp.zeros((1, out_tile, n_feat), jnp.float32))
        res = jnp.take(jnp.concatenate(outs, axis=0), inv, axis=0,
                       mode="clip")
        return res.reshape(-1, n_feat)[:out_rows]


def _dense_apply(a_pad, groups, ginv, tiles, T, out_rows, n_feat,
                 compute_dtype, transpose=False, packed=False):
    """For every output tile i: sum_k A[blk(i,k)] (@ or transposed-@)
    tiles[tile(i,k)], where the (blk, tile) pair lists are K-bucketed
    into power-of-2 width classes (`groups`: [(blk_mat, tile_mat)] per
    class, `ginv` restoring tile order — see _group_by_key).

    a_pad: [B+1, T, S] in its STORED dtype (possibly int8; last block =
    zeros) — or, with packed=True, bit-packed [B+1, T, S//8] uint8 —
    the cast/unpack to the compute dtype happens per chunk on the
    gathered [R, K, T, S] slice, so the full A tensor is never
    materialized in a wider dtype; likewise the backward's A^T lives in
    the einsum spec, never as a transposed copy. tiles: [n_tiles+1, S,
    F] (last = zeros). Returns [n_out_tiles*T, F] f32.

    Each class runs as one batched contraction ([R, T, K*S] @
    [R, K*S, F] after XLA canonicalization — MXU-shaped), chunked over
    rows so the unpacked A transient stays bounded (_apply_classes)."""
    spec = "rkts,rktf->rsf" if transpose else "rkts,rksf->rtf"
    s = a_pad.shape[-1] * 8 if packed else a_pad.shape[-1]

    def compute(bi, ti):  # [R, K] x2 -> [R, T, F] f32
        with jax.named_scope("unpack"):
            blks = jnp.take(a_pad, bi, axis=0, mode="clip")
            blks = _unpack_bits(blks, s, compute_dtype) if packed \
                else blks.astype(compute_dtype)
        with jax.named_scope("tile"):
            tls = jnp.take(tiles, ti, axis=0,
                           mode="clip")           # [R, K, S|T, F]
            return jnp.einsum(spec, blks, tls,
                              preferred_element_type=jnp.float32)

    # transients: unpacked A [R, K, T, S] + gathered tiles [R, K, S, F]
    return _apply_classes(
        groups, compute,
        lambda mats: mats[0].shape[1] * s * max(T, n_feat),
        (a_pad.shape[0] - 1, tiles.shape[0] - 1),
        ginv, T, n_feat, out_rows)


def _dense_apply_grouped(a_pad, classes, inv, tiles, T, out_rows,
                         n_feat, compute_dtype, transpose=False,
                         packed=False):
    """Union-gather dense apply: for every group of `group` consecutive
    output tiles, gather the union of the group's source tiles ONCE
    ([R, U, S, F]) and consume it directly in one batched contraction
    against the group's gathered A blocks ([R, group, U, T, S]) — the
    per-tile F-traffic dedupe _group_union documents.

    classes: [(a_idx [R, group, U_w], t_mat [R, U_w])] per U-width
    class; inv restores output-tile order from the class-concatenated
    [sum R_w * group] flat tile axis. Forward contracts (u, s) -> out
    [R, group, T, F]; transpose contracts (u, t) -> [R, group, S, F]
    (the backward's per-source-tile sum of A^T @ g)."""
    spec = "rduts,rutf->rdsf" if transpose else "rduts,rusf->rdtf"
    s = a_pad.shape[-1] * 8 if packed else a_pad.shape[-1]

    def compute(ai, ti):  # [R, group, U] + [R, U] -> [R, group, T|S, F]
        with jax.named_scope("unpack"):
            blks = jnp.take(a_pad, ai, axis=0,
                            mode="clip")          # [R, G, U, T, S(/8)]
            blks = _unpack_bits(blks, s, compute_dtype) if packed \
                else blks.astype(compute_dtype)
        with jax.named_scope("tile"):
            tls = jnp.take(tiles, ti, axis=0,
                           mode="clip")           # [R, U, S|T, F]
            return jnp.einsum(spec, blks, tls,
                              preferred_element_type=jnp.float32)

    # transients: unpacked A [R, G, U, T, S] + gathered union tiles
    # [R, U, S, F] (F can exceed G*T on wide input layers); square
    # tiles, so the output's in-tile dim is T in both directions
    return _apply_classes(
        classes, compute,
        lambda mats: max(mats[0].shape[1] * mats[0].shape[2] * T * s,
                         mats[0].shape[2] * s * n_feat),
        (a_pad.shape[0] - 1, tiles.shape[0] - 1),
        inv, T, n_feat, out_rows)


def make_block_spmm_fn(
    plan_arrays: Dict[str, jax.Array],
    in_deg: jax.Array,
    n_out: int,
    n_src_rows: int,
    tile: int,
    chunk_edges: Optional[int] = None,
    rem_dtype: Optional[str] = None,
    rem_amax: bool = False,
):
    """Differentiable hybrid mean-aggregation closure f(fbuf [R, F]) ->
    f32 [n_out, F]. `plan_arrays` holds the BlockPlan tensors (see
    sharded_block_tables for keys), already stripped to per-device blocks
    when used inside shard_map. `rem_dtype` narrows the REMAINDER's
    gather transport only (bucket_spmm.transport_dtypes) — the dense
    MXU path keeps the activation dtype. `rem_amax` swaps the static
    saturating fp8 cast for the amax-clamped one (the de-scale applies
    to the remainder alone, before it joins the dense partial).

    Named for the profiler (obs/profiler.py SCOPE_NAMES): `unpack` (the
    A-block take, the bit unpack or cast to the compute dtype), `tile`
    (the tiling of the operand, the tile take and the einsum),
    `unpermute` (output-tile order restored), `rem_gather` /
    `rem_reduce` / `rem_unpermute` (bucket_aggregate over the
    remainder), `cast` (the remainder's transport casts, amax
    included), `scale` (degree division, amax de-scale, the sum of the
    two partials), the whole backward under `bwd`."""
    from .bucket_spmm import (amax_transport_cast, transport_cast,
                              transport_dtypes)

    d = plan_arrays
    deg_col = in_deg[:, None]
    T = tile
    rem_fwd_dt, rem_bwd_dt = transport_dtypes(rem_dtype)

    def _rem_cast(x, dt):
        with jax.named_scope("cast"):
            if rem_amax:
                return amax_transport_cast(x, dt)
            return transport_cast(x, dt), None

    def tiles_of(x, n_tiles, S):
        with jax.named_scope("tile"):
            rpad = n_tiles * S - x.shape[0]
            xp = jnp.pad(x, ((0, rpad + S), (0, 0)))  # + one zero tile
            return xp.reshape(n_tiles + 1, S, x.shape[-1])

    def rem_mats(prefix):
        return [d[k] for k in sorted(d)
                if k.startswith(prefix) and not k.endswith("inv")]

    def dense_groups(direction):  # [(blk_mat, tile_mat)] in width order
        bs_ = sorted(k[:-1] for k in d
                     if k.startswith(f"blk_{direction}_g")
                     and k.endswith("b"))
        return [(d[k + "b"], d[k + "t"]) for k in bs_]

    def union_classes(direction):  # [(a_idx, t_mat)] in U-width order
        bs_ = sorted(k[:-1] for k in d
                     if k.startswith(f"blk_{direction}u_g")
                     and k.endswith("a"))
        return [(d[k + "a"], d[k + "t"]) for k in bs_]

    grouped = "blk_fwdu_inv" in d
    packed = "blk_a_bits" in d

    def a_padded():
        # append the zero block IN the stored dtype (bit-packed uint8 /
        # int8/bf16/f32); the per-step unpack/cast to the compute dtype
        # lives in _dense_apply
        a = d["blk_a_bits"] if packed else d["blk_a"]
        with jax.named_scope("unpack"):
            return jnp.concatenate(
                [a, jnp.zeros((1,) + a.shape[1:], a.dtype)], axis=0)

    @jax.custom_vjp
    def f(fbuf):
        n_s_tiles = -(-n_src_rows // T)
        tiles = tiles_of(fbuf, n_s_tiles, T)
        if grouped:
            dense = _dense_apply_grouped(
                a_padded(), union_classes("fwd"), d["blk_fwdu_inv"],
                tiles, T, n_out, fbuf.shape[-1], fbuf.dtype,
                packed=packed)
        else:
            dense = _dense_apply(a_padded(), dense_groups("fwd"),
                                 d["blk_fwd_ginv"], tiles, T, n_out,
                                 fbuf.shape[-1], fbuf.dtype,
                                 packed=packed)
        rem_in, rem_inv = _rem_cast(fbuf, rem_fwd_dt)
        rem = bucket_aggregate(
            rem_in, rem_mats("blkrem_fwd_"), d["blkrem_fwd_inv"],
            chunk_edges=chunk_edges, scope="rem_")
        with jax.named_scope("scale"):
            if rem_inv is not None:
                rem = rem * rem_inv
            return (dense + rem) / deg_col

    def fwd(fbuf):
        return f(fbuf), jnp.zeros((0,), fbuf.dtype)

    def bwd(proto, g):
        with jax.named_scope("bwd"):
            return _bwd(proto, g)

    def _bwd(proto, g):
        with jax.named_scope("scale"):
            gd32 = g.astype(jnp.float32) / deg_col
        with jax.named_scope("cast"):
            gd = gd32.astype(proto.dtype)
        # transpose dense: per source tile, sum A^T @ g_tile
        n_d_tiles = -(-n_out // T)
        g_tiles = tiles_of(gd, n_d_tiles, T)
        if grouped:
            dense = _dense_apply_grouped(
                a_padded(), union_classes("bwd"), d["blk_bwdu_inv"],
                g_tiles, T, n_src_rows, g.shape[-1], gd.dtype,
                transpose=True, packed=packed)
        else:
            dense = _dense_apply(a_padded(), dense_groups("bwd"),
                                 d["blk_bwd_ginv"], g_tiles, T,
                                 n_src_rows, g.shape[-1], gd.dtype,
                                 transpose=True, packed=packed)
        # the remainder's transport cast comes straight from the f32
        # cotangent — not through the proto.dtype rounding above
        # (matching bucket_spmm's single-rounding path)
        if rem_bwd_dt is not None:
            rem_in, rem_inv = _rem_cast(gd32, rem_bwd_dt)
        else:
            rem_in, rem_inv = gd, None
        rem = bucket_aggregate(
            rem_in, rem_mats("blkrem_bwd_"), d["blkrem_bwd_inv"],
            chunk_edges=chunk_edges, scope="rem_")
        with jax.named_scope("scale"):
            if rem_inv is not None:
                rem = rem * rem_inv
            return ((dense + rem).astype(proto.dtype),)

    f.defvjp(fwd, bwd)
    return f


def plan_to_arrays(p: BlockPlan) -> Dict[str, np.ndarray]:
    """Flatten a BlockPlan into the array dict make_block_spmm_fn uses."""
    arrs = {
        "blk_a": p.a_blocks,
        "blkrem_fwd_inv": p.rem_fwd_inv,
        "blkrem_bwd_inv": p.rem_bwd_inv,
    }
    if p.group > 1:
        arrs["blk_fwdu_inv"] = p.fwd_u_inv
        arrs["blk_bwdu_inv"] = p.bwd_u_inv
        for direction, classes in (("fwd", p.fwd_u_classes),
                                   ("bwd", p.bwd_u_classes)):
            for w_i, (a_idx, t_mat) in enumerate(classes):
                if a_idx.shape[0]:
                    arrs[f"blk_{direction}u_g{w_i:02d}a"] = a_idx
                    arrs[f"blk_{direction}u_g{w_i:02d}t"] = t_mat
    else:
        arrs["blk_fwd_ginv"] = p.fwd_ginv
        arrs["blk_bwd_ginv"] = p.bwd_ginv
        for direction, groups in (("fwd", p.fwd_groups),
                                  ("bwd", p.bwd_groups)):
            for w_i, (a_mat, b_mat) in enumerate(groups):
                if a_mat.shape[0]:
                    arrs[f"blk_{direction}_g{w_i:02d}b"] = a_mat
                    arrs[f"blk_{direction}_g{w_i:02d}t"] = b_mat
    # remainder tables are slot-major [w, rows] (bucket_spmm): a
    # bucket with no row has no column
    for b, m in enumerate(p.rem_fwd_mats):
        if m.shape[1]:
            arrs[f"blkrem_fwd_{b:02d}"] = m
    for b, m in enumerate(p.rem_bwd_mats):
        if m.shape[1]:
            arrs[f"blkrem_bwd_{b:02d}"] = m
    return arrs


def build_sharded_block_tables(sg, tile: int = 256,
                               n_feat_hint: int = 256,
                               byte_budget: int = DENSE_A_BYTE_BUDGET,
                               nnz_threshold: Optional[int] = None,
                               group: int = 1,
                               ) -> Tuple[Dict[str, np.ndarray], int]:
    """Stacked per-device hybrid plans (leading device axis), padded to
    shared shapes: same B (dense block count), same K (per-tile block
    list width), same remainder bucket ladders/caps. Returns
    (tables, tile)."""
    P = sg.num_parts
    n_src_rows = sg.n_max + sg.halo_size
    # HBM budget for the per-device dense-A tensor: keep the densest
    # blocks under byte_budget, spill the rest to the sparse remainder.
    # Past this size the A reads stop paying for the gathers they
    # replace and, at Reddit scale, the table alone would crowd a v5e's
    # 16 GB HBM (an unbudgeted clustered Reddit shard produced 6.5 GB).
    # First pass assumes bit-packed A (1 bit per entry — the common
    # case: simple graphs have 0/1 edge multiplicities); if the counts
    # force a wider dtype, plans rebuild under the correspondingly
    # smaller cap.
    def cap_for(bits: int) -> int:
        return budget_block_cap(byte_budget, tile, bits)

    # narrowest exact encoding for the A counts: 1-bit packing (counts
    # <= 1) buys 8x the dense coverage of int8 (<= 127) per HBM byte,
    # which in turn halves bf16 and quarters f32 (the device
    # unpacks/casts A to the activation dtype at use)
    import ml_dtypes

    def build_plans(cap, fw=None, bw=None, fk=None, bk=None):
        # fresh ladders unless given: a different block cap changes
        # which edges land in the remainder, and a ladder built for a
        # different remainder can under-size its top bucket
        # (build_tables_for_edges raises on one)
        return [
            BlockPlan(sg.edge_src[r], sg.edge_dst[r], sg.n_max,
                      n_src_rows, n_feat_hint, tile=tile,
                      nnz_threshold=nnz_threshold,
                      fwd_widths=fw, bwd_widths=bw,
                      fwd_k_widths=fk, bwd_k_widths=bk, max_blocks=cap,
                      group=group)
            for r in range(P)
        ]

    def required_bits(plans):
        a_max = max((float(p.a_blocks.max(initial=0.0)) for p in plans),
                    default=0.0)
        if a_max <= 1 and tile % 8 == 0:  # pack_a_blocks needs S % 8
            return 1, None  # bit-packed uint8 (pack_a_blocks)
        if a_max <= 127:
            return 8, np.int8
        if a_max <= 256:
            return 16, ml_dtypes.bfloat16
        return 32, np.float32

    # fixpoint on the A encoding: cap = budget / (bits per entry), but
    # the counts (and thus the bits required for exactness) depend on
    # which blocks the cap keeps. bits only ratchets up, so this
    # terminates in <= 4 builds. The SHIPPED encoding (emit_bits /
    # a_dtype) is re-read off the final plans: it may be narrower than
    # the cap assumed (e.g. the smaller cap dropped every multi-edge
    # block) — exact, merely under-using the budget.
    bits = 1
    while True:
        plans = build_plans(cap_for(bits))
        emit_bits, a_dtype = required_bits(plans)
        if emit_bits <= bits:
            break
        bits = emit_bits

    # unify ladders over the devices. The dense K classes keep the x1.5
    # ladder at the longest device's length. The remainder's widths
    # are fitted ONCE to the histograms of all the plans (known
    # only now, after the dense selection). A re-build keeps the SAME
    # cap, so the dense selection, and thus every remainder degree and
    # per-tile block count, is unchanged and the unified ladders
    # (covering the global max) are safe for every device; where only
    # the remainder's widths differ from a plan's own fit (P > 1),
    # only its remainder tables are rebuilt
    fk_len = max(len(p.fwd_k_widths) for p in plans)
    bk_len = max(len(p.bwd_k_widths) for p in plans)
    fk = ladder_prefix(fk_len)
    bk = ladder_prefix(bk_len)
    fw = fit_widths(degree_hist(p.rem_deg_in for p in plans))
    bw = fit_widths(degree_hist(p.rem_deg_out for p in plans))
    if any(p.fwd_k_widths != fk or p.bwd_k_widths != bk for p in plans):
        plans = build_plans(cap_for(bits), fw=fw, bw=bw, fk=fk, bk=bk)
    else:
        for p in plans:
            p.set_remainder_widths(fw, bw)

    B_max = max(p.a_blocks.shape[0] for p in plans)

    def dense_counts(p, direction):
        if group > 1:
            return (p.fwd_u_counts if direction == "fwd"
                    else p.bwd_u_counts)
        return p.fwd_gcounts if direction == "fwd" else p.bwd_gcounts

    fk_caps = [max(dense_counts(p, "fwd")[w] for p in plans)
               for w in range(fk_len)]
    bk_caps = [max(dense_counts(p, "bwd")[w] for p in plans)
               for w in range(bk_len)]

    def reoffset_inv(inv, counts, caps):
        inv = inv.astype(np.int64)
        out = np.full_like(inv, sum(caps))
        off_old = off_new = 0
        for n_b, cap in zip(counts, caps):
            sel = (inv >= off_old) & (inv < off_old + n_b)
            out[sel] = inv[sel] - off_old + off_new
            off_old += n_b
            off_new += cap
        return out.astype(np.int32)

    tables: Dict[str, List[np.ndarray]] = {}
    for p in plans:
        B = p.a_blocks.shape[0]
        a_pad = _pad_rows(p.a_blocks, B_max, 0.0)
        arrs = {
            # pad dense blocks to B_max with zero blocks; pad indices
            # point at the appended zero block (index B_max on device)
            ("blk_a_bits" if emit_bits == 1 else "blk_a"):
                pack_a_blocks(a_pad) if emit_bits == 1
                else a_pad.astype(a_dtype),
        }
        if group > 1:
            # inv entries encode r * group + d; reoffset the row part
            # to the shared per-class caps (sentinel sum(counts)*G ->
            # sum(caps)*G falls out of reoffset_inv's default)
            arrs["blk_fwdu_inv"] = (
                reoffset_inv(p.fwd_u_inv // group, p.fwd_u_counts,
                             fk_caps).astype(np.int64) * group
                + p.fwd_u_inv % group).astype(np.int32)
            arrs["blk_bwdu_inv"] = (
                reoffset_inv(p.bwd_u_inv // group, p.bwd_u_counts,
                             bk_caps).astype(np.int64) * group
                + p.bwd_u_inv % group).astype(np.int32)
            for direction, classes, caps in (
                    ("fwd", p.fwd_u_classes, fk_caps),
                    ("bwd", p.bwd_u_classes, bk_caps)):
                for w_i, (a_idx, t_mat) in enumerate(classes):
                    if not caps[w_i]:
                        continue
                    a_idx = np.where(a_idx == B, B_max, a_idx)
                    arrs[f"blk_{direction}u_g{w_i:02d}a"] = _pad_rows(
                        a_idx, caps[w_i], B_max).astype(np.int32)
                    arrs[f"blk_{direction}u_g{w_i:02d}t"] = _pad_rows(
                        t_mat, caps[w_i],
                        p.n_src_tiles if direction == "fwd"
                        else p.n_dst_tiles).astype(np.int32)
        else:
            arrs["blk_fwd_ginv"] = reoffset_inv(p.fwd_ginv,
                                                p.fwd_gcounts, fk_caps)
            arrs["blk_bwd_ginv"] = reoffset_inv(p.bwd_ginv,
                                                p.bwd_gcounts, bk_caps)
            for direction, groups, caps in (
                    ("fwd", p.fwd_groups, fk_caps),
                    ("bwd", p.bwd_groups, bk_caps)):
                for w_i, (a_mat, b_mat) in enumerate(groups):
                    if not caps[w_i]:
                        continue
                    # remap this device's pad-block id B to the shared
                    # zero block B_max; pad rows point at it entirely
                    # (the matching tile pad is the zero tile, already
                    # shared)
                    a_mat = np.where(a_mat == B, B_max, a_mat)
                    arrs[f"blk_{direction}_g{w_i:02d}b"] = _pad_rows(
                        a_mat, caps[w_i], B_max).astype(np.int32)
                    arrs[f"blk_{direction}_g{w_i:02d}t"] = _pad_rows(
                        b_mat, caps[w_i],
                        p.n_src_tiles if direction == "fwd"
                        else p.n_dst_tiles).astype(np.int32)
        for k, v in arrs.items():
            tables.setdefault(k, []).append(v)
    stacked = {k: np.stack(v) for k, v in tables.items()}
    # the remainder's bucket tables, slot-major at shared row caps
    stacked.update(stack_to_caps(
        [(p.rem_fwd_mats, p.rem_fwd_inv) for p in plans], n_src_rows,
        "blkrem_fwd"))
    stacked.update(stack_to_caps(
        [(p.rem_bwd_mats, p.rem_bwd_inv) for p in plans], sg.n_max,
        "blkrem_bwd"))
    # every remainder edge sits in exactly one slot, both directions
    validate_bucket_tables(stacked, sg.n_max, n_src_rows,
                           n_edges=[p.rem_count for p in plans],
                           stem="blkrem")
    return stacked, tile


def make_device_block_spmm_fn(d: Dict[str, jax.Array], in_deg: jax.Array,
                              n_out: int, n_src_rows: int, tile: int,
                              chunk_edges: Optional[int] = None,
                              rem_dtype: Optional[str] = None,
                              rem_amax: bool = False):
    """Bind per-device blocks of build_sharded_block_tables (inside
    shard_map, leading device axis stripped)."""
    plan_arrays = {k: v for k, v in d.items()
                   if k.startswith(("blk_", "blkrem_"))}
    return make_block_spmm_fn(
        plan_arrays, in_deg, n_out, n_src_rows, tile, chunk_edges,
        rem_dtype, rem_amax)
