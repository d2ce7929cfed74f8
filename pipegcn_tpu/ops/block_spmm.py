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
  - Backward: per SOURCE tile, sum_k A[blk_k]^T @ g_tile[dst_k] — so
    no scatter anywhere; the remainder's backward is the bucket
    kernel's transpose tables.
  - A is STORED in the order the step reads it, once a direction: the
    builder writes, for every class of output rows, the blocks of its
    (row, slot) pairs side by side as ONE operand [rows, K, S(/8), G*T]
    (the backward's copy holds the transposes: [rows, K, T(/8), G*S]),
    zero blocks in the pad slots, rows padded to the chunk multiple.
    Both directions run the one contraction "rksm,rksf->rmf" on a
    slice of it: the step gathers no A block, lays none out again and
    pads none. The output rows sit on the minor axis and the
    contraction is packed along the second-minor one because that is
    the layout the chip's compiler reads the einsum's left operand in
    (tests/test_tpu_compile.py): stored otherwise it is copied there
    every call.
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
    Direction,
    _bucket_widths,
    bucket_aggregate,
    degree_hist,
    direction_tables,
    fit_widths,
    stack_direction,
    validate_bucket_tables,
)


# HBM budget for a device's dense-A tensors AS STORED: both directions'
# arrangements, class and chunk pads and the stacked devices' shared
# row caps included (budget_block_cap) — shared with
# estimate_block_coverage and the multichip projection so every
# consumer predicts the same spill.
DENSE_A_BYTE_BUDGET = 2 << 30

# bound on one dense-apply chunk's materialized A elements (unpacked,
# compute dtype): 32M elems = 64 MB bf16
_DENSE_CHUNK_ELEMS = 32 * 1024 * 1024


def _class_shape(rows: int, width: int, group: int,
                 tile: int) -> Tuple[int, ...]:
    """Leading axes a dense class of `rows` output rows is STORED
    under: (rows,) where its unpacked A ([rows, width, tile,
    group*tile]) is within _DENSE_CHUNK_ELEMS, else (n_chunks,
    rows_per_chunk): the `xs` of the scan the kernel runs over it, the
    tail filled with zero slots here so the device pads nothing."""
    rpc = max(1, _DENSE_CHUNK_ELEMS // (group * width * tile * tile))
    return (rows,) if rows <= rpc else (-(-rows // rpc), rpc)


def _pad_rows(mat: np.ndarray, rows: int, fill) -> np.ndarray:
    if mat.shape[0] == rows:
        return mat
    return np.pad(mat, ((0, rows - mat.shape[0]),) +
                  ((0, 0),) * (mat.ndim - 1), constant_values=fill)


def pack_a_blocks(a_blocks: np.ndarray, axis: int = -1) -> np.ndarray:
    """Bit-pack 0/1-valued dense blocks [B, T, S] along `axis` ->
    uint8, that axis 8 times shorter.

    On simple graphs (edge multiplicity <= 1 — the common case after
    self-loop normalization) every A entry is 0 or 1, so one bit per
    entry suffices: 8x less HBM than int8, which buys 8x more dense
    blocks under the same byte budget. Little-endian bit order matches
    the device-side _unpack_bits."""
    assert a_blocks.shape[axis] % 8 == 0, a_blocks.shape
    assert a_blocks.max(initial=0.0) <= 1.0, "bit-packing needs 0/1 A"
    return np.packbits(a_blocks.astype(bool), axis=axis,
                       bitorder="little")


def _unpack_bits(a: jax.Array, compute_dtype) -> jax.Array:
    """Device-side inverse of pack_a_blocks along the SECOND-MINOR
    axis, the contraction's: uint8 [..., C/8, M] -> [..., C, M] in the
    compute dtype. A byte row becomes eight rows under it and the minor
    axis is not touched."""
    shifts = jnp.arange(8, dtype=jnp.uint8)[:, None]
    bits = (a[..., None, :] >> shifts) & jnp.uint8(1)
    return bits.reshape(a.shape[:-2] + (a.shape[-2] * 8, a.shape[-1])
                        ).astype(compute_dtype)


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


def occupied_blocks(sg, r: int, tile: int,
                    n_src_tiles: int) -> Tuple[np.ndarray, np.ndarray]:
    """(ids, counts) of the (dst-tile, src-tile) blocks that hold a
    real edge of device r, ids ascending: id = dst_tile * n_src_tiles
    + src_tile. Goes through np.unique on the occupied ids (O(E)
    memory) — a dense bincount over the n_dst_tiles x n_src_tiles id
    space would be tens of GB at 10M-node-shard scale."""
    dst = np.asarray(sg.edge_dst[r]).astype(np.int64)
    real = dst < sg.n_max
    src = np.asarray(sg.edge_src[r]).astype(np.int64)[real]
    return np.unique((dst[real] // tile) * n_src_tiles + src // tile,
                     return_counts=True)


def _select_dense(counts: np.ndarray, thr: int,
                  max_blocks: Optional[int]) -> np.ndarray:
    """Mask of the occupied blocks that go the MXU path: `thr` edges
    or more and, under a cap, the `max_blocks` densest of those (best
    edges-replaced-per-byte; ties at the cutoff lose their lowest
    ids); the rest spill to the sparse remainder. The ONE definition of
    the split: BlockPlan, the coverage estimate and the budget share
    it."""
    sel = counts >= thr
    if max_blocks is not None and int(sel.sum()) > max_blocks:
        cutoff = np.sort(counts[sel])[-max_blocks]
        sel &= counts >= cutoff
        over = int(sel.sum()) - max_blocks
        if over > 0:  # ties at the cutoff
            sel[np.nonzero(sel & (counts == cutoff))[0][:over]] = False
    return sel


def _union_sizes(keys: np.ndarray, others: np.ndarray,
                 group: int) -> np.ndarray:
    """Distinct other-tiles of every group of `group` consecutive key
    tiles that holds a dense block: the width _group_union's classes
    are cut by, from the blocks' tile ids alone."""
    if not keys.shape[0]:
        return np.zeros(0, np.int64)
    span = int(others.max()) + 1
    pairs = np.unique((keys // group) * span + others)
    return np.unique(pairs // span, return_counts=True)[1]


def _class_shapes(sizes: Sequence[np.ndarray], group: int, tile: int):
    """(widths, shapes) of one direction's dense classes over the
    stacked devices: the x1.5 ladder up to the largest union any device
    holds, and per rung the leading axes it is stored under
    (_class_shape of the most rows any device files under it; None for
    a rung no device fills)."""
    top = max((int(u.max(initial=1)) for u in sizes), default=1)
    widths = _bucket_widths(top)
    w_arr = np.asarray(widths, dtype=np.int64)
    caps = np.zeros(len(widths), np.int64)
    for u in sizes:
        caps = np.maximum(caps, np.bincount(
            np.searchsorted(w_arr, u), minlength=len(widths)))
    return widths, [_class_shape(int(c), w, group, tile) if c else None
                    for w, c in zip(widths, caps)]


def dense_a_bytes(blocks: Sequence[Tuple[np.ndarray, np.ndarray]],
                  tile: int, bits: int, group: int = 1) -> int:
    """Bytes of A one device STORES for the stacked devices' dense
    blocks (`blocks`: per device the (dst-tile, src-tile) ids of its
    blocks): both directions, every class at the shared row cap and
    chunk multiple, the pad slots' zero blocks counted as the blocks
    they are."""
    slots = 0
    for key, other in ((0, 1), (1, 0)):
        widths, shapes = _class_shapes(
            [_union_sizes(b[key], b[other], group) for b in blocks],
            group, tile)
        slots += sum(int(np.prod(shape)) * group * w
                     for w, shape in zip(widths, shapes) if shape)
    return slots * tile * tile * bits // 8


def budget_block_cap(byte_budget: int, tile: int, bits: int,
                     occupied: Sequence[Tuple[np.ndarray, np.ndarray]],
                     thr: int, n_src_tiles: int,
                     group: int = 1) -> Optional[int]:
    """Most dense A-blocks a device may keep so that what the builder
    STORES of them (dense_a_bytes at `bits` per entry, 1 = the
    bit-packed encoding of 0/1 graphs) fits `byte_budget`; None where
    every block of `thr` edges or more fits. `occupied`: per stacked
    device its occupied_blocks. One cap for all devices, found by
    bisection on the count of densest blocks kept (what is stored
    grows with it but for a chunk's tail, and only a count that was
    seen to fit is returned; at least 1)."""
    def stored(cap):
        kept = [ids[_select_dense(counts, thr, cap)]
                for ids, counts in occupied]
        return dense_a_bytes([(b // n_src_tiles, b % n_src_tiles)
                              for b in kept], tile, bits, group)

    hi = max((int(np.count_nonzero(c >= thr)) for _, c in occupied),
             default=0)
    if hi == 0 or stored(hi) <= byte_budget:
        return None
    lo = 1
    while hi - lo > 1:
        mid = (lo + hi) // 2
        if stored(mid) <= byte_budget:
            lo = mid
        else:
            hi = mid
    return lo


def estimate_block_coverage(sg, tile: int, n_feat_hint: int,
                            nnz_threshold: Optional[int] = None,
                            byte_budget: Optional[int] = DENSE_A_BYTE_BUDGET,
                            group: int = 1) -> float:
    """Fraction of real edges lying in (dst-tile, src-tile) blocks dense
    enough for the MXU path (>= `nnz_threshold`, defaulting to
    BlockPlan's read-cost break-even).

    The cheap O(E) structural signal `auto` uses to choose between the
    hybrid block kernel and the pure bucket kernel without paying for a
    full plan build. High coverage means the layout (usually
    cluster-renumbered, partition/halo.py `cluster`) concentrates
    community edges into dense tiles.

    `byte_budget` mirrors build_sharded_block_tables' HBM cap
    (budget_block_cap, the builder's own): without it the estimate
    counts dense blocks the real plan would spill, and `auto` could
    pick the block kernel at a realized coverage far below the
    threshold. The cap tracks the builder's A encoding: 1-bit packing
    when the graph is simple (no duplicate edges) and tile % 8 == 0,
    else the int8 cap — the bf16/f32 ratchets (multiplicity > 127)
    are rare enough to leave optimistic."""
    thr = nnz_threshold if nnz_threshold is not None else max(
        1, (tile * tile) // max(n_feat_hint, 1))
    n_src_rows = sg.n_max + sg.halo_size
    n_src_tiles = -(-n_src_rows // tile)
    occupied = [occupied_blocks(sg, r, tile, n_src_tiles)
                for r in range(sg.num_parts)]
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
        cap = budget_block_cap(byte_budget, tile, bits, occupied, thr,
                               n_src_tiles, group)
    dense = tot = 0
    for r in range(sg.num_parts):
        _, _, d, t = _part_block_stats(sg, r, tile, n_src_tiles, thr,
                                       max_blocks=cap,
                                       occupied=occupied[r])
        dense += d
        tot += t
    return dense / max(tot, 1)


def _part_block_stats(sg, r: int, tile: int, n_src_tiles: int, thr: int,
                      max_blocks: Optional[int] = None, occupied=None):
    """(coverage, dense_block_count, dense_edges, real_edges) of one
    device's shard at the given tile/threshold — the dense/remainder
    split (_select_dense) as estimate_block_coverage, the tuner and the
    multichip projection tool read it. `max_blocks` keeps only the
    densest blocks, matching BlockPlan's budget cutoff; `occupied`
    spares the O(E) pass where the caller holds occupied_blocks."""
    _, counts = occupied if occupied is not None else occupied_blocks(
        sg, r, tile, n_src_tiles)
    kept = counts[_select_dense(counts, thr, max_blocks)]
    dense, tot = int(kept.sum()), int(counts.sum())
    return dense / max(tot, 1), int(kept.shape[0]), dense, tot


class BlockPlan:
    """Host-side hybrid plan for one device's edge list.

    Attributes (all numpy, static shapes):
      a_blocks:    [B, T, S] f32 — dense block values (1.0 per edge),
                   in block-id order (dst tile major).
      block_dst/block_src: [B] the blocks' destination / source tile.
      dense_classes(direction): the blocks filed by output row and
                   slot (_group_union): per width class the index
                   matrices the builders arrange A and the tile lists
                   by (_dense_tables).
      rem_fwd/rem_bwd: the remainder edges' bucket tables, forward
                   and transpose (bucket_spmm.Direction: cut by source
                   rows into parts where the rows would make a table
                   taller than GATHER_PART_BYTES).
    """

    def __init__(self, edge_src: np.ndarray, edge_dst: np.ndarray,
                 n_out: int, n_src_rows: int, n_feat: int,
                 tile: int = 256,
                 nnz_threshold: Optional[int] = None,
                 fwd_widths: Optional[Sequence[int]] = None,
                 bwd_widths: Optional[Sequence[int]] = None,
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
        uniq, counts = np.unique(bid_o, return_counts=True)
        # HBM budget: under a cap only the densest blocks stay dense
        dense_sel = _select_dense(counts, nnz_threshold, max_blocks)

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
        self.block_dst = (dense_ids // n_src_tiles).astype(np.int64)
        self.block_src = (dense_ids % n_src_tiles).astype(np.int64)
        self.dense_count = int(in_dense_o.sum())

        # ---- sparse remainder (bucket tables both directions) ----
        # widths fitted to the remainder's own degree histograms, a part
        # each (bucket_spmm.fit_widths) unless given; the directions
        # keep their edges and per-part degrees so the sharded builder
        # can fit ONE ladder a part over every device's remainder and
        # rebuild these tables alone
        r_src, r_dst = src_o[~in_dense_o], dst_o[~in_dense_o]
        self.rem_count = int(r_src.shape[0])
        self.rem_fwd = Direction(r_src, r_dst, n_out, n_src_rows,
                                 fwd_widths)
        self.rem_bwd = Direction(r_dst, r_src, n_src_rows, n_out,
                                 bwd_widths)

    def set_remainder_widths(self, fwd_widths, bwd_widths) -> None:
        """(Re)build the remainder's bucket tables at the given widths
        (one ladder, or one a part); a part already at its widths is
        left alone. The dense half is not touched: which edges are
        remainder is fixed by the block selection."""
        self.rem_fwd.set_widths(fwd_widths)
        self.rem_bwd.set_widths(bwd_widths)

    def dense_classes(self, direction: str,
                      widths: Optional[Sequence[int]] = None):
        """_group_union of one direction's (output tile, other tile)
        pairs: the forward files blocks by destination tile, the
        backward by source tile; `group` consecutive output tiles share
        a row. `widths`: the stacked devices' shared ladder (the plan's
        own x1.5 ladder when None)."""
        B = self.a_blocks.shape[0]
        if direction == "fwd":
            return _group_union(self.block_dst, self.block_src,
                                self.n_dst_tiles, self.n_src_tiles,
                                self.group, B, widths=widths)
        return _group_union(self.block_src, self.block_dst,
                            self.n_src_tiles, self.n_dst_tiles,
                            self.group, B, widths=widths)


def _dense_apply(classes, inv, tiles, T, out_rows, n_feat, compute_dtype):
    """For every output tile i: sum_k A[i, k] @ tiles[tile(i, k)], one
    batched contraction a class of output rows ([R, G*T, K*S] @
    [R, K*S, F] — MXU-shaped as stored, _dense_tables), forward and
    backward alike: the backward's A is the transposes' arrangement.

    classes: [(a, t_mat)] per width class. `a` is the class's A as the
    builder stored it, [R, K, S, G*T] in its STORED dtype (bf16 / f32 /
    int8) or bit-packed uint8 [R, K, S/8, G*T]: `group` consecutive
    output tiles share a row and the gathered union of their K other
    tiles, t_mat [R, K] (pad -> the zero tile; pad slots of `a` are
    zero blocks). The cast/unpack to the compute dtype happens per
    chunk, so the full A is never materialized in a wider dtype. A
    class over the chunk bound comes as [n_chunks, R, ...] (and t_mat
    as [n_chunks, R, K]): the xs of a lax.scan, so the unpacked A
    transient stays bounded and nothing is padded or gathered here.
    tiles: [n_tiles+1, S, F] (last = zeros). `inv` restores output-tile
    order from the class-concatenated [sum R * G] tile axis (plus one
    zero sentinel row). Returns [n_out_tiles*T, F] f32."""
    if not classes:  # no dense block at all: the remainder holds all
        return jnp.zeros((out_rows, n_feat), jnp.float32)
    def compute(a, ti):  # [R, K, S(/8), G*T], [R, K] -> [R, G*T, F] f32
        with jax.named_scope("unpack"):
            blks = _unpack_bits(a, compute_dtype) \
                if a.dtype == jnp.uint8 else a.astype(compute_dtype)
        with jax.named_scope("tile"):
            tls = jnp.take(tiles, ti, axis=0,
                           mode="clip")           # [R, K, S, F]
            # k and s stay two contracted axes: merged into one the
            # chip's compiler windows the contraction 5 to 13% slower
            # (PERF.md section 6, PR 37)
            return jnp.einsum("rksm,rksf->rmf", blks, tls,
                              preferred_element_type=jnp.float32)

    # every class writes its rows (G output tiles each, side by side as
    # the einsum leaves them) into ONE buffer, the class concatenation
    # plus a zero sentinel row, in place: a scanned class carries it
    # through its loop, and the einsum's fusion writes a chunk where it
    # belongs. (With a result of its own a class, the chip's compiler
    # keeps that in its fast memory for the whole loop, 111 MB of it
    # for the widest Reddit class, and the operand's take then loses
    # its place there: twice the time. Cut into output tiles before it
    # is written, a chunk of ONE row becomes a plain matmul whose
    # unpacked A is written out and laid out again. PERF.md section 6,
    # PR 37.)
    m = classes[0][0].shape[-1]
    n_rows = sum(int(np.prod(a.shape[:-3])) for a, _ in classes)
    res = jnp.zeros((n_rows + 1, m, n_feat), jnp.float32)
    # a scan's carry keeps its type: under shard_map the result varies
    # over the mesh axes its inputs vary over, from the first iteration
    vma = jax.typeof(tiles).vma.union(
        *(jax.typeof(a).vma for a, _ in classes))
    if vma:
        res = jax.lax.pcast(res, tuple(vma), to="varying")
    off = 0
    for a, ti in classes:
        if a.ndim == 5:
            def body(res, xs, off=off, step=a.shape[1]):
                i, a_c, t_c = xs
                out = compute(a_c, t_c)
                with jax.named_scope("tile"):
                    return jax.lax.dynamic_update_slice(
                        res, out, (off + i * step, 0, 0)), None

            res, _ = jax.lax.scan(
                body, res, (jnp.arange(a.shape[0]), a, ti))
            off += a.shape[0] * a.shape[1]
        else:
            out = compute(a, ti)
            with jax.named_scope("tile"):
                res = jax.lax.dynamic_update_slice(res, out, (off, 0, 0))
            off += a.shape[0]
    # mode='clip': indices in-bounds by construction (the last row's
    # tiles are the sentinel) — fill-mode gathers are the one path that
    # can mint NaN from valid data (bucket_spmm rationale)
    with jax.named_scope("unpermute"):
        return jnp.take(res.reshape(-1, T, n_feat), inv, axis=0,
                        mode="clip").reshape(-1, n_feat)[:out_rows]


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
    f32 [n_out, F]. `plan_arrays` holds one device's tables (_dense_tables
    and the remainder's, see build_sharded_block_tables for keys), the
    leading device axis stripped when used inside shard_map. `rem_dtype`
    narrows the REMAINDER's gather transport only
    (bucket_spmm.transport_dtypes) — the dense MXU path keeps the
    activation dtype. `rem_amax` swaps the static
    saturating fp8 cast for the amax-clamped one (the de-scale applies
    to the remainder alone, before it joins the dense partial).

    Named for the profiler (obs/profiler.py SCOPE_NAMES): `unpack` (the
    slice of a chunk's A, its bit unpack or cast to the compute dtype;
    the compiler fuses the latter into the einsum), `tile`
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

    def dense_classes(direction):  # [(a, t_mat)] in width order
        stems = sorted(k[:-1] for k in d
                       if k.startswith(f"blk_{direction}_g")
                       and k.endswith("a"))
        return [(d[k + "a"], d[k + "t"]) for k in stems]

    @jax.custom_vjp
    def f(fbuf):
        n_s_tiles = -(-n_src_rows // T)
        tiles = tiles_of(fbuf, n_s_tiles, T)
        dense = _dense_apply(dense_classes("fwd"), d["blk_fwd_inv"],
                             tiles, T, n_out, fbuf.shape[-1],
                             fbuf.dtype)
        rem_in, rem_inv = _rem_cast(fbuf, rem_fwd_dt)
        rem = bucket_aggregate(
            rem_in, *direction_tables(d, "blkrem_fwd"),
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
        # transpose dense: per source tile, sum A^T @ g_tile, the
        # forward's contraction over the transposes' own arrangement
        n_d_tiles = -(-n_out // T)
        g_tiles = tiles_of(gd, n_d_tiles, T)
        dense = _dense_apply(dense_classes("bwd"), d["blk_bwd_inv"],
                             g_tiles, T, n_src_rows, g.shape[-1],
                             gd.dtype)
        # the remainder's transport cast comes straight from the f32
        # cotangent — not through the proto.dtype rounding above
        # (matching bucket_spmm's single-rounding path)
        if rem_bwd_dt is not None:
            rem_in, rem_inv = _rem_cast(gd32, rem_bwd_dt)
        else:
            rem_in, rem_inv = gd, None
        rem = bucket_aggregate(
            rem_in, *direction_tables(d, "blkrem_bwd"),
            chunk_edges=chunk_edges, scope="rem_")
        with jax.named_scope("scale"):
            if rem_inv is not None:
                rem = rem * rem_inv
            return ((dense + rem).astype(proto.dtype),)

    f.defvjp(fwd, bwd)
    return f


def _required_bits(plans: Sequence[BlockPlan], tile: int):
    """(bits, dtype) of the narrowest exact encoding for the plans' A
    counts: 1-bit packing (counts <= 1) buys 8x the dense coverage of
    int8 (<= 127) per HBM byte, which in turn halves bf16 and quarters
    f32 (the device unpacks/casts A to the activation dtype at use)."""
    import ml_dtypes

    a_max = max((float(p.a_blocks.max(initial=0.0)) for p in plans),
                default=0.0)
    if a_max <= 1 and tile % 8 == 0:  # pack_a_blocks needs 8 | tile
        return 1, None  # bit-packed uint8 (pack_a_blocks)
    if a_max <= 127:
        return 8, np.int8
    if a_max <= 256:
        return 16, ml_dtypes.bfloat16
    return 32, np.float32


def _reoffset_inv(inv, counts, rows, group):
    """A plan's inverse permutation (r * group + d over its own class
    rows) moved to the rows each class is stored under (the shared
    caps, chunk tails included); its sentinel to theirs."""
    r_old = inv.astype(np.int64) // group
    out = np.full_like(r_old, sum(rows) * group)
    off_old = off_new = 0
    for n_b, n_st in zip(counts, rows):
        sel = (r_old >= off_old) & (r_old < off_old + n_b)
        out[sel] = (r_old[sel] - off_old + off_new) * group \
            + inv[sel] % group
        off_old += n_b
        off_new += n_st
    return out.astype(np.int32)


def _dense_tables(plans: Sequence[BlockPlan], bits: int,
                  a_dtype) -> Dict[str, np.ndarray]:
    """The dense half's tables of the stacked plans, a leading device
    axis on each, per direction d in (fwd, bwd) and width class w:

      blk_<d>_g<w>a  A in the order the kernel reads it (_dense_apply):
                     [P, rows, K, tile(/8), G*tile], a class over the
                     chunk bound [P, n_chunks, rows, ...]
                     (_class_shape). Row r holds, side by side, the
                     blocks of its G output tiles against the union of
                     their K other tiles, each block with its
                     CONTRACTED axis first (the forward's [S, T], the
                     backward's [T, S]) and, at bits == 1, bit-packed
                     along it; a slot with no block, a row past a
                     device's own and a chunk's tail are zero blocks.
      blk_<d>_g<w>t  [P, rows, K] int32 other-tile ids (pad -> the
                     zero tile), under the same leading axes.
      blk_<d>_inv    [P, n_key_tiles] output tile -> r * G + g in the
                     class concatenation (no dense block -> the
                     sentinel row behind it).

    Rows are padded to the largest device's count a class: one traced
    program serves every device."""
    tile, group = plans[0].tile, plans[0].group

    def encode(a, axis):  # [B, T, S] -> [B+1, contracted(/8), other]
        enc = pack_a_blocks(a, axis=axis) if bits == 1 \
            else a.astype(a_dtype)
        if axis == 2:
            enc = np.ascontiguousarray(enc.transpose(0, 2, 1))
        return np.concatenate(
            [enc, np.zeros((1,) + enc.shape[1:], enc.dtype)])

    per_dev = [{} for _ in plans]
    for d, axis in (("fwd", 2), ("bwd", 1)):
        sizes = [_union_sizes(*((p.block_dst, p.block_src) if d == "fwd"
                                else (p.block_src, p.block_dst)), group)
                 for p in plans]
        widths, shapes = _class_shapes(sizes, group, tile)
        rows = [int(np.prod(shape)) if shape else 0 for shape in shapes]
        for p, arrs in zip(plans, per_dev):
            classes, inv, counts, _ = p.dense_classes(d, widths)
            arrs[f"blk_{d}_inv"] = _reoffset_inv(inv, counts, rows, group)
            enc = encode(p.a_blocks, axis)
            zero_tile = p.n_src_tiles if d == "fwd" else p.n_dst_tiles
            for w_i, (a_idx, t_mat) in enumerate(classes):
                if not rows[w_i]:
                    continue
                # [n, G, K, C, M] -> [n, K, C, G*M]
                n, c_in, m_in = a_idx.shape[0], enc.shape[1], enc.shape[2]
                a = np.zeros((rows[w_i], widths[w_i], c_in,
                              group * m_in), enc.dtype)
                a[:n] = enc[a_idx].transpose(0, 2, 3, 1, 4).reshape(
                    (n,) + a.shape[1:])
                arrs[f"blk_{d}_g{w_i:02d}a"] = a.reshape(
                    shapes[w_i] + a.shape[1:])
                arrs[f"blk_{d}_g{w_i:02d}t"] = _pad_rows(
                    t_mat, rows[w_i], zero_tile).astype(np.int32).reshape(
                        shapes[w_i] + t_mat.shape[1:])
    return {k: np.stack([arrs[k] for arrs in per_dev])
            for k in per_dev[0]}


def _dense_keys(tables, direction: str) -> List[str]:
    """Stems 'blk_<direction>_g<w>' of one direction's dense classes,
    in width order ('a' the stored A, 't' its tile ids)."""
    return sorted(k[:-1] for k in tables
                  if k.startswith(f"blk_{direction}_g")
                  and k.endswith("a"))


def dense_pad_stats(tables: Dict[str, np.ndarray], tile: int) -> dict:
    """What the stacked tables' dense half stores, per direction, read
    off the A arrays themselves (built or loaded): `dense_blocks` (the
    slots that hold an edge, summed over the devices), `dense_slots`
    (every [tile, tile] slot stored: pad slots, pad rows and chunk
    tails with them, each an MXU pass a call), their ratio `dense_pad`,
    `a_bytes` (the arrays' bytes on ONE device) and `dense_edges` ([P]
    edges each device's A counts: a bit set under packing, the entries'
    sum otherwise)."""
    out = {}
    for d in ("fwd", "bwd"):
        blocks = slots = a_bytes = 0
        n_dev = tables[f"blk_{d}_inv"].shape[0]
        edges = np.zeros(n_dev, np.int64)
        for stem in _dense_keys(tables, d):
            a = np.asarray(tables[stem + "a"])
            k, c, m = a.shape[-3:]
            # [P, rows, K, C, G, tile]: one (k, g) pair a slot
            a = a.reshape(n_dev, -1, k, c, m // tile, tile)
            if a.dtype == np.uint8:
                # the bits set, eight bytes of the minor axis at a time
                cnt = np.bitwise_count(a.view(np.uint64))
            else:
                cnt = a.astype(np.float32)
            per_slot = cnt.sum(axis=(3, 5), dtype=np.int64 if
                               a.dtype == np.uint8 else np.float64)
            blocks += int(np.count_nonzero(per_slot))
            slots += per_slot.size
            a_bytes += a.nbytes // n_dev
            edges += np.rint(per_slot.sum(axis=(1, 2, 3))).astype(np.int64)
        out[d] = {"dense_blocks": blocks, "dense_slots": int(slots),
                  "dense_pad": round(slots / max(blocks, 1), 4),
                  "a_bytes": int(a_bytes), "dense_edges": edges}
    return out


def validate_dense_tables(tables: Dict[str, np.ndarray], tile: int,
                          n_edges: Optional[Sequence[int]] = None) -> dict:
    """Edge conservation for the dense half, as validate_bucket_tables
    has it for the remainder: each direction's A counts every dense
    edge once — the two arrangements hold the same edges on every
    device and, where given, `n_edges` of them ([P]: the edges the
    remainder does not hold). Run at build and on a cache load: a
    table that lost a block must not train. Returns dense_pad_stats
    without the per-device counts."""
    stats = dense_pad_stats(tables, tile)
    fwd, bwd = (stats[d].pop("dense_edges") for d in ("fwd", "bwd"))
    want = fwd if n_edges is None else np.asarray(n_edges, np.int64)
    if not (np.array_equal(fwd, want) and np.array_equal(bwd, want)):
        raise ValueError(
            f"dense A tables hold {fwd.tolist()} (fwd) and "
            f"{bwd.tolist()} (bwd) edges a device, expected "
            f"{want.tolist()}: a block was lost or counted twice")
    return stats


def plan_to_arrays(p: BlockPlan) -> Dict[str, np.ndarray]:
    """One plan's tables as make_block_spmm_fn takes them: the stacked
    builder's (_dense_tables, A in the plan's own narrowest encoding)
    with the device axis stripped, and the remainder's."""
    arrs = {k: v[0] for k, v in _dense_tables(
        [p], *_required_bits([p], p.tile)).items()}
    # the remainder's, slot-major [w, rows] (bucket_spmm), a part at a
    # time: the stacked builder's keys, a bucket with no row left out
    for stem, d in (("blkrem_fwd", p.rem_fwd), ("blkrem_bwd", p.rem_bwd)):
        arrs.update((k, v[0]) for k, v in stack_direction([d], stem).items())
    return arrs


def build_sharded_block_tables(sg, tile: int = 256,
                               n_feat_hint: int = 256,
                               byte_budget: int = DENSE_A_BYTE_BUDGET,
                               nnz_threshold: Optional[int] = None,
                               group: int = 1,
                               ) -> Tuple[Dict[str, np.ndarray], int]:
    """Stacked per-device hybrid plans (leading device axis), padded to
    shared shapes: the dense classes' ladders and row caps
    (_dense_tables), the remainder's bucket ladders and caps. Returns
    (tables, tile)."""
    P = sg.num_parts
    n_src_rows = sg.n_max + sg.halo_size
    n_src_tiles = -(-n_src_rows // tile)
    thr = nnz_threshold if nnz_threshold is not None else max(
        1, (tile * tile) // max(n_feat_hint, 1))
    # HBM budget for the per-device dense-A tensors: keep the densest
    # blocks whose STORED arrangements fit byte_budget, spill the rest
    # to the sparse remainder. Past this size the A reads stop paying
    # for the gathers they replace and, at Reddit scale, the tables
    # alone would crowd a v5e's 16 GB HBM (an unbudgeted clustered
    # Reddit shard produced 6.5 GB). First pass assumes bit-packed A (1
    # bit per entry — the common case: simple graphs have 0/1 edge
    # multiplicities); if the counts force a wider dtype, plans rebuild
    # under the correspondingly smaller cap.
    occupied = [occupied_blocks(sg, r, tile, n_src_tiles)
                for r in range(P)]

    def build_plans(bits):
        cap = budget_block_cap(byte_budget, tile, bits, occupied, thr,
                               n_src_tiles, group)
        return [
            BlockPlan(sg.edge_src[r], sg.edge_dst[r], sg.n_max,
                      n_src_rows, n_feat_hint, tile=tile,
                      nnz_threshold=thr, max_blocks=cap, group=group)
            for r in range(P)
        ]

    # fixpoint on the A encoding: the cap follows the bits per entry,
    # but the counts (and thus the bits required for exactness) depend
    # on which blocks the cap keeps. bits only ratchets up, so this
    # terminates in <= 4 builds. The SHIPPED encoding (emit_bits /
    # a_dtype) is re-read off the final plans: it may be narrower than
    # the cap assumed (e.g. the smaller cap dropped every multi-edge
    # block) — exact, merely under-using the budget.
    bits = 1
    while True:
        plans = build_plans(bits)
        emit_bits, a_dtype = _required_bits(plans, tile)
        if emit_bits <= bits:
            break
        bits = emit_bits

    # the remainder's widths are fitted ONCE a part to the histograms
    # of all the plans (known only now, after the dense selection);
    # where they differ from a plan's own fit (P > 1), its remainder
    # tables alone are rebuilt. The dense classes keep the x1.5 ladder,
    # at the longest device's length (_dense_tables)
    fw, bw = ([fit_widths(degree_hist(getattr(p, d).degs[i]
                                      for p in plans))
               for i in range(getattr(plans[0], d).k)]
              for d in ("rem_fwd", "rem_bwd"))
    for p in plans:
        p.set_remainder_widths(fw, bw)

    stacked = _dense_tables(plans, emit_bits, a_dtype)
    # every dense edge sits in each direction's A once
    validate_dense_tables(stacked, tile,
                          n_edges=[p.dense_count for p in plans])
    # the remainder's bucket tables, slot-major at shared row caps
    stacked.update(stack_direction([p.rem_fwd for p in plans],
                                   "blkrem_fwd"))
    stacked.update(stack_direction([p.rem_bwd for p in plans],
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
