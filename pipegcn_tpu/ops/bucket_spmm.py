"""Degree-bucketed dense SpMM (mean aggregation) — scatter-free.

A second TPU-native replacement for DGL's SpMM kernel (reference
module/layer.py:47-49), built for the regime where the per-device shard
does NOT fit VMEM. XLA lowers
`segment_sum` to scatter-add, which serializes badly on TPU; this
formulation removes every scatter from both the forward AND the backward:

  1. Host: bucket destination rows by local degree, into widths
     fitted to the degree histogram (fit_widths). Each
     bucket b holds a padded neighbor-index table idx_b laid out
     SLOT-MAJOR, [D_b, n_b] (D_b = bucket width, n_b = its rows
     rounded up to ROW_TILE): row j of the table is neighbor slot j of
     every destination of the bucket. Pad entries point at a zero
     sentinel row appended to fbuf.
  2. Device: per bucket, the gather streams slot 0 of every row, then
     slot 1, ...: msgs = fbuf_pad[idx_b] is [D_b, n_b, F] in the
     transport dtype, and out_b = sum over axis 0 of it, widened to
     f32 inside the reduction: a sum of D_b slices [n_b, F] that the
     TPU runs as plain vector adds. The only f32 tensor written is the
     [n_b, F] result. A one-byte transport (fp8) is gathered as 16-bit
     words, [D_b, n_b, F/2] uint16 from a word view of fbuf_pad packed
     once a call: the same rows in the same order and the same bytes,
     in the element type whose requests the chip serves at half the
     price (SLAB_BYTES note), split back into bytes inside the
     reduction.
  3. The sums go, in bucket order, into one f32 buffer, written in
     place; one final gather by a precomputed inverse permutation
     restores destination order.

Where the source rows would make fbuf_pad taller than the chip keeps
in its fast memory space S(1) (GATHER_PART_BYTES; Yelp's 717k rows,
not Reddit's 233k), a request costs about 3.6 times as much, so a
direction is cut by SOURCE rows into equal parts (Direction), each
with its own slot-major tables, fitted widths, zero sentinel and
inverse permutation over every destination row; the kernel packs and
gathers one part after the other and sums the parts' unpermuted
results in f32. One part is the uncut kernel.

Why slot-major, and why n_b is a multiple of ROW_TILE = 32: the chip's
gather yields the flat [D_b * n_b, F] stream, and the 3-D view the
reduction needs is free only if its second-minor axis is a whole
number of sublane tiles (8 rows of f32, 16 of bf16, 32 of fp8). The
ladder's widths (13, 19, 141, ...) never are, so a destination-major
[n_b, D_b, F] view was a physical copy of every message, widened to
f32 on the way: 10 to 17 bytes of HBM traffic an element where the
reduction itself needs 1 to 4 (PERF.md section 6, PR 30). Rounding
the ROWS of a bucket up costs at most 31 sentinel rows a bucket;
rounding its width up would cost up to a quarter more gathered rows.

The backward needs d_fbuf[src] += g[dst]/deg[dst] summed over edges —
itself an SpMM with edge roles swapped — so the host also builds
transpose tables (bucket by *source* out-degree) and the custom VJP runs
the same scatter-free kernel in the other direction, accumulating in f32.

Padding: the gather is request-bound, so what a table costs is its
SLOTS (width x rows, pad entries included), and the widths are chosen
to issue as few of them as the degree histogram allows (fit_widths: an
optimal partition of the histogram into at most max(8, the x1.5
ladder's non-empty rungs) buckets). The x1.5 ladder this replaced
(_ladder_rungs, still the dense K classes' in block_spmm) bounds the
padding at 1.5x and read 1.16 to 1.25 slots an edge on the benchmark's
graphs; the fitted widths read 1.03 to 1.04 (PERF.md section 6, PR 34;
pad_stats reports it for any table). No edge is ever dropped: a width
list that does not cover a row's degree raises in the builder, and the
validation counts every direction's entries against the edges it was
built from. All shapes are static; per-device
tables are padded to shared row caps so one traced program serves every
device in shard_map.
"""

from __future__ import annotations

import functools
import itertools
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# bound on the materialized [D_b, rows, F] gather per bucket chunk
# (elements, not bytes, and in the TRANSPORT dtype: no f32 copy of it
# exists): 32M elems = 128 MB in f32, 64 MB in bf16, 32 MB in fp8. A
# chunk is a slice of the slot-major table along its rows, a multiple
# of ROW_TILE of them, so every chunk keeps the free 3-D view
DEFAULT_CHUNK_ELEMS = 32 * 1024 * 1024

# rows of a bucket (and of a chunk) come in multiples of the largest
# sublane tile of the three transport dtypes (f32 8, bf16 16, fp8 32):
# the [D_b * rows, F] gather stream then views as [D_b, rows, F] with
# no copy in any of them (module docstring)
ROW_TILE = 32


def row_cap(n: int) -> int:
    """`n` rows rounded up to a whole number of ROW_TILEs (0 stays 0)."""
    return -(-int(n) // ROW_TILE) * ROW_TILE

# TPU row-gather fast path: measured on v5e, gathering rows of <= 256
# bytes runs at ~400-460M rows/s while wider rows fall off a cliff to
# ~75-80M rows/s (5-6x). Rows are therefore processed in feature slabs
# of SLAB_BYTES, each slab materialized as its own compact [R, slab]
# operand (a strided slice of the wide buffer does NOT trigger the fast
# path) via a lax.scan over the slab axis.
#
# Inside the fast path the gather is request-bound, and what a request
# costs depends on the ELEMENT TYPE of the row, not only on its bytes
# (docs/PERF_NOTES.md "The row-gather cliff", PERF.md section 6, PR 31;
# one chunk of 130,848 rows of 256 bytes from a 233k-row table, which
# the compiled program keeps in memory space S(1)): bf16[.,128] 1.97 ns,
# u16[.,128] 2.09 ns, f8e5m2[.,256] 3.63 ns, f8e4m3fn[.,256] 4.07 ns.
# An fp8 row of 256 features is half the REQUESTS of a bf16 one, at
# twice the price each: the gather gained nothing from it until the
# row was handed over as 128 sixteen-bit words (_pack_words). A table
# too large for that memory space (Yelp's 717k rows, 183 MB a slab)
# is read from HBM at 9 to 12.5 ns a request whatever the type, so no
# gather reads one: see GATHER_PART_BYTES.
# The slab width stays 256 BYTES for every dtype.
SLAB_BYTES = 256

# the tallest table a gather reads, in bytes of 256-byte slab rows, its
# zero sentinel row included: a direction whose source rows would make
# a taller one is cut by source rows into parts (source_parts), each
# gathered from its own table. On a v5e the compiler keeps a table of
# up to 102.5 MiB (the tallest measured) in memory space S(1), where a
# request costs 1.7 ns; a 175 MiB table stays in HBM at 6.1 ns
# (scripts/gather_parts_microbench.py; docs/PERF_NOTES.md "The
# row-gather cliff"). Every part writes a result for nearly every
# destination row, so fewer parts cost less beside the gathers: Yelp's
# step read 0.3546 s an epoch in two parts of 87.5 MiB, 0.3791 in three
# of 58 MiB (PERF.md section 6). A constant of the device, as
# SLAB_BYTES is
GATHER_PART_BYTES = 96 * 2**20


def source_parts(n_rows: int) -> int:
    """Parts a direction of `n_rows` source rows is cut into: the fewest
    whose tables (a part's rows and its sentinel, SLAB_BYTES each) stay
    within GATHER_PART_BYTES."""
    per_part = GATHER_PART_BYTES // SLAB_BYTES - 1
    return max(1, -(-int(n_rows) // per_part))


def part_bounds(n_rows: int, k: int) -> List[Tuple[int, int]]:
    """[lo, hi) source rows of each of k parts: equal within a row, so
    the builder and the kernel find the same ones from the height and
    the part count alone."""
    return [(p * n_rows // k, (p + 1) * n_rows // k) for p in range(k)]


def _ladder_rungs():
    """The x1.5 width progression [1, 2, 3, 4, 6, 9, 13, ...]: the
    ladder of the block kernel's dense K classes, and the yardstick of
    fit_widths (which never issues more slots, nor more than max(8,
    this ladder's non-empty rungs) buckets). It bounds padding at 1.5x
    worst-case vs 2x for power-of-2 steps; on the row buckets it read
    1.16 (Yelp) to 1.25 (Reddit's remainder) slots an edge, which is
    why they are fitted to the histogram now (PERF.md section 6, PR
    34)."""
    w = 1
    while True:
        yield w
        w = max(w + 1, (w * 3) // 2)


def _bucket_widths(max_deg: int, min_width: int = 0) -> List[int]:
    """Ladder rungs up to (and including) the first >= max_deg.

    `min_width` truncates the ladder from BELOW: rungs narrower than it
    are dropped, merging every low-degree row into the first surviving
    rung (the bucket-merge launch/transient lever — fewer per-bucket
    gather launches and concat operands at a padding cost bounded by
    min_width per merged row). 0 keeps the full ladder."""
    widths = []
    for w in _ladder_rungs():
        if w < min_width:
            continue
        widths.append(w)
        if w >= max_deg:
            return widths


def ladder_prefix(n: int) -> List[int]:
    """First n rungs (the sharded builders regenerate shared ladders
    of a given length from the same generator)."""
    return list(itertools.islice(_ladder_rungs(), n))


# fewest buckets a fitted ladder may use where the x1.5 ladder fills
# fewer. A bucket is a gather program and a reduce program in every
# layer, direction and scan: about 0.7 MB of compiled step each, which
# a warm run pays for in loading it. On Reddit's remainder the x1.5
# ladder fills seven rungs, five of them nearly empty, at 1.25 slots an
# edge; by the number of fitted widths, on the same histogram (CPU
# count, PERF.md section 6, PR 34): 5 widths 1.076, 6 1.061, 7 1.050, 8
# 1.043, 10 1.032. So the fit gets the ladder's own bucket count and,
# where that is small, eight: most of what the histogram offers for one
# more bucket than the ladder filled there
FIT_MIN_BUCKETS = 8

# candidate widths the dynamic programme looks at: every degree present
# up to this many, else a thinned set (_fit_candidates), so a 100k-degree
# hub costs milliseconds on the host
_FIT_CANDIDATES = 512


def degree_hist(degs) -> np.ndarray:
    """[P, D] with hist[r, d] = rows of degree d on shard r, from the
    per-row degree arrays, one a shard. fit_widths fits ONE ladder to
    all of them, so one traced program serves every device."""
    hists = [np.bincount(np.asarray(d, dtype=np.int64), minlength=1)
             for d in degs]
    out = np.zeros((len(hists), max(h.shape[0] for h in hists)), np.int64)
    for r, h in enumerate(hists):
        out[r, :h.shape[0]] = h
    return out


def _row_caps(n: np.ndarray) -> np.ndarray:
    """Per-shard row counts [P, ...] -> the shared cap: the largest,
    rounded up to ROW_TILE (what stack_to_caps pads every shard to)."""
    return -(-n.max(axis=0) // ROW_TILE) * ROW_TILE


def _ladder_slots(hist: np.ndarray, widths: Sequence[int]):
    """(slots, non-empty buckets) of the per-shard histograms `hist`
    ([P, D], or [D] for one shard) under ascending `widths`: slots =
    sum over buckets of width x P x the shared row cap, which is what
    the stacked tables make the devices gather."""
    hist = np.atleast_2d(hist)
    cum = np.concatenate([np.zeros((hist.shape[0], 1), np.int64),
                          np.cumsum(hist[:, 1:], axis=1)], axis=1)
    top = np.minimum(np.asarray(widths, np.int64), hist.shape[1] - 1)
    rows = np.diff(cum[:, top], prepend=0, axis=1)
    rows[:, -1] += cum[:, -1] - cum[:, top[-1]]  # a short ladder's top
    slots = (np.asarray(widths, np.int64) * _row_caps(rows)).sum()
    return int(slots) * hist.shape[0], int(rows.any(axis=0).sum())


def _fit_candidates(degs: np.ndarray, rows: np.ndarray,
                    old: Sequence[int]) -> np.ndarray:
    """Indices into the ascending present degrees that the programme may
    end a bucket at: all of them up to _FIT_CANDIDATES, else the bucket
    tops of the x1.5 ladder `old` (so its answer stays feasible), the
    largest degree, and even quantiles of the rows and of the edges."""
    m = degs.shape[0]
    if m <= _FIT_CANDIDATES:
        return np.arange(m)
    q = np.linspace(0.0, 1.0, _FIT_CANDIDATES // 2)
    cr = np.cumsum(rows)
    ce = np.cumsum(rows * degs)
    tops = np.searchsorted(degs, np.asarray(old), side="right") - 1
    picks = [tops[tops >= 0], [m - 1], np.searchsorted(cr, q * cr[-1]),
             np.searchsorted(ce, q * ce[-1])]
    return np.unique(np.minimum(np.concatenate(picks), m - 1))


def fit_widths(hist: np.ndarray, max_buckets: Optional[int] = None,
               min_width: int = 0) -> List[int]:
    """Bucket widths fitted to the degree histograms of the shards
    (hist[r, d] = rows of degree d on shard r, degree_hist; a 1-D
    histogram is one shard; degree 0 has no bucket): ascending, the
    last at least the largest degree, none below `min_width`
    (--bucket-merge, as in _bucket_widths), minimising the gathered
    SLOTS of the stacked tables

        sum_b  w_b * P * row_cap(max_r rows of shard r with
                                 w_(b-1) < degree <= w_b)

    over every choice of at most `max_buckets` widths: the optimal
    partition of a histogram into intervals, a dynamic programme over
    the degrees present. `max_buckets` defaults to max(FIT_MIN_BUCKETS,
    the NON-EMPTY buckets _bucket_widths gives these histograms), so
    the x1.5 ladder's filled rungs are a feasible answer: the fit never
    issues more slots than that ladder and never asks for more buckets
    than max(8, its). Equal slots take the fewer buckets. Every
    returned width is a degree present (or `min_width`), so no bucket
    of the fitted histograms is empty on every shard."""
    hist = np.atleast_2d(np.asarray(hist, dtype=np.int64))
    total = hist.sum(axis=0)
    degs = np.nonzero(total[1:])[0] + 1
    floor = max(1, int(min_width))
    if degs.shape[0] == 0:
        return [floor]
    old = _bucket_widths(int(degs[-1]), min_width)
    if max_buckets is None:
        max_buckets = max(FIT_MIN_BUCKETS, _ladder_slots(hist, old)[1])
    # a bucket's top: the largest degree present in it, never below the
    # floor (degrees under the floor share the first surviving width)
    cand = _fit_candidates(degs, total[degs], old)
    below = np.searchsorted(degs[cand], floor, side="left")
    if below and (below == cand.shape[0] or degs[cand[below]] != floor):
        below -= 1              # the last degree under the floor ends
    cand = cand[below:]         # the first bucket, at the floor's width
    width = np.maximum(degs[cand], floor)
    m = cand.shape[0]
    # cum[r, j]: rows of shard r up to candidate j - 1; cost[i, j]: one
    # bucket over candidates i .. j (i <= j) at the shards' shared cap
    cum = np.concatenate([np.zeros((hist.shape[0], 1), np.int64),
                          np.cumsum(hist[:, degs], axis=1)[:, cand]],
                         axis=1)
    cost = width[None, :] * hist.shape[0] * _row_caps(
        cum[:, None, 1:] - cum[:, :m, None])
    inf = np.iinfo(np.int64).max // 4
    cost = np.where(np.arange(m)[:, None] <= np.arange(m)[None, :],
                    cost, inf)
    best = cost[0].copy()       # best[j]: candidates 0 .. j in k buckets
    back = [np.zeros(m, np.int64)]
    answer = (int(best[-1]), 0)
    for k in range(1, min(int(max_buckets), m)):
        # the last bucket starts at candidate i + 1: cost row i + 1
        tot = np.minimum(best[:-1, None] + cost[1:], inf)
        arg = tot.argmin(axis=0)
        best = tot[arg, np.arange(m)]
        back.append(arg + 1)
        if best[-1] < answer[0]:
            answer = (int(best[-1]), k)
    out, j = [], m - 1
    for k in range(answer[1], -1, -1):
        out.append(int(width[j]))
        j = int(back[k][j]) - 1
    return out[::-1]


def build_tables_for_edges(
    edge_src: np.ndarray,
    edge_dst: np.ndarray,
    n_out: int,
    n_src_rows: int,
    widths: Sequence[int],
) -> Tuple[List[np.ndarray], np.ndarray, List[int]]:
    """Bucket tables for one device's edge list (any order; pad edges
    must have dst == n_out and are dropped).

    Returns (idx_mats, inv_perm, counts):
      idx_mats[b]: [widths[b], row_cap(n_b)] int32 into fbuf_pad rows,
        slot-major (module docstring): column i holds the neighbors of
        the bucket's i-th row; pad = n_src_rows (the zero sentinel
        row), which fills the slots past a row's degree and the whole
        columns past n_b;
      inv_perm: [n_out] int32 into the concatenated bucket output, each
        bucket row_cap(n_b) rows of it (rows with zero degree point at
        its final zero sentinel row);
      counts[b]: real rows n_b in bucket b.
    """
    real = edge_dst < n_out
    src = edge_src[real].astype(np.int64)
    dst = edge_dst[real].astype(np.int64)
    from ..native import stable_argsort

    order = stable_argsort(dst)
    src, dst = src[order], dst[order]
    row_ptr = np.searchsorted(dst, np.arange(n_out + 1))
    deg = (row_ptr[1:] - row_ptr[:-1]).astype(np.int64)

    widths_arr = np.asarray(widths, dtype=np.int64)
    if int(deg.max(initial=0)) > int(widths_arr[-1]):
        # a table only has `width` slots a row: the tail of a longer
        # row would be lost without a word
        raise ValueError(
            f"bucket widths end at {int(widths_arr[-1])} but a row has "
            f"{int(deg.max())} edges: the widths were fitted to another "
            f"degree histogram than these edges'")
    # bucket id = first width >= deg (deg 0 handled separately)
    bid = np.searchsorted(widths_arr, np.maximum(deg, 1))

    idx_mats: List[np.ndarray] = []
    counts: List[int] = []
    inv_perm = np.full(n_out, -1, dtype=np.int64)
    offset = 0
    for b, w in enumerate(widths):
        rows = np.nonzero((bid == b) & (deg > 0))[0]
        n_b = rows.shape[0]
        mat = np.full((w, row_cap(n_b)), n_src_rows, dtype=np.int32)
        # fill each row's neighbors from CSR, down its column
        if n_b:
            # vectorized ragged fill: positions (slot j < deg[i], row i)
            slot, col = np.nonzero(
                np.arange(w)[:, None] < deg[rows][None, :])
            mat[slot, col] = src[row_ptr[rows][col] + slot].astype(
                np.int32)
            inv_perm[rows] = offset + np.arange(n_b)
        idx_mats.append(mat)
        counts.append(n_b)
        offset += mat.shape[1]
    # zero-degree rows -> final zero sentinel row of the concat output
    inv_perm[inv_perm < 0] = offset
    return idx_mats, inv_perm.astype(np.int32), counts


def pad_to_caps(mats: Sequence[np.ndarray], inv: np.ndarray,
                caps: Sequence[int], sentinel: int):
    """One device's slot-major tables (build_tables_for_edges) widened
    to row caps shared across devices, so one traced program serves
    them all: all-sentinel columns are appended up to each cap (their
    output is ignored: no inv_perm entry points into the pad range) and
    inv_perm, built on this device's own bucket offsets, moves to the
    shared ones. Returns (mats [w_b, cap_b], inv)."""
    inv = inv.astype(np.int64)
    out = np.full_like(inv, sum(caps))  # default: zero sentinel row
    padded = []
    off_old = off_new = 0
    for m, cap in zip(mats, caps):
        n_b = m.shape[1]
        in_b = (inv >= off_old) & (inv < off_old + n_b)
        out[in_b] = inv[in_b] - off_old + off_new
        off_old += n_b
        off_new += cap
        padded.append(m if n_b == cap else np.pad(
            m, ((0, 0), (0, cap - n_b)), constant_values=sentinel))
    return padded, out.astype(np.int32)


def stack_to_caps(parts: Sequence[Tuple[Sequence[np.ndarray], np.ndarray]],
                  sentinel: int, stem: str) -> Dict[str, np.ndarray]:
    """Every device's (mats, inv) of one direction stacked for
    shard_map under the keys '<stem>_<b>' [P, w_b, cap_b] and
    '<stem>_inv' [P, n]: cap_b is the largest row count any device's
    table of bucket b has (each a multiple of ROW_TILE already); a
    bucket empty on every device gets no key. The zero-padded bucket
    index keeps lexicographic key order == width order (bucket ladders
    are < 100 wide: 2^99 degrees is beyond any graph)."""
    caps = [max(m.shape[1] for m in bucket)
            for bucket in zip(*(mats for mats, _ in parts))]
    padded = [pad_to_caps(mats, inv, caps, sentinel) for mats, inv in parts]
    tables = {f"{stem}_inv": np.stack([inv for _, inv in padded])}
    for b, cap in enumerate(caps):
        if cap:
            tables[f"{stem}_{b:02d}"] = np.stack([m[b] for m, _ in padded])
    return tables


def _rides_as_words(dtype, f: int) -> bool:
    """Whether a [R, f] operand of this dtype is gathered as 16-bit
    words: one-byte elements (the fp8 transports) and an even width.
    Everything else (bf16, f32, an odd width) is gathered as it is."""
    return jnp.dtype(dtype).itemsize == 1 and f % 2 == 0


def _pack_words(x):
    """One-byte elements [R, f] -> uint16 [R, f/2], byte for byte:
    column j rides in the low byte of word j, column f/2 + j in its
    high byte, so each byte plane widens to a contiguous half of the
    row (_widen_sum) and no message is ever shuffled. Unsigned: no bit
    pattern is touched and an all-zero row stays all zero. One pass
    over the table a call, never over the messages: each half is
    sliced BEFORE it is widened, which the chip's compiler makes one
    fusion of (widening the whole row first costs a second pass)."""
    h = x.shape[-1] // 2

    def plane(cols):
        return jax.lax.bitcast_convert_type(cols, jnp.uint8).astype(
            jnp.uint16)

    return plane(x[:, :h]) | (plane(x[:, h:]) << 8)


def _widen_sum(msgs, dt):
    """Gathered words uint16 [w, rows, f/2] -> the f32 sums over w of
    the `dt` elements they carry (_pack_words' layout), as the two
    [rows, f/2] halves of [rows, f]: columns [0, f/2) from the low
    bytes, [f/2, f) from the high ones. The same values
    `astype(float32)` gives for every one of the 256 byte patterns, NaN
    included, since each byte goes back through the dtype's own
    convert. Byte split, convert and sum compile to one fusion that
    reads the words and writes the two halves."""
    def plane(b):
        return jax.lax.bitcast_convert_type(
            b.astype(jnp.uint8), dt).astype(jnp.float32).sum(axis=0)

    return plane(msgs & 0xFF), plane(msgs >> 8)


def _gather_sum(table, mat, scope="", dt=None):
    """One slot-major table's (or chunk's) messages gathered and summed:
    mat [w, rows] -> the f32 [rows, F] sums, as a tuple of column
    blocks in order (one, or the word path's two halves). `table` is
    fbuf_pad itself, or (dt given) its uint16 word view from
    _pack_words, whose rows carry F elements of `dt` in F/2 words.

    The barrier keeps the widening to f32 INSIDE the reduction. The
    chip's gather yields the flat [w * rows, F] stream and a free view
    of it as [w, rows, F] (rows is a multiple of ROW_TILE); left alone,
    XLA hoists the convert above that view, where it becomes a pass of
    its own that writes every message to HBM in f32 (a convert whose
    consumer is a view does not fuse into the reduce behind it). With
    the gathered messages pinned in their transport dtype, convert and
    reduce compile to one fusion that reads 1, 2 or 4 bytes an element
    and writes [rows, F] (tests/test_tpu_compile.py holds it there)."""
    with jax.named_scope(scope + "gather"):
        msgs = jax.lax.optimization_barrier(
            jnp.take(table, mat, axis=0, mode="clip"))
    with jax.named_scope(scope + "reduce"):
        if dt is not None:
            return _widen_sum(msgs, dt)
        return (msgs.astype(jnp.float32).sum(axis=0),)


def chunk_rows(w: int, n_b: int, f: int, chunk_elems: int):
    """(rows a chunk, chunks) bucket_aggregate cuts a [w, n_b] table
    into at a feature (slab) width of f. A gather stays under
    `chunk_elems` elements, chunks are equal and ROW_TILE-aligned, and
    the last one ends at the table's end, so rows * chunks - n_b rows
    are gathered twice. Cutting at the budget's own row count
    re-gathered up to a whole chunk a bucket (2% of Yelp's requests on
    the x1.5 ladder, more on any finer one); spreading n_b evenly over
    the fewest chunks and rounding up to ROW_TILE re-gathers up to
    ROW_TILE rows a CHUNK, which is more than that on a bucket of 47
    chunks. So: the row count between half the budget's and all of it
    that re-gathers least (the larger on a tie), never more than the
    even spread does: under ROW_TILE * chunks rows."""
    max_rows = max(ROW_TILE, chunk_elems // (w * f) // ROW_TILE * ROW_TILE)
    if n_b <= max_rows:
        return row_cap(n_b), 1
    rows = np.arange(max_rows, max_rows // 2, -ROW_TILE)
    gathered = -(-n_b // rows) * rows
    r = int(rows[np.argmin(gathered)])
    return r, -(-n_b // r)


def part_heights(idx_mats) -> List[int]:
    """Rows each part of a direction holds in bucket_aggregate's buffer
    of sums: its buckets' rows (a table's last axis) and its zero row.
    `idx_mats` is a list of tables a part (one device's [w, rows], or
    stacked [P, w, rows])."""
    return [sum(int(m.shape[-1]) for m in mats) + 1 for mats in idx_mats]


def result_bytes(tables, stem: str, width: int) -> int:
    """The float32 bytes one call of bucket_aggregate over one
    direction's tables ('<stem>_<b>', a part's under its own stem; one
    device's or stacked) writes for its sums, `width` features wide
    (past one slab of the one-byte transports, SLAB_BYTES elements,
    rounded up to whole slabs): the one buffer of every part's bucket
    sums and zero row (part_heights), and one take a part, [n_out,
    width]. Read off the tables' shapes as the kernel sizes those
    buffers."""
    heights = part_heights([[tables[k] for k in _bucket_keys(tables, s)]
                            for s in _part_stems(tables, stem)])
    n_out = int(tables[stem + "_inv"].shape[-1])
    f = width if width <= SLAB_BYTES else -(-width // SLAB_BYTES) * SLAB_BYTES
    return 4 * f * (sum(heights) + len(heights) * n_out)


def bucket_aggregate(
    fbuf: jax.Array,
    idx_mats: Sequence[jax.Array],
    inv_perm: jax.Array,
    chunk_elems: int = DEFAULT_CHUNK_ELEMS,
    chunk_edges: Optional[int] = None,
    slab: Optional[int] = None,
    scope: str = "",
) -> jax.Array:
    """Scatter-free sum aggregation. fbuf [R, F] (any float dtype);
    returns f32 [n_out, F] where n_out = inv_perm length. idx_mats are
    slot-major, [w_b, rows_b] with rows_b a multiple of ROW_TILE
    (build_tables_for_edges), and index into fbuf with R itself as the
    zero-row sentinel.

    A direction cut by source rows into k parts (Direction) comes
    as k lists of tables and a list of k inverse permutations: part p
    indexes rows part_bounds(R, k)[p] of fbuf, counted from the part's
    first, with the part's row count as its zero sentinel. Each part
    is packed from its own rows and gathered from its own table (one
    that fits memory space S(1), GATHER_PART_BYTES), and the parts'
    sums meet in the unpermute: sum over p of take(sums_p, inv_p), in
    f32 and in part order. One part is the same program with one take;
    which runs follows from the tables alone.

    Every destination row's float32 sum is written ONCE, in place, into
    one buffer of every part's sums (result_bytes counts it): part p's
    buckets' rows one after the other and a zero row (part_heights),
    F features wide. The buffer is allocated, not filled (lax.empty):
    every bucket row is written by its reduce, and the zero rows are
    written as such. Each part's take then reads it in rows of all F
    features and writes [n_out, F], the result's own layout; their sum
    is left to the consumer's fusion (the degree division), so no
    bucket result, no stacked slab output and no relayout of the result
    exists.

    `chunk_edges` (the --spmm-chunk edge budget) overrides the default
    element budget: each gather materializes at most ~chunk_edges
    messages. A bucket over the budget runs as a lax.scan over slices
    of its table along the rows, ROW_TILE-aligned and BALANCED
    (chunk_rows: the fewest chunks the budget allows, of equal size),
    each writing its rows of the bucket's sums; the last slice is moved
    back to end at the table's end, so no padded copy of the int32
    table exists, and under ROW_TILE rows a chunk are gathered twice
    (and written twice, the same values).

    Rows wider than SLAB_BYTES are processed per feature slab (see
    SLAB_BYTES note above) by a lax.scan over the slabs: each slab's
    tables are packed from its columns of fbuf, and its sums go to its
    columns of the buffer. `slab` overrides the element width (0
    disables slabbing); a width that is no whole number of slabs is
    padded with zero columns to one (`relayout`).

    One-byte elements and an even (slab) width ride the gathers
    as 16-bit words (_rides_as_words): fbuf_pad is packed into uint16
    [R + 1, f/2] once a call and a slab (_pack_words), the takes read
    that, and the reduction splits the words back into the two byte
    planes as it widens them (_widen_sum), each plane's half written
    where it belongs. Rows, order, chunking, tables and the f32 sums
    are the element path's, to the bit; which path runs follows from
    fbuf's dtype and width alone. Every bucket, chunked or not, goes
    through _gather_sum.

    Every gather runs with mode='clip' (clamped, no bounds-check
    select): the table indices are in-bounds BY CONSTRUCTION (pad
    entries point at appended zero sentinel rows, validated host-side
    by validate_bucket_tables), and jnp.take's default FILL_OR_DROP
    mode is the one component of this kernel that can FABRICATE NaN
    out of valid data — exactly the failure shape of the epoch-0
    products-scale NaN that appeared on the experimental TPU platform
    but never on CPU (docs/RESILIENCE.md "Numerics").

    The work is named for the profiler (obs/profiler.py SCOPE_NAMES):
    `gather` (the row takes and the packing of their tables), `reduce`
    (the sum over a bucket's width, widened to f32 as it reads, and its
    write into the buffer of sums), `unpermute` (the inv_perm takes),
    `relayout` (the zero columns of a width that is no whole number of
    slabs). `scope` prefixes them: the block kernel's remainder passes
    "rem_"."""
    r, f = fbuf.shape
    if slab is None:
        slab = SLAB_BYTES // fbuf.dtype.itemsize
    if not slab or f <= slab:
        slab = f
    n_s = -(-f // slab)
    if chunk_edges:
        chunk_elems = chunk_edges * slab
    if not isinstance(inv_perm, (list, tuple)):
        idx_mats, inv_perm = [idx_mats], [inv_perm]
    k = len(inv_perm)
    bounds = part_bounds(r, k)
    heights = part_heights(idx_mats)
    bases = np.cumsum([0] + heights[:-1]).tolist()
    # under shard_map the buffer varies over the mesh axes its inputs
    # vary over (a scan's carry keeps its type from the first
    # iteration on)
    vma = jax.typeof(fbuf).vma.union(
        *(jax.typeof(m).vma for mats in idx_mats for m in mats))

    def varying(x):
        return jax.lax.pcast(x, tuple(vma), to="varying") if vma else x

    with jax.named_scope(scope + "reduce"):
        sums = varying(jax.lax.empty((sum(heights), n_s * slab),
                                     jnp.float32))
        zero = varying(jnp.zeros((1, n_s * slab), jnp.float32))
        for b, h in zip(bases, heights):
            sums = jax.lax.dynamic_update_slice(sums, zero, (b + h - 1, 0))
    if n_s * slab > f:
        with jax.named_scope(scope + "relayout"):
            fbuf = jnp.pad(fbuf, ((0, 0), (0, n_s * slab - f)))

    def one_slab(sums, s):
        col = s * slab
        for p, ((lo, hi), mats) in enumerate(zip(bounds, idx_mats)):
            rows = fbuf
            if p:
                # a part's table is packed once the part before it is
                # summed, so one part's table is live at a time and
                # each keeps its place in S(1) (scheduled freely, the
                # chip's compiler interleaves the parts' chunk loops
                # and leaves a third of them reading from HBM)
                with jax.named_scope(scope + "gather"):
                    rows, sums = jax.lax.optimization_barrier((fbuf, sums))
            if k > 1 or n_s > 1:
                rows = jax.lax.dynamic_slice(rows, (lo, col),
                                             (hi - lo, slab))
            sums = _part_sums(rows, mats, sums, bases[p], col, chunk_elems,
                              scope)
        return sums, None

    if n_s == 1:
        sums, _ = one_slab(sums, 0)
    else:
        sums, _ = jax.lax.scan(one_slab, sums, jnp.arange(n_s))
    # a part contributes to every destination row that has an edge in
    # it; a row with none reads the part's zero row. One take a part,
    # rows of all F features; their sum is no pass of its own, the
    # chip's compiler fuses it into the consumer that reads the result
    # (tests/test_tpu_compile.py)
    with jax.named_scope(scope + "unpermute"):
        out = functools.reduce(jnp.add, [
            jnp.take(sums, inv + b, axis=0, mode="clip")
            for inv, b in zip(inv_perm, bases)])
    if n_s * slab > f:
        with jax.named_scope(scope + "relayout"):
            out = out[:, :f]
    return out


def _part_sums(fbuf, idx_mats, sums, base, col, chunk_elems, scope):
    """One part's buckets over one slab (bucket_aggregate): fbuf [R, f]
    is the part's source rows in the slab's columns, the tables index
    it with R as the zero sentinel. Each bucket's sums are written in
    place into `sums`, from row `base` on in bucket order, at columns
    [col, col + f); returns the buffer."""
    f = fbuf.shape[-1]
    with jax.named_scope(scope + "gather"):
        fbuf_pad = jnp.concatenate(
            [fbuf, jnp.zeros((1, f), fbuf.dtype)], axis=0
        )
        # the operand the gathers read: fbuf_pad, or its 16-bit
        # word view where the elements are one byte wide (SLAB_BYTES
        # note); `dt` tells the reduction what the words carry
        table, dt = fbuf_pad, None
        if _rides_as_words(fbuf.dtype, f):
            table, dt = _pack_words(fbuf_pad), fbuf.dtype

    def write(sums, mat, row):
        # the word path's two byte planes each go straight to their
        # half of the slab's columns: no pass joins them
        planes = _gather_sum(table, mat, scope, dt)
        with jax.named_scope(scope + "reduce"):
            c = col
            for plane in planes:
                sums = jax.lax.dynamic_update_slice(sums, plane, (row, c))
                c = c + plane.shape[-1]
            return sums

    row = base
    for mat in idx_mats:
        w, n_b = mat.shape
        if n_b == 0:
            continue
        rows_per_chunk, n_chunks = chunk_rows(w, n_b, f, chunk_elems)
        if n_chunks == 1:
            sums = write(sums, mat, row)
        else:
            def body(sums, i):
                # the last chunk starts early enough to be whole: it
                # writes under ROW_TILE * n_chunks rows of the chunk
                # before it again, the same values
                start = jnp.minimum(i * rows_per_chunk, n_b - rows_per_chunk)
                m = jax.lax.dynamic_slice_in_dim(mat, start, rows_per_chunk,
                                                 axis=1)
                return write(sums, m, row + start), None

            sums, _ = jax.lax.scan(body, sums, jnp.arange(n_chunks))
        row += n_b
    return sums


class TablePart(NamedTuple):
    """One part's tables (build_tables_for_edges) and the widths they
    were built at."""
    mats: List[np.ndarray]
    inv: np.ndarray
    counts: List[int]
    widths: List[int]


def part_degrees(gsrc: np.ndarray, gdst: np.ndarray, n_out: int,
                 n_src_rows: int, k: int) -> List[np.ndarray]:
    """Per part of k (part_bounds over the n_src_rows source rows), the
    [n_out] degrees of the destination rows over the part's edges: one
    mask pass a part over the edges (none for one part)."""
    if k == 1:
        return [np.bincount(gdst, minlength=n_out)]
    return [np.bincount(gdst[(gsrc >= lo) & (gsrc < hi)], minlength=n_out)
            for lo, hi in part_bounds(n_src_rows, k)]


class Direction:
    """One direction's bucket tables, cut by source rows into parts.

    The edges (real ones only) are gathered from `gsrc`, n_src_rows
    source rows, into `gdst`, n_out destination rows. The source rows
    are cut into k = source_parts(n_src_rows) ranges (part_bounds);
    part p holds the edges whose source lies in its range, with indices
    counted from the range's first row and the range's row count as its
    zero sentinel, its widths fitted to its own degrees (or given), and
    an inverse permutation over ALL n_out rows: a row with no edge in
    the part points at the part's zero output row. One part is the
    uncut table. `k` overrides the count (the attention kernel and the
    sequential runner keep one)."""

    def __init__(self, gsrc: np.ndarray, gdst: np.ndarray, n_out: int,
                 n_src_rows: int, widths=None, k: Optional[int] = None):
        self.k = source_parts(n_src_rows) if k is None else int(k)
        self.n_out, self.n_src_rows = n_out, n_src_rows
        self._edges = (gsrc, gdst)
        self.parts: List[Optional[TablePart]] = [None] * self.k
        self.set_widths(widths if widths is not None else
                        [fit_widths(degree_hist([d])) for d in self.degs])

    @functools.cached_property
    def degs(self) -> List[np.ndarray]:
        """Per part, the destination rows' degrees over its edges."""
        return part_degrees(*self._edges, self.n_out, self.n_src_rows,
                            self.k)

    def set_widths(self, widths) -> None:
        """(Re)build the parts at `widths`: one ladder a part, or one
        ladder for every part. A part already at its widths is left
        alone."""
        if len(widths) and np.ndim(widths[0]) == 0:
            widths = [widths] * self.k
        if len(widths) != self.k:
            raise ValueError(f"{len(widths)} width ladders for a "
                             f"direction cut into {self.k} parts")
        gsrc, gdst = self._edges
        bounds = part_bounds(self.n_src_rows, self.k)
        for p, ((lo, hi), w) in enumerate(zip(bounds, widths)):
            if self.parts[p] is not None and self.parts[p].widths == list(w):
                continue
            if self.k == 1:
                src, dst = gsrc, gdst
            else:
                sel = (gsrc >= lo) & (gsrc < hi)
                src, dst = gsrc[sel] - lo, gdst[sel]
            self.parts[p] = TablePart(*build_tables_for_edges(
                src, dst, self.n_out, hi - lo, w), list(w))

    @property
    def widths(self) -> Tuple[Tuple[int, ...], ...]:
        return tuple(tuple(p.widths) for p in self.parts)

    def whole(self) -> TablePart:
        """The one part of an uncut direction."""
        if self.k != 1:
            raise ValueError(f"a direction cut into {self.k} parts by "
                             f"source rows has no one table: read .parts")
        return self.parts[0]


def stack_direction(dirs: Sequence[Direction], stem: str
                    ) -> Dict[str, np.ndarray]:
    """Every device's Direction stacked for shard_map (stack_to_caps a
    part): part 0 under '<stem>_<b>' / '<stem>_inv', part p > 0 under
    '<stem>_p<p>_<b>' / '<stem>_p<p>_inv', so an uncut direction keeps
    the keys it always had. The devices share the source-row count, so
    they share the cut."""
    out: Dict[str, np.ndarray] = {}
    bounds = part_bounds(dirs[0].n_src_rows, dirs[0].k)
    for p, (lo, hi) in enumerate(bounds):
        out.update(stack_to_caps([(d.parts[p].mats, d.parts[p].inv)
                                  for d in dirs], hi - lo,
                                 _part_stem(stem, p)))
    return out


def _part_stem(stem: str, p: int) -> str:
    return stem if p == 0 else f"{stem}_p{p}"


class BucketPlan:
    """Host-side plan for one device: forward + transpose bucket tables.

    fwd aggregates src->dst (the training SpMM over the [R=n_inner+halo]
    source rows into n_out destination rows); bwd aggregates dst->src for
    the gradient. Each is a Direction, cut by its own source rows (R for
    fwd, n_out for bwd) into parts where those would make a table taller
    than GATHER_PART_BYTES. Tables are numpy, slot-major and ready for
    bucket_aggregate as they are; pad_to_caps widens them to row caps
    shared across devices. Widths: one ladder (for every part) or one a
    part; `parts` = (fwd, bwd) part counts overrides the heights'.
    """

    def __init__(self, edge_src: np.ndarray, edge_dst: np.ndarray,
                 n_out: int, n_src_rows: int,
                 fwd_widths=None, bwd_widths=None,
                 parts: Optional[Tuple[int, int]] = None):
        real = edge_dst < n_out
        src, dst = edge_src[real], edge_dst[real]
        k_fwd, k_bwd = parts if parts is not None else (None, None)
        self.n_out = n_out
        self.n_src_rows = n_src_rows
        self.fwd = Direction(src, dst, n_out, n_src_rows, fwd_widths, k_fwd)
        # transpose: swap roles; "destinations" are the source rows
        self.bwd = Direction(dst, src, n_src_rows, n_out, bwd_widths, k_bwd)


def transport_dtypes(rem_dtype: Optional[str]):
    """(forward, backward) gather-transport dtypes for a remainder/
    bucket transport spec. The gather path is request-rate-bound at
    256-byte rows (SLAB_BYTES note), so BYTES PER FEATURE set the
    row count: fp8 packs 256 features into one 256 B slab, half the
    gathered rows of bf16 at F=256. Half the rows is half the time
    only because bucket_aggregate hands such a row to the gather as
    128 16-bit words: as 256 one-byte elements a request cost twice a
    bf16 row's and the gather took as long as under bf16 (the ledger's
    PR 30 lines). The reduction reads a quarter of f32's bytes either
    way. Activations travel e4m3 (range +-448 suits post-norm
    activations), cotangents e5m2 (gradient dynamic range needs
    exponent bits); accumulation stays f32 either way. None = no cast
    (the activation dtype)."""
    if rem_dtype in (None, "", "none"):
        return None, None
    if rem_dtype == "float8":
        return jnp.float8_e4m3fn, jnp.float8_e5m2
    if rem_dtype == "bfloat16":
        return jnp.bfloat16, jnp.bfloat16
    raise ValueError(f"unknown transport dtype: {rem_dtype!r}")


# finite maxima of the fp8 transport dtypes: they have NO inf, so an
# overflowing astype produces NaN — transport_cast saturates instead
# (the standard fp8 convention). Raw layer-0 features beyond the range
# (use_pp=False / gcn) thus degrade gracefully rather than poisoning
# the epoch with NaN.
_F8_MAX = {jnp.float8_e4m3fn: 448.0, jnp.float8_e5m2: 57344.0}


def transport_cast(x: jax.Array, dt) -> jax.Array:
    """Saturating cast to a transport dtype (identity when dt is
    None); fp8 targets clamp to their finite max first."""
    if dt is None:
        return x
    m = _F8_MAX.get(dt)
    if m is not None:
        x = jnp.clip(x.astype(jnp.float32), -m, m)
    return x.astype(dt)


def amax_transport_cast(x: jax.Array, dt):
    """Amax-clamped fp8 cast (resilience/numerics guardrail): scale the
    tensor by a power of two chosen from its own amax so values land
    mid-range in the fp8 format — instead of the static clamp
    saturating large activations (a silent bias) or small cotangents
    flushing to zero (a silent underflow). Returns ``(y, inv_scale)``;
    the caller multiplies the (linear) aggregation's output by
    ``inv_scale`` to undo it. inv_scale is None when dt is not an fp8
    format (the plain saturating cast applies)."""
    if dt is None:
        return x, None
    m = _F8_MAX.get(dt)
    if m is None:
        return transport_cast(x, dt), None
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf))
    # power-of-two scale targeting half the finite max (headroom for
    # the aggregation's intermediate values); exact to re-divide, so
    # the de-scale introduces no extra rounding. Degenerate amax
    # (zero / non-finite) keeps scale 1 — a NaN input must stay a NaN
    # output for the tripwire, never become a NaN *scale*.
    ok = jnp.isfinite(amax) & (amax > 0)
    s = jnp.where(ok, jnp.exp2(jnp.floor(jnp.log2(
        m / 2.0 / jnp.where(ok, amax, 1.0)))), 1.0)
    y = jnp.clip(xf * s, -m, m).astype(dt)
    return y, 1.0 / s


def make_bucket_spmm_fn(
    fwd_mats: Sequence[jax.Array],
    fwd_inv: jax.Array,
    bwd_mats: Sequence[jax.Array],
    bwd_inv: jax.Array,
    in_deg: jax.Array,
    n_src_rows: int,
    chunk_elems: int = DEFAULT_CHUNK_ELEMS,
    chunk_edges: Optional[int] = None,
    rem_dtype: Optional[str] = None,
    rem_amax: bool = False,
):
    """Differentiable mean-aggregation closure: f(fbuf [R, F]) ->
    f32 [n_out, F]; backward is the transpose bucket aggregation, f32
    accumulation, cotangent cast back to fbuf's dtype. `rem_dtype`
    optionally narrows the GATHER TRANSPORT (see transport_dtypes) —
    the one cast before aggregation halves gathered rows at F=256.
    `rem_amax` swaps the static saturating fp8 cast for the
    amax-clamped one (amax_transport_cast): per-tensor power-of-two
    scaling into mid-range, inverse applied after aggregation. The
    transport casts run under the named scope `cast`, the degree
    division and the amax de-scale under `scale`, the whole backward
    under `bwd`."""
    deg_col = in_deg[:, None]
    fwd_dt, bwd_dt = transport_dtypes(rem_dtype)

    def _cast(x, dt):
        with jax.named_scope("cast"):
            if rem_amax:
                return amax_transport_cast(x, dt)
            return transport_cast(x, dt), None

    @jax.custom_vjp
    def f(fbuf):
        y, inv = _cast(fbuf, fwd_dt)
        out = bucket_aggregate(y, fwd_mats, fwd_inv, chunk_elems,
                               chunk_edges)
        with jax.named_scope("scale"):
            out = out / deg_col
            return out * inv if inv is not None else out

    def fwd(fbuf):
        return f(fbuf), jnp.zeros((0,), fbuf.dtype)

    def bwd(proto, g):
        # transpose aggregation; cotangents travel in the transport
        # dtype (default: the activation dtype — half the gather
        # traffic and double the slab width vs f32, same precision as
        # the halo exchange), while bucket_aggregate still accumulates
        # in f32. The transport cast comes straight from the f32
        # value — never through an intermediate rounding.
        with jax.named_scope("bwd"):
            with jax.named_scope("scale"):
                gd32 = g.astype(jnp.float32) / deg_col
            if bwd_dt is not None:
                gd, inv = _cast(gd32, bwd_dt)
            else:
                with jax.named_scope("cast"):
                    gd, inv = gd32.astype(proto.dtype), None
            d_fbuf = bucket_aggregate(gd, bwd_mats, bwd_inv, chunk_elems,
                                      chunk_edges)
            with jax.named_scope("scale"):
                if inv is not None:
                    d_fbuf = d_fbuf * inv
                return (d_fbuf[:n_src_rows].astype(proto.dtype),)

    f.defvjp(fwd, bwd)
    return f


def build_sharded_bucket_tables(sg, chunk_elems: int = DEFAULT_CHUNK_ELEMS,
                                min_width: int = 0,
                                plan_cache: Optional[dict] = None,
                                dirty: Optional[Sequence[int]] = None
                                ) -> Dict[str, np.ndarray]:
    """Stacked per-device tables for shard_map (leading device axis),
    padded to shared bucket widths and per-bucket row caps so the traced
    program is identical on every device. The widths are fitted to the
    degree histograms of ALL the shards (fit_widths), one ladder a
    direction; a cap is the largest row count any shard has in a bucket.

    `min_width` is the narrowest width allowed (see fit_widths): every
    lower-degree row joins the first bucket, the bucket-merge
    launch-overhead lever, surfaced as --bucket-merge.

    `plan_cache` (a mutable dict, updated in place) with `dirty` (shard
    ids whose edges changed) is the streaming-delta fast path: per-
    shard degrees and BucketPlans are recomputed only for
    dirty shards, clean shards reuse the cached ones: the O(E_r) per-
    shard plan builds are the dominant cost, and a delta batch touches
    few shards. Cached plans are only valid at the SAME widths, and a
    fitted ladder would move with every histogram (every table's shape
    with it: a recompile). So a dirty rebuild KEEPS the cached ladder
    for as long as its top width covers the new largest degree, and
    refits (every plan rebuilt) only when it no longer does or on a
    build from nothing. After deltas the tables can therefore differ
    from a cache-free build of the same graph in their WIDTHS and
    shapes, never in what they sum: every edge sits in exactly one
    slot either way.

    Each direction is cut by its source rows into parts where those
    would make a table taller than GATHER_PART_BYTES (Direction); every
    part has its own ladder, fitted to that part's histograms over all
    the shards, and its own caps.

    Returns {'bkt_fwd_<b>': [P, w_b, cap_b], 'bkt_fwd_inv': [P, n_max],
             'bkt_bwd_<b>': ..., 'bkt_bwd_inv': [P, R]}: slot-major
    (module docstring), cap_b a multiple of ROW_TILE; a direction cut
    into parts has its part p > 0 under 'bkt_fwd_p<p>_<b>' and
    'bkt_fwd_p<p>_inv' (stack_direction).
    """
    P = sg.num_parts
    n_src_rows = sg.n_max + sg.halo_size
    ks = (source_parts(n_src_rows), source_parts(sg.n_max))
    cache = plan_cache if plan_cache is not None else {}
    stale = set(range(P)) if dirty is None or not cache else set(dirty)
    if cache.get("shape") != (sg.n_max, n_src_rows) or \
            cache.get("min_width") != min_width:
        cache.clear()
        stale = set(range(P))

    # per-shard degrees of each part (cached; only dirty shards rescan
    # their edges)
    degs = cache.get("degs", [None] * P)
    degs += [None] * (P - len(degs))
    for r in range(P):
        if degs[r] is None or r in stale:
            real = sg.edge_dst[r] < sg.n_max
            src, dst = sg.edge_src[r][real], sg.edge_dst[r][real]
            degs[r] = (part_degrees(src, dst, sg.n_max, n_src_rows, ks[0]),
                       part_degrees(dst, src, n_src_rows, sg.n_max, ks[1]))
    hists = [[degree_hist(d[i][p] for d in degs) for p in range(k)]
             for i, k in enumerate(ks)]
    fw, bw = cache.get("widths", ((), ()))
    covers = (fw and bw and all(
        w[-1] >= h.shape[1] - 1
        for w, h in zip(fw + bw, hists[0] + hists[1])))
    if dirty is None or not covers:
        kept = (fw, bw)
        fw, bw = (tuple(tuple(fit_widths(h, min_width=min_width))
                        for h in hs) for hs in hists)
        if (fw, bw) != kept:
            stale = set(range(P))  # ladder moved: every plan is invalid

    old_plans = cache.get("plans", [None] * P)
    old_plans += [None] * (P - len(old_plans))
    plans = [
        old_plans[r] if old_plans[r] is not None and r not in stale
        else BucketPlan(sg.edge_src[r], sg.edge_dst[r], sg.n_max,
                        n_src_rows, fwd_widths=fw, bwd_widths=bw)
        for r in range(P)
    ]
    if plan_cache is not None:
        plan_cache.update(
            shape=(sg.n_max, n_src_rows), min_width=min_width,
            widths=(fw, bw), degs=degs, plans=plans)
    tables = {**stack_direction([p.fwd for p in plans], "bkt_fwd"),
              **stack_direction([p.bwd for p in plans], "bkt_bwd")}
    validate_bucket_tables(
        tables, sg.n_max, n_src_rows,
        n_edges=[sum(int(x.sum()) for x in d[0]) for d in degs])
    return tables


def _bucket_keys(tables, stem: str) -> List[str]:
    """Keys of one part's bucket tables ('<stem>_<b>'), in width
    order, the inverse permutation left out."""
    return sorted(k for k in tables if k.startswith(stem + "_")
                  and k[len(stem) + 1:].isdigit())


def _part_stems(tables, stem: str) -> List[str]:
    """The stems of a direction's parts in `tables`, part 0 first
    (stack_direction's keys)."""
    out = [stem]
    while f"{_part_stem(stem, len(out))}_inv" in tables:
        out.append(_part_stem(stem, len(out)))
    return out


def direction_tables(tables, stem: str):
    """(idx_mats, inv_perm) of one direction as bucket_aggregate takes
    them: one part's list of tables and its permutation, or a list of
    each for a direction cut into parts."""
    stems = _part_stems(tables, stem)
    if len(stems) == 1:
        return [tables[k] for k in _bucket_keys(tables, stem)], \
            tables[stem + "_inv"]
    return ([[tables[k] for k in _bucket_keys(tables, s)] for s in stems],
            [tables[s + "_inv"] for s in stems])


def table_widths(tables, stem: str) -> Tuple[Tuple[int, ...], ...]:
    """Per part of one direction, the widths of its buckets that hold a
    row (tables [P, w, cap] or one device's [w, cap])."""
    return tuple(tuple(int(tables[k].shape[-2])
                       for k in _bucket_keys(tables, s))
                 for s in _part_stems(tables, stem))


def _parts_of(tables, stem: str, n_src_rows: int):
    """[(part stem, [lo, hi) source rows)] of one direction of
    `n_src_rows` source rows: the cut the kernel finds from the height
    and the part count (part_bounds)."""
    stems = _part_stems(tables, stem)
    return list(zip(stems, part_bounds(n_src_rows, len(stems))))


def table_edges(tables: Dict[str, np.ndarray], stem: str,
                n_src_rows: int) -> np.ndarray:
    """[P] edges each device's stacked tables of one direction hold:
    their entries that are no sentinel, over all its parts (a part's
    sentinel is its row count)."""
    n_dev = tables[stem + "_inv"].shape[0]
    return sum((np.count_nonzero(np.asarray(tables[k]) != hi - lo,
                                 axis=(1, 2))
                for s, (lo, hi) in _parts_of(tables, stem, n_src_rows)
                for k in _bucket_keys(tables, s)),
               np.zeros(n_dev, np.int64))


def pad_stats(tables: Dict[str, np.ndarray], stem: str, n_src_rows: int,
              chunk_elems: int = DEFAULT_CHUNK_ELEMS,
              f: int = SLAB_BYTES) -> dict:
    """What one direction's stacked tables ('<stem>_<b>' [P, w, cap],
    a part's under its own stem) make the gathers issue: `widths` (the
    buckets that hold a row, a list a part),
    `slots` (sum over devices, parts and buckets of w x the rows the
    kernel gathers, chunk tails as bucket_aggregate cuts them at a slab
    of `f` elements: the one-byte transports' 256, the most chunks),
    `edges` (entries that are no sentinel) and their ratio `pad_ratio`:
    gather requests an edge, the number the widths are fitted to lower;
    `parts` (tables the source rows are cut into, GATHER_PART_BYTES)
    and `part_rows` (source rows of the tallest part)."""
    parts = _parts_of(tables, stem, n_src_rows)
    widths, slots = [], 0
    for s, _ in parts:
        widths.append([])
        for k in _bucket_keys(tables, s):
            n_dev, w, cap = tables[k].shape
            rows, n_chunks = chunk_rows(w, cap, f, chunk_elems)
            widths[-1].append(int(w))
            slots += n_dev * w * rows * n_chunks
    edges = int(table_edges(tables, stem, n_src_rows).sum())
    return {"widths": widths,
            "slots": int(slots), "edges": edges,
            "pad_ratio": round(slots / max(edges, 1), 4),
            "parts": len(parts),
            "part_rows": max(hi - lo for _, (lo, hi) in parts)}


def bucket_pad_stats(tables: Dict[str, np.ndarray], n_max: int,
                     n_src_rows: int, stem: str = "bkt",
                     width: int = SLAB_BYTES) -> dict:
    """pad_stats of both directions of build_sharded_bucket_tables'
    tables (or, stem 'blkrem', of the block kernel's remainder), each
    with the `result_bytes` one call writes at `width` features (the
    widest in-step aggregation's; one device's call)."""
    return {d: {**pad_stats(tables, f"{stem}_{d}", rows),
                "result_bytes": result_bytes(tables, f"{stem}_{d}", width)}
            for d, rows in (("fwd", n_src_rows), ("bwd", n_max))}


def validate_bucket_tables(tables: Dict[str, np.ndarray], n_max: int,
                           n_src_rows: int,
                           n_edges: Optional[Sequence[int]] = None,
                           stem: str = "bkt") -> None:
    """Host-side check of sharded bucket tables ([P, ...] device
    axis leading; tables slot-major [P, w, cap], so a bucket's
    rows are its LAST axis; stem 'blkrem' for the block kernel's
    remainder), part by part where a direction is cut by source rows.

    Bounds: every index must lie in [0, bound] where bound is
    the consuming gather's zero-sentinel row: a part's row count for
    its tables, the rows of its buckets for its inverse permutation.
    The device kernel gathers
    with mode='clip' ON THE STRENGTH OF THIS CHECK — an out-of-bounds
    index from a build bug or a rotted cache must surface HERE as a
    named ValueError at build/load time, never as a silently-clamped
    wrong row (or, under the previous fill-mode gathers, a NaN minted
    mid-epoch).

    Edge conservation: with `n_edges` (the real edges of each device,
    what the tables were built from) every direction must hold exactly
    that many entries that are no sentinel over all its parts, device
    by device. A row's
    tail lost to a width too short, or an entry overwritten by the
    sentinel, changes a mean by less than the transports' own noise,
    so no comparison of outputs sees it (PERF.md section 2): it is
    counted here. Without `n_edges` the two directions are still held
    to each other.

    O(tables) numpy passes — noise next to the O(E) build."""
    fwd, bwd = stem + "_fwd", stem + "_bwd"
    src_rows = {fwd: n_src_rows, bwd: n_max}
    for d, n in src_rows.items():
        for s, (lo, hi) in _parts_of(tables, d, n):
            keys = _bucket_keys(tables, s)
            bounds = [(k, hi - lo) for k in keys] + [
                (s + "_inv", sum(int(tables[k].shape[-1]) for k in keys))]
            for k, top in bounds:
                a = np.asarray(tables[k])
                lo_v = int(a.min(initial=0))
                hi_v = int(a.max(initial=0))
                if lo_v < 0 or hi_v > top:
                    raise ValueError(
                        f"bucket table {k!r} holds out-of-bounds indices "
                        f"[{lo_v}, {hi_v}] (valid range [0, {top}]): "
                        f"corrupt table cache or a table-build bug — "
                        f"rebuild the partition artifact's cached tables")
    held = {d: table_edges(tables, d, n) for d, n in src_rows.items()}
    want = held[fwd] if n_edges is None else np.asarray(n_edges, np.int64)
    for d, got in held.items():
        if not np.array_equal(got, want):
            raise ValueError(
                f"bucket tables {d!r} hold {got.tolist()} edges a device "
                f"where {want.tolist()} were "
                + ("given" if n_edges is not None else
                   f"put into {fwd!r}")
                + ": an edge was dropped or overwritten (a table-build "
                  "bug or a corrupt table cache) — rebuild the partition "
                  "artifact's cached tables")


def make_device_bucket_spmm_fn(d: Dict[str, jax.Array], in_deg: jax.Array,
                               n_src_rows: int,
                               chunk_elems: int = DEFAULT_CHUNK_ELEMS,
                               chunk_edges: Optional[int] = None,
                               rem_dtype: Optional[str] = None,
                               rem_amax: bool = False):
    """Bind the per-device blocks of build_sharded_bucket_tables (call
    inside shard_map, after stripping the leading device axis) into the
    differentiable closure."""
    return make_bucket_spmm_fn(
        *direction_tables(d, "bkt_fwd"), *direction_tables(d, "bkt_bwd"),
        in_deg, n_src_rows, chunk_elems, chunk_edges, rem_dtype,
        rem_amax)
