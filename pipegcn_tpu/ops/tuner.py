"""Shape-aware SpMM kernel auto-tuner: measured cost tables, not guesses.

The hand-tuned ``auto`` thresholds this replaces (edge-count and
dense-coverage cutoffs in ``parallel/trainer.py``) were invalidated by
the very first second shape they met (the products-shape block-kernel
crash). This module instead *times* each viable kernel configuration —
{sorted-XLA, bucket, block} x remainder transport dtype
{none, bf16, fp8, fp8+amax} x block group size — on a sample of the
heaviest shard, and persists the winner plus the full measured cost
table into the partition artifact (``tuning.json`` sidecar, valid for
both the v2 npz and v3 mmap directory formats).

What is sampled (``sample_slice``): whole blocks of destination rows,
aligned to ``block_tile`` x the grid's largest group (1024 rows at the
defaults), spread over the row range, each row with its FULL in-edge
list, the source ids left where they are. Every (destination tile,
source tile) pair of a sampled tile-row then holds exactly the edges it
holds on the shard, so a block candidate meets the shard's dense tiles
and the bucket ladder the shard's in-degrees. Why: the sample this
replaces drew ~400 single rows uniformly and renumbered them, which
left no tile dense enough for the MXU path, and its 200k edges were
4 ms of device work, the size of one host round trip — on the chip it
ranked the 3.4x faster block kernel 1.6x slower (PERF.md, PR 21).

What is ranked (``shard_estimate``): what a call would cost AT THE
SHARD'S SIZE, never the milliseconds of a sampled call. A sample keeps
a few thousand destination rows and the WHOLE source space, so most of
a sampled call is work that follows the source rows (packing the table
into words, the backward's visit of every source row, the block
kernel's tile padding) and does not grow with the edges: on Reddit the
1M-edge sample put the bucket kernel with fp8 transport 2 ms behind the
block kernel, a near-tie, where at the shard's 114.85M edges it costs
twice the epoch (PERF.md section 6, PR 34). So every candidate is timed
on TWO nested samples of the same source space (the budget's blocks and
a quarter of them), the line through the two best reps gives
``per_edge_s`` and ``fixed_s``, and the ranking is on ``est_call_s =
fixed_s + per_edge_s * shard_edges``. A near-tie (candidates the
argmin's own spread, carried through the same arithmetic, cannot tell
from it) falls to the fixed preference order, ``DEFAULT_IMPL``'s family
first. A shard under the budget is timed whole, once: its estimate is
its time. A sample of a single block has no smaller one: its time is
read in proportion to the edges.

Timing follows the microbench idiom (scripts/spmm_microbench.py):
tables ride as jit ARGUMENTS, never closure constants (closed-over
arrays embed into the HLO as constants), and every call ends in the
device->host read of the one scalar the program returns, so the clock
stops after the device does. That scalar is fed by the forward's
output AND by the gradient under a real cotangent, over the widest
operand an aggregation inside the step sees: the aggregation is
linear, so a program that returns the gradient alone has its forward
removed as dead code (what format 1 timed).

Staleness: a persisted table is trusted only when its tuner format,
source-graph edge checksum AND config signature (backend, feature
width, tile, bucket-merge, chunk) all match. Any mismatch is returned
as a human-readable reason so the caller can re-tune live WITH A LOUD
RECORD instead of silently dispatching from a rotted table.
"""

from __future__ import annotations

import json
import os
import time
from types import SimpleNamespace
from typing import (Any, Callable, Dict, List, Optional, Sequence,
                    Tuple)

import numpy as np

from ..obs.trace import recorder

# 6: candidates are ranked by est_call_s, the estimate of a call at the
# shard's size from two nested samples (shard_estimate), and the row
# buckets' widths are fitted to the degree histogram, their chunks
# balanced (bucket_spmm fit_widths, chunk_rows; PR 34): a table of
# format 5 ranks sampled milliseconds, of costs the candidates no
# longer have. 5: the grid lost its three streaming-slab twins with the path they
# timed (PR 32): a table of format 4 was ranked over another grid and
# may name a candidate that no longer exists. 4: one-byte transports
# are gathered as 16-bit words (bucket_spmm _pack_words): every fp8
# candidate's time changed, the others' did not, so a table of format
# 3 ranks them by a cost fp8 no longer has.
# 3: the bucket and remainder kernels gather slot-major and widen to
# f32 inside the reduction (ops/bucket_spmm.py): a table timed on the
# destination-major kernels of format 2 ranks transports by a cost
# they no longer have. 2: the sample keeps whole destination tile-rows
# (sample_slice); a table timed on the row-wise sample of format 1 is
# stale
TUNER_FORMAT = 6
TUNING_FILE = "tuning.json"

# the larger sample takes whole blocks of destination rows until this
# many edges are covered; the CLI surfaces it as --tuner-samples. Sized
# on a v5e by where a candidate's time becomes a line in its edges
# (PERF.md section 6, PR 34: nested samples of 1 to 16 blocks of the
# Reddit shape, 0.5M edges a block): from 1M edges up every candidate's
# slope reads within a quarter of its cost an edge at the shard's size
# (`block-f8` 1.55 ns between 1M and 4M for 1.53 traced, `bucket-f8`
# 5.5 for 5.2), while at ONE block the block candidates with fp8
# transport read 2 ms under their line (4.5 ms for 6.4), which made
# the 1M / 0.5M pair rank them 3.4 times too dear. So 4M, whose nested
# quarter is the 1M point; a candidate costs 4 to 8 s to build, compile
# and time there, and 2 to 3 s at the quarter
DEFAULT_EDGE_BUDGET = 4_000_000

# what a tuning record says about its sample beside the cost table
# (the contracted `tuning` record of the metrics stream carries them):
# the dense coverage the block candidates met on the sample against the
# whole shard's, the destination tile-rows sampled, the edges of the
# timed samples (two, nested; one where the shard was timed whole) and
# of the shard the estimates are made for, and the winner's estimate,
# to hold against a traced spmm_s
SAMPLE_FIELDS = ("sample_dense_coverage", "shard_dense_coverage",
                 "sample_tile_rows", "timed_edges", "shard_edges",
                 "est_epoch_spmm_s")

# the second, nested sample: this share of the first one's blocks
# (never under one). A quarter keeps the two points of the line far
# apart (the slope's error is the clock's over their distance) at a
# fifth more timed work
NESTED_FRACTION = 0.25

# tiles per sampled block when the grid is not at hand (choose_reorder,
# tests): candidate_grid's largest default group
_SAMPLE_GROUP = 4

# deterministic no-measurement fallback: the scatter-free bucket kernel
# is in-domain at every shard size (unlike block, which needs a dense
# tile structure worth the table bytes). Used when tuning is disabled
# and no persisted table exists, when every candidate errors, and as
# the head of the preference order a near-tie falls to (pick_winner).
# This is a fixed preference order, NOT a shape threshold.
DEFAULT_IMPL = "bucket"

# in-step aggregations per epoch where the caller does not say: the
# 4-layer use_pp bench stack's 3 graph layers, each one forward + one
# backward
_SPMM_PER_EPOCH = 3

# in-process memo of live tuning runs keyed by (checksum, signature):
# tests and repeated trainer constructions over the same artifact must
# not re-pay ~a dozen candidate compiles each time
_MEMO: Dict[Tuple, Dict[str, Any]] = {}


def clear_memo() -> None:
    """Drop the in-process live-tune memo (test isolation hook)."""
    _MEMO.clear()


# ---------------------------------------------------------------------
# sampling


def sample_slice(sg, edge_budget: int = DEFAULT_EDGE_BUDGET,
                 seed: int = 0, block_rows: int = 256 * _SAMPLE_GROUP,
                 blocks: Optional[Sequence[int]] = None):
    """A 1-part ShardedGraph-shaped view of the heaviest shard's edges
    that keeps the shard's tile structure.

    Destinations are whole blocks of `block_rows` consecutive rows
    (`block_tile` x the grid's largest group), one drawn from each of k
    equal strata of the occupied row range (seeded), k sized so the
    blocks cover about `edge_budget` edges and never under one block;
    every sampled row keeps its FULL in-edge list. The blocks are
    packed in order into rows 0..n_max of the sample — whole blocks, so
    a destination tile stays a tile — and source ids are left as they
    are: a halo source stays behind the shard's n_max, and every
    (destination tile, source tile) pair of a sampled tile-row holds
    the edges it holds on the shard. A shard under the budget is taken
    whole, ids in place. `blocks` names the blocks to keep instead of
    drawing them (nested_blocks: the second, smaller sample of a
    campaign, out of the first one's blocks).

    The result quacks like a ShardedGraph for the sharded table
    builders: num_parts=1, n_max = the sampled rows, halo_size = what
    is left of the shard's source id space behind them.

    Returns (sample, info); info carries sample_edges / shard_edges /
    full_edges and the sampled blocks' first rows.
    """
    r = int(np.argmax(np.asarray(sg.edge_count)))
    ec = int(sg.edge_count[r])
    es = np.asarray(sg.edge_src[r][:ec])
    ed = np.asarray(sg.edge_dst[r][:ec])
    real = ed < sg.n_max
    if not real.all():
        es, ed = es[real], ed[real]
    shard_edges = int(ed.size)
    n_src = int(sg.n_max + sg.halo_size)

    block_rows = int(block_rows)
    n_blocks = -(-int(sg.n_max) // block_rows)
    blk = ed // block_rows
    occupied = np.flatnonzero(np.bincount(blk, minlength=n_blocks))
    if blocks is not None:
        chosen = np.asarray(blocks, dtype=np.int64)
    elif shard_edges > edge_budget and occupied.size > 1:
        k = -(-int(edge_budget) * occupied.size // shard_edges)
        k = min(max(k, 1), int(occupied.size))
        rng = np.random.default_rng(seed)
        chosen = np.array([rng.choice(stratum) for stratum
                           in np.array_split(occupied, k)])
    else:
        chosen = np.arange(n_blocks)

    # pack the chosen blocks in order; only the shard's last block can
    # be short, and it then comes last, so every block start stays a
    # multiple of block_rows
    starts = chosen.astype(np.int64) * block_rows
    lens = np.minimum(starts + block_rows, int(sg.n_max)) - starts
    n_dst = int(lens.sum())
    shift = np.zeros(n_blocks, dtype=np.int64)
    shift[chosen] = np.cumsum(lens) - lens - starts
    if chosen.size < n_blocks:
        sel = np.zeros(n_blocks, dtype=bool)
        sel[chosen] = True
        keep = sel[blk]
        es, ed, blk = es[keep], ed[keep], blk[keep]
    new_dst = (ed + shift[blk]).astype(np.int32)
    new_src = es.astype(np.int32)
    # CSR order (dst ascending) so the sorted-XLA candidate times the
    # same formulation the trainer dispatches
    if new_dst.size and np.any(new_dst[1:] < new_dst[:-1]):
        order = np.argsort(new_dst, kind="stable")
        new_src, new_dst = new_src[order], new_dst[order]

    in_deg = np.maximum(
        np.bincount(new_dst, minlength=n_dst), 1).astype(np.float32)

    sample = SimpleNamespace(
        num_parts=1, n_max=n_dst, b_max=0, halo_size=n_src - n_dst,
        e_max=int(new_src.size),
        edge_count=np.array([new_src.size], dtype=np.int64),
        edge_src=new_src[None, :], edge_dst=new_dst[None, :],
        in_deg=in_deg[None, :], n_feat=getattr(sg, "n_feat", 0),
        cache_dir=None,
    )
    info = {
        "sample_edges": int(new_src.size),
        "sample_rows": n_dst,
        "shard_edges": shard_edges,
        # a device runs its own shard: the heaviest one's edges, not
        # the graph's, are what an estimate is made for
        "full_edges": int(np.sum(np.asarray(sg.edge_count))),
        "sampled_rank": r,
        "block_rows": block_rows,
        "block_starts": [int(x) for x in starts],
    }
    return sample, info


def nested_blocks(info: Dict[str, Any],
                  fraction: float = NESTED_FRACTION) -> List[int]:
    """The blocks of the second, smaller sample of a campaign: every
    n-th block of the sample `info` describes, `fraction` of them and
    never under one, so both samples spread over the same row range and
    share the source space. All of them where no smaller sample exists
    (one block, or the shard taken whole)."""
    blocks = [s // info["block_rows"] for s in info["block_starts"]]
    if info["sample_edges"] >= info["shard_edges"]:
        return blocks
    k = max(1, int(round(len(blocks) * fraction)))
    picks = np.linspace(0, len(blocks) - 1, k).round().astype(int)
    return [blocks[i] for i in picks]


# ---------------------------------------------------------------------
# candidate grid


def candidate_grid(*, block_group: int = 0,
                   rem_dtype: str = "auto",
                   rem_amax: bool = False) -> List[Dict[str, Any]]:
    """Viable kernel configs to time. An explicitly-pinned transport
    dtype (`rem_dtype` other than "auto") or group size (`block_group`
    > 1) restricts the grid to the pinned value — the tuner never
    overrides an explicit user choice, it only fills defaults."""
    if rem_dtype == "auto":
        rems = [(None, False), ("bfloat16", False), ("float8", False),
                ("float8", True)]
    else:
        rems = [(rem_dtype, rem_amax)]
    groups = [block_group] if block_group and block_group > 1 else [1, 4]

    def cand(impl, rd=None, ra=False, g=1):
        parts = [impl]
        if impl == "block" and g > 1:
            parts.append(f"u{g}")
        if rd == "bfloat16":
            parts.append("bf16")
        elif rd == "float8":
            parts.append("f8amax" if ra else "f8")
        return {"name": "-".join(parts), "impl": impl, "rem_dtype": rd,
                "rem_amax": ra, "block_group": g}

    return ([cand("xla")]
            + [cand("bucket", rd, ra) for rd, ra in rems]
            + [cand("block", rd, ra, g) for rd, ra in rems
               for g in groups])


# ---------------------------------------------------------------------
# timing


def _operand(sample, width: int):
    """The bf16 feature operand every candidate of a campaign gathers
    from, made once and on the device: the sample keeps the shard's
    source id space, so a host-made one would cost seconds per
    candidate."""
    import jax
    import jax.numpy as jnp

    return jax.random.normal(
        jax.random.PRNGKey(0),
        (sample.n_max + sample.halo_size, width), jnp.bfloat16)


def _timed_reps(program, args, reps: int) -> List[float]:
    """Wall seconds of `reps` calls of a jitted `program` that returns
    one scalar, after a call that compiles and settles. Each call ends
    in the device->host read of that scalar, so the clock stops after
    the device does."""
    float(program(*args))
    ts = []
    for _ in range(max(1, reps)):
        t0 = time.perf_counter()
        float(program(*args))
        ts.append(time.perf_counter() - t0)
    return ts


def _time_candidate(sample, cand: Dict[str, Any], width: int, *,
                    reps: int, **kw) -> List[float]:
    """Measured seconds of `reps` forward+backward SpMMs of this
    candidate on the sample (_candidate_program's arguments). Raises
    on kernel failure — the caller records the error in the cost
    table."""
    return _timed_reps(*_candidate_program(sample, cand, width, **kw),
                       reps)


def _candidate_program(sample, cand: Dict[str, Any], width: int, *,
                       fbuf, block_tile: int, block_nnz: Optional[int],
                       chunk_edges: Optional[int], bucket_merge: int,
                       table_width: Optional[int] = None,
                       byte_budget: Optional[int] = None,
                       tables: Optional[Dict[Tuple, Any]] = None):
    """(jitted program, its arguments) of one forward+backward SpMM of
    this candidate on the sample, over the operand `fbuf` (_operand),
    `width` wide.

    The program returns one scalar: the sum of the forward's output
    AND of the gradient under a cotangent of its own. Both halves
    matter: the aggregation is linear, so its backward needs nothing
    of its forward, and a program that returns the gradient alone (the
    `grad` of a `.sum()`, which is what was timed until tuner format 2)
    has its forward removed as dead code: it times the transposed
    tables only.

    `table_width` is the width the trainer builds the block tables for
    (their dense threshold), where that is not the timed one. `tables`
    memoizes the host tables over a campaign (the transport variants of
    one kernel share them); `byte_budget` is the block builder's
    dense-A budget, which the caller scales to the sampled share of the
    shard's rows."""
    import jax
    import jax.numpy as jnp

    n_max = sample.n_max
    n_src = n_max + sample.halo_size
    in_deg = jnp.asarray(sample.in_deg[0])
    tables = {} if tables is None else tables

    impl = cand["impl"]
    if impl == "xla":
        from .spmm import spmm_mean

        es = jnp.asarray(sample.edge_src[0])
        ed = jnp.asarray(sample.edge_dst[0])

        def apply(tabs, deg, f):
            return spmm_mean(f, tabs["es"], tabs["ed"], deg, n_max,
                             chunk=chunk_edges, sorted_edges=True)

        tabs = {"es": es, "ed": ed}
    elif impl == "bucket":
        from .bucket_spmm import (build_sharded_bucket_tables,
                                  make_device_bucket_spmm_fn)

        key = ("bucket",)
        if key not in tables:
            tables[key] = build_sharded_bucket_tables(
                sample, min_width=bucket_merge)
        tabs = {k: jnp.asarray(v[0]) for k, v in tables[key].items()}

        def apply(tabs, deg, f):
            fn = make_device_bucket_spmm_fn(
                tabs, deg, n_src, chunk_edges=chunk_edges,
                rem_dtype=cand["rem_dtype"], rem_amax=cand["rem_amax"])
            return fn(f)
    elif impl == "block":
        from .block_spmm import (DENSE_A_BYTE_BUDGET,
                                 build_sharded_block_tables,
                                 make_device_block_spmm_fn)

        key = ("block", cand["block_group"])
        if key not in tables:
            tables[key] = build_sharded_block_tables(
                sample, tile=block_tile, n_feat_hint=table_width or width,
                byte_budget=byte_budget or DENSE_A_BYTE_BUDGET,
                nnz_threshold=block_nnz, group=cand["block_group"])
        host, tile = tables[key]
        tabs = {k: jnp.asarray(v[0]) for k, v in host.items()}

        def apply(tabs, deg, f):
            fn = make_device_block_spmm_fn(
                tabs, deg, n_max, n_src, tile, chunk_edges=chunk_edges,
                rem_dtype=cand["rem_dtype"], rem_amax=cand["rem_amax"])
            return fn(f)
    else:
        raise ValueError(f"unknown tuner candidate impl {impl!r}")

    def fwd_bwd(t, deg, f, g):
        out, vjp = jax.vjp(lambda ff: apply(t, deg, ff), f)
        return (out.astype(jnp.float32).sum()
                + vjp(g.astype(out.dtype))[0].astype(jnp.float32).sum())

    cot = jax.random.normal(jax.random.PRNGKey(1), (n_max, width),
                            jnp.float32)
    return jax.jit(fwd_bwd), (tabs, in_deg, fbuf, cot)


def shard_size_refusal(sg, r: int, width: int,
                       chunk_edges: Optional[int]) -> Optional[str]:
    """Why the raw-edge-list kernel (`xla`) cannot run shard `r` at its
    own size, in the compiler's words, or None where it can.

    The table-driven kernels work in bounded chunks whatever the shard
    holds; this one materializes a message per edge ([edges, width]
    f32 unless `chunk_edges` bounds it), so the sample says nothing of
    whether the shard fits: a sample's 1M edges are 2 GB where Yelp's
    7.9M are 16. Nothing is modelled: the forward+backward is compiled
    (never run) at the shard's shapes, and a compiler that cannot place
    it says so."""
    import jax
    import jax.numpy as jnp

    from .spmm import spmm_mean

    n_out, e = int(sg.n_max), int(sg.edge_count[r])

    def fwd_bwd(es, ed, deg, f, g):
        out, vjp = jax.vjp(
            lambda ff: spmm_mean(ff, es, ed, deg, n_out,
                                 chunk=chunk_edges, sorted_edges=True), f)
        return out.sum() + vjp(g)[0].astype(jnp.float32).sum()

    shape = jax.ShapeDtypeStruct
    try:
        jax.jit(fwd_bwd).lower(
            shape((e,), jnp.int32), shape((e,), jnp.int32),
            shape((n_out,), jnp.float32),
            shape((n_out + int(sg.halo_size), width), jnp.bfloat16),
            shape((n_out, width), jnp.float32)).compile()
    except Exception as exc:  # noqa: BLE001 — the refusal is the result
        return repr(exc)[:200]
    return None


def shard_estimate(best: Sequence[float], spread: Sequence[float],
                   edges: Sequence[int],
                   shard_edges: int) -> Dict[str, Optional[float]]:
    """One candidate's call at the shard's size, from its timed samples
    (largest first): `best` rep and `spread` (a typical rep over it) on
    each, of `edges` edges. Returns fixed_s, per_edge_s, est_call_s and
    est_spread_s.

    Two samples: the line through the two best reps, read at the
    shard's edges. What follows the source rows of a call (both samples
    keep the whole source space) lands in `fixed_s` and is not
    multiplied; what follows the edges in `per_edge_s`. A line that
    would cross zero before the smaller sample (the clock's noise on a
    short call) is taken through the origin instead: the proportional
    estimate, which no fixed cost can exceed. The estimate is
    t1 * (1 + k) - t2 * k in the two timed calls, so that is what their
    spreads move it by.

    One sample, no line: the shard whole (its time is the estimate) or
    a single block, read in proportion to the edges."""
    t1, e1 = best[0], edges[0]
    if len(best) == 1:
        k = shard_edges / e1
        return {"fixed_s": None, "per_edge_s": None,
                "est_call_s": t1 * k, "est_spread_s": spread[0] * k}
    t2, e2 = best[1], edges[1]
    per_edge = max(t1 - t2, 0.0) / (e1 - e2)
    fixed = t1 - per_edge * e1
    if fixed < 0.0:
        fixed, per_edge = 0.0, t1 / e1
    k = (shard_edges - e1) / (e1 - e2)
    return {"fixed_s": fixed, "per_edge_s": per_edge,
            "est_call_s": fixed + per_edge * shard_edges,
            "est_spread_s": spread[0] * (1 + k) + spread[1] * k}


def pick_winner(costs: List[Dict[str, Any]]) -> Dict[str, Any]:
    """The argmin of `est_call_s`, the estimate of a call at the
    shard's size, unless it is a near-tie: a candidate whose estimate
    is no higher than the argmin's plus the argmin's spread (how far
    a typical rep of its timed calls reads over the best one, carried
    through the estimate's arithmetic: `est_spread_s`) cannot be told
    from it by this clock,
    and the choice among those falls to the fixed preference order —
    DEFAULT_IMPL's family first, then the order of the grid (plain
    transport before the narrower ones). A candidate's own noise never
    makes it a tie: only the argmin's spread widens the set. A
    candidate that cannot run the shard at its own size
    (`out_of_domain`) keeps its time in the table and is never picked.
    No candidate left: the default kernel."""
    ok = [c for c in costs if c.get("error") is None
          and not c.get("out_of_domain")]
    if not ok:
        return {"name": DEFAULT_IMPL, "impl": DEFAULT_IMPL,
                "rem_dtype": None, "rem_amax": False, "block_group": 1}
    best = min(ok, key=lambda c: c["est_call_s"])
    highest = best["est_call_s"] + best.get("est_spread_s", 0.0)
    tied = [c for c in ok if c["est_call_s"] <= highest]
    return min(tied, key=lambda c: c["impl"] != DEFAULT_IMPL)


# ---------------------------------------------------------------------
# the tuner


def signature_for(*, width: int, block_tile: int, bucket_merge: int,
                  chunk_edges: Optional[int],
                  rng_impl: str = "threefry",
                  halo_dtype: str = "none",
                  epoch_block: int = 0,
                  reorder: str = "none",
                  layout_version: int = 1,
                  step_width: Optional[int] = None) -> Dict[str, Any]:
    """Config signature a persisted table must match to be trusted.
    Backend is part of it: CPU timings say nothing about the TPU. The
    floor-lever knobs (rng_impl / halo_dtype / epoch_block) are part of
    it too: they reshape the step program around the SpMM, so a cost
    table measured under one lever setting must not silently pick
    kernels for another. So are the artifact's node layout
    (reorder/layout_version): a cost table measured on the pre-reorder
    gather streams must not pick kernels for the reordered ones.
    Tables persisted before these keys existed mismatch (exact-dict
    compare) and re-tune once — deliberate; the keyword defaults match
    TrainConfig's / pre-reorder artifacts' for older call sites.
    `width` is the widest operand of any aggregation (what the block
    tables' dense threshold is built for), `step_width` the widest one
    an in-step aggregation sees (what is timed; `width` unless the
    first layer's aggregation is precomputed)."""
    import jax

    return {
        "backend": jax.default_backend(),
        "width": int(width),
        "step_width": int(step_width or width),
        "block_tile": int(block_tile),
        "bucket_merge": int(bucket_merge),
        "chunk_edges": int(chunk_edges) if chunk_edges else 0,
        "rng_impl": str(rng_impl or "threefry"),
        "halo_dtype": str(halo_dtype or "none"),
        "epoch_block": int(epoch_block or 0),
        "reorder": str(reorder or "none"),
        "layout_version": int(layout_version or 1),
    }


def tune(sg, width: int, *, block_tile: int = 256,
         block_nnz: Optional[int] = None, block_group: int = 0,
         rem_dtype: str = "auto", rem_amax: bool = False,
         chunk_edges: Optional[int] = None, bucket_merge: int = 0,
         rng_impl: str = "threefry", halo_dtype: str = "none",
         epoch_block: int = 0,
         edge_budget: int = DEFAULT_EDGE_BUDGET, reps: int = 5,
         seed: int = 0, step_width: Optional[int] = None,
         spmm_per_epoch: int = _SPMM_PER_EPOCH,
         log: Optional[Callable[[str], None]] = None) -> Dict[str, Any]:
    """Run the micro-benchmark campaign and return the tuning record
    (winner + full measured cost table). Results are memoized
    in-process by (source checksum, signature, budget) so repeated
    trainer constructions over the same artifact pay once.

    `width` is the widest operand of any aggregation: the block tables
    are built for it. The candidates are timed over `step_width`
    columns, the widest operand an aggregation INSIDE the step sees
    (under use_pp the first layer's, the widest on a wide-feature
    graph, is precomputed once), on two nested samples where the shard
    is over `edge_budget` (module docstring): each is ranked by
    `est_call_s`, its call at the shard's size, and the per-epoch
    estimates count `spmm_per_epoch` such calls."""
    step_width = int(step_width or width)
    sig = signature_for(width=width, step_width=step_width,
                        block_tile=block_tile,
                        bucket_merge=bucket_merge,
                        chunk_edges=chunk_edges,
                        rng_impl=rng_impl, halo_dtype=halo_dtype,
                        epoch_block=epoch_block,
                        reorder=getattr(sg, "reorder", "none"),
                        layout_version=getattr(sg, "layout_version", 1))
    checksum = int(getattr(sg, "source_edge_checksum", -1)) \
        & ((1 << 64) - 1)
    memo_key = (checksum, json.dumps(sig, sort_keys=True),
                int(edge_budget), int(block_group),
                str(rem_dtype), bool(rem_amax), int(spmm_per_epoch))
    hit = _MEMO.get(memo_key)
    if hit is not None:
        return hit

    from .block_spmm import (DENSE_A_BYTE_BUDGET, _part_block_stats,
                             budget_block_cap, occupied_blocks)

    cands = candidate_grid(block_group=block_group, rem_dtype=rem_dtype,
                           rem_amax=rem_amax)
    group = max(c["block_group"] for c in cands)
    sample, info = sample_slice(sg, edge_budget=edge_budget, seed=seed,
                                block_rows=block_tile * group)
    shard_edges = info["shard_edges"]
    # the timed samples, largest first: the budget's blocks and, where
    # the shard is larger than they are, a nested share of them
    # (shard_estimate needs two sizes; a shard timed whole needs none,
    # and a sample of one block has no smaller one)
    samples = [(sample, info["sample_edges"])]
    fewer = nested_blocks(info)
    if len(fewer) < len(info["block_starts"]):
        small, small_info = sample_slice(
            sg, block_rows=block_tile * group, blocks=fewer)
        samples.append((small, small_info["sample_edges"]))
    timed_edges = [e for _, e in samples]

    # the block builder's dense-A budget is the shard's: a sample
    # gets the share its rows are of the shard's, so a budget that
    # spills tiles on the shard spills them on the sample too
    def byte_budget(smp) -> int:
        return max(1, int(DENSE_A_BYTE_BUDGET * smp.n_max
                          / max(1, sg.n_max)))

    # did the sample carry the tiles? Dense coverage of the sampled
    # rows against the whole shard's, at the tile, threshold and
    # budget the block candidates are built with
    thr = block_nnz if block_nnz is not None else max(
        1, block_tile * block_tile // max(width, 1))
    n_src_tiles = -(-(sg.n_max + sg.halo_size) // block_tile)
    bits = 1 if block_tile % 8 == 0 else 8

    def coverage(g, r, budget):
        # under the cap the builder would keep this device's blocks to
        occ = occupied_blocks(g, r, block_tile, n_src_tiles)
        return _part_block_stats(
            g, r, block_tile, n_src_tiles, thr, occupied=occ,
            max_blocks=budget_block_cap(budget, block_tile, bits, [occ],
                                        thr, n_src_tiles))[0]

    shard_cov = coverage(sg, info["sampled_rank"], DENSE_A_BYTE_BUDGET)
    sample_cov = coverage(sample, 0, byte_budget(sample))

    # every sample keeps the shard's source id space: one operand
    fbuf = _operand(sample, step_width)
    if log:
        log(f"# tuner: samples of {timed_edges} edges "
            f"({len(info['block_starts'])} blocks of "
            f"{info['block_rows']} rows the largest) for a shard of "
            f"{shard_edges}; dense coverage {sample_cov:.3f} "
            f"(shard {shard_cov:.3f})")

    tables: List[Dict[Tuple, Any]] = [{} for _ in samples]
    costs: List[Dict[str, Any]] = []
    for cand in cands:
        entry = dict(cand)
        # the candidate's span: what JAX lowered, compiled or loaded for
        # it are its counters, the seconds of its timed calls `timed_s`
        cspan = recorder().open("candidate", candidate=cand["name"],
                                timed_s=0.0)
        try:
            reps_best, reps_spread = [], []
            for (smp, _), tabs in zip(samples, tables):
                ts = _time_candidate(
                    smp, cand, step_width, block_tile=block_tile,
                    block_nnz=block_nnz, chunk_edges=chunk_edges,
                    bucket_merge=bucket_merge, reps=reps, fbuf=fbuf,
                    table_width=width, byte_budget=byte_budget(smp),
                    tables=tabs)
                cspan.attrs["timed_s"] += sum(ts)
                reps_best.append(min(ts))
                # a typical rep over the best one. Not the slowest: one
                # rep in a hundred is a host stall of many times the
                # call (117 ms for 24.5, PERF.md section 6, PR 34), and
                # the estimate multiplies a spread as it does a time
                reps_spread.append(float(np.median(ts)) - min(ts))
            entry["spmm_fwdbwd_s"] = reps_best[0]
            entry["spread_s"] = reps_spread[0]
            entry.update(shard_estimate(reps_best, reps_spread,
                                        timed_edges, shard_edges))
            est, per_edge = entry["est_call_s"], entry["per_edge_s"]
            entry["est_epoch_spmm_s"] = round(est * spmm_per_epoch, 6)
            entry["error"] = None
            if cand["impl"] == "xla" and timed_edges[0] < shard_edges:
                entry["out_of_domain"] = shard_size_refusal(
                    sg, info["sampled_rank"], step_width, chunk_edges)
            if log:
                log(f"# tuner: {cand['name']:16s} "
                    f"{reps_best[0] * 1e3:8.2f} ms sampled"
                    + (f", {per_edge * 1e9:7.3f} ns an edge + "
                       f"{entry['fixed_s'] * 1e3:7.2f} ms"
                       if per_edge is not None else "")
                    + f": a call at the shard's size {est:.4f} s "
                      f"(+- {entry['est_spread_s']:.4f})"
                    + (f" OUT OF DOMAIN at the shard's size: "
                       f"{entry['out_of_domain']}"
                       if entry.get("out_of_domain") else ""))
        except Exception as exc:  # noqa: BLE001 — a crashing candidate
            # is a RESULT (out-of-domain config), not a tuner failure
            entry.update(dict.fromkeys(
                ("spmm_fwdbwd_s", "spread_s", "fixed_s", "per_edge_s",
                 "est_call_s", "est_spread_s", "est_epoch_spmm_s")))
            entry["error"] = repr(exc)[:200]
            if log:
                log(f"# tuner: {cand['name']:16s} FAILED: "
                    f"{entry['error']}")
        finally:
            cspan.attrs["timed_s"] = round(cspan.attrs["timed_s"], 6)
            recorder().close(cspan)
        costs.append(entry)

    winner = pick_winner(costs)
    record = {
        "tuner_format": TUNER_FORMAT,
        "source_edge_checksum": checksum,
        "signature": sig,
        "winner": {k: winner.get(k, False) for k in
                   ("name", "impl", "rem_dtype", "rem_amax",
                    "block_group")},
        "costs": costs,
        "reps": int(reps),
        "spmm_per_epoch": int(spmm_per_epoch),
        "sample_dense_coverage": round(sample_cov, 6),
        "shard_dense_coverage": round(shard_cov, 6),
        "sample_tile_rows": -(-info["sample_rows"] // block_tile),
        "timed_edges": timed_edges,
        "est_epoch_spmm_s": winner.get("est_epoch_spmm_s"),
        "time_unix": time.time(),
        **info,
    }
    _MEMO[memo_key] = record
    return record


# ---------------------------------------------------------------------
# persistence (tuning.json sidecar in the artifact directory)


def tuning_path(cache_dir: str) -> str:
    return os.path.join(cache_dir, TUNING_FILE)


def save_tuning(cache_dir: str, record: Dict[str, Any]) -> None:
    """Atomically persist the tuning record next to the artifact's
    npz/mmap payload (both formats are directories, so the sidecar
    rides along for free and versions with the artifact). Routed
    through the storage-fault seams (resilience/storage.py): a torn or
    failed write leaves the previous sidecar — or nothing — and
    load_tuning's never-raise contract degrades to a live re-tune."""
    from ..resilience.storage import write_text_atomic

    write_text_atomic(tuning_path(cache_dir),
                      json.dumps(record, indent=1), fsync=False)


def load_tuning(cache_dir: str, *,
                expect_checksum: Optional[int] = None,
                signature: Optional[Dict[str, Any]] = None,
                ) -> Tuple[Optional[Dict[str, Any]], Optional[str]]:
    """(record, None) when the persisted table is present AND trusted;
    (None, reason) otherwise. Never raises: a corrupt sidecar must
    degrade to a live re-tune, not kill trainer setup."""
    path = tuning_path(cache_dir)
    if not os.path.exists(path):
        return None, "missing"
    try:
        with open(path) as f:
            rec = json.load(f)
    except (OSError, ValueError) as exc:
        return None, f"corrupt: {exc!r}"[:200]
    if not isinstance(rec, dict):
        return None, "corrupt: not a JSON object"
    if rec.get("tuner_format") != TUNER_FORMAT:
        return None, (f"format {rec.get('tuner_format')!r} != "
                      f"{TUNER_FORMAT}")
    w = rec.get("winner")
    if not isinstance(w, dict) or w.get("impl") not in (
            "xla", "bucket", "block"):
        return None, f"corrupt winner: {w!r}"[:200]
    if expect_checksum is not None:
        want = int(expect_checksum) & ((1 << 64) - 1)
        if rec.get("source_edge_checksum") != want:
            return None, ("stale: source_edge_checksum mismatch "
                          "(artifact rebuilt from a different graph)")
    if signature is not None and rec.get("signature") != signature:
        return None, (f"stale: signature {rec.get('signature')!r} != "
                      f"{signature!r}")[:300]
    return rec, None


# ---------------------------------------------------------------------
# --reorder auto resolution (measured, not a hand threshold)


def choose_reorder(g, *, modes: Tuple[str, ...] = ("none", "degree-bfs"),
                   edge_budget: int = DEFAULT_EDGE_BUDGET, reps: int = 2,
                   log: Optional[Callable[[str], None]] = None
                   ) -> Tuple[str, Dict[str, float]]:
    """Pick the artifact reorder mode for ``--reorder auto`` by
    MEASUREMENT: build a 1-part layout of ``g`` under each candidate
    mode, sample whole blocks of its destination rows (sample_slice),
    and time the bucket kernel's forward+backward on them. Returns
    (winning mode, {mode: seconds}); an unmeasurable campaign (every
    candidate erroring) falls back to "none" — the layout every
    artifact already has."""
    from ..partition import ShardedGraph

    width = int(g.ndata["feat"].shape[-1]) if "feat" in g.ndata else 64
    parts = np.zeros(g.num_nodes, dtype=np.int32)
    timings: Dict[str, float] = {}
    cand = {"name": "bucket", "impl": "bucket", "rem_dtype": None,
            "rem_amax": False, "block_group": 1}
    for mode in modes:
        sg1 = ShardedGraph.build(g, parts, n_parts=1, reorder=mode)
        sample, _ = sample_slice(sg1, edge_budget=edge_budget)
        fbuf = _operand(sample, width)
        try:
            t = min(_time_candidate(
                sample, cand, width, block_tile=256, block_nnz=None,
                chunk_edges=None, bucket_merge=0, reps=reps, fbuf=fbuf))
        except Exception as exc:  # noqa: BLE001 — out-of-domain
            if log:
                log(f"# choose_reorder: {mode} FAILED: {exc!r}"[:160])
            continue
        timings[mode] = round(t, 6)
        if log:
            log(f"# choose_reorder: {mode:10s} {t * 1e3:8.2f} ms")
    if not timings:
        return "none", timings
    return min(timings, key=timings.get), timings
