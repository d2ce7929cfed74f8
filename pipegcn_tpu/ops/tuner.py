"""Shape-aware SpMM kernel auto-tuner: measured cost tables, not guesses.

The hand-tuned ``auto`` thresholds this replaces (edge-count and
dense-coverage cutoffs in ``parallel/trainer.py``) were invalidated by
the very first second shape they met (the products-shape block-kernel
crash). This module instead *times* each viable kernel configuration —
{sorted-XLA, bucket, block} x remainder transport dtype
{none, bf16, fp8, fp8+amax} x block group size — on a sampled slice of
the real degree distribution, and persists the winner plus the full
measured cost table into the partition artifact (``tuning.json``
sidecar, valid for both the v2 npz and v3 mmap directory formats).

Sampling keeps the *shape* the kernels are sensitive to: destination
rows are drawn uniformly but each keeps its FULL in-edge list, so the
sampled in-degree distribution matches the shard's. The per-SpMM cost
is scaled back by full_edges / sample_edges for reporting; the argmin
is taken on the measured numbers directly.

Timing follows the microbench idiom (scripts/spmm_microbench.py):
tables ride as jit ARGUMENTS, never closure constants (closed-over
arrays embed into the HLO as constants), and every sample ends in a
device->host scalar read (`float(jnp.sum(...))`), so the clock stops
after the device does.

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
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

TUNER_FORMAT = 1
TUNING_FILE = "tuning.json"

# destination-row sampling stops once this many edges are covered; the
# CLI surfaces it as --tuner-samples
DEFAULT_EDGE_BUDGET = 200_000

# deterministic no-measurement fallback: the scatter-free bucket kernel
# is in-domain at every shard size (unlike block, which needs a dense
# tile structure worth the table bytes). Used when tuning is disabled
# and no persisted table exists, and when every candidate errors. This
# is a fixed preference order, NOT a shape threshold.
DEFAULT_IMPL = "bucket"

# SpMM invocations per epoch of the 4-layer use_pp bench stack: 3 graph
# layers, each one forward + one backward aggregation
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
                 seed: int = 0):
    """A 1-part ShardedGraph-shaped view of the heaviest shard's edges.

    Destination rows are sampled uniformly, each keeping its full
    in-edge list, until `edge_budget` edges are covered — preserving
    the in-degree distribution the bucket ladder and the block tiling
    both key on. Row ids are compacted (sampled destinations first, so
    every dst id < n_max; remaining source rows follow) and the result
    quacks like a ShardedGraph for the sharded table builders:
    num_parts=1, halo_size=0, all rows inner.

    Returns (sample, info) where info carries sample_edges /
    full_edges / scale.
    """
    r = int(np.argmax(np.asarray(sg.edge_count)))
    ec = int(sg.edge_count[r])
    es = np.asarray(sg.edge_src[r][:ec], dtype=np.int64)
    ed = np.asarray(sg.edge_dst[r][:ec], dtype=np.int64)
    real = ed < sg.n_max
    es, ed = es[real], ed[real]
    full_edges = int(np.sum(np.asarray(sg.edge_count)))

    if es.size > edge_budget:
        deg = np.bincount(ed, minlength=sg.n_max)
        rows = np.flatnonzero(deg > 0)
        rng = np.random.default_rng(seed)
        rng.shuffle(rows)
        cum = np.cumsum(deg[rows])
        n_keep = max(1, int(np.searchsorted(cum, edge_budget) + 1))
        chosen = rows[:n_keep]
        sel = np.zeros(sg.n_max, dtype=bool)
        sel[chosen] = True
        keep = sel[ed]
        es, ed = es[keep], ed[keep]
    else:
        chosen = np.unique(ed)

    # compact ids: sampled destinations first, then the remaining
    # source rows (halo slots and unsampled inner rows alike)
    chosen = np.sort(chosen)
    n_dst = int(chosen.size)
    src_space = sg.n_max + sg.halo_size
    remap = np.full(src_space, -1, dtype=np.int64)
    remap[chosen] = np.arange(n_dst)
    extra = np.unique(es[remap[es] < 0])
    remap[extra] = n_dst + np.arange(extra.size)
    n_rows = n_dst + int(extra.size)

    new_src = remap[es].astype(np.int32)
    new_dst = remap[ed].astype(np.int32)
    # CSR order (dst ascending) so the sorted-XLA candidate times the
    # same formulation the trainer dispatches
    order = np.argsort(new_dst, kind="stable")
    new_src, new_dst = new_src[order], new_dst[order]

    in_deg = np.maximum(
        np.bincount(new_dst, minlength=n_rows), 1).astype(np.float32)

    sample = SimpleNamespace(
        num_parts=1, n_max=n_rows, b_max=0, halo_size=0,
        e_max=int(new_src.size),
        edge_count=np.array([new_src.size], dtype=np.int64),
        edge_src=new_src[None, :], edge_dst=new_dst[None, :],
        in_deg=in_deg[None, :], n_feat=getattr(sg, "n_feat", 0),
        cache_dir=None,
    )
    info = {
        "sample_edges": int(new_src.size),
        "sample_rows": n_rows,
        "full_edges": full_edges,
        "scale": full_edges / max(1, int(new_src.size)),
        "sampled_rank": r,
    }
    return sample, info


# ---------------------------------------------------------------------
# candidate grid


def candidate_grid(*, block_group: int = 0,
                   rem_dtype: str = "auto",
                   rem_amax: bool = False,
                   slab: str = "auto") -> List[Dict[str, Any]]:
    """Viable kernel configs to time. An explicitly-pinned transport
    dtype (`rem_dtype` other than "auto") or group size (`block_group`
    > 1) restricts the grid to the pinned value — the tuner never
    overrides an explicit user choice, it only fills defaults.

    `slab` extends the grid with the streaming-slab gather path
    (bucket_spmm build_slab_plan): "auto" adds one measured slab twin
    per kernel family (on the first transport variant — a full slab x
    transport cross product would double the compile bill for a
    row-structure lever that is independent of the cast); "on"/"off"
    pin every candidate."""
    if rem_dtype == "auto":
        rems = [(None, False), ("bfloat16", False), ("float8", False),
                ("float8", True)]
    else:
        rems = [(rem_dtype, rem_amax)]
    groups = [block_group] if block_group and block_group > 1 else [1, 4]
    pin_slab = {"on": True, "off": False}.get(slab)
    base_slab = bool(pin_slab)

    def name(impl, rd, ra, g, sl=False):
        parts = [impl]
        if impl == "block" and g > 1:
            parts.append(f"u{g}")
        if rd == "bfloat16":
            parts.append("bf16")
        elif rd == "float8":
            parts.append("f8amax" if ra else "f8")
        if sl:
            parts.append("slab")
        return "-".join(parts)

    cands = [{"name": "xla", "impl": "xla", "rem_dtype": None,
              "rem_amax": False, "block_group": 1, "slab": False}]
    for i, (rd, ra) in enumerate(rems):
        slabs = [base_slab]
        if pin_slab is None and i == 0:
            slabs = [False, True]
        for sl in slabs:
            cands.append({"name": name("bucket", rd, ra, 1, sl),
                          "impl": "bucket", "rem_dtype": rd,
                          "rem_amax": ra, "block_group": 1, "slab": sl})
    for i, (rd, ra) in enumerate(rems):
        for g in groups:
            slabs = [base_slab]
            if pin_slab is None and i == 0:
                slabs = [False, True]
            for sl in slabs:
                cands.append({"name": name("block", rd, ra, g, sl),
                              "impl": "block", "rem_dtype": rd,
                              "rem_amax": ra, "block_group": g,
                              "slab": sl})
    return cands


# ---------------------------------------------------------------------
# timing


def _time_candidate(sample, cand: Dict[str, Any], width: int, *,
                    block_tile: int, block_nnz: Optional[int],
                    chunk_edges: Optional[int], bucket_merge: int,
                    reps: int) -> float:
    """Measured seconds for ONE forward+backward SpMM of this candidate
    on the sample (min over reps). Raises on kernel failure — the
    caller records the error in the cost table."""
    import jax
    import jax.numpy as jnp

    n_max = sample.n_max
    n_src = n_max  # 1-part sample: halo_size == 0, all rows inner
    rng = np.random.default_rng(0)
    fbuf = jnp.asarray(
        rng.standard_normal((n_src, width)).astype(np.float32)
    ).astype(jnp.bfloat16)
    in_deg = jnp.asarray(sample.in_deg[0])

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

        tables = build_sharded_bucket_tables(
            sample, min_width=bucket_merge,
            slab=bool(cand.get("slab")))
        tabs = {k: jnp.asarray(v[0]) for k, v in tables.items()}

        def apply(tabs, deg, f):
            fn = make_device_bucket_spmm_fn(
                tabs, deg, n_src, chunk_edges=chunk_edges,
                rem_dtype=cand["rem_dtype"], rem_amax=cand["rem_amax"])
            return fn(f)
    elif impl == "block":
        from .block_spmm import (build_sharded_block_tables,
                                 make_device_block_spmm_fn)

        tables, tile = build_sharded_block_tables(
            sample, tile=block_tile, n_feat_hint=width,
            nnz_threshold=block_nnz, group=cand["block_group"],
            slab=bool(cand.get("slab")))
        tabs = {k: jnp.asarray(v[0]) for k, v in tables.items()}

        def apply(tabs, deg, f):
            fn = make_device_block_spmm_fn(
                tabs, deg, n_max, n_src, tile, chunk_edges=chunk_edges,
                rem_dtype=cand["rem_dtype"], rem_amax=cand["rem_amax"])
            return fn(f)
    else:
        raise ValueError(f"unknown tuner candidate impl {impl!r}")

    grad_fn = jax.jit(lambda t, deg, f: jax.grad(
        lambda ff: apply(t, deg, ff).astype(jnp.float32).sum())(f))
    float(jnp.sum(grad_fn(tabs, in_deg, fbuf)))  # compile + settle
    ts = []
    for _ in range(max(1, reps)):
        t0 = time.perf_counter()
        float(jnp.sum(grad_fn(tabs, in_deg, fbuf)))
        ts.append(time.perf_counter() - t0)
    return min(ts)


# ---------------------------------------------------------------------
# the tuner


def signature_for(*, width: int, block_tile: int, bucket_merge: int,
                  chunk_edges: Optional[int],
                  rng_impl: str = "threefry",
                  halo_dtype: str = "none",
                  epoch_block: int = 0,
                  reorder: str = "none",
                  layout_version: int = 1) -> Dict[str, Any]:
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
    TrainConfig's / pre-reorder artifacts' for older call sites."""
    import jax

    return {
        "backend": jax.default_backend(),
        "width": int(width),
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
         epoch_block: int = 0, slab: str = "auto",
         edge_budget: int = DEFAULT_EDGE_BUDGET, reps: int = 2,
         seed: int = 0,
         log: Optional[Callable[[str], None]] = None) -> Dict[str, Any]:
    """Run the micro-benchmark campaign and return the tuning record
    (winner + full measured cost table). Results are memoized
    in-process by (source checksum, signature, budget) so repeated
    trainer constructions over the same artifact pay once."""
    sig = signature_for(width=width, block_tile=block_tile,
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
                str(rem_dtype), bool(rem_amax), str(slab))
    hit = _MEMO.get(memo_key)
    if hit is not None:
        return hit

    sample, info = sample_slice(sg, edge_budget=edge_budget, seed=seed)
    cands = candidate_grid(block_group=block_group, rem_dtype=rem_dtype,
                           rem_amax=rem_amax, slab=slab)
    costs: List[Dict[str, Any]] = []
    for cand in cands:
        entry = dict(cand)
        try:
            s = _time_candidate(
                sample, cand, width, block_tile=block_tile,
                block_nnz=block_nnz, chunk_edges=chunk_edges,
                bucket_merge=bucket_merge, reps=reps)
            entry["spmm_fwdbwd_s"] = s
            entry["est_epoch_spmm_s"] = round(
                s * info["scale"] * _SPMM_PER_EPOCH, 6)
            entry["error"] = None
            if log:
                log(f"# tuner: {cand['name']:16s} {s * 1e3:8.2f} ms "
                    f"(est epoch SpMM "
                    f"{entry['est_epoch_spmm_s']:.3f} s)")
        except Exception as exc:  # noqa: BLE001 — a crashing candidate
            # is a RESULT (out-of-domain config), not a tuner failure
            entry["spmm_fwdbwd_s"] = None
            entry["est_epoch_spmm_s"] = None
            entry["error"] = repr(exc)[:200]
            if log:
                log(f"# tuner: {cand['name']:16s} FAILED: "
                    f"{entry['error']}")
        costs.append(entry)

    ok = [c for c in costs if c["error"] is None]
    if ok:
        best = min(ok, key=lambda c: c["spmm_fwdbwd_s"])
    else:
        best = {"name": DEFAULT_IMPL, "impl": DEFAULT_IMPL,
                "rem_dtype": None, "rem_amax": False, "block_group": 1,
                "slab": False}
    # the sample's gather-contiguity stat rides in the record: the
    # number the reorder lever is supposed to move, next to the
    # measured winner it produced (host numpy on the sample tables —
    # noise next to the candidate compiles)
    try:
        from .bucket_spmm import (build_sharded_bucket_tables,
                                  gather_contiguity)
        contig = gather_contiguity(
            build_sharded_bucket_tables(sample), sample.n_max)
    except Exception:  # noqa: BLE001 — a stat, never a tuner failure
        contig = None
    record = {
        "tuner_format": TUNER_FORMAT,
        "source_edge_checksum": checksum,
        "signature": sig,
        "winner": {k: best.get(k, False) for k in
                   ("name", "impl", "rem_dtype", "rem_amax",
                    "block_group", "slab")},
        "costs": costs,
        "reps": int(reps),
        "gather_contiguity": contig,
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
    mode, sample a degree-distribution-preserving slice, and time the
    bucket kernel's forward+backward on it — under the reordered
    layouts both with and without the streaming-slab plan (the path
    the reorder exists to enable), keeping each mode's best. Returns
    (winning mode, {mode: seconds}); an unmeasurable campaign (every
    candidate erroring) falls back to "none" — the layout every
    artifact already has."""
    from ..partition import ShardedGraph

    width = int(g.ndata["feat"].shape[-1]) if "feat" in g.ndata else 64
    parts = np.zeros(g.num_nodes, dtype=np.int32)
    timings: Dict[str, float] = {}
    for mode in modes:
        sg1 = ShardedGraph.build(g, parts, n_parts=1, reorder=mode)
        sample, _ = sample_slice(sg1, edge_budget=edge_budget)
        best = None
        for sl in ([False] if mode == "none" else [False, True]):
            cand = {"name": "bucket-slab" if sl else "bucket",
                    "impl": "bucket", "rem_dtype": None,
                    "rem_amax": False, "block_group": 1, "slab": sl}
            try:
                t = _time_candidate(sample, cand, width, block_tile=256,
                                    block_nnz=None, chunk_edges=None,
                                    bucket_merge=0, reps=reps)
            except Exception as exc:  # noqa: BLE001 — out-of-domain
                if log:
                    log(f"# choose_reorder: {mode} "
                        f"({cand['name']}) FAILED: {exc!r}"[:160])
                continue
            best = t if best is None else min(best, t)
        if best is not None:
            timings[mode] = round(best, 6)
            if log:
                log(f"# choose_reorder: {mode:10s} {best * 1e3:8.2f} ms")
    if not timings:
        return "none", timings
    return min(timings, key=timings.get), timings
