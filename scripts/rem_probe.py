#!/usr/bin/env python
"""Attribute the remainder (slab-gather) SpMM term on the real chip.

Round 4 measured the remainder at ~230M padded slab rows/s inside the
full program — ~60% of the isolated row-gather cliff rate (~400M rows/s
at 256-byte rows, docs/PERF_NOTES.md). This probe decomposes the gap by
running the production remainder (bucket ladder over the Reddit-scale
block plan's spill edges) in surgical variants, same shapes throughout:

  anchor   flat jnp.take of the same number of padded rows at the same
           row width — the cliff-rate anchor, measured in-session
  rem      production path: transport_cast(bf16->fp8) + bucket ladder
  nocast   ladder only, fbuf pre-cast outside the jit (cast share)
  idx0     index mats zeroed — every gather hits row 0, collapsing the
           gather's HBM traffic but keeping launches/pads/sums/concat
           (structure share)
  noinv    inv_perm zeroed (the final restore-order gather's share)
  chunk-*  chunk_edges sweep (scan-chunking overhead share)
  bf16     the 2-slab bf16 transport for reference

Verdict logic: if `rem` per-row rate ~= `anchor` rate, the 60% figure
was contention with the dense path inside the full program (fix =
program-level reordering); if `rem` is itself slow and `idx0` is fast,
it's genuine gather traffic (fix = Pallas slab-gather with pipelined
DMA, docs/PERF_NOTES.md design); if `idx0` is also slow, it's ladder
structure (launches/pad/concat — fix = fewer/merged buckets).

Replaces: the timing side of the reference's aggregation hot loop
(module/layer.py:47-49) — this is framework diagnostics, no reference
counterpart.

Usage: python scripts/rem_probe.py [--part partitions/...]
"""

import argparse
import json
import os
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--part",
                    default="partitions/bench-reddit-1-c2-s1024")
    ap.add_argument("--width", type=int, default=256)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--group", type=int, default=4)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from pipegcn_tpu.backend import device_line, place_compile_cache

    place_compile_cache()
    # results are labelled by jax.default_backend(); say which it is
    print(f"# {device_line()}", file=sys.stderr)

    from pipegcn_tpu.models import ModelConfig
    from pipegcn_tpu.ops.bucket_spmm import (bucket_aggregate,
                                             transport_cast,
                                             transport_dtypes)
    from pipegcn_tpu.parallel import Trainer, TrainConfig

    # partitions/ is not git-tracked and vanishes between rounds;
    # ensure() rebuilds host-side (no jax) rather than failing the step
    from pipegcn_tpu.partition.bench_artifact import ensure

    if not os.path.isabs(args.part):
        args.part = os.path.join(REPO, args.part)
    sg = ensure(args.part, log=lambda m: print(m, file=sys.stderr))
    cfg = ModelConfig(
        layer_sizes=(sg.n_feat, 256, 256, 256, sg.n_class),
        use_pp=True, norm="layer", dropout=0.5,
        train_size=sg.n_train_global, spmm_chunk=2_097_152,
        dtype="bfloat16", spmm_impl="block", block_group=args.group,
        rem_dtype="float8",
    )
    tr = Trainer(sg, cfg, TrainConfig(lr=0.01, n_epochs=1, eval=False))
    d = {k: v[0] for k, v in tr.data.items()}
    n_src = sg.n_max + sg.halo_size
    fp8, _ = transport_dtypes("float8")

    keys = sorted(k for k in d
                  if k.startswith("blkrem_fwd_") and not k.endswith("inv"))
    mats = [d[k] for k in keys]
    inv = d["blkrem_fwd_inv"]
    # real gathered rows per call: bucket tables are row-padded to
    # shared caps; padded rows gather the sentinel, so they cost a
    # request too — count the full table extent
    padded_rows = int(sum(int(m.shape[0]) * int(m.shape[1])
                          for m in mats))
    print(f"# remainder fwd tables: {len(mats)} buckets, "
          f"{padded_rows/1e6:.1f}M padded rows/SpMM", file=sys.stderr)

    rng = np.random.default_rng(0)
    fbuf = jnp.asarray(
        rng.standard_normal((n_src, args.width)).astype(np.float32)
    ).astype(jnp.bfloat16)
    fbuf8 = transport_cast(fbuf, fp8)
    zero_mats = [jnp.zeros_like(m) for m in mats]
    zero_inv = jnp.zeros_like(inv)

    def timed(fn, ops, label, rows):
        jfn = jax.jit(fn)
        float(jnp.sum(jfn(*ops)))  # compile + settle
        float(jnp.sum(jfn(*ops)))
        ts = []
        for _ in range(args.reps):
            t0 = time.perf_counter()
            float(jnp.sum(jfn(*ops)))
            ts.append(time.perf_counter() - t0)
        t = min(ts)
        print(f"{label:12s} {t*1e3:8.1f} ms  "
              f"{rows/t/1e6:7.0f} M rows/s", file=sys.stderr)
        return t

    res = {"backend": jax.default_backend(), "group": args.group,
           "padded_rows": padded_rows}

    # cliff-rate anchor: one flat gather of the same row count from the
    # same fp8 buffer (random uniform indices — same cache behavior
    # class as the ladder's shuffled neighbor ids)
    flat_idx = jnp.asarray(
        rng.integers(0, n_src, size=padded_rows).astype(np.int32))

    def anchor(f8, idx):
        return jnp.take(f8, idx, axis=0).astype(jnp.float32).sum(0)

    res["anchor_s"] = timed(anchor, (fbuf8, flat_idx), "anchor",
                            padded_rows)
    # sorted-index anchor: if ascending requests run much faster than
    # random ones, locality-ordering bucket rows at table build (free,
    # host-side) is a production lever worth a follow-up
    res["anchor_sorted_s"] = timed(
        anchor, (fbuf8, jnp.sort(flat_idx)), "anchor-sort", padded_rows)

    def rem(f, ms, iv):
        return bucket_aggregate(transport_cast(f, fp8), ms, iv,
                                chunk_edges=cfg.spmm_chunk)

    def rem_pre(f8, ms, iv):
        return bucket_aggregate(f8, ms, iv, chunk_edges=cfg.spmm_chunk)

    res["rem_s"] = timed(rem, (fbuf, mats, inv), "rem", padded_rows)
    res["nocast_s"] = timed(rem_pre, (fbuf8, mats, inv), "nocast",
                            padded_rows)
    res["idx0_s"] = timed(rem_pre, (fbuf8, zero_mats, inv), "idx0",
                          padded_rows)
    res["noinv_s"] = timed(rem_pre, (fbuf8, mats, zero_inv), "noinv",
                           padded_rows)

    for ce in (None, 8_388_608):
        def rem_c(f8, ms, iv, ce=ce):
            return bucket_aggregate(f8, ms, iv, chunk_edges=ce)

        res[f"chunk_{ce or 'def'}_s"] = timed(
            rem_c, (fbuf8, mats, inv), f"chunk-{ce or 'def'}",
            padded_rows)

    def rem_bf16(f, ms, iv):
        return bucket_aggregate(f, ms, iv, chunk_edges=cfg.spmm_chunk)

    # bf16 gathers 2 slabs per row
    res["bf16_s"] = timed(rem_bf16, (fbuf, mats, inv), "bf16",
                          2 * padded_rows)

    out = os.path.join(REPO, "results",
                       f"rem_probe_{jax.default_backend()}.json")
    with open(out, "w") as f:
        json.dump(res, f, indent=1)
    print(f"# wrote {out}")


if __name__ == "__main__":
    main()
