#!/usr/bin/env python
"""Pre-build + disk-cache kernel tables for a partition artifact.

Mostly host-side: run ahead of a measurement so the next
bench/microbench skips the minutes-long O(E) table builds. The caches
live under partitions/, which a chip call's machine starts without, so
on the chip this belongs in the same command as the measurement. One
invocation per kernel
configuration; the cache key (Trainer._cached_tables) encodes
(impl, tile, width, nnz, group, merge).

--impl auto additionally runs the SpMM auto-tuner's micro-bench
campaign on the current backend (a sample of whole destination
tile-rows — the one part of prewarm that does touch the device) and persists the tuning.json
sidecar into the artifact, then warms the winner's tables. Run it on
the backend you will train on: the table signature pins the backend,
so a CPU-prewarmed table is (correctly) rejected on TPU.

Usage: python scripts/prewarm_tables.py --impl block --group 4
       [--part partitions/bench-reddit-1-c2-s1024] [--block-nnz N]
       python scripts/prewarm_tables.py --impl auto   # tune + warm
"""

import argparse
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--part",
                    default="partitions/bench-reddit-1-c2-s1024")
    ap.add_argument("--impl", default="block",
                    choices=["auto", "block", "bucket", "gat"])
    ap.add_argument("--group", type=int, default=1)
    ap.add_argument("--block-nnz", type=int, default=0)
    ap.add_argument("--bucket-merge", type=int, default=0)
    ap.add_argument("--tuner-samples", type=int, default=4_000_000)
    ap.add_argument("--retune", action="store_true",
                    help="with --impl auto: delete any persisted "
                         "tuning.json first and force a fresh "
                         "micro-bench campaign")
    ap.add_argument("--hidden", type=int, default=256)
    ap.add_argument("--reorder", default="none",
                    choices=["none", "degree", "bfs", "degree-bfs"],
                    help="prewarm the locality-REORDERED layout of "
                         "--part instead (suffix -r<mode>); the O(E) "
                         "artifact build happens here, host-side")
    args = ap.parse_args()

    from pipegcn_tpu.models import ModelConfig
    from pipegcn_tpu.parallel import Trainer

    # rebuilt if missing: partitions/ is not git-tracked and vanishes
    # between rounds
    from pipegcn_tpu.partition.bench_artifact import ensure

    if not os.path.isabs(args.part):
        args.part = os.path.join(REPO, args.part)
    if args.reorder != "none" and not args.part.endswith(
            f"-r{args.reorder}"):
        from pipegcn_tpu.partition.partitioner import reorder_suffix

        args.part += reorder_suffix(args.reorder)
    sg = ensure(args.part, log=lambda m: print(m, file=sys.stderr))
    if args.retune and args.impl == "auto":
        from pipegcn_tpu.ops import tuner

        p = tuner.tuning_path(sg.cache_dir)
        if os.path.exists(p):
            os.remove(p)
            print(f"removed {p} (forcing re-tune)", file=sys.stderr)
    cfg = ModelConfig(
        model="gat" if args.impl == "gat" else "graphsage",
        layer_sizes=(sg.n_feat,) + (args.hidden,) * 3 + (sg.n_class,),
        use_pp=args.impl != "gat", norm="layer",
        train_size=sg.n_train_global,
        spmm_impl="bucket" if args.impl == "gat" else args.impl,
        block_nnz=args.block_nnz or None,
        block_group=args.group, bucket_merge=args.bucket_merge,
        tuner_samples=args.tuner_samples,
        dtype="bfloat16",
    )
    t0 = time.perf_counter()
    Trainer.prewarm_tables(sg, cfg)
    print(f"warmed {args.impl} tables (group={args.group}, "
          f"nnz={args.block_nnz or 'auto'}) "
          f"in {time.perf_counter() - t0:.1f}s")
    if args.impl == "auto":
        from pipegcn_tpu.ops import tuner

        rec, why = tuner.load_tuning(sg.cache_dir)
        if rec is not None:
            print(f"tuning.json winner: {rec['winner']['name']} "
                  f"(backend {rec['signature']['backend']})")
        else:
            print(f"no tuning.json persisted ({why})", file=sys.stderr)


if __name__ == "__main__":
    main()
