#!/usr/bin/env python
"""GAT epoch time at Reddit scale — attention-bucket kernel vs raw.

The GAT family used to run only on the raw-edge segment path (the
19.8 s/epoch-class regime, docs/PERF_NOTES.md); this measures the
scatter-free attention-bucket kernel (ops/gat_bucket.py) on the real
chip against the SAGE headline. Reuses the bench partition artifact
(and its cached tables after the first run).

Each timed dispatch ends in a device->host read of its losses
(train_epochs returns them as numpy), so the clock stops after the
device does. Fails without a TPU unless --cpu (a harness dry run).

Usage: python scripts/gat_bench.py [--part partitions/bench-reddit-1-c2-s1024]
       [--impl bucket|xla] [--epochs 4] [--heads 4]
"""

import argparse
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
    ap.add_argument("--dataset", default=None,
                    help="build (and cache) a dedicated artifact from "
                         "this dataset spec instead of --part — e.g. "
                         "synthetic:60000:30:602:41 (a Reddit-scale "
                         "GAT epoch took tens of seconds on earlier "
                         "code; record removed)")
    ap.add_argument("--impl", default="bucket",
                    choices=["bucket", "xla"])
    ap.add_argument("--epochs", type=int, default=4,
                    help="timed fused-epoch block length")
    ap.add_argument("--heads", type=int, default=4)
    ap.add_argument("--hidden", type=int, default=256)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--rem-dtype", default="none",
                    choices=["none", "bfloat16", "float8"],
                    help="wide-gather transport narrowing "
                         "(ModelConfig.rem_dtype)")
    ap.add_argument("--cpu", action="store_true")
    args = ap.parse_args()

    import jax

    from pipegcn_tpu.backend import start_measurement

    start_measurement(cpu=args.cpu)

    from pipegcn_tpu.models import ModelConfig
    from pipegcn_tpu.parallel import Trainer, TrainConfig
    from pipegcn_tpu.partition import ShardedGraph
    from pipegcn_tpu.partition.bench_artifact import build_artifact, ensure

    log = lambda m: print(m, file=sys.stderr)  # noqa: E731
    if args.dataset:
        part_path = os.path.join(
            REPO, "partitions",
            "gat-" + args.dataset.replace(":", "_") + "-c-s1024")
        if ShardedGraph.exists(part_path):
            sg = ShardedGraph.load(part_path)
        else:
            sg = build_artifact(args.dataset, 1, 1024, part_path, log=log)
    else:
        # rebuilt if missing: partitions/ is not git-tracked and
        # vanishes between rounds
        if not os.path.isabs(args.part):
            args.part = os.path.join(REPO, args.part)
        sg = ensure(args.part, log=log)
    cfg = ModelConfig(
        # 3 graph layers like the SAGE headline (no use_pp for GAT)
        layer_sizes=(sg.n_feat, args.hidden, args.hidden, args.hidden,
                     sg.n_class),
        model="gat", n_heads=args.heads, norm="layer", dropout=0.5,
        train_size=sg.n_train_global, spmm_impl=args.impl,
        spmm_chunk=2_097_152, dtype="bfloat16",
        rem_dtype=args.rem_dtype,
    )
    tcfg = TrainConfig(lr=0.01,
                       n_epochs=args.epochs * (args.reps + 2),
                       enable_pipeline=True, eval=False,
                       fused_epochs=args.epochs)
    t0 = time.time()
    tr = Trainer(sg, cfg, tcfg)
    print(f"# trainer init (tables) {time.time()-t0:.0f}s",
          file=sys.stderr)

    # compile and warm the fused program off the clock
    blk = max(1, args.epochs)
    e = 0
    for label in ("compile+first", "warm"):
        t0 = time.perf_counter()
        losses = tr.train_epochs(e, blk)
        e += blk
        print(f"# {label} block of {blk}: {time.perf_counter()-t0:.0f}s "
              f"loss={float(losses[-1]):.4f}", file=sys.stderr)

    times = []
    for r in range(args.reps):
        t0 = time.perf_counter()
        losses = tr.train_epochs(e, blk)
        dt = time.perf_counter() - t0
        e += blk
        times.append(dt / blk)
        print(f"# block {r}: {dt:.2f}s -> {dt/blk:.3f} s/epoch "
              f"loss={float(losses[-1]):.4f}", file=sys.stderr)
    import json

    print(json.dumps({
        "metric": f"gat_{args.impl}_epoch_time"
                  + ("" if args.rem_dtype == "none"
                     else f"_{args.rem_dtype}"),
        "value": round(float(np.median(times)), 4),
        "unit": "s/epoch",
        "heads": args.heads,
        "hidden": args.hidden,
        "dispatch_epochs": blk,
        "backend": jax.default_backend(),
    }))


if __name__ == "__main__":
    main()
