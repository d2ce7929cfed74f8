#!/usr/bin/env python
"""Off-shape chip point for the auto-kernel policy (VERDICT r4 item 8).

The auto thresholds (_AUTO_BLOCK_MIN_EDGES / _AUTO_BLOCK_MIN_COVERAGE,
parallel/trainer.py) and the f8-transport lever were calibrated on ONE
graph family (synthetic-Reddit: 233k nodes, deg 492, F=602/256). This
benches a second family on chip — the ogbn-products shape (2.45M nodes,
deg ~51, F=100, 47 classes, hidden 128: reference
scripts/ogbn-products.sh + helper/utils.py:17-30) or the Yelp shape —
and records what `auto` resolves to there plus the measured
block/bucket/f8 ranking, so the policy rests on two shape points
instead of one.

Dispatch discipline follows scripts/gat_bench.py: the fused program is
compiled and warmed off the clock and every timed dispatch ends in a
device->host read of its losses. Fails without a TPU unless --cpu or
--build-only (host work).

Usage:
  python scripts/offshape_bench.py --shape products --build-only  # host
  python scripts/offshape_bench.py --shape products --impl auto
"""

import argparse
import json
import os
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

# dataset spec + reference model config per shape:
#   products: 2,449,029 nodes / avg deg ~51 / 100 feats / 47 classes;
#     3 layers x 128 hidden, dropout 0.3 (scripts/ogbn-products.sh)
#   yelp: 716,847 nodes / deg ~19 / 300 feats / 100 classes;
#     4 layers x 512 hidden, dropout 0.1 (scripts/yelp.sh)
SHAPES = {
    "products": ("synthetic:2449029:51:100:47", 128, 3, 0.3),
    "yelp": ("synthetic:716847:19:300:100", 512, 4, 0.1),
}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--shape", default="products", choices=sorted(SHAPES))
    ap.add_argument("--impl", default="auto",
                    choices=["auto", "block", "bucket"])
    ap.add_argument("--rem-dtype", default="float8",
                    choices=["none", "bfloat16", "float8"])
    ap.add_argument("--block-group", type=int, default=4)
    ap.add_argument("--epochs", type=int, default=8,
                    help="max fused-epoch block length")
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--build-only", action="store_true",
                    help="build + cache the partition artifact (and "
                         "kernel tables) on the host, no measurement")
    ap.add_argument("--cpu", action="store_true")
    args = ap.parse_args()

    import jax

    from pipegcn_tpu.backend import start_measurement

    start_measurement(cpu=args.cpu or args.build_only)

    from pipegcn_tpu.models import ModelConfig
    from pipegcn_tpu.parallel import Trainer, TrainConfig
    from pipegcn_tpu.partition import ShardedGraph

    dataset, hidden, n_layers, dropout = SHAPES[args.shape]
    part_path = os.path.join("partitions", f"offshape-{args.shape}-1-s1024")
    t0 = time.time()
    if ShardedGraph.exists(part_path):
        sg = ShardedGraph.load(part_path)
        print(f"# loaded cached artifact ({time.time()-t0:.0f}s)",
              file=sys.stderr)
    else:
        from pipegcn_tpu.graph import load_data
        from pipegcn_tpu.partition import (locality_clusters,
                                           partition_graph)

        g = load_data(dataset)
        parts = partition_graph(g, 1, seed=0)
        cluster = locality_clusters(g, target_size=1024, seed=0)
        sg = ShardedGraph.build(g, parts, n_parts=1, cluster=cluster)
        sg.save(part_path)
        print(f"# built artifact ({time.time()-t0:.0f}s)",
              file=sys.stderr)
    sg.cache_dir = part_path

    cfg = ModelConfig(
        layer_sizes=(sg.n_feat,) + (hidden,) * (n_layers - 1)
                    + (sg.n_class,),
        use_pp=True, norm="layer", dropout=dropout,
        train_size=sg.n_train_global, spmm_chunk=2_097_152,
        dtype="bfloat16", spmm_impl=args.impl,
        block_group=args.block_group, rem_dtype=args.rem_dtype,
    )
    tcfg = TrainConfig(lr=0.003,
                       n_epochs=args.epochs * (args.reps + 2),
                       enable_pipeline=True, eval=False,
                       fused_epochs=args.epochs)
    t0 = time.time()
    tr = Trainer(sg, cfg, tcfg)
    resolved = ("block" if tr._block_tables is not None else
                "bucket" if tr._bucket_tables is not None else
                args.impl)
    print(f"# trainer init (tables) {time.time()-t0:.0f}s; "
          f"impl={args.impl} resolved={resolved}", file=sys.stderr)
    if args.build_only:
        print(f"# artifact + {resolved} tables cached at {part_path}")
        return

    def check_finite(losses, e_last):
        # abort on the FIRST non-finite intermediate loss: the
        # products-shape NaN burned every remaining measurement block
        # after epoch 0 went NaN (VERDICT r5) — a diverged run must
        # stop spending chip time IMMEDIATELY, loudly, red
        bad = ~np.isfinite(np.asarray(losses, np.float64))
        if bad.any():
            j = int(np.argmax(bad))
            print(f"# NON-FINITE LOSS at epoch "
                  f"{e_last - len(losses) + 1 + j} — aborting the "
                  f"measurement (exit 3); diagnose with the numerics "
                  f"tripwire (docs/RESILIENCE.md 'Numerics')",
                  file=sys.stderr)
            sys.exit(3)

    blk = max(1, args.epochs)
    e = 0
    for label in ("compile+first", "warm"):
        t0 = time.perf_counter()
        losses = tr.train_epochs(e, blk)
        e += blk
        print(f"# {label} block of {blk}: {time.perf_counter()-t0:.0f}s "
              f"loss={float(losses[-1]):.4f}", file=sys.stderr)
        check_finite(losses, e - 1)

    times = []
    for r in range(args.reps):
        t0 = time.perf_counter()
        losses = tr.train_epochs(e, blk)
        dt = time.perf_counter() - t0
        e += blk
        times.append(dt / blk)
        print(f"# block {r}: {dt:.2f}s -> {dt/blk:.3f} s/epoch "
              f"loss={float(losses[-1]):.4f}", file=sys.stderr)
        check_finite(losses, e - 1)

    final_loss = float(losses[-1])
    print(json.dumps({
        "metric": f"offshape_{args.shape}_{args.impl}_epoch_time"
                  + ("" if args.rem_dtype == "none"
                     else f"_{args.rem_dtype}"),
        "value": round(float(np.median(times)), 4),
        "unit": "s/epoch",
        "resolved_impl": resolved,
        "block_group": args.block_group,
        "hidden": hidden,
        "dispatch_epochs": blk,
        "backend": jax.default_backend(),
        "loss": round(final_loss, 4) if np.isfinite(final_loss) else None,
    }))
    if not np.isfinite(final_loss):
        # the known products-shape NaN (VERDICT "Next round" item 1)
        # must never again publish a green JSON: timing a diverged run
        # measures nothing
        print("# FINAL LOSS NON-FINITE — benchmark invalid; exiting 3",
              file=sys.stderr)
        sys.exit(3)


if __name__ == "__main__":
    main()
