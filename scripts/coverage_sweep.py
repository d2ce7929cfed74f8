#!/usr/bin/env python
"""Structural dense-coverage sweep: cluster granularity x nnz threshold.

The block kernel's epoch splits between the dense MXU term and the
slabbed remainder; VERDICT round 2 asks for remainder < 50% of the
epoch. Which (locality cluster target_size, block_nnz) maximizes the
edges captured in budget-capped dense tiles is a purely STRUCTURAL
question — this sweep answers it host-side so scarce TPU windows only
measure the top candidates.

For each cluster granularity it rebuilds the single-part Reddit-scale
layout (local ids sorted by cluster), then reports, per nnz threshold:
budget-capped dense coverage, dense block count, remainder edges, and
the v5e cost model's epoch projection (docs/PERF_NOTES.md rates).

Writes results/coverage_sweep.md.
"""

import argparse
import os
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def model_epoch(dense_edges, rem_edges, dense_blocks, tile, width=256,
                block_s=2.14e-6, row_rate=230e6, pad=1.25,
                rem_bytes_per_feat=2, aux_s=0.066, fixed_s=0.518,
                layer_pairs=3):
    """Probe-CALIBRATED v5e epoch model (round 4).

    Fitted to the measured table-surgery decomposition
    (results/probe_traffic_tpu_g1.json, one v5e, Reddit-scale layout,
    38,744 blocks / 22.5M remainder edges):
      - dense fwd+bwd 116 ms -> `block_s` ~ 2.14 us/block (per layer
        pair, aux split evenly) — an EMPIRICAL unit absorbing the
        unpack transient + scheduling, ~5x the naive read+MXU sum the
        round-3 model used (its 0.53 s miss);
      - remainder fwd+bwd 277 ms -> `row_rate` ~ 230M padded slab
        rows/s (well under the 390-460M isolated-gather cliff rate);
      - `aux_s`: per-layer-pair shared prep (dense-only + rem-only -
        full = 66 ms); `fixed_s`: measured epoch minus SpMM epoch
        (1.5006 - 0.982 = 0.518 s: linears, norms, dropout RNG, fbuf
        assembly, dispatch).
    Validation: predicts the float8 headline config at 1.331 s vs
    1.2963 measured (+2.7%). `rem_bytes_per_feat`: 2 = bf16 transport,
    1 = fp8 (--rem-dtype float8)."""
    n_slabs = max(1, (width * rem_bytes_per_feat) // 256)
    t_dense = layer_pairs * dense_blocks * block_s
    t_rem = layer_pairs * rem_edges * pad * n_slabs / row_rate
    return (t_dense + t_rem + layer_pairs * aux_s + fixed_s,
            t_dense, t_rem)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", default="synthetic-reddit")
    ap.add_argument("--tile", type=int, default=256)
    ap.add_argument("--cluster-sizes", type=int, nargs="+",
                    default=[4096, 1024, 512])
    ap.add_argument("--nnz", type=int, nargs="+",
                    default=[0, 64, 108, 160])
    ap.add_argument("--out", default="results/coverage_sweep.md")
    ap.add_argument("--block-s", type=float, default=2.14e-6,
                    help="empirical dense cost per block per layer "
                         "pair (probe-calibrated)")
    ap.add_argument("--row-rate", type=float, default=230e6,
                    help="remainder padded slab rows/s "
                         "(probe-calibrated)")
    ap.add_argument("--aux-s", type=float, default=0.066,
                    help="shared SpMM prep per layer pair")
    ap.add_argument("--rem-bytes-per-feat", type=int, default=2,
                    help="2 = bf16 transport, 1 = fp8 (--rem-dtype)")
    ap.add_argument("--fixed-s", type=float, default=0.518,
                    help="non-SpMM epoch floor (measured epoch minus "
                         "probe SpMM epoch)")
    args = ap.parse_args()

    from pipegcn_tpu.backend import start_measurement

    start_measurement(cpu=True)  # a host-side study by design

    from pipegcn_tpu.graph import load_data
    from pipegcn_tpu.ops.block_spmm import (DENSE_A_BYTE_BUDGET,
                                            _part_block_stats,
                                            budget_block_cap,
                                            occupied_blocks)
    from pipegcn_tpu.partition import ShardedGraph, locality_clusters
    from pipegcn_tpu.partition.partitioner import partition_graph

    g = load_data(args.dataset)
    parts = partition_graph(g, 1, seed=0)
    tile = args.tile

    rows = []
    for tsize in args.cluster_sizes:
        t0 = time.time()
        cluster = locality_clusters(g, target_size=tsize, seed=0)
        sg = ShardedGraph.build(g, parts, n_parts=1, cluster=cluster)
        n_src_tiles = -(-(sg.n_max + sg.halo_size) // tile)
        occupied = occupied_blocks(sg, 0, tile, n_src_tiles)
        build_s = time.time() - t0
        seen_thr = set()
        for thr0 in args.nnz:
            thr = thr0 or max(1, (tile * tile) // 602)
            if thr in seen_thr:  # 0 resolves to the break-even, which
                continue         # may duplicate an explicit entry
            seen_thr.add(thr)
            cap = budget_block_cap(DENSE_A_BYTE_BUDGET, tile, 1,
                                   [occupied], thr, n_src_tiles)
            cov, n_dense, dense_e, tot_e = _part_block_stats(
                sg, 0, tile, n_src_tiles, thr, max_blocks=cap,
                occupied=occupied)
            rem_e = tot_e - dense_e
            t_ep, t_d, t_r = model_epoch(
                dense_e, rem_e, n_dense, tile,
                block_s=args.block_s, row_rate=args.row_rate,
                aux_s=args.aux_s,
                rem_bytes_per_feat=args.rem_bytes_per_feat,
                fixed_s=args.fixed_s)
            rows.append((tsize, thr, cov, n_dense, rem_e, t_ep, t_d, t_r,
                         build_s))
            print(f"tsize={tsize} thr={thr}: cov={cov:.3f} "
                  f"blocks={n_dense} rem={rem_e/1e6:.1f}M "
                  f"model={t_ep:.3f}s (dense {t_d:.3f} rem {t_r:.3f})",
                  file=sys.stderr)

    lines = [
        "# Dense-coverage structural sweep (tile=%d, budget-capped)"
        % tile,
        "",
        f"Dataset {args.dataset}; 1 partition; budget cap {cap} "
        "bit-packed blocks. Cost model rates from docs/PERF_NOTES.md "
        "(projection only — TPU measurement picks among the top rows).",
        "",
        "| cluster target | nnz thr | coverage | dense blocks "
        "| remainder edges | model epoch (s) | dense (s) | rem (s) |",
        "|---|---|---|---|---|---|---|---|",
    ]
    for (tsize, thr, cov, n_dense, rem_e, t_ep, t_d, t_r, _) in rows:
        lines.append(
            f"| {tsize} | {thr} | {cov:.3f} | {n_dense} "
            f"| {rem_e/1e6:.1f}M | {t_ep:.3f} | {t_d:.3f} | {t_r:.3f} |")
    best = min(rows, key=lambda r: r[5])
    lines += ["",
              f"Model-best: cluster target {best[0]}, thr {best[1]} -> "
              f"{best[5]:.3f} s/epoch projected (remainder share "
              f"{best[7]/best[5]:.0%})."]
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        f.write("\n".join(lines) + "\n")
    print("\n".join(lines[-3:]))


if __name__ == "__main__":
    main()
