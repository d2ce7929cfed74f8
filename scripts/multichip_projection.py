"""Project multi-chip epoch time for the Reddit-scale benchmark.

Real hardware here is ONE v5e chip, so multi-chip numbers cannot be
measured; this tool produces the next-best thing — a real P-way METIS
partition of the benchmark graph and, from it, the measured quantities
that determine multi-chip performance:

  - per-device inner nodes / edges (compute balance),
  - halo sizes and per-epoch ICI traffic (Trainer.est_ici_bytes_per_epoch,
    the exact gather/ppermute volumes of the pipelined step),
  - dense-tile coverage per device (the block kernel's regime survives
    partitioning or it doesn't),
  - a projected epoch time from the round-4 probe-CALIBRATED cost
    model (2.14 us/dense-block, 230M padded slab rows/s, measured aux
    + non-SpMM floor; validated at +2.7% against the fp8 single-chip
    headline — results/tpu_bench.md) — scaled by the MAX-loaded
    device, plus the ICI time at v5e's 2x 400 GB/s links (pipelined:
    overlapped, so counted only as a floor check).

Writes results/multichip_projection.md.

Usage:
  JAX_PLATFORMS=cpu python scripts/multichip_projection.py [--parts 8]
"""

import argparse
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--parts", type=int, default=8)
    ap.add_argument("--dataset", default="synthetic-reddit")
    ap.add_argument("--out", default="results/multichip_projection.md")
    ap.add_argument("--part-dir", default="partitions/projection")
    args = ap.parse_args()

    if args.dataset != "synthetic-reddit":
        print("# WARNING: epoch-model constants (BLOCK_S/ROW_RATE/"
              "AUX_S/FIXED_S, N1_ROWS) are probe-calibrated on the "
              "synthetic-reddit P=1 chip run; aux/floor scaling for "
              f"'{args.dataset}' is extrapolation, not calibration",
              file=sys.stderr)

    os.environ.setdefault(
        "XLA_FLAGS",
        f"--xla_force_host_platform_device_count={args.parts}")
    from pipegcn_tpu.backend import start_measurement

    start_measurement(cpu=True)  # a host-side model by design

    from pipegcn_tpu.graph import load_data
    from pipegcn_tpu.ops.block_spmm import (DENSE_A_BYTE_BUDGET,
                                            _part_block_stats,
                                            budget_block_cap,
                                            occupied_blocks)
    from pipegcn_tpu.partition import (ShardedGraph, locality_clusters,
                                       partition_graph)

    path = f"{args.part_dir}-{args.parts}"
    t0 = time.time()
    if ShardedGraph.exists(path):
        sg = ShardedGraph.load(path)
        print(f"# loaded cached projection partitions "
              f"({time.time()-t0:.0f}s)", file=sys.stderr)
    else:
        g = load_data(args.dataset)
        parts = partition_graph(g, args.parts, method="metis", obj="vol",
                                seed=0)
        cluster = locality_clusters(g, seed=0)
        sg = ShardedGraph.build(g, parts, n_parts=args.parts,
                                cluster=cluster)
        sg.save(path)
        print(f"# built projection partitions ({time.time()-t0:.0f}s)",
              file=sys.stderr)

    P = sg.num_parts
    inner = sg.inner_count.astype(np.int64)
    edges = sg.edge_count.astype(np.int64)
    halos = []        # halo EDGE endpoints (edges sourced from halo)
    halo_rows = []    # UNIQUE halo rows resident in the fbuf
    for r in range(P):
        e = int(sg.edge_count[r])
        src = sg.edge_src[r][:e]
        halos.append(int((src >= sg.n_max).sum()))
        halo_rows.append(int(np.unique(src[src >= sg.n_max]).size))
    send = sg.send_counts.sum(axis=1).astype(np.int64)

    # ICI volume of the pipelined step: per layer, each device sends its
    # boundary rows (send lists) and receives its halo rows, in the
    # compute dtype, forward + backward; 3 graph layers exchange (use_pp
    # skips layer 0). Width 256, bf16.
    width, isz, n_exch = 256, 2, 3
    tx_bytes = send * width * isz * n_exch * 2  # fwd feats + bwd grads

    # Probe-CALIBRATED per-device epoch model (round 4: fitted to the
    # measured table-surgery decomposition, validated at +2.7% on the
    # fp8 single-chip headline — scripts/coverage_sweep.model_epoch,
    # results/tpu_bench.md). Production transport: fp8 remainder.
    BLOCK_S, ROW_RATE, PAD = 2.14e-6, 230e6, 1.25
    AUX_S, FIXED_S = 0.066, 0.518
    N1_ROWS = 232_965          # P=1 fbuf rows (no halo at P=1)
    N_SLABS = 1                # fp8: one 256-byte slab at width 256
    tile = 256
    thr = max(1, (tile * tile) // 602)
    n_src_tiles = -(-(sg.n_max + sg.halo_size) // tile)
    # cap at the HBM byte budget exactly as the real plan builder does —
    # uncapped counts would project dense capacity the budgeted plan
    # spills to the remainder
    occupied = [occupied_blocks(sg, r, tile, n_src_tiles)
                for r in range(P)]
    cap = budget_block_cap(DENSE_A_BYTE_BUDGET, tile, 1, occupied, thr,
                           n_src_tiles)
    stats = [_part_block_stats(sg, r, tile, n_src_tiles, thr,
                               max_blocks=cap, occupied=occupied[r])
             for r in range(P)]
    cov = np.array([st[0] for st in stats])
    dense_blocks = np.array([st[1] for st in stats])

    rem_edges = edges * (1 - cov)
    rows_d = inner + np.asarray(halo_rows, np.int64)
    t_rem = 3 * rem_edges * PAD * N_SLABS / ROW_RATE
    t_dense = 3 * dense_blocks * BLOCK_S
    # shared SpMM prep scales with the fbuf rows each device holds
    t_aux = 3 * AUX_S * rows_d / N1_ROWS
    # the 0.518 s non-SpMM floor's scaling is bracketed until the
    # epoch-anatomy ablation attributes it: optimistic = scales with
    # inner rows (norms/dropout/linears), pessimistic = scales with
    # total fbuf rows (assembly/concat over inner+halo)
    floor_opt = FIXED_S * inner / N1_ROWS
    floor_pess = FIXED_S * rows_d / N1_ROWS
    t_ici = tx_bytes / 400e9                        # per-direction link
    t_dev = t_rem + t_dense + t_aux + floor_pess
    t_dev_opt = t_rem + t_dense + t_aux + floor_opt
    proj = float(t_dev.max())
    proj_opt = float(t_dev_opt.max())

    lines = [
        f"# Multi-chip projection ({P}-way METIS, {args.dataset})",
        "",
        "One v5e chip is available; this projects the multi-chip epoch "
        "from a REAL partition of the benchmark graph plus the round-4 "
        "probe-CALIBRATED cost model (fitted to the measured "
        "table-surgery decomposition; +2.7% on the fp8 single-chip "
        "headline — results/tpu_bench.md), fp8 remainder transport. "
        "The sharded program itself is validated on the virtual CPU "
        "mesh (dryrun_multichip, tests/). Per-device epoch column uses "
        "the PESSIMISTIC floor scaling (fbuf rows); the optimistic "
        "(inner-rows) bound is reported below the table.",
        "",
        "| device | inner nodes | edges | halo rows (unique) | send rows/layer | "
        "dense cov | est ICI MB/epoch | est epoch s |",
        "|---|---|---|---|---|---|---|---|",
    ]
    for r in range(P):
        lines.append(
            f"| {r} | {inner[r]:,} | {edges[r]:,} | {halo_rows[r]:,} "
            f"| {send[r]:,} | {cov[r]:.2f} | {tx_bytes[r]/2**20:.0f} "
            f"| {t_dev[r]:.3f} |")
    lines += [
        "",
        f"Projected epoch (max device, comm overlapped, pessimistic "
        f"floor): **{proj:.3f} s**; optimistic floor: {proj_opt:.3f} s"
        + (f" — vs 1.2963 s measured single-chip, "
           f"{1.2963/proj:.1f}-{1.2963/proj_opt:.1f}x scaling at P={P}."
           if args.dataset == "synthetic-reddit" else "."),
        f"Worst-case exposed-ICI floor if NOTHING overlapped: "
        f"{float(t_ici.max()):.4f} s "
        f"({100*float(t_ici.max())/proj:.1f}% of the projected epoch) — "
        "the pipelined design exists to hide exactly this term "
        "(results/overlap_study.md shows all pipelined exchanges leave "
        "the critical path).",
        "",
        f"Reference baseline: 0.266 s/epoch on 2 GPUs; the projection "
        f"crosses it at P={P} if {proj:.3f} <= 0.266 "
        f"({'yes' if proj <= 0.266 else 'no'}).",
    ]
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        f.write("\n".join(lines) + "\n")
    print("\n".join(lines))


if __name__ == "__main__":
    main()
