#!/usr/bin/env python
"""Attribute the non-SpMM epoch floor by config ablation on the chip.

The probe-traffic decomposition (results/probe_traffic_tpu_g1.json)
puts the SpMM terms at 0.982 s of the measured 1.5006 s epoch; the
remaining 0.518 s floor covers linears, norms, dropout RNG, fbuf
assembly and dispatch. This script times the SAME production config
with one ingredient removed at a time — the deltas attribute the
floor to its parts so the next kernel/layout lever targets the right
term (the reference has no analogue; this is perf tooling for the
driver headline, reference README.md:93-94).

Variants: baseline (block-u4-float8, the headline config) |
dropout=0 (no RNG, no mask traffic) | norm=None (no LayerNorm
fwd/bwd) | n_linear tail only dispatch floor probe: fused=1 vs 4.

The ablation clock itself lives in pipegcn_tpu/obs/anatomy.py
(`time_config`) next to the structural HLO
attribution (`step_anatomy`, the CLI's --anatomy flag); this script is
the chip-window wrapper that picks the headline config's variants and
writes results/epoch_anatomy.json.

Usage: python scripts/epoch_anatomy.py [--part ...] [--reps 3]
"""

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--part",
                    default="partitions/bench-reddit-1-c2-s1024")
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--blk", type=int, default=4)
    ap.add_argument("--cpu", action="store_true")
    ap.add_argument("--out", default="results/epoch_anatomy.json")
    args = ap.parse_args()

    import dataclasses

    import jax

    from pipegcn_tpu.backend import start_measurement

    start_measurement(cpu=args.cpu)

    from pipegcn_tpu.models import ModelConfig
    from pipegcn_tpu.parallel import TrainConfig

    # partitions/ is not git-tracked and vanishes between rounds;
    # ensure() rebuilds host-side (no jax) rather than failing the step
    from pipegcn_tpu.partition.bench_artifact import ensure

    if not os.path.isabs(args.part):
        args.part = os.path.join(REPO, args.part)
    sg = ensure(args.part, log=lambda m: print(m, file=sys.stderr))
    base = ModelConfig(
        layer_sizes=(sg.n_feat, 256, 256, 256, sg.n_class),
        use_pp=True, norm="layer", dropout=0.5,
        train_size=sg.n_train_global, spmm_chunk=2_097_152,
        dtype="bfloat16", spmm_impl="block", block_group=4,
        rem_dtype="float8")
    tcfg = TrainConfig(lr=0.01, n_epochs=200, enable_pipeline=True,
                       eval=False, fused_epochs=args.blk, seed=0)

    variants = [
        ("baseline", base, tcfg),
        ("dropout0", dataclasses.replace(base, dropout=0.0), tcfg),
        ("no-norm", dataclasses.replace(base, norm=None), tcfg),
        # combined leg: if its delta ~= dropout0 + no-norm deltas the
        # floor decomposes additively and the un-ablatable rest
        # (linears/loss/opt/assembly) is baseline - combined - dispatch
        ("dropout0-no-norm",
         dataclasses.replace(base, dropout=0.0, norm=None), tcfg),
        # fast-RNG lever: if this recovers most of the dropout0 delta,
        # --rng-impl rbg is a production win with dropout kept at 0.5
        ("rbg", base, dataclasses.replace(tcfg, rng_impl="rbg")),
        ("fused1", base, dataclasses.replace(tcfg, fused_epochs=1)),
    ]
    from pipegcn_tpu.obs.anatomy import time_config

    rec = {"backend": jax.default_backend()}
    base_s = None
    for name, cfg, tc in variants:
        blk = tc.fused_epochs
        s, setup, comp = time_config(sg, cfg, tc, args.reps, blk)
        rec[name] = round(s, 4)
        delta = "" if base_s is None else f" (delta {s - base_s:+.4f})"
        base_s = base_s if base_s is not None else s
        print(f"# {name}: {s:.4f} s/epoch{delta} "
              f"(setup {setup:.0f}s compile {comp:.0f}s)", flush=True)

    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(rec, f, indent=1)
    print(json.dumps(rec))


if __name__ == "__main__":
    main()
