"""Staleness accuracy-parity study (PipeGCN's central claim).

The paper's core claim is that epoch-stale boundary features/gradients do
not hurt final accuracy (reference README.md:97-98 reproduces Reddit
97.1% WITH pipelining). The round-1 synthetic configs saturated at 100%
in 10 epochs and could not discriminate; this study uses a deliberately
hard SBM graph (low homophily 0.45, 12 classes, 3% train labels, sparse
degree 5) whose accuracy plateaus around ~68%, and compares

    vanilla        — synchronous halo exchange every layer
    pipelined      — staleness-1 exchange (--enable-pipeline)
    pipelined+corr — staleness-1 + feat/grad EMA smoothing

over several seeds. Writes a markdown table to results/staleness_parity.md.

The study is RESUMABLE: each (variant, seed) unit trains in cheap
~--leg-epochs legs with a per-leg checkpoint under --state-dir, and the
markdown table is rewritten after every leg with whatever is complete so
far (incomplete units listed with their progress). A killed run — the
fate of every monolithic attempt at the degree-492 Reddit-shape config,
where one variant x seed is hours — resumes from its last leg instead of
from epoch 0. --time-budget bounds one invocation; repeated invocations
advance the same study.

Usage:
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python scripts/parity_study.py [--seeds 3] [--epochs 300] [--tpu]
"""

import argparse
import json
import os
import sys
import time

import numpy as np

# runnable as `python scripts/parity_study.py` from the repo root
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


VARIANTS = {
    "vanilla": dict(enable_pipeline=False),
    "pipelined": dict(enable_pipeline=True),
    "pipelined+corr": dict(enable_pipeline=True, feat_corr=True,
                           grad_corr=True),
}


def _unit_key(name: str, seed: int) -> str:
    return f"{name.replace('+', '-')}_s{seed}"


def _load_progress(state_dir: str, key: str) -> dict:
    path = os.path.join(state_dir, key, "progress.json")
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError):
        return {"epochs_done": 0, "best_val": -1.0, "test_acc": -1.0}


def _save_progress(state_dir: str, key: str, prog: dict) -> None:
    d = os.path.join(state_dir, key)
    os.makedirs(d, exist_ok=True)
    tmp = os.path.join(d, "progress.json.tmp")
    with open(tmp, "w") as f:
        json.dump(prog, f)
    os.replace(tmp, os.path.join(d, "progress.json"))  # atomic: a
    # mid-write kill must not corrupt the resume point


def write_table(args, progress: dict) -> None:
    """Rewrite the markdown output from CURRENT state: aggregated
    mean +/- std over completed (variant, seed) units, plus a progress
    row per incomplete unit — a killed run still leaves a readable
    partial-results table behind."""
    lines = [
        f"# Staleness accuracy parity (hard synthetic, {args.model})",
        "",
        f"SBM graph: {args.nodes} nodes, avg degree {args.degree}, "
        f"{args.feat} feats, {args.classes} classes, homophily "
        f"{args.homophily}, {args.train_frac:.0%} train labels;",
        f"{args.model} 3x{args.hidden}, dropout 0.3, lr 3e-3, "
        f"{args.epochs} epochs, {args.parts} partitions, "
        f"{args.seeds} seeds; spmm_impl={args.spmm_impl}, "
        f"rem_dtype={args.rem_dtype}.",
        "",
        "| variant | best val (mean ± std) | test @ best val (mean ± std) |",
        "|---|---|---|",
    ]
    summary = {}
    pending = []
    for name in VARIANTS:
        done, part = [], []
        for seed in range(1, args.seeds + 1):
            p = progress[_unit_key(name, seed)]
            if p["epochs_done"] >= args.epochs:
                done.append((p["best_val"], p["test_acc"]))
            else:
                part.append((seed, p))
        if done:
            bv = np.array([r[0] for r in done])
            ts = np.array([r[1] for r in done])
            summary[name] = (bv.mean(), ts.mean(),
                             ts.std(), len(done))
            tag = "" if not part else \
                f" ({len(done)}/{args.seeds} seeds)"
            lines.append(
                f"| {name}{tag} | {bv.mean():.4f} ± {bv.std():.4f} "
                f"| {ts.mean():.4f} ± {ts.std():.4f} |")
        for seed, p in part:
            cur = (f", best val {p['best_val']:.4f} so far"
                   if p["best_val"] >= 0 else "")
            pending.append(f"- {name} seed {seed}: "
                           f"{p['epochs_done']}/{args.epochs} "
                           f"epochs{cur}")
    if pending:
        lines += ["", "Incomplete units (resumes from the last "
                      f"~{args.leg_epochs}-epoch leg checkpoint in "
                      f"`{args.state_dir}`):"] + pending
    if len(summary) == len(VARIANTS) and not pending:
        spread = max(s[1] for s in summary.values()) - \
            min(s[1] for s in summary.values())
        noise = max(max(s[2] for s in summary.values()), 1e-4)
        if spread <= 2 * noise:
            verdict = (
                "staleness-1 pipelining (with or without EMA "
                "correction) tracks the synchronous baseline within "
                "seed noise, the analogue of the reference's Reddit "
                "97.1%-with-pipelining reproduction (README.md:97-98).")
        else:
            verdict = (
                f"on this config ({args.train_frac:.0%} labels, "
                f"homophily {args.homophily}) staleness costs "
                f"~{spread:.3f} accuracy beyond seed noise (max std "
                f"{noise:.3f}) for this model family; the EMA "
                f"corrections recover part of it.")
        lines += [
            "",
            f"Max mean-test-accuracy spread across variants: "
            f"{spread:.4f} — " + verdict,
        ]
    elif summary:
        lines += ["", "Study in progress — verdict withheld until "
                      "every variant x seed completes."]
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    tmp = args.out + ".tmp"
    with open(tmp, "w") as f:
        f.write("\n".join(lines) + "\n")
    os.replace(tmp, args.out)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", type=int, default=3)
    ap.add_argument("--epochs", type=int, default=300)
    ap.add_argument("--parts", type=int, default=8)
    ap.add_argument("--model", default="graphsage",
                    choices=["graphsage", "gcn", "gat"],
                    help="model family to study (the staleness claim "
                         "should hold for all of them)")
    ap.add_argument("--out", default="")
    ap.add_argument("--tpu", action="store_true",
                    help="run on the default (TPU) backend instead of CPU")
    # graph shape overrides: the default is the small hard-SBM config;
    # --nodes 232965 runs the Reddit-node-count long-horizon analogue
    # of the reference's 97.1%-with-pipelining reproduction
    ap.add_argument("--nodes", type=int, default=6000)
    ap.add_argument("--degree", type=int, default=5)
    ap.add_argument("--feat", type=int, default=6)
    ap.add_argument("--classes", type=int, default=12)
    ap.add_argument("--homophily", type=float, default=0.45)
    ap.add_argument("--train-frac", type=float, default=0.03)
    ap.add_argument("--hidden", type=int, default=64)
    ap.add_argument("--fused", type=int, default=25)
    ap.add_argument("--name", default="",
                    help="output suffix, e.g. 'reddit_scale'")
    ap.add_argument("--spmm-impl", default="xla",
                    choices=["xla", "bucket", "block", "auto"])
    ap.add_argument("--rem-dtype", default="none",
                    choices=["none", "bfloat16", "float8"],
                    help="gather-transport narrowing under study "
                         "(ModelConfig.rem_dtype)")
    ap.add_argument("--leg-epochs", type=int, default=150,
                    help="epochs per resumable leg: each leg ends in a "
                         "checkpoint + a rewritten partial table, so a "
                         "killed run loses at most one leg")
    ap.add_argument("--state-dir", default="",
                    help="leg checkpoints + progress files (default "
                         "results/parity_state<suffix>)")
    ap.add_argument("--time-budget", type=float, default=0.0,
                    help="seconds: stop cleanly (table written, resume "
                         "hint printed) before starting a leg past this "
                         "budget; 0 = run to completion")
    args = ap.parse_args()
    suffix = "" if args.model == "graphsage" else f"_{args.model}"
    if args.name:
        suffix += f"_{args.name}"
    if not args.out:
        args.out = f"results/staleness_parity{suffix}.md"
    if not args.state_dir:
        args.state_dir = f"results/parity_state{suffix}"

    from pipegcn_tpu.backend import start_measurement

    # a CPU-mesh study by default; --tpu requires the chip
    start_measurement(cpu=not args.tpu)

    from pipegcn_tpu.graph import synthetic_graph
    from pipegcn_tpu.models import ModelConfig
    from pipegcn_tpu.parallel import Trainer, TrainConfig
    from pipegcn_tpu.partition import ShardedGraph, partition_graph
    from pipegcn_tpu.utils.checkpoint import (checkpoint_exists,
                                              load_checkpoint)

    g = synthetic_graph(num_nodes=args.nodes, avg_degree=args.degree,
                        n_feat=args.feat, n_class=args.classes,
                        homophily=args.homophily,
                        train_frac=args.train_frac, val_frac=0.2,
                        seed=0)
    parts = partition_graph(g, args.parts, seed=0)
    sg = ShardedGraph.build(g, parts, n_parts=args.parts)
    eval_graphs = {"val": (g, "val_mask"), "test": (g, "test_mask")}

    progress = {_unit_key(n, s): _load_progress(args.state_dir,
                                                _unit_key(n, s))
                for n in VARIANTS for s in range(1, args.seeds + 1)}
    t_start = time.time()
    leg = max(1, args.leg_epochs)

    for name, kw in VARIANTS.items():
        for seed in range(1, args.seeds + 1):
            key = _unit_key(name, seed)
            prog = progress[key]
            ckpt_dir = os.path.join(args.state_dir, key, "ckpt")
            while prog["epochs_done"] < args.epochs:
                if args.time_budget and \
                        time.time() - t_start > args.time_budget:
                    write_table(args, progress)
                    print(f"# time budget exhausted at {key} "
                          f"({prog['epochs_done']}/{args.epochs}); "
                          f"re-run to resume from {args.state_dir}",
                          file=sys.stderr)
                    return
                end = min(prog["epochs_done"] + leg, args.epochs)
                cfg = ModelConfig(
                    layer_sizes=(sg.n_feat, args.hidden, args.hidden,
                                 sg.n_class), norm="layer",
                    dropout=0.3, train_size=sg.n_train_global,
                    model=args.model, spmm_impl=args.spmm_impl,
                    rem_dtype=args.rem_dtype,
                )
                tcfg = TrainConfig(seed=seed, lr=3e-3, n_epochs=end,
                                   log_every=25,
                                   fused_epochs=min(args.fused, leg),
                                   **kw)
                t = Trainer(sg, cfg, tcfg)
                start_epoch = 0
                if prog["epochs_done"] > 0 and \
                        checkpoint_exists(ckpt_dir):
                    host_state, start_epoch = load_checkpoint(
                        ckpt_dir, t.host_state())
                    t.restore_state(host_state)
                res = t.fit(eval_graphs, log_fn=lambda *_: None,
                            sharded_eval=True,
                            start_epoch=start_epoch,
                            checkpoint_dir=ckpt_dir,
                            checkpoint_every=leg)
                # the leg's best merges into the unit's running best:
                # each fit() tracks only its own window
                if res["best_val"] > prog["best_val"]:
                    prog["best_val"] = float(res["best_val"])
                    prog["test_acc"] = float(res["test_acc"])
                prog["epochs_done"] = end
                _save_progress(args.state_dir, key, prog)
                write_table(args, progress)
                print(f"{name} seed={seed}: epoch {end}/{args.epochs}, "
                      f"best_val={prog['best_val']:.4f} "
                      f"test={prog['test_acc']:.4f}", file=sys.stderr)

    write_table(args, progress)
    with open(args.out) as f:
        print(f.read())


if __name__ == "__main__":
    main()
