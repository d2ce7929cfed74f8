#!/usr/bin/env python
"""Full-density, full-length convergence study (VERDICT round-3 item 3).

The reference's headline accuracy artifact is Reddit trained 3000
epochs to 97.10% test (reference README.md:91-99, train.py:377-400),
with PipeGCN's claim being that staleness-1 pipelining (and the
smoothing corrections) reach the same accuracy. Every prior study in
this repo ran at avg degree 6-16; Reddit's reality is ~492, where halo
ratios, staleness error and normalization statistics are qualitatively
different. This study runs THE comparison at full density:

  synthetic SBM graph at avg degree 492 (noise raised so the task has
  a real learning curve), P=4 partitions, 4x256 GraphSAGE + use_pp,
  3000 epochs; legs: vanilla | pipelined | pipelined+corrections.

P=4 runs on ONE device via TrainConfig.emulate_parts (vmap-with-
axis_name; bit-matches the real mesh — tests/test_trainer.py::
test_emulate_parts_matches_mesh), so a single TPU chip can carry it
at chip speed; --cpu is the same script as a slow dry run.

Resumable: per-leg checkpoints + a jsonl history under --state-dir;
--time-budget makes a run stop cleanly mid-leg so several chip calls
can be strung together. When every leg reaches --epochs, writes the
report with reference-format result lines.
"""

import argparse
import json
import os
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

LEGS = ("vanilla", "pipelined", "corrected")


def leg_tcfg(leg, args):
    from pipegcn_tpu.parallel import TrainConfig

    return TrainConfig(
        lr=args.lr, n_epochs=args.epochs, seed=0,
        enable_pipeline=leg != "vanilla",
        feat_corr=leg == "corrected", grad_corr=leg == "corrected",
        fused_epochs=args.fused, eval=False, emulate_parts=True,
    )


def run_leg(leg, sg, g, cfg, args, deadline):
    """Advance one leg toward args.epochs; returns (done, history)."""
    import jax

    from pipegcn_tpu.parallel import Trainer
    from pipegcn_tpu.utils.checkpoint import (
        checkpoint_exists, load_checkpoint, peek_epoch, save_checkpoint)

    sdir = os.path.join(args.state_dir, leg)
    hist_path = os.path.join(sdir, "history.jsonl")
    lhist_path = None
    if args.light_dir:
        os.makedirs(args.light_dir, exist_ok=True)
        lhist_path = os.path.join(args.light_dir,
                                  f"{leg}_history.jsonl")
    def write_rows(path, rows):
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w") as f:
            for r in rows:
                f.write(json.dumps(r) + "\n")

    history = []
    src = hist_path if os.path.exists(hist_path) else (
        lhist_path if lhist_path and os.path.exists(lhist_path)
        else None)
    if src:
        with open(src) as f:
            for l in f:
                if not l.strip():
                    continue
                try:
                    history.append(json.loads(l))
                except json.JSONDecodeError:
                    # the window queue SIGKILLs mid-append on timeout;
                    # a half-written trailing row must not wedge every
                    # later window — the checkpoint is the source of
                    # truth and rows >= start are truncated below
                    break

    # completed-leg fast path and exhausted-budget bail BEFORE Trainer
    # construction, which at full scale pays device upload + minutes of
    # kernel-table work per call. The LIGHT checkpoint (params+opt+norm
    # only, git-committable ~MBs) backs the full local one: gitignored
    # state did not survive the round-3->4 boundary, and losing hours
    # of full-scale training to a workspace wipe is not acceptable.
    light = os.path.join(args.light_dir, f"{leg}.npz") \
        if args.light_dir else None
    ck_epoch = peek_epoch(sdir)
    from_light = False
    if ck_epoch is None and light and os.path.exists(light):
        with np.load(light) as zz:
            ck_epoch = int(zz["__epoch__"])
        from_light = True
    start = (ck_epoch + 1) if ck_epoch is not None else 0
    if history and history[-1]["epoch"] >= start:
        history = [r for r in history if r["epoch"] < start]
        write_rows(hist_path, history)
        if lhist_path and os.path.exists(lhist_path):
            write_rows(lhist_path, history)
    if src == lhist_path and not os.path.exists(hist_path) and history:
        # re-seed the authoritative copy after a workspace wipe
        write_rows(hist_path, history)
    if lhist_path and src == hist_path and history:
        # seed/catch-up the survival mirror: --light-dir may be enabled
        # mid-study, and a gapped mirror would later become the
        # authoritative history after a wipe
        lrows = []
        if os.path.exists(lhist_path):
            with open(lhist_path) as f:
                lrows = [json.loads(l) for l in f if l.strip()]
        if len(lrows) < len(history):
            write_rows(lhist_path, history)
    if start >= args.epochs:
        return True, history
    if deadline and time.time() > deadline:
        return False, history

    # the CHECKPOINT is the source of truth for where to resume — a
    # kill between the history flush and the checkpoint save must not
    # wedge the study, so newer history rows are truncated instead
    t = Trainer(sg, cfg, leg_tcfg(leg, args))
    if checkpoint_exists(sdir):
        state, _ = load_checkpoint(sdir, t.state)
        t.state = state
    elif from_light:
        # params/opt/norm from the light checkpoint over a fresh
        # trainer: the staleness/EMA carries restart from zeros and
        # re-warm within ~an epoch (the staleness-exactness property).
        # The file stores replica 0 only (the psum'd update keeps every
        # part's copy identical); re-broadcast over the leading P axis
        import jax.numpy as jnp

        from pipegcn_tpu.utils.checkpoint import load_pytree

        subset = {k: t.state[k] for k in ("params", "opt", "norm")}
        tmpl0 = jax.tree_util.tree_map(lambda v: v[0], subset)
        r0 = load_pytree(light, tmpl0)
        restored = jax.tree_util.tree_map(
            lambda full, x: jnp.broadcast_to(x, full.shape)
            .astype(full.dtype), subset, r0)
        t.state = {**t.state, **restored}
        print(f"# [{leg}] light-resume at epoch {start} "
              "(staleness/EMA carries reset; re-warm ~1 epoch)",
              flush=True)
    print(f"# [{leg}] resuming at epoch {start}", flush=True)

    os.makedirs(sdir, exist_ok=True)
    hist_f = open(hist_path, "a")
    e = start
    while e < args.epochs:
        # an already-exhausted budget (e.g. the first full-scale window
        # spent it on the artifact build) must not commit to another
        # full eval_every chunk — the outer queue timeout would kill it
        # mid-chunk and lose the work since the last checkpoint
        if deadline and time.time() > deadline:
            print(f"# [{leg}] time budget reached at epoch {e}",
                  flush=True)
            hist_f.close()
            return False, history
        k = min(args.eval_every - (e % args.eval_every),
                args.epochs - e)
        # sub-chunk the dispatches: the deadline is re-checked per
        # sub-chunk so a run never commits to more than --fused epochs
        # past its budget — a caller's hard timeout kills the process,
        # and everything since the last checkpoint would be lost
        losses = None
        done_k = 0
        while done_k < k:
            kk = min(args.fused, k - done_k)
            losses = t.train_epochs(e + done_k, kk)
            done_k += kk
            if deadline and time.time() > deadline:
                break
        e += done_k
        rec = {"epoch": e - 1, "loss": round(float(losses[-1]), 5)}
        if e % args.eval_every == 0 or e == args.epochs:
            rec["val"] = round(t.evaluate(g, "val_mask"), 5)
            rec["test"] = round(t.evaluate(g, "test_mask"), 5)
        history.append(rec)
        hist_f.write(json.dumps(rec) + "\n")
        hist_f.flush()
        if lhist_path:
            with open(lhist_path, "a") as lf:
                lf.write(json.dumps(rec) + "\n")
        save_checkpoint(sdir, t.state, e - 1)
        if light:
            from pipegcn_tpu.utils.checkpoint import save_pytree

            os.makedirs(args.light_dir, exist_ok=True)
            # replica 0 only: every part's params/opt/norm copy is
            # identical by the psum'd update, so committing all P is
            # pure repo bloat
            save_pytree(
                light,
                jax.tree_util.tree_map(
                    lambda v: np.asarray(v[0]),
                    {k: t.state[k] for k in ("params", "opt", "norm")}),
                extra={"__epoch__": np.asarray(e - 1, np.int64)})
        # deadline-after-checkpoint: handled by the top-of-loop check
        # (e == args.epochs instead exits to the completion return)
    hist_f.close()
    print(f"# [{leg}] complete: {history[-1]}", flush=True)
    return True, history


def write_report(args, results, backend):
    lines = [
        "# Full-density convergence study "
        "(avg degree ~492, 3000 epochs)",
        "",
        f"Graph: {args.nodes} nodes / avg degree {args.degree} "
        f"(~{args.nodes * args.degree // 2} undirected edges), "
        f"{args.feat} features, {args.classes} classes, noise "
        f"{args.noise}, label noise {args.label_noise}, homophily "
        f"{args.homophily}. Model: "
        f"{args.layers}x{args.hidden} GraphSAGE + use_pp, bf16, "
        f"P={args.parts} (emulate_parts on {backend}). The reference's "
        "comparison "
        "(README.md:91-99) at the density its prior studies lacked.",
        "",
        "| leg | final loss | best val | test @ best val | "
        "final test |",
        "|---|---|---|---|---|",
    ]
    for leg in LEGS:
        h = results.get(leg)
        if not h:
            continue
        evals = [r for r in h if "val" in r]
        best = max(evals, key=lambda r: r["val"]) if evals else {}
        lines.append(
            f"| {leg} | {h[-1]['loss']:.4f} | "
            f"{best.get('val', float('nan')):.4f} | "
            f"{best.get('test', float('nan')):.4f} | "
            f"{evals[-1]['test'] if evals else float('nan'):.4f} |")
    # reference-format result lines (train.py:377-400 analogue)
    lines.append("")
    for leg in LEGS:
        h = results.get(leg)
        evals = [r for r in h if "val" in r] if h else []
        if evals:
            best = max(evals, key=lambda r: r["val"])
            lines.append(
                f"Final Test Result ({leg}) | Accuracy "
                f"{100 * best['test']:.2f}%")
    van = results.get("vanilla")
    pip = results.get("pipelined")
    if van and pip:
        bv = max((r for r in van if "val" in r),
                 key=lambda r: r["val"])["test"]
        bp = max((r for r in pip if "val" in r),
                 key=lambda r: r["val"])["test"]
        lines += [
            "",
            f"Pipelined - vanilla test delta: {100 * (bp - bv):+.2f} pp "
            "(reference reports parity within noise on Reddit, "
            "README.md:91-99).",
        ]
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        f.write("\n".join(lines) + "\n")
    print("\n".join(lines))


def graph_ident(args):
    """Every arg that shapes the generated graph or the build — cache
    and leg-state keys are only paths, so an edited config must be
    caught by comparing this, not silently trained across tasks."""
    return {k: getattr(args, k) for k in
            ("nodes", "degree", "feat", "classes", "noise",
             "label_noise", "homophily", "parts", "cluster_size")}


def check_task_identity(args):
    """Refuse to resume LEG state (checkpoints + history) recorded for
    a different task or training config — unlike the derived artifact
    cache (rebuilt in place on mismatch), thousands of trained epochs
    must never be silently mixed across tasks or auto-deleted. The
    stamp lives in BOTH --state-dir and --light-dir: after a workspace
    wipe only the light dir survives, and a light resume must be
    guarded just as strictly."""
    ident = {**graph_ident(args), "hidden": args.hidden,
             "layers": args.layers, "lr": args.lr}
    dirs = [args.state_dir] + ([args.light_dir] if args.light_dir
                               else [])
    for d in dirs:
        path = os.path.join(d, "task.json")
        if os.path.exists(path):
            with open(path) as f:
                prev = json.load(f)
            if prev != ident:
                raise RuntimeError(
                    f"{d} holds legs trained on {prev}, not the "
                    f"requested {ident}; point the study at a fresh "
                    "directory (or delete it) to start over")
        else:
            os.makedirs(d, exist_ok=True)
            with open(path, "w") as f:
                json.dump(ident, f)


def build_or_load_artifacts(args):
    """Generate (or load cached) full graph + ShardedGraph build.

    At 8k nodes the rebuild is seconds and caching is off by default;
    at full Reddit shape (232,965 nodes / ~114M directed edges) the
    SBM generation + partition + halo build is tens of host-minutes,
    so --cache-artifacts persists both (the ShardedGraph via its own
    artifact format, the eval graph as an npz) and per-window resumes
    only pay the disk read. ShardedGraph.load also re-arms the derived
    kernel-table disk cache (cache_dir), so block-table builds are
    paid once per cache too.
    """
    from pipegcn_tpu.graph import Graph, synthetic_graph
    from pipegcn_tpu.partition import ShardedGraph, partition_graph

    cache = os.path.join(args.state_dir, "artifacts") \
        if args.cache_artifacts else None
    gpath = os.path.join(cache, "eval_graph.npz") if cache else None
    ident = graph_ident(args)
    cfg_path = os.path.join(cache, "config.json") if cache else None
    if cache and ShardedGraph.exists(cache) and os.path.exists(gpath):
        t0 = time.time()
        cached_ident = None
        if os.path.exists(cfg_path):
            with open(cfg_path) as f:
                cached_ident = json.load(f)
        if cached_ident != ident:
            # derived cache for a different config: rebuild in place
            # (an unattended queue must not wedge on a config edit;
            # cross-task LEG state is guarded separately by task.json,
            # which refuses rather than deletes)
            import shutil

            print(f"# cached artifacts at {cache} were built for "
                  f"{cached_ident}, not {ident} — rebuilding",
                  flush=True)
            shutil.rmtree(cache)
            return build_or_load_artifacts(args)
        sg = ShardedGraph.load(cache)
        with np.load(gpath) as z:
            g = Graph(num_nodes=int(z["num_nodes"]), src=z["src"],
                      dst=z["dst"],
                      ndata={k[3:]: z[k] for k in z.files
                             if k.startswith("nd_")})
        print(f"# loaded cached artifacts ({time.time() - t0:.1f}s)",
              flush=True)
        return g, sg

    t0 = time.time()
    g = synthetic_graph(
        num_nodes=args.nodes, avg_degree=args.degree, n_feat=args.feat,
        n_class=args.classes, homophily=args.homophily,
        noise=args.noise, label_noise=args.label_noise,
        train_frac=0.66, val_frac=0.1, seed=0)
    parts = partition_graph(g, args.parts, seed=0)
    cluster = None
    if args.cluster_size:
        from pipegcn_tpu.partition import locality_clusters

        cluster = locality_clusters(g, target_size=args.cluster_size,
                                    seed=0)
    sg = ShardedGraph.build(g, parts, n_parts=args.parts,
                            cluster=cluster)
    print(f"# built artifacts ({time.time() - t0:.1f}s)", flush=True)
    if cache:
        # eval_graph.npz FIRST (atomically, tmp + rename), THEN
        # sg.save — whose manifest.json is written last and is the
        # existence guard. A kill anywhere in this sequence leaves
        # either no manifest (clean rebuild next window) or a fully
        # valid cache; never a truncated npz behind a valid manifest.
        os.makedirs(cache, exist_ok=True)
        tmp = gpath + ".tmp.npz"
        np.savez(tmp, num_nodes=np.int64(g.num_nodes), src=g.src,
                 dst=g.dst,
                 **{f"nd_{k}": v for k, v in g.ndata.items()})
        os.replace(tmp, gpath)
        with open(cfg_path, "w") as f:
            json.dump(ident, f)
        sg.save(cache)
        sg.cache_dir = cache
    return g, sg


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--nodes", type=int, default=8000)
    ap.add_argument("--degree", type=int, default=492)
    ap.add_argument("--feat", type=int, default=128)
    ap.add_argument("--classes", type=int, default=41)
    ap.add_argument("--hidden", type=int, default=256)
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--epochs", type=int, default=3000)
    ap.add_argument("--lr", type=float, default=0.01)
    ap.add_argument("--noise", type=float, default=4.0)
    ap.add_argument("--label-noise", type=float, default=0.0,
                    help="fraction of labels flipped to a random other "
                         "class (accuracy ceiling ~1-p; full-density "
                         "studies need it — degree-492 aggregation "
                         "saturates clean SBM tasks at 100%)")
    ap.add_argument("--homophily", type=float, default=0.7)
    ap.add_argument("--fused", type=int, default=25,
                    help="epochs per fused device dispatch (eval "
                         "intervals are sub-chunked to this, and the "
                         "--time-budget deadline is checked between "
                         "dispatches)")
    ap.add_argument("--eval-every", type=int, default=50)
    ap.add_argument("--time-budget", type=float, default=0,
                    help="seconds; stop cleanly (resumable) when hit")
    ap.add_argument("--parts", type=int, default=4,
                    help="partitions (emulated on one device); the "
                         "reference's Reddit headline uses 2 "
                         "(reference scripts/reddit.sh)")
    ap.add_argument("--cluster-size", type=int, default=0,
                    help="locality-cluster reorder target for the "
                         "block kernel (0 = none; full-scale runs "
                         "want the bench's 1024)")
    ap.add_argument("--cache-artifacts", action="store_true",
                    help="cache the graph + ShardedGraph build under "
                         "--state-dir so per-window resumes skip the "
                         "O(E) host rebuild (essential at full "
                         "Reddit scale)")
    ap.add_argument("--spmm-impl", default="xla",
                    help="aggregation kernel (bench.py surface); the "
                         "full-scale run needs 'auto' — the raw xla "
                         "gather path cannot hold [57M, 602] "
                         "activations on one chip")
    ap.add_argument("--spmm-chunk", type=int, default=0,
                    help="bound raw-path gathered messages to [chunk, "
                         "F] per pass (0 = unchunked; bench.py uses "
                         "2097152 at Reddit shape)")
    ap.add_argument("--block-group", type=int, default=1,
                    help="union-gather group size for the block "
                         "kernel's dense path")
    ap.add_argument("--cpu", action="store_true")
    ap.add_argument("--light-dir", default="",
                    help="git-TRACKED dir for compact per-leg "
                         "checkpoints (params+opt+norm, ~MBs) + "
                         "history mirrors; survives the workspace "
                         "wipe between driver rounds, unlike the "
                         "gitignored --state-dir. Resume from it "
                         "resets the staleness carries (~1-epoch "
                         "re-warm)")
    ap.add_argument("--state-dir",
                    default="results/convergence_state")
    ap.add_argument("--out",
                    default="results/convergence_fulldensity.md")
    args = ap.parse_args()

    import jax

    from pipegcn_tpu.backend import start_measurement

    start_measurement(cpu=args.cpu)

    from pipegcn_tpu.models import ModelConfig

    check_task_identity(args)
    deadline = time.time() + args.time_budget if args.time_budget else 0
    g, sg = build_or_load_artifacts(args)
    print(f"# graph: {g.num_nodes} nodes / {g.num_edges} directed "
          f"edges; halo {sg.halo_size} rows/device "
          f"({sg.halo_size / sg.n_max:.1%} of inner)", flush=True)
    cfg = ModelConfig(
        layer_sizes=(sg.n_feat,) + (args.hidden,) * (args.layers - 1)
        + (sg.n_class,),
        use_pp=True, norm="layer", dropout=0.5,
        train_size=sg.n_train_global, dtype="bfloat16",
        spmm_impl=args.spmm_impl,
        spmm_chunk=args.spmm_chunk or None,
        block_group=args.block_group)

    results = {}
    all_done = True
    for leg in LEGS:
        done, history = run_leg(leg, sg, g, cfg, args, deadline)
        results[leg] = history
        all_done = all_done and done
        if deadline and time.time() > deadline:
            break
    if all_done and all(results.get(l) for l in LEGS):
        write_report(args, results, jax.default_backend())
    else:
        print("# study incomplete — rerun to resume", flush=True)
        # nonzero exit so a caller reruns instead of marking it done
        sys.exit(2)


if __name__ == "__main__":
    main()
