"""Comm/compute overlap evidence (VERDICT round-1 item 3).

PipeGCN's reason to exist is hiding halo-exchange latency behind compute
(reference feature_buffer.py:153-163; README.md:93-94 reports exposed
comm ~5.9% of epoch on 2 GPUs). In this framework the pipelined step
carries last epoch's halo blocks in the step state, so the current
epoch's ppermutes have no consumer inside the step and XLA is free to
schedule them behind the GEMMs/aggregations.

This study quantifies that on an N-device mesh (virtual CPU devices by
default — the one real TPU chip cannot run a >1-device mesh, so the
multi-device scheduling evidence comes from the CPU backend; the
single-chip pipelined-vs-vanilla delta at Reddit scale is reported by
bench.py separately):

  vanilla epoch      — halo exchange is a data dependency of every layer
  pipelined epoch    — same collectives, dependency broken by staleness
  collectives alone  — Trainer.measure_comm's standalone cost

If the pipelined epoch time is ~= vanilla - collective cost, the
exchange is being hidden; if it's ~= vanilla, XLA serialized it.
Writes results/overlap_study.md.

Usage:
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python scripts/overlap_study.py [--parts 8] [--tpu]
"""

import argparse
import contextlib
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _collective_matmul_deps(hlo: str):
    """Count collective-permutes in the optimized HLO whose results are
    (transitively) consumed by a dot — i.e. whose latency sits on the
    critical path into compute. Pipelined programs should have ZERO:
    their fresh exchanges flow only into the output carry, so any
    scheduler may hide them behind the epoch's compute; vanilla
    programs' exchanges all feed the layer matmuls.

    Works on the def-use structure (backend-independent), fusion bodies
    included via a contains-dot check per called computation."""
    import re

    comp_has_dot = {}
    name = None
    for line in hlo.splitlines():
        m = re.match(r"\s*(?:ENTRY )?%?([\w.\-]+)\s*\([^)]*\)\s*->", line)
        if m:
            name = m.group(1)
            comp_has_dot.setdefault(name, False)
        if name and re.search(r"\bdot\(", line):
            comp_has_dot[name] = True

    instr = {}       # name -> (op, [operand names], line)
    users = {}       # name -> [user names]
    for line in hlo.splitlines():
        # result type is either a plain shape or a tuple type with
        # spaces — async ops like collective-permute-start return
        # '(bf16[..], bf16[..])', which a bare \S+ would fail to span
        m = re.match(
            r"\s*(?:ROOT )?%?([\w.\-]+) = (?:\([^=]*?\)|\S+) "
            r"([\w\-]+)\((.*)", line)
        if not m:
            continue
        nm, op, rest = m.groups()
        operands = re.findall(r"%([\w.\-]+)", rest)
        instr[nm] = (op, operands, line)
        for o in operands:
            users.setdefault(o, []).append(nm)

    n_coll, n_feeding = 0, 0
    for nm, (op, _, line) in instr.items():
        if not op.startswith("collective-permute"):
            continue
        if op == "collective-permute-done":
            continue  # counted via its start
        n_coll += 1
        # BFS through users; fusions count as dots if their body has one
        seen, stack, feeds = set(), [nm], False
        while stack and not feeds:
            cur = stack.pop()
            for u in users.get(cur, []):
                if u in seen:
                    continue
                seen.add(u)
                uop, _, uline = instr[u]
                if uop == "dot":
                    feeds = True
                    break
                if uop == "fusion":
                    cm = re.search(r"calls=%?([\w.\-]+)", uline)
                    if cm and comp_has_dot.get(cm.group(1)):
                        feeds = True
                        break
                stack.append(u)
        n_feeding += int(feeds)
    return n_coll, n_feeding


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--parts", type=int, default=8)
    ap.add_argument("--nodes", type=int, default=60_000)
    ap.add_argument("--degree", type=int, default=30)
    ap.add_argument("--hidden", type=int, default=256)
    ap.add_argument("--epochs", type=int, default=24)
    ap.add_argument("--out", default="results/overlap_study.md")
    ap.add_argument("--tpu", action="store_true")
    args = ap.parse_args()

    import jax

    from pipegcn_tpu.backend import start_measurement

    start_measurement(cpu=not args.tpu)

    from pipegcn_tpu.graph import synthetic_graph
    from pipegcn_tpu.models import ModelConfig
    from pipegcn_tpu.parallel import Trainer, TrainConfig
    from pipegcn_tpu.parallel.halo import identity_collectives
    from pipegcn_tpu.partition import ShardedGraph, partition_graph

    g = synthetic_graph(num_nodes=args.nodes, avg_degree=args.degree,
                        n_feat=64, n_class=16, homophily=0.5, seed=0)
    parts = partition_graph(g, args.parts, seed=0)
    sg = ShardedGraph.build(g, parts, n_parts=args.parts)
    halo_frac = sg.halo_size / max(sg.n_max, 1)
    print(f"# {args.parts} parts, n_max {sg.n_max}, halo {sg.halo_size} "
          f"({halo_frac:.1%} of inner)", file=sys.stderr)

    cfg = ModelConfig(
        layer_sizes=(sg.n_feat, args.hidden, args.hidden, sg.n_class),
        use_pp=False, norm="layer", dropout=0.3,
        train_size=sg.n_train_global, dtype="bfloat16",
    )

    def run(pipeline: bool, identity: bool = False):
        guard = identity_collectives() if identity \
            else contextlib.nullcontext()
        with guard:
            return _run_timed(pipeline, identity)

    def _run_timed(pipeline: bool, identity: bool = False):
        t = Trainer(sg, cfg, TrainConfig(
            lr=1e-2, n_epochs=args.epochs, enable_pipeline=pipeline,
            seed=0, eval=False))
        base = t._epoch_rng_base()
        rng0 = jax.random.fold_in(base, 0)
        # one AOT compile serves both the HLO inspection and the timed
        # epochs (calling through t.train_epoch would compile a second
        # time via the jit cache)
        import jax.numpy as jnp

        scale = jnp.float32(t.loss_scaler.scale)
        compiled = t._step.lower(t.state, t.data, rng0,
                                 scale).compile()
        hlo = compiled.as_text()
        state = t.state
        state, _ = compiled(state, t.data, rng0, scale)
        jax.block_until_ready(state["params"])
        times = []
        for e in range(1, args.epochs):
            rng = jax.random.fold_in(base, e)
            t0 = time.perf_counter()
            state, _ = compiled(state, t.data, rng, scale)
            jax.block_until_ready(state["params"])
            times.append(time.perf_counter() - t0)
        t.state = state
        # identity legs would time elided no-op collectives — skip
        comm = t.measure_comm() if pipeline and not identity else None
        return float(np.median(times)), comm, hlo

    pipe_s, comm, pipe_hlo = run(True)
    van_s, _, van_hlo = run(False)
    # exposed-wait legs: the SAME programs traced with the ring
    # ppermutes replaced by identity (shapes intact) — the timing
    # delta is the comm wait the scheduler failed to hide, i.e. the
    # reference's per-epoch Comm(s) semantics (train.py:366-371)
    pipe_id_s, _, _ = run(True, identity=True)
    van_id_s, _, _ = run(False, identity=True)
    exposed_pipe = max(0.0, pipe_s - pipe_id_s)
    exposed_van = max(0.0, van_s - van_id_s)
    overlap_pct = (100.0 * (1.0 - exposed_pipe / exposed_van)
                   if exposed_van > 0 else float("nan"))
    pipe_dep = _collective_matmul_deps(pipe_hlo)
    van_dep = _collective_matmul_deps(van_hlo)
    coll_s = comm["comm"] + comm["bgrad"]  # fwd ring + cotangent ring

    backend = jax.default_backend()
    lines = [
        "# Comm/compute overlap study",
        "",
        f"{args.parts}-device mesh ({backend}), "
        f"{args.nodes} nodes / avg degree {args.degree}, "
        f"3-layer x {args.hidden} GraphSAGE bf16, halo "
        f"{halo_frac:.1%} of inner rows, median over {args.epochs - 1} "
        "epochs.",
        "",
        "## Structural evidence (optimized HLO def-use)",
        "",
        "Whether a scheduler CAN hide an exchange is a property of the",
        "program's dependency structure: a collective whose result feeds",
        "a matmul is on the critical path; one that only feeds the",
        "next-epoch staleness carry can be scheduled entirely behind the",
        "epoch's compute (the functional analogue of the reference's",
        "thread-based async transfer, feature_buffer.py:153-163).",
        "",
        "| program | collective-permutes | feeding a dot (blocking) |",
        "|---|---|---|",
        f"| vanilla | {van_dep[0]} | {van_dep[1]} |",
        f"| pipelined | {pipe_dep[0]} | {pipe_dep[1]} |",
        "",
        ("All pipelined exchanges are OFF the critical path (zero dot "
         "consumers): XLA's latency-hiding scheduler is free to run "
         "them behind compute on TPU, so the design target is the "
         "reference's <6% exposed comm (README.md:93-94) with headroom "
         "to 0%."
         if pipe_dep[0] > 0 and pipe_dep[1] == 0 and van_dep[1] > 0 else
         "WARNING: measured dependency counts do NOT show the expected "
         "pattern (vanilla blocking > 0, pipelined blocking == 0) — "
         "either the dataflow regressed or the HLO parser missed ops; "
         "investigate before citing this study."),
        "",
        "## Wall-clock on the virtual CPU mesh (context only)",
        "",
        "| measurement | s/epoch |",
        "|---|---|",
        f"| vanilla (synchronous halo) | {van_s:.4f} |",
        f"| pipelined (staleness-1) | {pipe_s:.4f} |",
        f"| halo collectives alone | {coll_s:.4f} |",
        f"| vanilla, permutes->identity | {van_id_s:.4f} |",
        f"| pipelined, permutes->identity | {pipe_id_s:.4f} |",
        "",
        "## Exposed wait (timing-derived, reference Comm(s) semantics)",
        "",
        "Re-tracing each program with the ring ppermutes replaced by",
        "identity (same shapes, zero traffic) and differencing the",
        "epoch times yields the comm wait each schedule actually",
        "EXPOSES — the reference's per-epoch Comm(s)",
        "(helper/timer/comm_timer.py, train.py:366-371) — rather than",
        "the standalone collective cost measure_comm reports:",
        "",
        "| program | exposed comm s/epoch | % of epoch |",
        "|---|---|---|",
        f"| vanilla | {exposed_van:.4f} | "
        f"{100.0 * exposed_van / van_s:.1f}% |",
        f"| pipelined | {exposed_pipe:.4f} | "
        f"{100.0 * exposed_pipe / pipe_s:.1f}% |",
        "",
        f"**Overlap: {overlap_pct:.1f}%** of the vanilla exposed wait "
        "is hidden by the pipelined schedule (reference reports ~94% "
        "hidden, i.e. 5.9% exposed, on 2 GPUs — README.md:93-94). "
        "CPU-mesh caveat: collectives here are intra-process copies, "
        "so both exposures are small and noisy; the same two identity "
        "legs run unchanged on a real multi-chip mesh (--tpu), where "
        "this becomes the headline overlap metric.",
        "",
        f"On XLA:CPU the collectives are intra-process copies "
        f"({100.0 * coll_s / van_s:.1f}% of the vanilla epoch), far "
        "below the staleness carry's own bookkeeping cost, so CPU",
        "wall-clock cannot demonstrate the hiding — the structural",
        "table above is the meaningful evidence. The real TPU",
        "environment has ONE chip (P=1 has no collectives to hide);",
        "bench.py reports the single-chip pipelined-vs-vanilla delta",
        "at Reddit scale separately.",
    ]
    out_dir = os.path.dirname(args.out)
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
    with open(args.out, "w") as f:
        f.write("\n".join(lines) + "\n")
    print("\n".join(lines))


if __name__ == "__main__":
    main()
