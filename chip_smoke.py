#!/usr/bin/env python3
"""chip_smoke.py: the quickest proof that the trainer's main path still
starts, trains and computes the right thing on the chip.

One process, no children. Drives `pipegcn_tpu.cli.main.run(args)` with
args from the CLI parser, exactly as `python main.py ...` would, on the
repository's headline configuration (scripts/reddit_tpu.sh on the
synthetic Reddit shape): GraphSAGE 4 layers x 256 hidden, 602 features,
41 classes, average degree 492, bfloat16, --enable-pipeline --use-pp
--spmm-impl auto --local-reorder cluster --fused-epochs 4, at the full
shape (232,965 nodes, ~114.6M directed edges). A cold run of it took
698 s on a one-chip v5e machine (PR 21), 370 s of that single-threaded
host work before the device does anything; the limit is 1200 s.
`--nodes N` cuts the node count, and only the node count, for a
cheaper call (half the nodes: 495 s cold). Evaluation is off
(--no-eval): the unsharded evaluator cannot hold this graph and the
sharded one would double the set-up.

It refuses to run unless JAX finds a TPU, and nothing in it falls back:
a kernel downgrade, a tuner candidate that fails to compile, a fault
record, a missing native library, a loss that does not fall or a kernel
that disagrees with the float32 reference each fail the run. Timings it
prints are smoke timings (one sample, set-up included), not benchmark
numbers.

With four or more devices it runs the same configuration again at
--n-partitions 4, pipelined and then vanilla, and checks placement
(`--parts 4` runs only those legs: a four-chip call costs four times
the budget, and all three legs at the full shape take about 25 minutes).

Outputs (logs and the metrics JSONL only) go to
chiprun_out/chip_smoke/run-<UTC time>-<N>chip/ beside this file;
partition artifacts
and kernel tables stay under partitions/chip_smoke/ (gitignored).
The last line of stdout is one JSON object:
  {"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import statistics
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))

REDDIT_NODES = 232_965
DEGREE, N_FEAT, N_CLASS = 492, 602, 41
HIDDEN, N_LAYERS = 256, 4
# main.py cuts fused blocks at every 10th epoch (the reference's log
# cadence), so 20 epochs dispatch as 4,4,2,4,4,2: two scan lengths
# compile, and the two later blocks of 4 are the steady samples
FUSED, N_EPOCHS = 4, 20

# the repo's own fp8-transport bounds (tests/test_bucket_spmm.py):
# median relative error of the aggregation against the f32 reference
REF_FWD_MEDIAN_REL = 0.03
REF_BWD_MEDIAN_REL = 0.10


class SmokeFailure(AssertionError):
    """A smoke check did not hold."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


# ---------------------------------------------------------------- args


def smoke_args(out_dir: str, part_dir: str, *, n_parts: int, nodes: int,
               pipeline: bool = True, degree: int = DEGREE,
               n_feat: int = N_FEAT, n_class: int = N_CLASS,
               hidden: int = HIDDEN,
               n_layers: int = N_LAYERS, n_epochs: int = N_EPOCHS,
               fused: int = FUSED):
    """The parsed CLI namespace of one smoke leg: scripts/reddit_tpu.sh
    on a synthetic graph, evaluation off, telemetry on."""
    from pipegcn_tpu.cli.parser import create_parser

    tag = f"p{n_parts}-{'pipelined' if pipeline else 'vanilla'}"
    argv = [
        "--dataset", f"synthetic:{nodes}:{degree}:{n_feat}:{n_class}",
        "--dropout", "0.5", "--lr", "0.01",
        "--n-partitions", str(n_parts),
        "--n-epochs", str(n_epochs),
        "--model", "graphsage",
        "--n-layers", str(n_layers), "--n-hidden", str(hidden),
        "--log-every", "10",
        "--use-pp", "--dtype", "bfloat16",
        "--spmm-impl", "auto", "--local-reorder", "cluster",
        "--fused-epochs", str(fused),
        "--no-eval", "--fix-seed", "--seed", "0",
        # reuse an artifact a previous run of this script left here
        # (a fresh machine has none and builds it)
        "--skip-partition",
        "--partition-dir", part_dir,
        "--results-dir", out_dir,
        "--metrics-out", os.path.join(out_dir, f"metrics-{tag}.jsonl"),
    ]
    if pipeline:
        argv.append("--enable-pipeline")
    return create_parser().parse_args(argv)


# --------------------------------------------------------------- checks


def check_stream(metrics_path: str, trainer) -> dict:
    """Read the metrics stream back and hold the run to it: finite
    falling loss, no fallback and no fault record, a tuning record whose
    every candidate ran and whose winner is the kernel that dispatched."""
    from pipegcn_tpu.obs.metrics import read_metrics

    recs = list(read_metrics(metrics_path))
    epochs = [r for r in recs if r.get("event") == "epoch"]
    losses = [float(r["loss"]) for r in epochs]
    check(len(losses) >= 2, f"{metrics_path}: {len(losses)} epoch records")
    check(bool(np.isfinite(losses).all()),
          f"non-finite loss in the stream: {losses}")
    check(losses[-1] < losses[0],
          f"loss did not fall: first {losses[0]:.4f}, last "
          f"{losses[-1]:.4f}")
    fallbacks = [r for r in recs if r.get("event") == "fallback"]
    check(not fallbacks and not trainer.fallbacks,
          f"kernel downgraded: {fallbacks or trainer.fallbacks}")
    faults = [r for r in recs if r.get("event") == "fault"]
    check(not faults, f"fault records in the stream: {faults}")
    tunings = [r for r in recs if r.get("event") == "tuning"]
    check(len(tunings) == 1, f"{len(tunings)} tuning records, want 1")
    tuning = tunings[0]
    errors = [(c["name"], c["error"]) for c in tuning["costs"]
              if c.get("error")]
    check(not errors,
          f"tuner candidates that did not compile or run: {errors}")
    check(tuning["source"] in ("live", "artifact"),
          f"tuner fell back to its default: source={tuning['source']}")
    ran = trainer._current_impl()
    check(ran == tuning["winner"]["impl"],
          f"tuner chose {tuning['winner']['impl']} but {ran} dispatched")
    # (length, per-epoch step time) of each dispatched block, in order:
    # the epochs of one block share one step_time_s
    blocks = []
    for r in epochs:
        if blocks and blocks[-1][1] == r["step_time_s"]:
            blocks[-1][0] += 1
        else:
            blocks.append([1, r["step_time_s"]])
    return {"losses": losses, "tuning": tuning, "kernel": ran,
            "blocks": blocks}


def check_placement(trainer, n_parts: int, require_memory_stats: bool,
                    log=print) -> dict:
    """P > 1: every sharded array has one [1, ...] shard on each of
    n_parts distinct devices, device memory is spread rather than piled
    on device 0, and the halo exchange moves bytes. Facts are logged
    before they are judged, so a failed check still leaves them."""
    mesh_devs = list(trainer.mesh.devices.flat)
    # the halo ring is mesh order (parallel/mesh.py: jax.devices()[:P]):
    # say what each hop is on the physical interconnect
    ring = []
    for i, d in enumerate(mesh_devs):
        nxt = mesh_devs[(i + 1) % n_parts]
        a, b = getattr(d, "coords", None), getattr(nxt, "coords", None)
        hops = (sum(abs(x - y) for x, y in zip(a, b))
                if a is not None and b is not None else None)
        ring.append({"from": str(d), "to": str(nxt), "coords": [a, b],
                     "manhattan": hops})
    stats = [d.memory_stats() for d in mesh_devs]
    in_use = [int(s["bytes_in_use"]) for s in stats] if all(stats) \
        else None
    ici = int(trainer.est_ici_bytes_per_epoch())
    log(f"  placement: mesh {[str(d) for d in mesh_devs]}")
    for h in ring:
        log(f"    ring hop {h['from']} -> {h['to']}: coords "
            f"{h['coords'][0]} -> {h['coords'][1]}, manhattan "
            f"{h['manhattan']}")
    log(f"    bytes_in_use per device {in_use}; est ICI bytes/epoch "
        f"{ici}")

    sharded = dict(trainer.data)
    sharded.update({f"comm/{g}/{k}": v
                    for g, sub in (trainer.state["comm"] or {}).items()
                    for k, v in sub.items()})
    for name, arr in sharded.items():
        devs = {s.device for s in arr.addressable_shards}
        check(len(devs) == n_parts,
              f"{name} lives on {len(devs)} device(s), want {n_parts}")
        check(all(s.data.shape[0] == 1 for s in arr.addressable_shards),
              f"{name} is not split along the parts axis")
    check(len(set(mesh_devs)) == n_parts, f"mesh devices {mesh_devs}")
    check(ici > 0, f"est_ici_bytes_per_epoch() = {ici}")
    if in_use is not None:
        check(max(in_use) <= 3 * min(in_use),
              f"bytes_in_use piled up: {in_use}")
    else:
        check(not require_memory_stats,
              "the backend reports no memory_stats()")
    return {"devices": [str(d) for d in mesh_devs], "ring": ring,
            "ici_bytes_per_epoch": ici, "bytes_in_use": in_use}


def check_reference(winner: dict, nodes: int = 8_000, degree: int = 200,
                    width: int = HIDDEN) -> dict:
    """The kernel configuration the tuner chose, on a small graph,
    against the float32 raw-edge reference (ops/spmm.py spmm_mean):
    forward and gradient within the repo's own transport bounds."""
    import jax
    import jax.numpy as jnp

    from pipegcn_tpu.graph import synthetic_graph
    from pipegcn_tpu.models import ModelConfig
    from pipegcn_tpu.ops.spmm import spmm_mean
    from pipegcn_tpu.parallel import TrainConfig, Trainer
    from pipegcn_tpu.partition import (ShardedGraph, locality_clusters,
                                       partition_graph)

    if winner["impl"] == "xla":
        return {"skipped": "the winner IS the raw-edge reference path"}
    g = synthetic_graph(num_nodes=nodes, avg_degree=degree, n_feat=8,
                        n_class=4, seed=1)
    sg = ShardedGraph.build(g, partition_graph(g, 1, seed=0), n_parts=1,
                            cluster=locality_clusters(g, seed=0))
    cfg = ModelConfig(
        layer_sizes=(8, width, 4), norm="layer", dropout=0.0,
        train_size=sg.n_train_global, dtype="bfloat16",
        spmm_impl=winner["impl"],
        block_group=int(winner.get("block_group") or 1),
        rem_dtype=winner.get("rem_dtype"),
        rem_amax=bool(winner.get("rem_amax")))
    tr = Trainer(sg, cfg, TrainConfig(n_epochs=0, eval=False))
    check(tr._current_impl() == winner["impl"],
          f"reference trainer built {tr._current_impl()}")
    d = {k: v[0] for k, v in tr.data.items()}
    n_max = sg.n_max
    es = jnp.asarray(sg.edge_src[0].astype(np.int32))
    ed = jnp.asarray(sg.edge_dst[0].astype(np.int32))
    fbuf = jnp.asarray(np.random.default_rng(0).standard_normal(
        (n_max + sg.halo_size, width)).astype(np.float32))

    def kernel(d, f):
        # tables ride as arguments: the closure is rebuilt under trace
        return tr.make_device_spmm_closure(d)(
            f.astype(jnp.bfloat16)).astype(jnp.float32)

    def reference(f):
        return spmm_mean(f, es, ed, d["in_deg"], n_max, None, True)

    def rel(a, b):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        return float(np.median(np.abs(a - b) / (np.abs(b) + 1e-3)))

    fwd = rel(jax.jit(kernel)(d, fbuf), jax.jit(reference)(fbuf))
    bwd = rel(
        jax.jit(jax.grad(lambda f, d: (kernel(d, f) ** 2).sum()))(fbuf, d),
        jax.jit(jax.grad(lambda f: (reference(f) ** 2).sum()))(fbuf))
    out = {"kernel": winner["name"], "nodes": nodes,
           "edges": int(sg.edge_count[0]),
           "dense_tiles": ((tr.tables_pad or {}).get("fwd") or {}).get(
               "dense_blocks", 0),
           "fwd_median_rel_err": round(fwd, 5),
           "bwd_median_rel_err": round(bwd, 5)}
    check(np.isfinite(fwd) and fwd < REF_FWD_MEDIAN_REL,
          f"forward disagrees with the f32 reference: {out}")
    check(np.isfinite(bwd) and bwd < REF_BWD_MEDIAN_REL,
          f"gradient disagrees with the f32 reference: {out}")
    return out


# --------------------------------------------------------------- phases


def device_memory() -> list:
    import jax

    out = []
    for d in jax.local_devices():
        s = d.memory_stats() or {}
        out.append({"device": str(d),
                    "bytes_in_use": s.get("bytes_in_use"),
                    "peak_bytes_in_use": s.get("peak_bytes_in_use"),
                    "bytes_limit": s.get("bytes_limit")})
    return out


def train_leg(args, log=print, require_memory_stats: bool = False) -> dict:
    """One leg through the normal entry point, then every check on it.
    Returns the facts the leg established (JSON-able)."""
    from pipegcn_tpu.cli.main import run

    n_parts = args.n_partitions
    t0 = time.perf_counter()
    res = run(args)
    wall = time.perf_counter() - t0
    trainer = res["trainer"]
    facts = check_stream(args.metrics_out, trainer)
    tuning = facts["tuning"]
    # full-length blocks; the first compiles, the second warms
    full = [t for n, t in facts["blocks"] if n == args.fused_epochs]
    leg = {
        "n_parts": n_parts, "pipeline": bool(args.enable_pipeline),
        "dataset": args.dataset, "wall_s": round(wall, 1),
        "setup_s": res["setup_s"],
        "kernel": tuning["winner"]["name"],
        "tuning_source": tuning["source"],
        "artifact_source": trainer.sg.source,
        "tables_source": trainer.tables_source,
        "tables_pad": trainer.tables_pad,
        # [length, seconds] of every dispatch, compiles included
        "dispatches_s": [[n, round(n * t, 3)]
                         for n, t in facts["blocks"]],
        "compile_first_block_s": round(
            facts["blocks"][0][0] * facts["blocks"][0][1], 2),
        "steady_epoch_s": round(statistics.median(full[2:] or full[-1:]),
                                4),
        "loss_first": round(facts["losses"][0], 4),
        "loss_last": round(facts["losses"][-1], 4),
        "memory": device_memory(),
    }
    log(f"--- leg P={n_parts} "
        f"{'pipelined' if args.enable_pipeline else 'vanilla'}: "
        f"{args.dataset}")
    log(f"  smoke timings (one sample, not benchmark numbers), seconds:")
    for k, v in res["setup_s"].items():
        log(f"    {k:<24}{v:>10.2f}")
    log(f"    {'compile + first block':<24}"
        f"{leg['compile_first_block_s']:>10.2f}  ({args.fused_epochs} "
        f"epochs)")
    log(f"    {'steady epoch':<24}{leg['steady_epoch_s']:>10.4f}  "
        f"(median of {len(full[2:]) or 1} later block(s) of "
        f"{args.fused_epochs})")
    log(f"    every dispatch [epochs, s]: {leg['dispatches_s']}")
    log(f"    {'whole leg':<24}{wall:>10.1f}")
    tuned = ("measured live in this run" if tuning["source"] == "live"
             else "loaded from the artifact (tuning.json)")
    log(f"  derived inputs: partition artifact {leg['artifact_source']}"
        f"; kernel tables {trainer.tables_source}; tuning {tuned}")
    log(f"  kernel that ran: {tuning['winner']['name']} "
        f"(impl={facts['kernel']}); the tuner's sample: "
        f"{tuning.get('sample_tile_rows')} tile-rows, dense coverage "
        f"{tuning.get('sample_dense_coverage')} (shard "
        f"{tuning.get('shard_dense_coverage')}), timed edges "
        f"{tuning.get('timed_edges')} for a shard of "
        f"{tuning.get('shard_edges')}; cost table:")
    for c in tuning["costs"]:
        log(f"    {c['name']:<18}"
            f"{c['spmm_fwdbwd_s'] * 1e3:>10.2f} ms sampled   a call at "
            f"the shard's size {c['est_call_s']:.4f} s   est epoch SpMM "
            f"{c['est_epoch_spmm_s']:.3f} s   error={c['error']}")
    log(f"  loss {leg['loss_first']} -> {leg['loss_last']} over "
        f"{len(facts['losses'])} epochs; 0 fallback, 0 fault records")
    for m in leg["memory"]:
        log(f"  {m['device']}: bytes_in_use={m['bytes_in_use']} "
            f"peak_bytes_in_use={m['peak_bytes_in_use']} "
            f"limit={m['bytes_limit']}")
    if n_parts > 1:
        leg["placement"] = check_placement(trainer, n_parts,
                                           require_memory_stats, log)
    leg["winner"] = dict(tuning["winner"])
    # drop the device buffers before the next leg builds its own
    del res, trainer
    gc.collect()
    return leg


class _Tee:
    def __init__(self, *streams):
        self.streams = streams

    def write(self, s):
        for st in self.streams:
            st.write(s)
        return len(s)

    def flush(self):
        for st in self.streams:
            st.flush()


def _new_run_dir(root: str, n_devices: int) -> str:
    """run-<UTC time>-<N>chip: every chip call starts on a fresh machine
    and its output directory is merged back into one place, so a
    counter would collide where a timestamp does not."""
    stamp = time.strftime("%Y%m%dT%H%M%SZ", time.gmtime())
    path = os.path.join(root, f"run-{stamp}-{n_devices}chip")
    os.makedirs(path)
    return path


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--nodes", type=int, default=REDDIT_NODES,
                    help=f"node count of the synthetic Reddit-shape "
                         f"graph (default: Reddit's {REDDIT_NODES}); "
                         f"nothing else about the shape can be changed")
    ap.add_argument("--parts", choices=["auto", "1", "4"],
                    default="auto",
                    help="auto: the P=1 leg, then the P=4 legs when "
                         "four devices are visible; 1 or 4: only that "
                         "part of it (a four-chip call costs four "
                         "times the chip budget)")
    opts = ap.parse_args(argv)

    t_start = time.perf_counter()
    import jax
    import jaxlib

    from pipegcn_tpu import native
    from pipegcn_tpu.backend import (CACHE_ENV, device_summary,
                                     place_compile_cache, require_tpu)
    from pipegcn_tpu.obs.hw import peaks_for

    cache_dir = place_compile_cache()
    dev = require_tpu()     # raises unless platform == "tpu"

    out_dir = _new_run_dir(os.path.join(HERE, "chiprun_out",
                                        "chip_smoke"), dev["count"])
    part_dir = os.path.join(HERE, "partitions", "chip_smoke")
    logf = open(os.path.join(out_dir, "smoke.log"), "w")
    sys.stdout = _Tee(sys.__stdout__, logf)

    # compile and cache traffic of this process: the program's own
    # counters (obs/trace.py), from JAX's monitoring events
    from pipegcn_tpu.obs.trace import recorder

    recorder().install()

    from importlib import metadata

    print(f"chip_smoke: platform={dev['platform']} "
          f"device_kind={dev['kind']!r} count={dev['count']} | "
          f"jax {jax.__version__} jaxlib {jaxlib.__version__} "
          f"libtpu {metadata.version('libtpu')}")
    pk = peaks_for(dev["kind"])     # raises on a kind not in the table
    print(f"published peaks for {dev['kind']!r}: "
          f"{pk.bf16_flops / 1e12:.0f} TFLOP/s bf16, "
          f"{pk.hbm_bytes_s / 1e9:.0f} GB/s HBM")
    n_cached = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) \
        else 0
    placed_by = (CACHE_ENV if os.environ.get(CACHE_ENV)
                 else "fixed in-checkout path")
    print(f"compile cache: {cache_dir} ({placed_by}, {n_cached} entries "
          f"at start)")
    check(native.available(), f"native library {native.status()}")
    print(f"native library: {native.status()}")
    print(f"graph: synthetic Reddit shape, {opts.nodes} nodes"
          + ("" if opts.nodes == REDDIT_NODES else
             f" (NODE COUNT CUT from {REDDIT_NODES} by --nodes; degree "
             f"{DEGREE}, {N_FEAT} features, {N_CLASS} classes, "
             f"{N_LAYERS}x{HIDDEN} unchanged)")
          + "; layout: --local-reorder cluster, no node reorder "
            "(pinned by the arguments, not read from partitions/)")

    legs = []
    if opts.parts in ("auto", "1"):
        legs.append(train_leg(smoke_args(out_dir, part_dir, n_parts=1,
                                         nodes=opts.nodes),
                              require_memory_stats=True))
    if opts.parts == "4" or (opts.parts == "auto" and dev["count"] >= 4):
        from pipegcn_tpu.ops import tuner

        # the P=1 leg's in-process tuner memo is keyed by the source
        # graph, not the partition count: drop it so the P=4 shard is
        # tuned on the chip too
        tuner.clear_memo()
        for pipeline in (True, False):
            legs.append(train_leg(
                smoke_args(out_dir, part_dir, n_parts=4,
                           nodes=opts.nodes, pipeline=pipeline),
                require_memory_stats=True))
    else:
        print(f"--- P=4 phase NOT RUN: {dev['count']} device(s) "
              f"visible, four needed"
              + ("" if opts.parts == "auto" else " (--parts 1)"))
    ref = check_reference(legs[0]["winner"])
    print(f"--- reference agreement on a small input: {ref}")

    tot = recorder().totals
    small = [c for c in tot["compile_times"] if c < 1.0]
    cache = {"requests": tot["requests"], "hits": tot["cache_hits"],
             "misses": tot["written"],
             "cache_load_s": round(tot["cache_load_s"], 1),
             "lower_s": round(tot["lower_s"], 1),
             "backend_compiles": tot["compiles"],
             "backend_compile_s": round(tot["compile_s"], 1),
             "under_threshold": len(small),
             "under_threshold_s": round(sum(small), 1)}
    print(f"compile cache traffic this process: {cache['requests']} "
          f"requests, {cache['hits']} hits loaded in "
          f"{cache['cache_load_s']} s, {cache['misses']} written. "
          f"{cache['backend_compiles']} backend compiles no hit served "
          f"took {cache['backend_compile_s']} s; {len(small)} of them "
          f"were under JAX's 1 s write threshold and took "
          f"{cache['under_threshold_s']} s together, which is all a warm "
          f"process still pays for them; tracing and lowering took "
          f"{cache['lower_s']} s")
    with open(os.path.join(out_dir, "summary.json"), "w") as f:
        json.dump({"device": dev, "nodes": opts.nodes, "legs": legs,
                   "reference": ref, "cache": cache,
                   "wall_s": round(time.perf_counter() - t_start, 1)},
                  f, indent=1)
    print(f"chip_smoke: every phase passed in "
          f"{time.perf_counter() - t_start:.0f} s; outputs in {out_dir}")
    sys.stdout.flush()
    sys.stdout = sys.__stdout__
    logf.close()
    print(json.dumps({"ok": True, "device": device_summary()}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
