#!/usr/bin/env python
"""papers100M-scale end-to-end: partition -> shard -> one pipelined step.

VERDICT r4 item 6: the full-scale 64-part partition existed as metadata
only; no training step had ever run on a full-scale artifact. This
script runs the whole pipeline at the reference's papers100M shape —
111M nodes, 1.6B raw edges (3.2B mirrored), 64 partitions (reference
helper/utils.py:17-30; BASELINE.json multi-host grid) — bounded to one
host's RAM/disk, in resumable stages:

  1. edges    [E, 2] int32 memmap (power-law src + locality windows +
              jumps, the round-4 generator)
  2. parts    64-way METIS-class multilevel partition (native HEM/FM),
              saved this time (round 4's 4-hour result was wiped with
              the workspace)
  3. artifact ShardedGraph.build_chunked -> v3 mmap layout. Features
              are NOT stored (57 GB at F=128 exceeds this host's free
              disk next to the edges): the artifact holds a width-1
              placeholder plus real labels/masks/degrees/topology, and
              the step synthesizes rank features deterministically at
              load (SequentialRunner feat_fn).
  4. step     ONE pipelined training step over all 64 ranks via
              SequentialRunner(compact_halo=True, keep_carry=False) —
              exact epoch-0 semantics (stale buffers are zeros), peak
              RSS = one rank. The cross-rank carry for ALL ranks is
              inherently distributed state (P x layers x 2 x [H, F]),
              which is why multi-epoch full-scale training needs the
              real multi-host mesh, not more host RAM.

Each stage skips itself when its output exists; results/papers_dryrun
.json records per-stage wall + peak RSS.

Usage: nice -n 19 python scripts/papers_full_step.py [--nodes N]
       [--edges E] [--parts 64] [--smoke]   (--smoke = 1/100 scale)
"""

import argparse
import json
import os
import resource
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

N_FEAT, N_CLASS = 128, 172
TRAIN_FRAC = 0.01


def rss_gb() -> float:
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1e6


def gen_edges(path, n_nodes, n_edges, chunk=1 << 24):
    """Round-4 distribution (scripts/papers_partition_fullscale.py):
    pareto src skew, 90% +-500k locality window, 10% jumps.
    Written to a temp name and renamed: the skip-if-exists resume must
    never accept a half-filled file."""
    rng = np.random.default_rng(0)
    tmp = path + ".tmp.npy"
    edges = np.lib.format.open_memmap(
        tmp, mode="w+", dtype=np.int32, shape=(n_edges, 2))
    window = max(min(500_000, n_nodes // 8), 1)
    for i0 in range(0, n_edges, chunk):
        m = min(chunk, n_edges - i0)
        src = (rng.pareto(1.5, m) * (n_nodes / 50)).astype(np.int64) \
            % n_nodes
        jump = rng.random(m) < 0.1
        win = rng.integers(-window, window, m)
        dst = np.where(jump, rng.integers(0, n_nodes, m),
                       (src + win) % n_nodes)
        edges[i0:i0 + m, 0] = src.astype(np.int32)
        edges[i0:i0 + m, 1] = dst.astype(np.int32)
    edges.flush()
    del edges
    os.replace(tmp, path)


class _Mirror:
    """Lazy mirrored view over the [E, 2] memmap: rows [0, E) read
    column a, rows [E, 2E) column b — build_chunked touches only
    contiguous slices, so the doubled edge list never hits disk."""

    def __init__(self, edges, a, b):
        self._e = edges
        self._a, self._b = a, b
        self.shape = (2 * edges.shape[0],)
        self.dtype = edges.dtype

    def __len__(self):
        return self.shape[0]

    def __getitem__(self, sl):
        e = self._e.shape[0]
        start, stop, step = sl.indices(self.shape[0])
        assert step == 1
        parts = []
        if start < e:
            parts.append(self._e[start:min(stop, e), self._a])
        if stop > e:
            parts.append(self._e[max(start - e, 0):stop - e, self._b])
        return np.concatenate(parts) if len(parts) > 1 else parts[0]


def node_hash(i0, i1):
    nid = np.arange(i0, i1, dtype=np.uint64)
    x = nid * np.uint64(0x9E3779B97F4A7C15)
    x ^= x >> np.uint64(29)
    x *= np.uint64(0xBF58476D1CE4E5B9)
    x ^= x >> np.uint64(32)
    return x


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--nodes", type=int, default=111_000_000)
    ap.add_argument("--edges", type=int, default=1_600_000_000)
    ap.add_argument("--parts", type=int, default=64)
    ap.add_argument("--smoke", action="store_true",
                    help="1/100 scale pipeline check")
    ap.add_argument("--work-dir", default=os.path.join(REPO, "partitions",
                                                       "papers_full"))
    ap.add_argument("--out", default=os.path.join(REPO, "results",
                                                  "papers_dryrun.json"))
    args = ap.parse_args()
    if args.smoke:
        args.nodes //= 100
        args.edges //= 100
        args.work_dir += "_smoke"
        args.out = os.path.join(REPO, "results",
                                "papers_dryrun_smoke.json")

    os.makedirs(args.work_dir, exist_ok=True)
    rec = {}
    if os.path.exists(args.out):
        with open(args.out) as f:
            rec = json.load(f)  # keep extras (balance, step_loss, ...)
        if "first_step_s" in rec and "round3_150k_dryrun" not in rec:
            # the round-3 record measured a 150k-node stand-in; nest it
            # so its step time / RSS can't read as full-scale numbers
            legacy = {k: rec.pop(k) for k in
                      ("dryrun_devices", "first_step_s", "loss",
                       "peak_rss_gb", "note") if k in rec}
            rec["round3_150k_dryrun"] = legacy
    rec.update({
        "nodes": args.nodes, "raw_edges": args.edges,
        "mirrored_adjacency_entries": 2 * args.edges,
        "parts": args.parts, "n_feat": N_FEAT, "n_class": N_CLASS,
    })
    stages = rec.setdefault("stages", {})

    def record(name, t0, **extra):
        stages[name] = {"s": round(time.time() - t0, 1),
                        "peak_rss_gb": round(rss_gb(), 2)}
        rec.update(extra)
        with open(args.out, "w") as f:
            json.dump(rec, f, indent=1)
        print(f"# stage {name}: {stages[name]}", flush=True)
        return rec

    # ---- stage 1: edges ---------------------------------------------
    epath = os.path.join(args.work_dir, "edges.npy")
    if not os.path.exists(epath):
        t0 = time.time()
        gen_edges(epath, args.nodes, args.edges)
        record("gen", t0)
    edges = np.load(epath, mmap_mode="r")

    # ---- stage 2: partition -----------------------------------------
    from pipegcn_tpu.graph.csr import Graph
    from pipegcn_tpu.partition.partitioner import partition_graph

    ppath = os.path.join(args.work_dir, "parts.npy")
    if not os.path.exists(ppath):
        t0 = time.time()
        g_raw = Graph(num_nodes=args.nodes, src=edges[:, 0],
                      dst=edges[:, 1])
        # refine_iters=3 (default 10): round 4 measured the default at
        # ~4 h / 78 GB at this scale for a 1.05 balance; the
        # trainability chain needs the partition to exist more than it
        # needs the last FM sweeps (quality evidence:
        # results/partition_quality.md, run at defaults)
        parts = partition_graph(g_raw, args.parts, method="metis",
                                obj="vol", seed=0, refine_iters=3)
        sizes = np.bincount(parts, minlength=args.parts)
        np.save(ppath + ".tmp.npy", parts.astype(np.int16))
        os.replace(ppath + ".tmp.npy", ppath)
        record("partition", t0,
               balance=round(float(sizes.max() / sizes.mean()), 4))
        del g_raw, parts
    parts = np.load(ppath).astype(np.int32)

    # ---- stage 3: sharded artifact (v3 mmap) ------------------------
    from pipegcn_tpu.partition.halo import ShardedGraph

    apath = os.path.join(args.work_dir, "artifact")
    if not ShardedGraph.exists(apath):
        t0 = time.time()
        n = args.nodes
        nd_dir = os.path.join(args.work_dir, "ndata")
        os.makedirs(nd_dir, exist_ok=True)

        def memmapped(name, dtype, shape, fill):
            # temp-then-rename: skip-if-exists must never accept a
            # half-filled file after an interruption
            p = os.path.join(nd_dir, name + ".npy")
            if not os.path.exists(p):
                arr = np.lib.format.open_memmap(
                    p + ".tmp.npy", mode="w+", dtype=dtype, shape=shape)
                for i0 in range(0, n, 1 << 22):
                    i1 = min(i0 + (1 << 22), n)
                    arr[i0:i1] = fill(i0, i1)
                arr.flush()
                del arr
                os.replace(p + ".tmp.npy", p)
            return np.load(p, mmap_mode="r")

        # labels/splits from a node-id hash (deterministic, no storage
        # beyond the artifact); features are synthesized at step time
        label = memmapped(
            "label", np.int64, (n,),
            lambda a, b: (node_hash(a, b) % np.uint64(N_CLASS))
            .astype(np.int64))
        hsplit = lambda a, b: (node_hash(a, b) >> np.uint64(32)) \
            .astype(np.float64) / 2**32
        train_mask = memmapped("train", bool, (n,),
                               lambda a, b: hsplit(a, b) < TRAIN_FRAC)
        val_mask = memmapped(
            "val", bool, (n,),
            lambda a, b: (hsplit(a, b) >= TRAIN_FRAC)
            & (hsplit(a, b) < 2 * TRAIN_FRAC))
        test_mask = memmapped(
            "test", bool, (n,),
            lambda a, b: (hsplit(a, b) >= 2 * TRAIN_FRAC)
            & (hsplit(a, b) < 3 * TRAIN_FRAC))
        feat = memmapped("feat1", np.float32, (n, 1),
                         lambda a, b: np.zeros((b - a, 1), np.float32))
        if not os.path.exists(os.path.join(nd_dir, "in_deg.npy")):
            # in-degree of the mirrored graph, chunked
            deg = np.zeros(n, np.int64)
            for i0 in range(0, args.edges, 1 << 24):
                sl = slice(i0, min(i0 + (1 << 24), args.edges))
                deg += np.bincount(edges[sl, 0], minlength=n)
                deg += np.bincount(edges[sl, 1], minlength=n)
        in_deg = memmapped("in_deg", np.float32, (n,),
                           lambda a, b: deg[a:b].astype(np.float32))

        g = Graph(
            num_nodes=n,
            src=_Mirror(edges, 0, 1),
            dst=_Mirror(edges, 1, 0),
            ndata={"feat": feat, "label": label,
                   "train_mask": train_mask, "val_mask": val_mask,
                   "test_mask": test_mask, "in_deg": in_deg},
        )
        sg = ShardedGraph.build_chunked(g, parts, n_parts=args.parts)
        # trim_edges: the pareto-hub rank sets e_max ~2.7x the mean
        # edge count, so the padded [64, e_max] stack alone is ~69 GB —
        # more than this host's free disk; trimmed per-rank storage is
        # ~26 GB and is all the sequential step reads anyway
        sg.save(apath, mmap=True, trim_edges=True)
        record("artifact", t0)
        del sg, g
    sg = ShardedGraph.load(apath)
    print(f"# artifact: P={sg.num_parts} n_max={sg.n_max} "
          f"b_max={sg.b_max} e_max={sg.e_max} "
          f"halo(uniform)={sg.halo_size}", flush=True)

    # ---- stage 4: one pipelined step --------------------------------
    from pipegcn_tpu.backend import start_measurement

    start_measurement(cpu=True)  # a host-side study by design

    from pipegcn_tpu.models import ModelConfig
    from pipegcn_tpu.parallel import SequentialRunner, TrainConfig

    t0 = time.time()
    cfg = ModelConfig(
        layer_sizes=(N_FEAT, 128, 128, N_CLASS),
        use_pp=False, norm="layer", dropout=0.5,
        train_size=sg.n_train_global, spmm_impl="bucket",
        # f32 on the CPU host: bf16 is emulated (upcast per op) there
        # and measurably slower; the TPU path keeps bf16
        spmm_chunk=8_388_608, dtype="float32",
    )
    # rbg dropout keys: the threefry mask generation dominated CPU
    # epoch cost in the anatomy smoke (~2x); rbg is the same
    # production lever the TPU floor work uses (TrainConfig.rng_impl)
    tcfg = TrainConfig(lr=0.01, enable_pipeline=True, eval=False,
                       seed=0, rng_impl="rbg")

    def feat_fn(r):
        rng = np.random.default_rng(1000 + r)
        return rng.standard_normal((sg.n_max, N_FEAT)).astype(np.float32)

    run = SequentialRunner(
        sg, cfg, tcfg, feat_fn=feat_fn, compact_halo=True,
        keep_carry=False,
        log=lambda s: print(f"# {s} ({time.time()-t0:.0f}s, "
                            f"rss {rss_gb():.1f} GB)", flush=True))
    print(f"# compact halo: {run.H} rows (vs uniform {sg.halo_size}, "
          f"{sg.halo_size / max(run.H, 1):.1f}x)", flush=True)
    loss = run.run_epoch(
        0, state_path=os.path.join(args.work_dir, "step_state.pkl"))
    rec = record(
        "step", t0,
        step_loss=round(float(loss), 4),
        loss_at_init_expected=round(float(np.log(N_CLASS)), 4),
        compact_halo_rows=int(run.H),
        uniform_halo_rows=int(sg.halo_size),
        note=(
            "full pipelined step over the real 64-part artifact via "
            "SequentialRunner (compact halo, one-shot epoch-0 semantics "
            "— exactness vs the mesh trainer pinned by tests/"
            "test_sequential.py); features synthesized per rank at "
            "load, topology/labels/splits from the saved v3 artifact"))
    print(json.dumps(rec), flush=True)


if __name__ == "__main__":
    main()
