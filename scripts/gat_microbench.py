#!/usr/bin/env python
"""Attribute the GAT bucket kernel's epoch between its passes.

Full-scale GAT measured 38.4 s/epoch (fp8) against the SAGE headline's
1.30 s on earlier code (record removed) — ~6x slower per gather pass
than the SAGE bucket kernel on the same formulation. This times, on one
graph: (a) GAT forward (2 gather passes/edge-slot), (b) GAT
fwd+bwd (6 passes), (c) the SAGE bucket mean kernel fwd / fwd+bwd
(1 / 3 passes) as the rate reference. The per-pass ratio decides the
fix: if GAT passes run at bucket rates, the cost is pass COUNT (pack
el into the z slab, stats into one table); if they are intrinsically
slower, the [r, D, H] attention elementwise or scan structure is the
target.

Tables ride as jit ARGUMENTS, never closure constants (a closed-over
array is embedded in the HLO and recompiled with it).

Usage: python scripts/gat_microbench.py [--dataset synthetic:60000:30:602:41]
"""

import argparse
import json
import os
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", default="synthetic:60000:30:602:41")
    ap.add_argument("--heads", type=int, default=4)
    ap.add_argument("--hidden", type=int, default=256)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--rem-dtype", default="float8")
    ap.add_argument("--cpu", action="store_true")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from pipegcn_tpu.backend import start_measurement

    start_measurement(cpu=args.cpu)

    from pipegcn_tpu.models import ModelConfig
    from pipegcn_tpu.parallel import Trainer, TrainConfig
    from pipegcn_tpu.partition import (ShardedGraph, locality_clusters,
                                       partition_graph)
    from pipegcn_tpu.graph import load_data

    part_path = os.path.join(
        "partitions",
        "gat-" + args.dataset.replace(":", "_") + "-c-s1024")
    if ShardedGraph.exists(part_path):
        sg = ShardedGraph.load(part_path)
    else:
        g = load_data(args.dataset)
        parts = partition_graph(g, 1, seed=0)
        cluster = locality_clusters(g, target_size=1024, seed=0)
        sg = ShardedGraph.build(g, parts, n_parts=1, cluster=cluster)
        sg.save(part_path)
        sg.cache_dir = part_path

    H, dh = args.heads, args.hidden // args.heads
    R = sg.n_max + sg.halo_size
    n_dst = sg.n_max
    rd = None if args.rem_dtype in ("none", "") else args.rem_dtype

    # --- GAT tables through the trainer cache ---------------------------
    gat_cfg = ModelConfig(
        layer_sizes=(sg.n_feat, args.hidden, args.hidden, sg.n_class),
        model="gat", n_heads=H, train_size=sg.n_train_global,
        spmm_impl="bucket", spmm_chunk=2_097_152, dtype="bfloat16",
        rem_dtype=rd)
    tr = Trainer(sg, gat_cfg, TrainConfig(lr=0.01, n_epochs=1,
                                          eval=False))
    gat_d = {k: v[0] for k, v in tr.data.items()
             if k.startswith("gat_")}

    from pipegcn_tpu.ops.gat_bucket import make_device_gat_fn

    rng = np.random.default_rng(0)
    z = jnp.asarray(rng.standard_normal((R, H, dh)).astype(np.float32)
                    ).astype(jnp.bfloat16)
    el = jnp.asarray(rng.standard_normal((R, H)).astype(np.float32))
    er = jnp.asarray(rng.standard_normal((n_dst, H)).astype(np.float32))

    def timed(g_fn, ops, label):
        g_fn(*ops)  # compile
        float(jnp.sum(g_fn(*ops)[0]))
        ts = []
        for _ in range(args.reps):
            t0 = time.perf_counter()
            float(jnp.sum(g_fn(*ops)[0]))
            ts.append(time.perf_counter() - t0)
        print(f"# {label:16s} {min(ts)*1e3:9.1f} ms", flush=True)
        return min(ts)

    def gat_apply(tables, zz, ee, rr):
        fn = make_device_gat_fn(tables, n_dst, R, H,
                                gat_cfg.leaky_slope,
                                chunk_edges=gat_cfg.spmm_chunk,
                                rem_dtype=rd)
        return fn(zz, ee, rr)

    gat_fwd = jax.jit(gat_apply)

    @jax.jit
    def gat_both(tables, zz, ee, rr):
        def loss(zz_, ee_, rr_):
            return gat_apply(tables, zz_, ee_, rr_).sum()
        return jax.grad(loss, argnums=(0, 1, 2))(zz, ee, rr)

    rec = {"backend": jax.default_backend(), "rem_dtype": args.rem_dtype,
           "edges": int(sg.edge_count.sum())}
    rec["gat_fwd_s"] = timed(gat_fwd, (gat_d, z, el, er), "gat fwd")
    rec["gat_fwdbwd_s"] = timed(gat_both, (gat_d, z, el, er),
                                "gat fwd+bwd")

    # --- SAGE bucket mean kernel on the same graph (rate reference) ----
    sage_cfg = ModelConfig(
        layer_sizes=(sg.n_feat, args.hidden, args.hidden, sg.n_class),
        train_size=sg.n_train_global, spmm_impl="bucket",
        spmm_chunk=2_097_152, dtype="bfloat16", rem_dtype=rd)
    tr2 = Trainer(sg, sage_cfg, TrainConfig(lr=0.01, n_epochs=1,
                                            eval=False))
    buck_d = {k: v[0] for k, v in tr2.data.items()
              if k.startswith("bkt_")}
    if buck_d:
        from pipegcn_tpu.ops.bucket_spmm import (
            make_device_bucket_spmm_fn)

        fbuf = jnp.asarray(rng.standard_normal((R, args.hidden))
                           .astype(np.float32)).astype(jnp.bfloat16)
        in_deg = tr2.data["in_deg"][0]

        def bucket_apply(tables, ind, f):
            fn = make_device_bucket_spmm_fn(
                tables, ind, R, rem_dtype=rd)
            return fn(f)

        b_fwd = jax.jit(bucket_apply)

        @jax.jit
        def b_both(tables, ind, f):
            return jax.grad(
                lambda ff: bucket_apply(tables, ind, ff)
                .astype(jnp.float32).sum())(f)

        rec["bucket_fwd_s"] = timed(
            b_fwd, (buck_d, in_deg, fbuf), "bucket fwd")
        rec["bucket_fwdbwd_s"] = timed(
            lambda t, i, f: (b_both(t, i, f),),
            (buck_d, in_deg, fbuf), "bucket fwd+bwd")
        # per-pass rates: gat fwd = 2 passes, fwd+bwd = 6;
        # bucket fwd = 1, fwd+bwd = 3
        rec["gat_pass_s"] = rec["gat_fwdbwd_s"] / 6
        rec["bucket_pass_s"] = rec["bucket_fwdbwd_s"] / 3
        print(f"# per-pass: gat {rec['gat_pass_s']*1e3:.1f} ms vs "
              f"bucket {rec['bucket_pass_s']*1e3:.1f} ms "
              f"(x{rec['gat_pass_s']/rec['bucket_pass_s']:.1f})",
              flush=True)

    # --- narrow-row gather-rate curve ----------------------------------
    # The attention kernel's per-edge el/stat gathers fetch 8-16 B rows
    # (H=4 bf16/f32) — far below the 256 B slab the SAGE cliff analysis
    # covered. If the request rate collapses at sub-32 B rows, the GAT
    # fix is packing el/stats into the wide z slabs (one request per
    # edge total), not a different softmax. M matches this graph's
    # edge count so the numbers read directly as per-pass seconds.
    M = int(sg.edge_count.sum())
    idx = jnp.asarray(rng.integers(0, R, size=M).astype(np.int32))

    @jax.jit
    def flat_gather(tbl, ii):
        return (jnp.take(tbl, ii, axis=0).astype(jnp.float32).sum(0),)

    rec["narrow_gather"] = {}
    for elems, dt, tag_w in ((4, jnp.bfloat16, "8B"),
                             (4, jnp.float32, "16B"),
                             (16, jnp.bfloat16, "32B"),
                             (64, jnp.bfloat16, "128B"),
                             (128, jnp.bfloat16, "256B")):
        tbl = jnp.asarray(
            rng.standard_normal((R, elems)).astype(np.float32)).astype(dt)
        t = timed(flat_gather, (tbl, idx), f"gather {tag_w}-rows")
        rec["narrow_gather"][tag_w] = {
            "s": t, "rows_per_s": M / t if t > 0 else None}

    tag = f"{jax.default_backend()}_{args.rem_dtype}"
    out = os.path.join(REPO, "results", f"gat_microbench_{tag}.json")
    with open(out, "w") as f:
        json.dump(rec, f, indent=1)
    print(json.dumps(rec))


if __name__ == "__main__":
    main()
