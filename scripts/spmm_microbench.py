"""Decompose the block-SpMM epoch cost on the real chip.

Loads the cached Reddit-scale bench artifact + block tables, then times
the device aggregation closure in three configurations — full hybrid,
dense-tiles-only, remainder-only — forward and forward+backward, at the
training feature width. This attributes the measured epoch time between
the MXU dense path, the slabbed gather remainder, and everything else
(the bench's per-epoch number minus 6x the SpMM cost).

Timing forces a device->host scalar read per call, so the clock stops
after the device does.

Usage: python scripts/spmm_microbench.py [--part partitions/...]
"""

import argparse
import os
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--part",
                    default="partitions/bench-reddit-1-c2-s1024")
    ap.add_argument("--width", type=int, default=256)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--block-nnz", type=int, default=0)
    ap.add_argument("--group", type=int, default=1,
                    help="union-gather group size (block_group); the "
                         "prewarmed u4/u8 table caches make this cheap")
    ap.add_argument("--probe-traffic", action="store_true",
                    help="table-surgery decomposition of the dense "
                         "term: F-tile reads vs A reads vs MXU")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from pipegcn_tpu.backend import device_line, place_compile_cache

    place_compile_cache()
    # results are labelled by jax.default_backend(); say which it is
    print(f"# {device_line()}", file=sys.stderr)

    from pipegcn_tpu.models import ModelConfig
    from pipegcn_tpu.parallel import Trainer, TrainConfig

    # rebuilt if missing: partitions/ is not git-tracked and vanishes
    # between rounds
    from pipegcn_tpu.partition.bench_artifact import ensure

    if not os.path.isabs(args.part):
        args.part = os.path.join(REPO, args.part)
    sg = ensure(args.part, log=lambda m: print(m, file=sys.stderr))
    cfg = ModelConfig(
        layer_sizes=(sg.n_feat, 256, 256, 256, sg.n_class),
        use_pp=True, norm="layer", dropout=0.5,
        train_size=sg.n_train_global, spmm_chunk=2_097_152,
        dtype="bfloat16", spmm_impl="block",
        block_nnz=args.block_nnz or None,
        block_group=args.group,
    )
    tr = Trainer(sg, cfg, TrainConfig(lr=0.01, n_epochs=1, eval=False))
    d = {k: v[0] for k, v in tr.data.items()}
    n_max = sg.n_max
    n_src = n_max + sg.halo_size

    rng = np.random.default_rng(0)
    fbuf = jnp.asarray(
        rng.standard_normal((n_src, args.width)).astype(np.float32)
    ).astype(jnp.bfloat16)

    from pipegcn_tpu.ops.block_spmm import make_device_block_spmm_fn

    def variant(name, keep):
        # The tables ride as jit ARGUMENTS, never closure constants:
        # jit embeds closed-over arrays into the HLO as constants (GBs
        # of tables compiled into the program and its cache key). The
        # factory's host logic depends only on dict keys/shapes, so
        # re-invoking it under trace is sound (the Trainer passes the
        # same tables as shard_map operands for the same reason).
        dd = {k: v for k, v in d.items() if keep(k)}

        def apply(tables, in_deg, f):
            fn = make_device_block_spmm_fn(
                tables, in_deg, n_max, n_src, tr._block_tile,
                chunk_edges=cfg.spmm_chunk)
            return fn(f)

        fwd = jax.jit(apply)

        @jax.jit
        def grad(tables, in_deg, f):
            return jax.grad(lambda ff: apply(tables, in_deg, ff)
                            .astype(jnp.float32).sum())(f)

        def timed(g, label):
            g(dd, d["in_deg"], fbuf)  # compile
            float(jnp.sum(g(dd, d["in_deg"], fbuf)[0]))
            ts = []
            for _ in range(args.reps):
                t0 = time.perf_counter()
                float(jnp.sum(g(dd, d["in_deg"], fbuf)[0]))
                ts.append(time.perf_counter() - t0)
            print(f"{name:12s} {label:8s} {min(ts)*1e3:8.1f} ms",
                  file=sys.stderr)
            return min(ts)

        f = timed(fwd, "fwd")
        fb = timed(grad, "fwd+bwd")
        return f, fb

    is_dense = lambda k: k.startswith("blk_")
    is_rem = lambda k: k.startswith("blkrem_")
    aux = lambda k: not (is_dense(k) or is_rem(k))
    inv_only = lambda k: k.endswith("inv") or k.endswith("ginv")

    # ONE dense-keep predicate: the --probe-traffic deltas below are
    # only meaningful against the exact same program as this baseline
    dense_keep = lambda k: aux(k) or is_dense(k) \
        or (is_rem(k) and inv_only(k))

    full = variant("full", lambda k: True)
    dense = variant("dense-only", dense_keep)
    rem = variant("rem-only",
                  lambda k: aux(k) or is_rem(k)
                  or (is_dense(k) and inv_only(k)))
    print(f"# per-SpMM (fwd+bwd avg ~ epoch has 3 fwd + 3 bwd):")
    print(f"full fwd {full[0]*1e3:.1f} ms, fwd+bwd {full[1]*1e3:.1f} ms; "
          f"dense fwd {dense[0]*1e3:.1f}, rem fwd {rem[0]*1e3:.1f}")
    est_epoch = 3 * full[1]
    print(f"# est SpMM-only epoch: {est_epoch:.3f}s")

    if args.probe_traffic:
        # Attribute the dense-only time between F-tile reads and the
        # rest (A's slices, the MXU term) by TABLE SURGERY: identical
        # program shapes, but every tile index points at tile 0,
        # collapsing the operand's distinct HBM traffic to one tile.
        # (Numerics are wrong on purpose; only time matters.) The F-tile
        # delta decides whether the union-gather reuse design
        # (docs/PERF_NOTES.md "F-tile reuse headroom") is worth
        # building. A itself is stored in reading order and sliced, not
        # gathered (block_spmm._dense_tables): there is no A index to
        # collapse, so the A-collapsed and pre-unpacked-A probes of the
        # block-id-ordered table went with it.
        prefixes = ("blk_fwd_g", "blk_bwd_g")

        def surgery(name, zero_suffix):
            saved = {}
            for k in list(d.keys()):
                if k.startswith(prefixes):
                    if k.endswith(zero_suffix):
                        saved[k] = d[k]
                        d[k] = jnp.zeros_like(d[k])
            try:
                return variant(name, dense_keep)
            finally:
                d.update(saved)

        tile0 = surgery("tile0-dense", "t")   # all F-tile reads -> tile 0
        print("# dense decomposition (fwd): "
              f"baseline {dense[0]*1e3:.1f} ms, "
              f"F-tile-collapsed {tile0[0]*1e3:.1f} ms "
              f"(F-read share {(dense[0]-tile0[0])*1e3:.1f} ms)")

        # machine-readable record so the cost-model recalibration
        # (scripts/coverage_sweep.py --gather-rps/--fixed-s) can
        # consume the decomposition without log scraping
        import json

        rec = {
            "backend": jax.default_backend(),
            "group": args.group,
            "width": args.width,
            "full_fwd_s": full[0], "full_fwdbwd_s": full[1],
            "dense_fwd_s": dense[0], "dense_fwdbwd_s": dense[1],
            "rem_fwd_s": rem[0], "rem_fwdbwd_s": rem[1],
            "ftile_collapsed_fwd_s": tile0[0],
            "est_spmm_epoch_s": est_epoch,
        }
        # keyed by backend/config so a CPU smoke run or a different
        # group/fused probe never clobbers the real TPU calibration
        # record
        tag = f"{jax.default_backend()}_g{args.group}"
        out_path = os.path.join(REPO, "results",
                                f"probe_traffic_{tag}.json")
        os.makedirs(os.path.dirname(out_path), exist_ok=True)
        with open(out_path, "w") as f:
            json.dump(rec, f, indent=1)
        print(f"# wrote {out_path}")


if __name__ == "__main__":
    main()
