#!/usr/bin/env python
"""papers100M-class host pipeline demonstration at reduced scale.

The reference documents ogbn-papers100M (111M nodes, 1.6B directed raw
edges) as requiring a >=120 GB-RAM host (reference README.md:29-30,
helper/utils.py:17-30). This script demonstrates the RAM-bounded
replacements end to end on a papers100M-SHAPED synthetic graph:

  1. writes the OGB plain raw layout to disk (edge.npy [E,2] int64,
     node-feat.npy, node-label.npy, split/time/*.csv.gz) — so the real
     `load_ogb` code path runs, not a shortcut;
  2. `load_ogb(mmap=True)`: one-time chunked finalized-edge cache
     (mirror + self-loop normalize + in-degrees, int32 memmaps);
  3. `partition_graph` + `ShardedGraph.build_chunked` (bit-identical
     to build(), O(chunk) edge scratch) at --parts partitions;
  4. saves the artifact and reports peak RSS at each stage;
  5. optionally (--dryrun) jits ONE pipelined training step over a
     --parts-device virtual CPU mesh on the artifact.

Default scale: 1/10 papers100M — 11.1M nodes, 160M directed raw edges
(320M + self loops finalized), 128 features. Peak-RSS target: a small
multiple of the artifact itself (the O(E) scratch of the plain build
would add ~18 GB at this scale; the chunked build keeps it under
~1.5 GB).

Writes results/papers100m_scale.md.
"""

import argparse
import json
import os
import resource
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def rss_gb() -> float:
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1e6


def gen_raw_layout(base: str, n_nodes: int, n_edges: int, n_feat: int,
                   n_class: int, chunk: int = 1 << 24) -> None:
    """Write the OGB plain raw layout with chunked generation (the
    generator itself must not blow RAM at 160M edges). Community
    structure comes from a power-law-ish src skew + locality windows so
    partitioning finds real cuts."""
    import gzip

    import numpy as np

    raw = os.path.join(base, "raw")
    os.makedirs(raw, exist_ok=True)
    rng = np.random.default_rng(0)

    edges = np.lib.format.open_memmap(
        os.path.join(raw, "edge.npy"), mode="w+", dtype=np.int64,
        shape=(n_edges, 2))
    for i0 in range(0, n_edges, chunk):
        m = min(chunk, n_edges - i0)
        # sources skewed to low ids (hub papers); dsts local windows
        # around the source (citation locality) with occasional jumps
        src = (rng.pareto(1.5, m) * (n_nodes / 50)).astype(np.int64) \
            % n_nodes
        jump = rng.random(m) < 0.1
        window = rng.integers(-500_000, 500_000, m)
        dst = np.where(jump, rng.integers(0, n_nodes, m),
                       (src + window) % n_nodes)
        edges[i0:i0 + m, 0] = src
        edges[i0:i0 + m, 1] = dst
    edges.flush()
    del edges

    feat = np.lib.format.open_memmap(
        os.path.join(raw, "node-feat.npy"), mode="w+", dtype=np.float32,
        shape=(n_nodes, n_feat))
    node_chunk = max(1, (1 << 26) // n_feat)
    for i0 in range(0, n_nodes, node_chunk):
        m = min(node_chunk, n_nodes - i0)
        feat[i0:i0 + m] = rng.standard_normal((m, n_feat),
                                              dtype=np.float32)
    feat.flush()
    del feat

    label = rng.integers(0, n_class, n_nodes).astype(np.float64)
    label[rng.random(n_nodes) < 0.5] = np.nan  # most papers unlabeled
    np.save(os.path.join(raw, "node-label.npy"), label)

    sdir = os.path.join(base, "split", "time")
    os.makedirs(sdir, exist_ok=True)
    labeled = np.nonzero(~np.isnan(label))[0]
    rng.shuffle(labeled)
    k = labeled.size
    for part, ids in (("train", labeled[:int(k * 0.8)]),
                      ("valid", labeled[int(k * 0.8):int(k * 0.9)]),
                      ("test", labeled[int(k * 0.9):])):
        with gzip.open(os.path.join(sdir, part + ".csv.gz"), "wt") as f:
            f.write("\n".join(map(str, ids.tolist())) + "\n")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--nodes", type=int, default=11_100_000)
    ap.add_argument("--edges", type=int, default=160_000_000,
                    help="directed raw edges before mirroring")
    ap.add_argument("--feat", type=int, default=128)
    ap.add_argument("--classes", type=int, default=172)
    ap.add_argument("--parts", type=int, default=64)
    ap.add_argument("--root", default=os.path.join(REPO, "partitions",
                                                   "papers_scale_data"))
    ap.add_argument("--out", default=os.path.join(REPO, "partitions",
                                                  "papers_scale"))
    ap.add_argument("--dryrun", action="store_true",
                    help="also run one pipelined step on a --parts-"
                         "device virtual CPU mesh")
    args = ap.parse_args()

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import numpy as np

    from pipegcn_tpu.graph.datasets import load_ogb
    from pipegcn_tpu.partition import ShardedGraph, partition_graph

    stages = {}
    name = "ogbn-paperscale"
    base = os.path.join(args.root, name.replace("-", "_"))
    t0 = time.time()
    if not os.path.exists(os.path.join(base, "raw", "edge.npy")):
        gen_raw_layout(base, args.nodes, args.edges, args.feat,
                       args.classes)
    stages["gen"] = {"s": round(time.time() - t0, 1),
                     "peak_rss_gb": round(rss_gb(), 2)}
    print(f"# raw layout ready ({stages['gen']})", file=sys.stderr)

    t0 = time.time()
    g = load_ogb(name, args.root, mmap=True)
    stages["load"] = {"s": round(time.time() - t0, 1),
                      "peak_rss_gb": round(rss_gb(), 2)}
    print(f"# loaded: {g.num_nodes} nodes / {g.num_edges} finalized "
          f"edges ({stages['load']})", file=sys.stderr)

    t0 = time.time()
    # finalized edges are already mirrored: symmetric=True skips the
    # doubling mirror (the old scipy path's ~55 GB 1/10-scale peak)
    parts = partition_graph(g, args.parts, method="metis", obj="vol",
                            seed=0, symmetric=True)
    stages["partition"] = {"s": round(time.time() - t0, 1),
                           "peak_rss_gb": round(rss_gb(), 2)}
    print(f"# partitioned ({stages['partition']})", file=sys.stderr)

    t0 = time.time()
    sg = ShardedGraph.build_chunked(g, parts, n_parts=args.parts)
    stages["build_chunked"] = {"s": round(time.time() - t0, 1),
                               "peak_rss_gb": round(rss_gb(), 2)}
    print(f"# built: n_max={sg.n_max} e_max={sg.e_max} "
          f"halo={sg.halo_size} ({stages['build_chunked']})",
          file=sys.stderr)

    t0 = time.time()
    sg.save(args.out)
    stages["save"] = {"s": round(time.time() - t0, 1),
                      "peak_rss_gb": round(rss_gb(), 2)}

    result = {
        "nodes": g.num_nodes,
        "finalized_edges": g.num_edges,
        "parts": args.parts,
        "n_max": sg.n_max,
        "e_max": sg.e_max,
        "stages": stages,
    }
    print(json.dumps(result))
    md = [
        "# papers100M-scale host pipeline (1/10 scale)",
        "",
        f"Synthetic papers100M-shaped graph: {g.num_nodes:,} nodes, "
        f"{args.edges:,} directed raw edges -> {g.num_edges:,} finalized "
        f"(mirrored + self loops), {args.feat} features, "
        f"{args.parts} partitions.",
        "",
        "Reference analogue: >=120 GB-RAM host requirement for the real "
        "dataset (reference README.md:29-30). This pipeline memmaps the "
        "raw arrays, builds a finalized-edge cache once (chunked), and "
        "shards with build_chunked (bit-identical to build, O(chunk) "
        "edge scratch).",
        "",
        "| stage | wall (s) | cumulative peak RSS (GB) |",
        "|---|---|---|",
    ]
    for k, v in stages.items():
        md.append(f"| {k} | {v['s']} | {v['peak_rss_gb']} |")
    md += [
        "",
        "The cumulative-peak column (ru_maxrss) shows the RAM story:",
        "the memmap loader + finalized-edge cache stay chunk-bounded;",
        "the remaining peak belongs to the PARTITIONER (multilevel on",
        "the full finalized edge set) — build_chunked and the",
        "compressed save never exceed its high-water mark. The",
        "partitioner is the one stage that scales with E in RAM,",
        "matching where the reference spends its >=120 GB host",
        "(reference README.md:29-30). Round-4 reductions: chunked",
        "counting-sort CSR ingestion (no scipy COO doubling), a",
        "zero-copy implicit-weight level-0 view, int32 coarse weights,",
        "and level-by-level frees during uncoarsening took the",
        "1/10-scale partition peak from 54.9 GB to the table's value.",
        "",
    ]
    # dryrun results are produced rarely (--dryrun) and persisted
    # separately so this wholesale rewrite never clobbers them
    dj = os.path.join(REPO, "results", "papers_dryrun.json")
    if os.path.exists(dj):
        with open(dj) as f:
            md += [
                "64-virtual-device dryrun (structure-identical, reduced "
                "size for the 64-way XLA:CPU compile arena): one "
                "pipelined bucket-kernel training step jitted over the "
                "virtual mesh —",
                "`" + f.read().strip() + "`",
                "",
            ]
    with open(os.path.join(REPO, "results", "papers100m_scale.md"),
              "w") as f:
        f.write("\n".join(md))
    print("# wrote results/papers100m_scale.md", file=sys.stderr)

    if args.dryrun:
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={args.parts}"
        ).strip()
        from pipegcn_tpu.backend import start_measurement

        start_measurement(cpu=True)  # a CPU-mesh dry run by design
        from pipegcn_tpu.models import ModelConfig
        from pipegcn_tpu.parallel import Trainer, TrainConfig

        cfg = ModelConfig(
            layer_sizes=(sg.n_feat, 128, 128, sg.n_class), n_linear=0,
            norm="layer", dropout=0.5, train_size=sg.n_train_global,
            spmm_impl="bucket", dtype="bfloat16",
        )
        t0 = time.time()
        tr = Trainer(sg, cfg, TrainConfig(lr=0.01, enable_pipeline=True,
                                          eval=False))
        loss = tr.train_epoch(0)
        rec = {"dryrun_devices": args.parts,
               "first_step_s": round(time.time() - t0, 1),
               "loss": float(loss),
               "peak_rss_gb": round(rss_gb(), 2)}
        with open(os.path.join(REPO, "results",
                               "papers_dryrun.json"), "w") as f:
            json.dump(rec, f)
        print(json.dumps(rec))


if __name__ == "__main__":
    main()
