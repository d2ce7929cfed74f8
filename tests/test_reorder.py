"""Locality-aware reorder layout (round 9).

Covers the layout contract end to end: the reorder permutation
round-trips against the base layout and is validated on load, old
(pre-reorder) artifacts still load, training/eval semantics are
layout-invariant (losses within float-accumulation noise, eval
bit-parity), the tuner signature keys on the layout, and the
bench/report plumbing surfaces the layout with a pinned --json shape.
"""

import dataclasses
import json
import os

import numpy as np
import pytest

from pipegcn_tpu.graph.csr import Graph
from pipegcn_tpu.models import ModelConfig
from pipegcn_tpu.parallel import Trainer, TrainConfig
from pipegcn_tpu.partition import ShardedGraph, partition_graph
from pipegcn_tpu.partition.partitioner import (
    REORDER_MODES,
    reorder_key,
    reorder_suffix,
)


def _mesh_graph(n=20, n_feat=12, n_class=4, seed=0):
    """n x n 2D mesh (400 nodes at the default): regular structure so
    BFS renumbering produces predictable locality, with CONTIGUOUS
    train/val/test segments (an alternating mask would interleave the
    train-first base layout and destroy every gather run)."""
    ii, jj = np.meshgrid(np.arange(n), np.arange(n), indexing="ij")
    nid = ii * n + jj
    right = np.stack([nid[:, :-1].ravel(), nid[:, 1:].ravel()])
    down = np.stack([nid[:-1, :].ravel(), nid[1:, :].ravel()])
    und = np.concatenate([right, down], axis=1)
    N = n * n
    rng = np.random.default_rng(seed)
    ar = np.arange(N)
    return Graph(
        num_nodes=N,
        src=np.concatenate([und[0], und[1]]),
        dst=np.concatenate([und[1], und[0]]),
        ndata={
            "feat": rng.normal(size=(N, n_feat)).astype(np.float32),
            "label": rng.integers(0, n_class, size=N).astype(np.int64),
            "train_mask": ar < N // 2,
            "val_mask": (ar >= N // 2) & (ar < 3 * N // 4),
            "test_mask": ar >= 3 * N // 4,
        })


@pytest.fixture(scope="module")
def mesh():
    return _mesh_graph()


@pytest.fixture(scope="module")
def mesh_layouts(mesh):
    parts = partition_graph(mesh, 2, seed=0)
    sg_b = ShardedGraph.build(mesh, parts, n_parts=2)
    sg_r = ShardedGraph.build(mesh, parts, n_parts=2,
                              reorder="degree-bfs")
    return sg_b, sg_r


# ---------------------------------------------------------------------
# reorder keys + artifact naming


def test_reorder_key_modes_and_suffix(mesh):
    assert reorder_key(mesh, "none") is None
    for mode in ("degree", "bfs", "degree-bfs"):
        assert mode in REORDER_MODES
        key = reorder_key(mesh, mode)
        assert key.shape == (mesh.num_nodes,)
        assert key.dtype == np.int64
    # bfs renumbering is a permutation-derived key: all values distinct
    assert len(np.unique(reorder_key(mesh, "bfs"))) == mesh.num_nodes
    with pytest.raises(ValueError, match="unknown reorder mode"):
        reorder_key(mesh, "hilbert")
    assert reorder_suffix("none") == ""
    assert reorder_suffix("degree-bfs") == "-rdegree-bfs"
    with pytest.raises(ValueError, match="unknown reorder mode"):
        reorder_suffix("hilbert")


# ---------------------------------------------------------------------
# permutation round-trip against the base layout


def test_permutation_round_trip(mesh_layouts):
    sg_b, sg_r = mesh_layouts
    assert sg_b.reorder == "none" and sg_b.reorder_perm is None
    assert sg_r.reorder == "degree-bfs"
    assert sg_r.layout_version == ShardedGraph.LAYOUT_VERSION
    sg_r.validate_layout()  # must not raise
    for r in range(sg_r.num_parts):
        ic = int(sg_r.inner_count[r])
        assert ic == int(sg_b.inner_count[r])
        perm = np.asarray(sg_r.reorder_perm[r, :ic])
        inv = np.asarray(sg_r.reorder_inv[r, :ic])
        # mutually inverse permutations of [0, ic)
        np.testing.assert_array_equal(np.sort(perm), np.arange(ic))
        np.testing.assert_array_equal(inv[perm], np.arange(ic))
        # every node array round-trips through the permutation:
        # reordered local id l is base local id perm[l]
        for arr in ("global_nid", "feat", "label", "in_deg",
                    "train_mask"):
            np.testing.assert_array_equal(
                np.asarray(getattr(sg_r, arr))[r, :ic],
                np.asarray(getattr(sg_b, arr))[r, perm], err_msg=arr)
        # padding rows of the permutation are -1
        assert (np.asarray(sg_r.reorder_perm[r, ic:]) == -1).all()
    # train-first invariant survives the reorder sort key
    for r in range(sg_r.num_parts):
        t = int(sg_r.train_count[r])
        assert sg_r.train_mask[r, :t].all()
        assert not sg_r.train_mask[r, t:].any()


def test_reordered_artifact_roundtrip_and_validation(mesh_layouts,
                                                     tmp_path):
    _, sg_r = mesh_layouts
    path = str(tmp_path / "part_r")
    sg_r.save(path)
    sg2 = ShardedGraph.load(path)  # load() validates reordered layouts
    assert sg2.reorder == "degree-bfs"
    np.testing.assert_array_equal(sg2.reorder_perm, sg_r.reorder_perm)
    np.testing.assert_array_equal(sg2.reorder_inv, sg_r.reorder_inv)


def test_old_artifact_backward_compat(mesh_layouts, tmp_path):
    """A pre-reorder (layout v1) artifact — no reorder keys in the
    manifest, no permutation arrays — must load as reorder='none'."""
    sg_b, _ = mesh_layouts
    path = str(tmp_path / "part_v1")
    sg_b.save(path)
    mpath = os.path.join(path, "manifest.json")
    with open(mpath) as f:
        manifest = json.load(f)
    manifest.pop("reorder", None)
    manifest.pop("layout_version", None)
    with open(mpath, "w") as f:
        json.dump(manifest, f)
    sg2 = ShardedGraph.load(path)
    assert sg2.reorder == "none"
    assert sg2.layout_version == 1
    assert sg2.reorder_perm is None and sg2.reorder_inv is None
    for k in ShardedGraph._ARRAYS:
        np.testing.assert_array_equal(getattr(sg2, k), getattr(sg_b, k))


def test_validate_layout_named_errors(mesh_layouts):
    sg_b, sg_r = mesh_layouts
    # reorder tag without permutation arrays: metadata inconsistency
    broken = dataclasses.replace(sg_b, reorder="degree-bfs")
    with pytest.raises(ValueError,
                       match="boundary-slot validation.*inconsistent"):
        broken.validate_layout()
    # permutation arrays that are not mutually inverse
    perm = np.array(sg_r.reorder_perm)
    perm[0, 0], perm[0, 1] = perm[0, 1], perm[0, 0]
    with pytest.raises(ValueError,
                       match="boundary-slot validation.*inverse"):
        dataclasses.replace(sg_r, reorder_perm=perm).validate_layout()
    # a send list naming a non-inner local id
    idx = np.array(sg_r.send_idx)
    assert sg_r.send_counts[0, 0] > 0  # the mesh has a real boundary
    idx[0, 0, 0] = 10**6
    with pytest.raises(ValueError,
                       match="boundary-slot validation.*send_idx"):
        dataclasses.replace(sg_r, send_idx=idx).validate_layout()


# ---------------------------------------------------------------------
# training/eval semantics are layout-invariant


def _trainer(sg, g, **cfg_kw):
    cfg = ModelConfig(
        layer_sizes=(g.ndata["feat"].shape[1], 16,
                     int(g.ndata["label"].max()) + 1),
        dropout=0.0, train_size=int(g.ndata["train_mask"].sum()),
        **cfg_kw)
    return Trainer(sg, cfg, TrainConfig(seed=3, eval=False))


def test_eval_bit_parity_and_training_losses(mesh, mesh_layouts):
    sg_b, sg_r = mesh_layouts
    t_b = _trainer(sg_b, mesh)
    t_r = _trainer(sg_r, mesh)
    # identical init (layout-independent): full-graph eval logits are
    # bit-identical, and the SHARDED eval — which runs through the
    # reordered layout's halo exchange — produces the exact same
    # integer counts
    h_b = t_b.eval_dispatch(mesh, "val_mask")
    h_r = t_r.eval_dispatch(mesh, "val_mask")
    np.testing.assert_array_equal(np.asarray(h_b[2]),
                                  np.asarray(h_r[2]))
    s_b = t_b.eval_dispatch(mesh, "val_mask", sharded=True)
    s_r = t_r.eval_dispatch(mesh, "val_mask", sharded=True)
    np.testing.assert_array_equal(np.asarray(s_b[2]),
                                  np.asarray(s_r[2]))
    # training is an ordering-insensitive computation up to float
    # accumulation order: per-epoch losses agree to rtol 1e-5
    l_b = [t_b.train_epoch(e) for e in range(4)]
    l_r = [t_r.train_epoch(e) for e in range(4)]
    np.testing.assert_allclose(l_b, l_r, rtol=1e-5)
    # and the trained models evaluate to the same accuracy
    a_b = t_b.evaluate(mesh, "val_mask")
    a_r = t_r.evaluate(mesh, "val_mask")
    assert abs(a_b - a_r) < 0.02


def test_two_process_mesh_reorder(tmp_path):
    """Halo correctness under reorder across a REAL two-process CPU
    mesh (test_multihost's localhost rendezvous): both processes drive
    one partition each of the same SPMD job under the base and the
    reordered layout; losses must agree across layouts AND be
    identical across ranks (same SPMD program)."""
    import socket
    import subprocess
    import sys

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    driver = tmp_path / "driver.py"
    driver.write_text(
        "import sys\n"
        "import numpy as np\n"
        "import jax\n"
        "jax.config.update('jax_cpu_collectives_implementation',"
        " 'gloo')\n"
        f"jax.distributed.initialize('127.0.0.1:{port}', 2,"
        " int(sys.argv[1]))\n"
        "from tests.test_reorder import _mesh_graph, _trainer\n"
        "from pipegcn_tpu.partition import ShardedGraph, "
        "partition_graph\n"
        "g = _mesh_graph(14)\n"
        "parts = partition_graph(g, 2, seed=0)\n"
        "losses = {}\n"
        "for mode in ('none', 'degree-bfs'):\n"
        "    sg = ShardedGraph.build(g, parts, n_parts=2, reorder=mode)\n"
        "    t = _trainer(sg, g)\n"
        "    losses[mode] = [round(float(t.train_epoch(e)), 6)\n"
        "                    for e in range(3)]\n"
        "np.testing.assert_allclose(losses['none'],"
        " losses['degree-bfs'], rtol=1e-5)\n"
        "print('LOSSES', losses['none'])\n")
    env = {
        **os.environ,
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
        "PYTHONPATH": repo,
    }
    procs = [subprocess.Popen(
        [sys.executable, str(driver), str(rank)],
        env=env, cwd=repo, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True) for rank in (0, 1)]
    outs = [p.communicate(timeout=240)[0] for p in procs]
    for rank, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"rank {rank} failed:\n{out[-2000:]}"
    tails = [[ln for ln in o.splitlines() if ln.startswith("LOSSES")]
             for o in outs]
    assert tails[0] and tails[0] == tails[1], outs


# ---------------------------------------------------------------------
# tuner signature + artifact resolution


def test_tuner_signature_keys_on_layout(tmp_path):
    from pipegcn_tpu.ops import tuner

    base = dict(width=16, block_tile=256, bucket_merge=0,
                chunk_edges=None)
    sig_old = tuner.signature_for(**base)
    assert sig_old["reorder"] == "none"
    assert sig_old["layout_version"] == 1
    sig_new = tuner.signature_for(**base, reorder="degree-bfs",
                                  layout_version=2)
    assert sig_new != sig_old
    # a tuning.json persisted for one layout is rejected for another
    # (forces exactly one re-tune instead of trusting stale timings)
    tuner.save_tuning(str(tmp_path), {
        "tuner_format": tuner.TUNER_FORMAT,
        "signature": sig_old, "source_edge_checksum": 1,
        "winner": {"name": "bucket", "impl": "bucket"}, "table": []})
    rec, why = tuner.load_tuning(str(tmp_path), expect_checksum=1,
                                 signature=sig_new)
    assert rec is None and "signature" in why
    rec, why = tuner.load_tuning(str(tmp_path), expect_checksum=1,
                                 signature=sig_old)
    assert rec is not None and why is None


def test_resolve_reorder_prefers_existing_artifacts(tmp_path):
    from pipegcn_tpu.partition.bench_artifact import (
        artifact_path,
        resolve_reorder,
    )

    root = str(tmp_path)
    # concrete modes pass through untouched, artifact or not
    assert resolve_reorder(1, 1024, True, root, "degree",
                           log=lambda m: None) == "degree"
    # auto with no artifacts on disk would fall to measurement; with a
    # reordered artifact present it must reuse it (cheapest path)
    p = artifact_path(1, 1024, True, root, "degree-bfs")
    os.makedirs(p)
    with open(os.path.join(p, "manifest.json"), "w") as f:
        json.dump({}, f)
    assert resolve_reorder(1, 1024, True, root, "auto",
                           log=lambda m: None) == "degree-bfs"


# ---------------------------------------------------------------------
# report plumbing: the layout that produced the number, pinned --json


def test_report_surfaces_reorder(tmp_path, capsys):
    from pipegcn_tpu.cli.report import main as report_main
    from pipegcn_tpu.cli.report import summarize_run
    from pipegcn_tpu.obs import MetricsLogger, read_metrics

    p = tmp_path / "bench.jsonl"
    with MetricsLogger(p) as ml:
        ml.run_header(config={}, device={}, mesh={})
        ml.event("bench", metric="small_epoch_time", value=1.25,
                 unit="s/epoch", vs_baseline=1.0,
                 reorder="degree-bfs")
    s = summarize_run(read_metrics(p))
    # the pinned --json shape the bench trajectory consumes
    assert s["reorder"] == "degree-bfs"
    assert report_main([str(p), "--json"]) == 0
    assert json.loads(capsys.readouterr().out)["reorder"] == "degree-bfs"
    assert report_main([str(p)]) == 0
    assert "degree-bfs" in capsys.readouterr().out
