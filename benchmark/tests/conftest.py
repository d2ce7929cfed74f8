"""Shared helpers of the benchmark's own tests: a temp copy of the
benchmark at a tiny shape, and an in-process run of a cell on the CPU with
the harness's look for a chip lifted (by the test, never by an option)."""

import json
import os
import shutil
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)
if REPO not in sys.path:
    sys.path.insert(0, REPO)

TINY = {"synthetic:716847:10:300:100:ml": "synthetic:1500:10:24:10:ml",
        "synthetic:232965:492:602:41": "synthetic:1500:12:32:8"}


# limits for the tiny shape: a 1,500-node graph reads noisier than the
# cells do on the chip, so the cells' own limits do not apply here
LOOSE = {"loss1_gap": 0.003, "gnorm1_gap": 0.02, "loss_gap": 0.01,
         "gnorm_gap": 0.05, "mu_gap": 0.1, "delta_gap": 0.3, "mu_dir": 0.3}


def make_tiny_root(tmp_path, limits=None) -> str:
    """Copy BENCHMARK.json and the benchmark's data files into `tmp_path`
    with every configuration cut to a tiny graph and width 32, and every
    cell's limits replaced by `limits` (LOOSE by default)."""
    root = str(tmp_path)
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    dst = os.path.join(root, "benchmark")
    for sub in ("configs", "jobs", "layer_metrics", "limits", "model_work",
                "references"):
        shutil.copytree(os.path.join(BENCH, sub), os.path.join(dst, sub))
    for name in os.listdir(os.path.join(dst, "configs")):
        path = os.path.join(dst, "configs", name)
        with open(path) as f:
            cfg = json.load(f)
        cfg["dataset"] = TINY.get(cfg["dataset"], "synthetic:1500:12:32:8")
        cfg["n_hidden"] = 32
        with open(path, "w") as f:
            json.dump(cfg, f)
    for name in os.listdir(os.path.join(dst, "limits")):
        with open(os.path.join(dst, "limits", name), "w") as f:
            json.dump({"limits": limits or LOOSE}, f)
    return root


def run_cell(root, workload, trace, monkeypatch, seed=7, seconds=0.2,
             patch=None):
    """(exit code, last stdout line parsed, stderr text) of one run."""
    import time

    from benchmark import harness

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                       os.path.join(root, "jax_cache"))

    def on_cpu(chips):
        import jax

        d = jax.devices()
        return {"platform": d[0].platform, "kind": d[0].device_kind,
                "count": len(d)}

    monkeypatch.setattr(harness, "require_device", on_cpu)
    # a CPU has no published peaks: the test lends it the v5e's, so that
    # the readers of shares run (their values mean nothing here)
    from benchmark import work

    v5e = work.peaks_for("TPU v5 lite")
    monkeypatch.setattr(work, "peaks_for", lambda kind: v5e)
    if patch is not None:
        patch(monkeypatch)
    read_fd, write_fd = os.pipe()
    saved_out, saved_sys = os.dup(1), sys.stdout
    os.dup2(write_fd, 1)
    os.close(write_fd)
    try:
        rc = harness.main(["--workload", workload, "--seed", str(seed),
                           "--seconds", str(seconds), "--trace",
                           str(int(trace))], root, time.perf_counter())
    finally:
        os.dup2(saved_out, 1)
        os.close(saved_out)
        sys.stdout = saved_sys
    os.set_blocking(read_fd, False)
    try:
        out = os.read(read_fd, 1 << 22).decode()
    except BlockingIOError:
        out = ""
    os.close(read_fd)
    lines = [ln for ln in out.splitlines() if ln.strip()]
    return rc, (json.loads(lines[-1]) if lines else None), lines


@pytest.fixture
def tiny_root(tmp_path):
    return make_tiny_root(tmp_path)
