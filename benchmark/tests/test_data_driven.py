"""A later PR adds a configuration, a job, a plain reference, a per-layer
metric and a cell as files and entries, and edits no file that exists.
Shown on a temp copy."""

import hashlib
import json
import os
import shutil

from conftest import make_tiny_root, run_cell


def _digests(root):
    out = {}
    for base, _, files in os.walk(os.path.join(root, "benchmark")):
        for name in files:
            path = os.path.join(base, name)
            with open(path, "rb") as f:
                out[path] = hashlib.sha256(f.read()).hexdigest()
    return out


def test_add_a_cell_without_editing_a_file(tmp_path, monkeypatch):
    root = make_tiny_root(tmp_path)
    before = _digests(root)
    bench = os.path.join(root, "benchmark")

    def write(rel, obj):
        path = os.path.join(bench, rel)
        assert not os.path.exists(path)
        with open(path, "w") as f:
            f.write(obj if isinstance(obj, str) else json.dumps(obj))

    write("configs/extra-sage-2x32.json", {
        "name": "extra-sage-2x32", "source": "a test",
        "dataset": "synthetic:1200:8:16:5:ml", "graph_seed": 0,
        "model": "graphsage", "n_layers": 2, "n_hidden": 32,
        "dropout": 0.2, "lr": 0.01, "log_every": 5, "no_eval": True,
        "dtype": "bfloat16", "fused_epochs": 3, "local_reorder": "cluster"})
    write("jobs/p1-bucket.json",
          {"args": {"n_partitions": 1, "spmm_impl": "bucket"},
           "reference": "sage-of-the-test"})
    # the job names its plain reference: a file with `follow(...)`
    shutil.copy(os.path.join(bench, "references", "graphsage.py"),
                os.path.join(bench, "references", "sage-of-the-test.py"))
    write("layer_metrics/dropout_s.py",
          '"""Device time per epoch under the `dropout` scope."""\n\n\n'
          'def read(ctx):\n'
          '    t = ctx["trace"]\n'
          '    if not t or not t["scope_s"].get("dropout"):\n'
          '        return None\n'
          '    return t["scope_s"]["dropout"] / ctx["epochs_traced"]\n')
    write("limits/extra_p1_bucket.json",
          {"limits": {"loss1_gap": 0.003, "loss_gap": 0.01, "mu_gap": 0.1,
                      "mu_dir": 0.3}})
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        b = json.load(f)
    b["configs"].append({"name": "extra-sage-2x32", "source": "a test",
                         "file": "benchmark/configs/extra-sage-2x32.json",
                         "reduced": [], "why": "the test's"})
    b["workloads"].append({"name": "extra_p1_bucket",
                           "config": "extra-sage-2x32",
                           "traffic": "p1-bucket", "chips": 1,
                           "why": "the test's"})
    b["per_layer"].append({"name": "dropout_s", "unit": "s/epoch",
                           "better": "lower", "source": "device_trace",
                           "layer": "train step", "moves": "epoch_s",
                           "workloads": ["extra_p1_bucket"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(b, f)

    rc, line, _ = run_cell(root, "extra_p1_bucket", 1, monkeypatch)
    assert rc == 0 and line["correct"] is True, line
    assert line["metrics"]["dropout_s"]["value"] > 0
    assert "step_mfu" in line["metrics"]       # no `workloads` key: every cell
    # log_every 5, fused 3: the cycle is 10 epochs in blocks of 3, 2, 3, 2
    assert line["attempted"] == 10
    rc, line, _ = run_cell(root, "extra_p1_bucket", 0, monkeypatch)
    assert rc == 0 and set(line["metrics"]) == {"epoch_s", "setup_s"}
    # an old cell does not report the new cell's metric, and still runs
    rc, line, _ = run_cell(root, "reddit_p1_auto", 1, monkeypatch)
    assert rc == 0 and "dropout_s" not in line["metrics"]
    after = _digests(root)
    assert {p: d for p, d in after.items() if p in before} == before


def test_a_reference_that_is_not_there_gives_no_result(tmp_path,
                                                       monkeypatch):
    root = make_tiny_root(tmp_path)
    path = os.path.join(root, "benchmark", "jobs", "p1-auto.json")
    with open(path) as f:
        job = json.load(f)
    job["reference"] = "nowhere"
    with open(path, "w") as f:
        json.dump(job, f)
    rc, line, _ = run_cell(root, "reddit_p1_auto", 0, monkeypatch)
    assert rc == 3 and line is None
