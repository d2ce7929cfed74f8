"""A later PR adds a configuration, a job, a plain reference, its model's
count of a step's work, a per-layer metric and a cell as files and entries,
and edits no file that exists. Shown on a temp copy."""

import hashlib
import json
import os
import shutil

import pytest

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

    config = {
        "name": "extra-sage-2x32", "source": "a test",
        "dataset": "synthetic:1200:8:16:5:ml", "graph_seed": 0,
        "model": "graphsage", "n_layers": 2, "n_hidden": 32,
        "dropout": 0.2, "lr": 0.01, "log_every": 5, "no_eval": True,
        "dtype": "bfloat16", "fused_epochs": 3, "local_reorder": "cluster"}
    write("configs/extra-sage-2x32.json", config)
    # the same model under a count of the test's own: a configuration of
    # another model family brings its count as a file, named by `work`
    write("configs/extra-twice-2x32.json",
          dict(config, name="extra-twice-2x32", work="twice-sage"))
    write("model_work/twice-sage.py",
          '"""GraphSAGE\'s count with `flops` doubled."""\n'
          'import os\n\n'
          'from benchmark import harness\n\n\n'
          'def epoch_work(facts, flags, itemsize):\n'
          '    sage = harness.load_named(os.path.join(os.path.dirname(\n'
          '        __file__), "graphsage.py"), "model_work_")\n'
          '    out = sage.epoch_work(facts, flags, itemsize)\n'
          '    return dict(out, flops=2 * out["flops"])\n')
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
    # a reader sees the cell it reads: its shape, its resolved flags, its
    # entries, the program's stream, and device seconds by scope path
    write("layer_metrics/cell_probe.py",
          '"""Made of what `ctx` carries of the cell."""\n\n\n'
          'def read(ctx):\n'
          '    facts, flags = ctx["facts"], ctx["flags"]\n'
          '    paths = ctx["trace"]["path_s"]\n'
          '    below = [p for p in paths if "spmm" in p.split("/")]\n'
          '    if not below or not ctx["stream"].get("epoch") \\\n'
          '            or ctx["config"]["model"] != flags["model"] \\\n'
          '            or ctx["job"]["args"]["spmm_impl"] != "bucket" \\\n'
          '            or ctx["cell"]["traffic"] != "p1-bucket" \\\n'
          '            or "params" in facts or "part_of_node" in facts:\n'
          '        return None\n'
          '    return (1000 * facts["n_nodes"] + facts["layer_sizes"][1]\n'
          '            + flags["fused_epochs"] / 10\n'
          '            + (paths[below[0]] > 0) / 100)\n')
    limits = {"limits": {"loss1_gap": 0.003, "loss_gap": 0.01, "mu_gap": 0.1,
                         "mu_dir": 0.3}}
    write("limits/extra_p1_bucket.json", limits)
    write("limits/twice_p1_bucket.json", limits)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        b = json.load(f)
    b["configs"].append({"name": "extra-sage-2x32", "source": "a test",
                         "file": "benchmark/configs/extra-sage-2x32.json",
                         "reduced": [], "why": "the test's"})
    b["configs"].append({"name": "extra-twice-2x32", "source": "a test",
                         "file": "benchmark/configs/extra-twice-2x32.json",
                         "reduced": [], "why": "the test's"})
    b["workloads"].append({"name": "extra_p1_bucket",
                           "config": "extra-sage-2x32",
                           "traffic": "p1-bucket", "chips": 1,
                           "why": "the test's"})
    b["workloads"].append({"name": "twice_p1_bucket",
                           "config": "extra-twice-2x32",
                           "traffic": "p1-bucket", "chips": 1,
                           "why": "the test's"})
    b["per_layer"].append({"name": "cell_probe", "unit": "1",
                           "better": "higher", "source": "program_counter",
                           "layer": "train step", "moves": "epoch_s",
                           "workloads": ["extra_p1_bucket",
                                         "twice_p1_bucket"]})
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
    # 1200 nodes, hidden 32, scans of 3, and seconds on a path below spmm
    assert line["metrics"]["cell_probe"]["value"] == pytest.approx(
        1200032.31, abs=1e-6)

    def epoch_flops(ln):     # step_mfu is operations over time and peak
        return ln["metrics"]["step_mfu"]["value"] * ln["device"]["window_s"]

    rc, twice, _ = run_cell(root, "twice_p1_bucket", 1, monkeypatch)
    assert rc == 0 and twice["correct"] is True, twice
    assert epoch_flops(twice) == pytest.approx(2 * epoch_flops(line),
                                               rel=1e-9)
    # the other readers of `work` read the same: its passes did not change
    assert twice["metrics"]["cell_probe"] == line["metrics"]["cell_probe"]
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


def test_a_model_without_a_count_of_its_work_gives_no_result(tmp_path,
                                                             monkeypatch):
    """Never GraphSAGE's count under another model's name: a configuration
    whose model (or `work`) names no file under `model_work/` gets no
    result line, traced or not, before any set-up."""
    root = make_tiny_root(tmp_path)
    bench = os.path.join(root, "benchmark")
    with open(os.path.join(bench, "configs", "reddit-sage-4x256.json")) as f:
        config = json.load(f)
    for name, change in (("a-gcn", {"model": "gcn"}),
                         ("a-sage", {"work": "nowhere"})):
        with open(os.path.join(bench, "configs", name + ".json"), "w") as f:
            json.dump(dict(config, name=name, **change), f)
        shutil.copy(os.path.join(bench, "limits", "reddit_p1_auto.json"),
                    os.path.join(bench, "limits", name + "_p1.json"))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        b = json.load(f)
    for name in ("a-gcn", "a-sage"):
        b["configs"].append({"name": name, "source": "a test",
                             "file": f"benchmark/configs/{name}.json",
                             "reduced": [], "why": "the test's"})
        b["workloads"].append({"name": name + "_p1", "config": name,
                               "traffic": "p1-auto", "chips": 1,
                               "why": "the test's"})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(b, f)
    for cell in ("a-gcn_p1", "a-sage_p1"):
        for trace in (0, 1):
            rc, line, _ = run_cell(root, cell, trace, monkeypatch)
            assert rc == 3 and line is None
    assert not os.path.exists(os.path.join(bench, "out"))   # nothing ran
