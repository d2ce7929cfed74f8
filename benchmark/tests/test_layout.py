"""The benchmark's file layout: whatever `BENCHMARK.json` names is there,
and whatever is there is named. No JAX, no run: a cell whose work file or
reader is missing would otherwise show only on the chip."""

import json
import os

import pytest

from conftest import BENCH, REPO

with open(os.path.join(REPO, "BENCHMARK.json")) as f:
    BENCHMARK = json.load(f)
CONFIGS = {c["name"]: c for c in BENCHMARK["configs"]}
CELLS = {w["name"]: w for w in BENCHMARK["workloads"]}
METRICS = BENCHMARK["end_to_end"] + BENCHMARK["per_layer"]


def _config(cell):
    with open(os.path.join(REPO, CONFIGS[cell["config"]]["file"])) as f:
        return json.load(f)


@pytest.mark.parametrize("cell", CELLS.values(), ids=list(CELLS))
def test_every_file_a_cell_is_found_by_is_there(cell):
    config = _config(cell)
    with open(os.path.join(BENCH, "jobs", cell["traffic"] + ".json")) as f:
        job = json.load(f)
    reference = job.get("reference", config.get("model", "graphsage"))
    work = config.get("work", config.get("model"))
    for sub, name in (("references", reference + ".py"),
                      ("model_work", f"{work}.py"),
                      ("limits", cell["name"] + ".json")):
        assert os.path.exists(os.path.join(BENCH, sub, name)), (sub, name)
    with open(os.path.join(BENCH, "limits", cell["name"] + ".json")) as f:
        assert json.load(f)["limits"], "a cell with no limit compares nothing"


@pytest.mark.parametrize("metric", BENCHMARK["per_layer"],
                         ids=[m["name"] for m in BENCHMARK["per_layer"]])
def test_every_per_layer_metric_has_its_reader(metric):
    path = os.path.join(BENCH, "layer_metrics", metric["name"] + ".py")
    with open(path) as f:
        assert "def read(ctx)" in f.read()
    assert metric["moves"] in {m["name"] for m in BENCHMARK["end_to_end"]}


def test_every_reader_has_its_entry():
    readers = {name[:-3] for name in os.listdir(
        os.path.join(BENCH, "layer_metrics")) if name.endswith(".py")}
    assert readers == {m["name"] for m in BENCHMARK["per_layer"]}


@pytest.mark.parametrize("metric", METRICS, ids=[m["name"] for m in METRICS])
def test_a_metrics_cells_exist(metric):
    assert set(metric.get("workloads", [])) <= set(CELLS)


def test_every_configuration_is_used_and_every_cell_has_one():
    assert {w["config"] for w in CELLS.values()} == set(CONFIGS)
