"""The result line of every cell in BENCHMARK.json, untraced and traced,
against the contract's wording (the check PR 22 failed on its second
configuration's traced run)."""

import json
import math
import os

import pytest

from conftest import REPO, run_cell

with open(os.path.join(REPO, "BENCHMARK.json")) as f:
    BENCHMARK = json.load(f)
CELLS = [w["name"] for w in BENCHMARK["workloads"]]
# readers that may find nothing to read on the CPU at the tiny shape: its
# compiler fuses a bucket's sum into the operation behind it (the seconds
# read `spmm/scale` here), and 1,500 nodes leave the block kernel no dense
# tile. On the chip the driver refuses a traced line that lacks them
SILENT_ON_CPU = {"spmm_reduce_s", "spmm_tile_s"}


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", CELLS)
def test_result_line(tiny_root, monkeypatch, cell, trace):
    rc, line, lines = run_cell(tiny_root, cell, trace, monkeypatch)
    assert rc == 0
    assert len(lines) == 1, "nothing but the result may reach stdout"
    assert set(line) >= {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert line["correct"] is True, line["compared"]
    assert line["attempted"] > 0 and line["failed"] == 0
    kind = "per_layer" if trace else "end_to_end"
    listed = [m for m in BENCHMARK[kind]
              if cell in m.get("workloads", [cell])]
    if trace:
        assert SILENT_ON_CPU <= {m["name"] for m in BENCHMARK[kind]}
        listed = [m for m in listed if m["name"] in line["metrics"]
                  or m["name"] not in SILENT_ON_CPU]
    for m in listed:
        got = line["metrics"][m["name"]]
        assert got["unit"] == m["unit"]
        assert math.isfinite(got["value"])
        if m["unit"] == "%":
            assert 0.0 <= got["value"] <= 100.0
    assert len(line["metrics"]) == len(listed)
    dev = line["device"]
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(dev)
    if trace:
        assert 0 < dev["busy_s"] <= dev["window_s"]
        assert len(line["breakdown"]["device_ops"]) <= 10
        assert len(line["breakdown"]["idle_gaps"]) <= 10
    assert list(line)[-1] == "compared"
    for row in line["compared"].values():
        assert row["value"] <= row["limit"]
