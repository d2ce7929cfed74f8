"""`trace_reduce.py` and `work.py` against hand-worked numbers, and on the
small recorded chip trace under `testdata/`."""

import glob
import os

import pytest

from conftest import BENCH

from benchmark import trace_reduce as T
from benchmark import work


def test_busy_is_the_union_of_one_devices_op_line():
    # a while op spanning two body ops, then a gap, then one more op: the
    # module, step and op lines of a TPU plane would each add the same 9
    tr = {"devices": {0: [["while.1", 0.0, 6e9], ["fusion.1", 0.0, 2e9],
                          ["fusion.2", 3e9, 3e9], ["copy.1", 8e9, 2e9]]},
          "op_text": {"fusion.1": "fusion.1 jit(multi)/layer1/spmm/gather",
                      "fusion.2": "fusion.2 jit(multi)/layer1/dense/dot"},
          "host": [["PjitFunction(multi)", 6.5e9, 1e9]], "layout": []}
    red = T.reduce_trace(tr, 1)
    assert red["window_s"] == pytest.approx(10.0)
    assert red["busy_s"] == pytest.approx(8.0)      # not 13, the plain sum
    assert red["scope_s"]["spmm"] == pytest.approx(2.0)
    assert red["scope_s"]["dense"] == pytest.approx(3.0)
    # the while's self time is what its children leave: 6 - 2 - 3
    assert dict(red["ops"])["while.1 [other]"] == pytest.approx(1.0)
    assert red["idle_gaps"][0] == ["host: PjitFunction(multi)",
                                   pytest.approx(2.0)]
    assert sum(red["scope_s"].values()) <= red["busy_s"] + 1e-9


def test_mean_over_devices_and_clipping_to_the_devices_used():
    tr = {"devices": {0: [["a", 0.0, 4e9]], 1: [["a", 0.0, 2e9]],
                      2: [["a", 0.0, 9e9]]},
          "op_text": {}, "host": [], "layout": []}
    red = T.reduce_trace(tr, 2)
    assert red["window_s"] == pytest.approx(4.0)
    assert red["busy_s"] == pytest.approx(3.0)


def test_scope_join_through_hlo_metadata():
    hlo = '''
  %fusion.7 = bf16[8,4]{1,0} fusion(%p0), kind=kLoop, calls=%c, metadata={op_name="jit(multi)/while/body/transpose(jvp(layer2))/spmm/reduce_sum" source_file="x.py"}
  ROOT %dot.3 = f32[8,4]{1,0} dot(%a, %b), metadata={op_name="jit(multi)/while/body/layer0/dense/dot_general"}
  %copy.1 = f32[8]{0} copy(%z)
'''
    scopes = T.hlo_scope_map(hlo)
    assert T.scope_of(scopes["fusion.7"]) == "spmm"
    assert T.scope_of(scopes["dot.3"]) == "dense"
    assert "copy.1" not in scopes
    tr = {"devices": {0: [["fusion.7", 0.0, 1e9], ["dot.3", 1e9, 1e9],
                          ["copy.1", 2e9, 1e9]]},
          "op_text": {}, "host": [], "layout": []}
    assert T.reduce_trace(tr, 1)["scope_s"] == {"other": pytest.approx(3.0)}
    joined = T.reduce_trace(tr, 1, [scopes])["scope_s"]
    assert joined == {"spmm": pytest.approx(1.0),
                      "dense": pytest.approx(1.0),
                      "other": pytest.approx(1.0)}
    assert T.scope_of("jit(f)/normalize/mul") == "other"   # not `norm`


def test_work_counts_for_a_three_node_graph():
    # a path 0-1-2 with self loops: 7 directed edges; sizes 5 -> 4 -> 3,
    # no dense tail, first aggregation precomputed
    w = work.epoch_work(3, 7, (5, 4, 3), 0, True, 2)
    # layer 0: one product over concat (10 wide), no input gradient:
    #   2*3*10*4 * 2 = 480; layer 1: two products of 4x3, three passes:
    #   2 * (2*3*4*3) * 3 = 432
    assert w["linear_flops"] == 480 + 432
    # one in-step aggregation of width 4, forward and backward: 2*7*4 * 2
    assert w["aggregation_flops"] == 112
    assert w["flops"] == 480 + 432 + 112
    # operand + result 2*3*4*2 = 48 bytes; adjacency: 9 cells as bits = 2
    # bytes, under the 4*7 + 4*4 = 44 of an index list
    assert [p["min_bytes"] for p in w["aggregation_passes"]] == [50, 50]
    peaks = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    least = work.aggregation_least_s(w, peaks)
    assert least == {"least_s": pytest.approx(10.0), "bound": "bytes"}
    # without the precompute both layers aggregate in the step
    assert work.in_step_aggregations((5, 4, 3), 0, False) == [5, 4]
    with pytest.raises(LookupError):
        work.peaks_for("cpu")


def test_compulsory_bytes_never_reach_edges_times_width():
    n, e, f = 232_965, 114_848_857, 256
    assert work.aggregation_min_bytes(n, e, f, 2) < e * f * 2 / 50


RECORDED = sorted(glob.glob(os.path.join(BENCH, "testdata",
                                         "chip_trace_*.json.gz")))


@pytest.mark.parametrize("path", RECORDED, ids=os.path.basename)
def test_recorded_chip_trace(path):
    tr = T.load_recorded(path)
    assert set(T.reduce_trace(tr, 1)["scope_s"]) == {"other"}, \
        "the chip's trace names no scope itself: the join is what finds them"
    red = T.reduce_trace(tr, 1, tr["hlo_scopes"])
    assert 0 < red["busy_s"] <= red["window_s"]
    assert sum(red["scope_s"].values()) <= red["busy_s"] * (1 + 1e-9)
    assert red["scope_s"].get("spmm", 0) > 0, "the scope join found no spmm"
    plain_sum = sum(e[2] for e in tr["devices"][0]) * 1e-9
    assert plain_sum >= red["busy_s"]
    assert len(red["ops"]) > 10


def test_a_recorded_chip_trace_is_kept():
    assert RECORDED, "benchmark/testdata/ holds no recorded chip trace"


def test_the_one_off_recorder_writes_what_the_reduction_reads(
        tiny_root, monkeypatch):
    import jax

    from benchmark import harness, record_trace

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                       os.path.join(tiny_root, "jax_cache"))
    monkeypatch.setattr(harness, "require_device", lambda chips: {
        "platform": "cpu", "kind": jax.devices()[0].device_kind,
        "count": 1})
    assert record_trace.main(["--workload", "reddit_p1_block"],
                             tiny_root) == 0
    out = os.path.join(tiny_root, "benchmark", "out", "reddit_p1_block")
    tr = T.load_recorded(os.path.join(
        out, "chip_trace_reddit_p1_block.json.gz"))
    red = T.reduce_trace(tr, 1, tr.get("hlo_scopes", []))
    assert 0 < red["busy_s"] <= red["window_s"]
    assert os.path.getsize(os.path.join(out, "trace_layout.txt")) > 0
