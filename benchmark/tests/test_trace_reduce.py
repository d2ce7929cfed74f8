"""`trace_reduce.py`, `work.py` and GraphSAGE's count of a step's work
against hand-worked numbers, and on the small recorded chip traces under
`testdata/`."""

import glob
import os

import pytest

from conftest import BENCH

from benchmark import harness
from benchmark import trace_reduce as T
from benchmark import work

sage_work = harness.load_named(
    os.path.join(BENCH, "model_work", "graphsage.py"), "model_work_")


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


def test_scope_path_keeps_what_the_program_named():
    assert T.scope_path(
        "jit(multi)/while/body/closed_call/jvp(layer1)/spmm/bwd/rem_gather/"
        "jit(_take)/gather") == "spmm/bwd/rem_gather"
    assert T.scope_path("jit(multi)/while/body/transpose(jvp(layer2))/spmm/"
                        "reduce/reduce_sum") == "spmm/reduce"
    # JAX's structure goes wherever it stands, a bare scope stays
    assert T.scope_path("jit(step)/shard_map/layer0/spmm/gather/while/body/"
                        "cond/branch_1_fun/custom_vjp_call_jaxpr/checkpoint/"
                        "pjit/custom_jvp_call/mul") == "layer0/spmm/gather"
    # the list that decides is JAX's: a name nobody listed is a scope
    assert T.scope_path("jit(multi)/while/body/jvp(layer1)/attention/"
                        "edge_softmax/exp") == "attention/edge_softmax"
    # an einsum names its call by its spec: JAX's, not the program's
    assert T.scope_path("jit(multi)/while/body/closed_call/jvp(layer1)/spmm/"
                        "tile/rduts,rusf->rdtf/dot_general") == "spmm/tile"
    assert T.scope_path("jit(multi)/while/body/add") == ""
    assert T.scope_path("") == "" and T.scope_path("copy") == ""
    # a fusion may list several instructions' names: the first counts
    assert T.scope_path("jit(f)/dense/dot_general;jit(f)/norm/mul") \
        == "dense"


def test_path_s_splits_what_scope_s_holds_as_one():
    """Self seconds by scope path: they add up to `scope_s`'s, the paths
    below `spmm` to `scope_s["spmm"]`, and a scope this file has never
    heard of shows as a path of its own."""
    pre = "jit(multi)/while/body/closed_call/"
    scopes = {
        "fusion.1": pre + "jvp(layer1)/spmm/gather/jit(_take)/gather",
        "fusion.2": pre + "jvp(layer1)/spmm/reduce/reduce_sum",
        "fusion.3": pre + "transpose(jvp(layer1))/spmm/bwd/rem_gather/"
                          "jit(_take)/gather",
        "fusion.4": pre + "jvp(layer1)/spmm/tile/dot_general",
        "fusion.5": pre + "jvp(layer1)/spmm/convert_element_type",
        "fusion.6": pre + "jvp(layer1)/edge_softmax/exp",
        "dot.7": pre + "jvp(layer1)/dense/dot_general",
    }
    durations = {"fusion.1": 4e9, "fusion.2": 2e9, "fusion.3": 3e9,
                 "fusion.4": 1e9, "fusion.5": 5e8, "fusion.6": 7e8,
                 "dot.7": 1.5e9, "copy.8": 2.5e8}
    t, events = 0.0, [["while.0", 0.0, sum(durations.values()) + 1e9]]
    for name, d in durations.items():
        events.append([name, t, d])
        t += d
    tr = {"devices": {0: events, 1: [list(e) for e in events]},
          "op_text": {}, "host": [], "layout": []}
    red = T.reduce_trace(tr, 2, [scopes])
    path_s, scope_s = red["path_s"], red["scope_s"]
    assert path_s["spmm/gather"] == pytest.approx(4.0)
    assert path_s["spmm/bwd/rem_gather"] == pytest.approx(3.0)
    assert path_s["spmm"] == pytest.approx(0.5)        # bare: no finer scope
    assert path_s["edge_softmax"] == pytest.approx(0.7)
    assert scope_s["other"] == pytest.approx(0.7 + 0.25 + 1.0)
    assert path_s[""] == pytest.approx(0.25 + 1.0)     # copy.8, the while
    assert sum(path_s.values()) == pytest.approx(sum(scope_s.values()),
                                                 rel=1e-12)
    under = sum(v for p, v in path_s.items() if "spmm" in p.split("/"))
    assert under == pytest.approx(scope_s["spmm"], rel=1e-12)
    assert T.path_seconds(path_s, "spmm", ("gather", "rem_gather")) \
        == pytest.approx(7.0)
    assert T.path_seconds(path_s, "spmm", ("tile", "unpack")) \
        == pytest.approx(1.0)
    assert T.path_seconds(path_s, "spmm", ("relayout",)) == 0
    # without the join there is no path, and every second is still there
    bare = T.reduce_trace(tr, 2)
    assert set(bare["path_s"]) == {""}
    assert bare["path_s"][""] == pytest.approx(sum(scope_s.values()))


def test_work_counts_for_a_three_node_graph():
    # a path 0-1-2 with self loops: 7 directed edges; sizes 5 -> 4 -> 3,
    # no dense tail, first aggregation precomputed
    w = sage_work.epoch_work(
        {"n_nodes": 3, "n_edges": 7, "layer_sizes": (5, 4, 3)},
        {"n_linear": 0, "use_pp": True}, 2)
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
    assert sage_work.in_step_aggregations((5, 4, 3), 0, False) == [5, 4]
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
    assert sum(red["path_s"].values()) == pytest.approx(
        sum(red["scope_s"].values()), rel=1e-12)
    assert sum(v for p, v in red["path_s"].items()
               if "spmm" in p.split("/")) == pytest.approx(
        red["scope_s"]["spmm"], rel=1e-12)
    plain_sum = sum(e[2] for e in tr["devices"][0]) * 1e-9
    assert plain_sum >= red["busy_s"]
    assert len(red["ops"]) > 10


@pytest.mark.parametrize("reader, seconds", [
    ("spmm_gather_s", 0.246551732), ("spmm_reduce_s", 0.35261015),
    ("spmm_tile_s", 0.036304549)])
def test_the_kernels_layers_on_the_block_cells_recorded_trace(reader,
                                                              seconds):
    """`reddit_p1_block` under PR 26's scopes, the first 6000 events of
    the chip's op line (my chip run, PR 28): forward passes of the first
    scan's first epoch, so `rem_gather`, `rem_reduce`, `tile` (with the
    einsum below it, named by its spec) and `unpack`."""
    tr = T.load_recorded(os.path.join(
        BENCH, "testdata", "chip_trace_reddit_p1_block.json.gz"))
    red = T.reduce_trace(tr, 1, tr["hlo_scopes"])
    read = harness.load_reader(BENCH, reader)
    assert read({"trace": red, "epochs_traced": 1}) == pytest.approx(
        seconds, rel=1e-9)
    assert read({"trace": red, "epochs_traced": 4}) == pytest.approx(
        seconds / 4, rel=1e-9)
    # nothing to read: no trace, or one whose operations carry no such path
    assert read({"trace": {}, "epochs_traced": 1}) is None
    assert read({"trace": T.reduce_trace(tr, 1), "epochs_traced": 1}) is None
    assert "spmm/tile" in red["path_s"] and not any(
        "->" in p for p in red["path_s"])


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
