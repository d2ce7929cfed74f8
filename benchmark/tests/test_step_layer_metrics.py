"""The train step's per-layer metrics that read the program's named scopes
and host spans (`dense_s`, `elementwise_s`, `host_gap_s`): each on a made
`ctx`, a value and `None`, and the first two on the recorded chip trace."""

import glob
import os

import pytest

from conftest import BENCH

from benchmark import harness
from benchmark import trace_reduce as T


def reader(name):
    return harness.load_reader(BENCH, name)


def ctx(scope_s=None, idle_gaps=None, epochs=10, trace=True):
    return {"trace": {"scope_s": scope_s or {}, "idle_gaps": idle_gaps or [],
                      "window_s": 20.0, "busy_s": 19.0} if trace else {},
            "epochs_traced": epochs}


def test_dense_s_is_the_dense_scope_per_epoch():
    read = reader("dense_s")
    assert read(ctx({"spmm": 9.0, "dense": 1.1, "other": 0.1})) \
        == pytest.approx(0.11)
    assert read(ctx({"spmm": 9.0})) is None        # no such scope
    assert read(ctx({"dense": 0.0})) is None
    assert read(ctx(trace=False)) is None          # no trace at all


def test_elementwise_s_adds_dropout_and_norm():
    read = reader("elementwise_s")
    assert read(ctx({"dropout": 0.12, "norm": 0.07, "dense": 1.0})) \
        == pytest.approx(0.019)
    assert read(ctx({"norm": 0.07})) == pytest.approx(0.007)   # dropout 0
    assert read(ctx({"dropout": 0.12})) == pytest.approx(0.012)  # no norm
    assert read(ctx({"spmm": 9.0, "dense": 1.0})) is None
    assert read(ctx(trace=False)) is None


def test_host_gap_s_counts_the_programs_own_spans_but_the_wait():
    read = reader("host_gap_s")
    gaps = [["host: fit/keys", 0.004], ["host: ReadSyncFlag", 0.009],
            ["host: fit/wait", 0.050], ["host: fit/harvest", 0.003],
            ["host: step", 0.001], ["host: no span", 0.002],
            ["host: PjitFunction(_threefry_fold_in)", 0.006]]
    # keys + harvest + step over 10 epochs; the wait, the runtime's own
    # events and the gaps under no span are not the program's Python
    assert read(ctx(idle_gaps=gaps)) == pytest.approx(0.0008)
    # the parent program opens `step` only
    assert read(ctx(idle_gaps=[["host: step", 0.02],
                               ["host: ReadSyncFlag", 0.01]])) \
        == pytest.approx(0.002)
    # only the wait carries a label of the program: idle, none of it the
    # program's Python
    assert read(ctx(idle_gaps=[["host: fit/wait", 0.05]])) == 0.0
    # no label of the program at all: nothing to read
    assert read(ctx(idle_gaps=[["host: ReadSyncFlag", 0.01],
                               ["host: no span", 0.3]])) is None
    assert read(ctx(idle_gaps=[])) is None
    assert read(ctx(trace=False)) is None


def test_readers_on_a_reduced_trace_with_host_spans():
    """Through `reduce_trace` itself: two scans with a gap between them in
    which the host built keys inside `fit/keys`."""
    tr = {"devices": {0: [["fusion.1", 0.0, 4e9], ["dot.2", 4e9, 1e9],
                          ["fusion.3", 5e9, 5e8],
                          ["fusion.1", 7e9, 4e9]]},
          "op_text": {}, "layout": [],
          "host": [["step", 5.8e9, 5e9], ["fit/keys", 6.0e9, 6e8]]}
    scopes = {"fusion.1": "jit(multi)/while/body/jvp(layer1)/spmm/gather/x",
              "dot.2": "jit(multi)/while/body/jvp(layer1)/dense/dot_general",
              "fusion.3": "jit(multi)/while/body/jvp(layer1)/dropout/mul"}
    red = T.reduce_trace(tr, 1, [scopes])
    c = {"trace": red, "epochs_traced": 2}
    assert reader("dense_s")(c) == pytest.approx(0.5)
    assert reader("elementwise_s")(c) == pytest.approx(0.25)
    # the gap [5.5, 7] lies in `fit/keys`, the innermost span at its middle
    assert red["idle_gaps"][0][0] == "host: fit/keys"
    assert reader("host_gap_s")(c) == pytest.approx(0.75)
    # a scope nested below `spmm` still reads as `spmm`
    assert red["scope_s"]["spmm"] == pytest.approx(8.0)


RECORDED = sorted(glob.glob(os.path.join(BENCH, "testdata",
                                         "chip_trace_*.json.gz")))


@pytest.mark.parametrize("path", RECORDED, ids=os.path.basename)
def test_dense_and_elementwise_on_the_recorded_chip_trace(path):
    """Reddit under `auto`: aggregation is nearly all of the epoch, the
    linears and the elementwise passes each under 2% of it (PERF.md
    section 5)."""
    tr = T.load_recorded(path)
    red = T.reduce_trace(tr, 1, tr["hlo_scopes"])
    c = {"trace": red, "epochs_traced": 1}
    spmm = harness.load_reader(BENCH, "spmm_s")(c)
    dense = reader("dense_s")(c)
    elementwise = reader("elementwise_s")(c)
    assert spmm > 0
    assert 0 < dense < 0.02 * spmm
    assert 0 < elementwise < 0.02 * spmm
    # without the join the trace names no scope: nothing to read
    bare = {"trace": T.reduce_trace(tr, 1), "epochs_traced": 1}
    assert reader("dense_s")(bare) is None
    assert reader("elementwise_s")(bare) is None
