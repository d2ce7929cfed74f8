"""The set-up's per-layer metrics that read the program's set-up spans
(`runtime_start_s`, `step_lower_s`, `step_cache_load_s`,
`step_backend_compile_s`): each on a stream the program recorded on the
CPU (one P=1 run with the pp precompute and two scan lengths, cut to the
records the readers see), and `None` where no record carries its field."""

import pytest

from conftest import BENCH

from benchmark import harness


def reader(name):
    return harness.load_reader(BENCH, name)


def _span(op, span_id, dur_ms, trace_id="setup", parent=None, **counters):
    rec = {"event": "span", "trace_id": trace_id, "span_id": span_id,
           "op": op, "t_start": 1792112477.0, "dur_ms": dur_ms,
           "status": "ok", "source": "r0"}
    if counters:
        rec.update({"lower_s": 0.0, "cache_hits": 0, "cache_load_s": 0.0,
                    "compiles": 0, "compile_s": 0.0, "programs": []},
                   **counters)
    if trace_id == "setup":
        rec["parent"] = parent
    return rec


# the set-up spans of a recorded run, with the counters as it wrote them
# (a second process on the same cache turned the compiles of `fit/load`
# and `init_state` into loads: the last two records)
RECORDED = [
    _span("setup/process", "s0", 2797.5048450025497, lower_s=0.0),
    _span("setup/runtime", "s1", 18.518614997447003, lower_s=0.0),
    _span("setup/graph", "s3", 9.583090999512933, lower_s=0.0),
    _span("pp_precompute", "s7", 356.1297839987674, parent="s4",
          lower_s=0.119498, compiles=1, compile_s=0.229565,
          programs=["jit(pp)"]),
    _span("init_state", "s8", 1571.3874809953268, parent="s4",
          lower_s=0.239159, compiles=13, compile_s=1.299934),
    _span("setup/trainer", "s4", 1938.2055570022203, lower_s=0.0),
    _span("fit/load", "s12", 1721.3312339954427, parent="s11",
          lower_s=0.464552, compiles=4, compile_s=1.199329,
          programs=["jit(iota)", "jit(multi)"]),
    _span("compute", "s11", 1721.5901889940142, trace_id="train-e0",
          lower_s=0.0, gc_s=0.06462, gc_collections=60,
          sections={"fit/boundary": 2.4e-05}),
    _span("compute", "s15", 6.359555998642463, trace_id="train-e4",
          lower_s=3.8e-05, gc_s=0.0, gc_collections=0, sections={}),
    _span("fit/measure_comm", "s18", 81.25931700487854, parent="s17",
          lower_s=0.039385, compiles=3, compile_s=0.028979),
    _span("fit/load", "s40", 310.2, parent="s39", lower_s=0.41,
          cache_hits=3, cache_load_s=0.2125),
    _span("init_state", "s41", 95.7, parent="s4", lower_s=0.2,
          cache_hits=13, cache_load_s=0.0481),
]
# what the parent program writes: training spans without counters
PARENT = [_span("compute", "s0", 1721.59, trace_id="train-e0"),
          _span("grad_reduce", "s1", 0.1, trace_id="train-e6")]


def ctx(records):
    return {"stream": {"span": records} if records else {}}


def test_runtime_start_s_adds_the_process_and_runtime_spans():
    read = reader("runtime_start_s")
    assert read(ctx(RECORDED)) == pytest.approx(
        (2797.5048450025497 + 18.518614997447003) / 1e3)
    # a process's second run writes no setup/process span
    assert read(ctx(RECORDED[1:])) == pytest.approx(0.018518614997447003)


@pytest.mark.parametrize("name, want", [
    ("step_lower_s", 0.119498 + 0.239159 + 0.464552 + 3.8e-05 + 0.039385
     + 0.41 + 0.2),
    ("step_cache_load_s", 0.2125 + 0.0481),
    ("step_backend_compile_s", 0.229565 + 1.299934 + 1.199329 + 0.028979),
])
def test_counter_readers_sum_every_span_once(name, want):
    assert reader(name)(ctx(RECORDED)) == pytest.approx(want)


@pytest.mark.parametrize("name", ["runtime_start_s", "step_lower_s",
                                  "step_cache_load_s",
                                  "step_backend_compile_s"])
def test_nothing_to_read_on_a_stream_without_set_up_spans(name):
    read = reader(name)
    assert read(ctx(PARENT)) is None      # the parent's training spans
    assert read(ctx([])) is None          # no span record at all
