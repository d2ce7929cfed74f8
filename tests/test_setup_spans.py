"""Set-up spans and compile counters (obs/trace.py, docs/OBSERVABILITY.md
"Set-up spans"): a CLI run with a metrics sink writes its set-up as one
tree of `span` records, each carrying what JAX compiled while it was the
innermost open span; the training `compute` spans carry what the host did
between two harvests; a second process on the same compile cache loads
what the first compiled."""

import json
import os
import subprocess
import sys

import pytest

from pipegcn_tpu.cli.main import run
from pipegcn_tpu.cli.parser import create_parser
from pipegcn_tpu.obs.metrics import read_metrics
from pipegcn_tpu.obs.trace import recorder, span

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SLACK_S = 0.005     # two clocks put on the unix clock at different times

ARGS = ["--dataset", "synthetic:400:6:12:4", "--n-partitions", "1",
        "--n-layers", "2", "--n-hidden", "16", "--dropout", "0.1",
        "--fused-epochs", "2", "--n-epochs", "10", "--log-every", "10",
        "--no-eval", "--fix-seed", "--seed", "3"]


def _spans(path):
    return [r for r in read_metrics(path) if r["event"] == "span"]


def _end(rec):
    return rec["t_start"] + rec["dur_ms"] / 1e3


@pytest.fixture(scope="module")
def cli_run(tmp_path_factory):
    """One run through the tuner (a grid of three candidates), the pp
    precompute and five blocks of two epochs."""
    tmp = tmp_path_factory.mktemp("setup_spans")
    args = create_parser().parse_args(ARGS + [
        "--use-pp", "--spmm-impl", "auto", "--rem-dtype", "bfloat16",
        "--block-group", "4",
        "--partition-dir", str(tmp / "parts"),
        "--results-dir", str(tmp / "res"),
        "--metrics-out", str(tmp / "m.jsonl")])
    res = run(args)
    return res, _spans(str(tmp / "m.jsonl"))


def test_cli_run_writes_the_set_up_tree(cli_run):
    res, spans = cli_run
    setup = [r for r in spans if r["trace_id"] == "setup"]
    ops = [r["op"] for r in setup]
    for op in ("setup/runtime", "setup/services", "setup/graph",
               "setup/trainer", "tuner", "candidate", "upload",
               "pp_precompute", "init_state", "build_step",
               "fit/measure_comm", "fit/load", "fit/tail"):
        assert op in ops, op
    top = {r["op"] for r in setup if r["parent"] is None}
    assert top == {"setup/runtime", "setup/services", "setup/graph",
                   "setup/trainer", "fit/tail"} | (
                       {"setup/process"} & set(ops))
    by_id = {r["span_id"]: r for r in spans}
    trainer = next(r for r in setup if r["op"] == "setup/trainer")
    for op in ("tuner", "upload", "pp_precompute", "init_state",
               "build_step"):
        assert by_id[next(r for r in setup if r["op"] == op)["parent"]] \
            is trainer
    # the tuner's children are its candidates, each with the seconds of
    # its timed calls and what compiling it cost
    tuner = next(r for r in setup if r["op"] == "tuner")
    cands = [r for r in setup if r["parent"] == tuner["span_id"]]
    assert sorted(r["candidate"] for r in cands) == [
        "block-u4-bf16", "bucket-bf16", "xla"]
    assert all(r["timed_s"] > 0 and r["compiles"] >= 1
               and r["compile_s"] > 0 and r["lower_s"] > 0 for r in cands)
    # every span once, every record valid (the sink validates on write)
    assert len(by_id) == len(spans)


def test_spans_nest_inside_their_parents(cli_run):
    """A set-up span lies inside its parent; a child of a training
    `compute` span lies in that block's host time, from the previous
    harvest to the block's own (the compute span is its dispatch)."""
    _, spans = cli_run
    by_id = {r["span_id"]: r for r in spans}
    linked = [r for r in spans if r.get("parent")]
    assert len(linked) >= 10
    harvests = sorted(_end(r) for r in spans if r["op"] == "compute")
    for r in linked:
        p = by_id[r["parent"]]
        assert _end(r) <= _end(p) + SLACK_S, (r["op"], p["op"])
        if p["op"] != "compute" or r["op"] == "fit/load":
            assert p["t_start"] - SLACK_S <= r["t_start"], (r["op"], p["op"])
        else:
            before = [t for t in harvests if t < _end(p) - SLACK_S]
            assert not before or before[-1] - SLACK_S <= r["t_start"]


def test_trainer_setup_s_is_a_view_of_its_spans(cli_run):
    res, spans = cli_run
    trainer = res["trainer"]
    node = next(r for r in spans if r["op"] == "setup/trainer")
    kids = [r for r in spans if r.get("parent") == node["span_id"]]
    # `tables` too where the tuner chose a kernel of tables
    assert {"tuner", "upload", "pp_precompute"} <= set(trainer.setup_s) \
        <= {"tuner", "tables", "upload", "pp_precompute"}
    for key, seconds in trainer.setup_s.items():
        assert sum(r["dur_ms"] for r in kids if r["op"] == key) / 1e3 \
            == pytest.approx(seconds, rel=1e-6, abs=1e-6), key
    # the run's own: a process's first run also keeps the graph of a
    # `prepare` called before it
    graph = max((r for r in spans if r["op"] == "setup/graph"),
                key=lambda r: r["t_start"])
    assert res["setup_s"]["graph_partition"] == pytest.approx(
        graph["dur_ms"] / 1e3, abs=0.006)       # printed to 2 decimals


def test_first_dispatch_carries_its_compiles_and_program(cli_run):
    _, spans = cli_run
    first = next(r for r in spans if r["op"] == "fit/load"
                 and r["epoch"] == 0)
    assert first["compiles"] >= 1 and first["compile_s"] > 0
    assert "jit(multi)" in first["programs"]     # the scan of two epochs
    compute = next(r for r in spans if r["span_id"] == first["parent"])
    assert compute["op"] == "compute" and compute["epoch"] == 0


def test_the_window_compiles_nothing(cli_run):
    """After the first two blocks of a scan length (the scan, then the
    eager add of a non-zero first epoch) nothing lowers, loads or
    compiles: no fit/load, and counters of zero but the microseconds
    `vmap` retraces the epoch keys in."""
    _, spans = cli_run
    loads = sorted(r["epoch"] for r in spans if r["op"] == "fit/load")
    assert loads == [0, 2]
    window = [r for r in spans if r["op"] == "compute"
              and r["epoch"] >= 4]
    assert [r["epoch"] for r in window] == [4, 6, 8]
    for r in window:
        assert r["compiles"] == r["cache_hits"] == 0
        assert r["compile_s"] == r["cache_load_s"] == 0.0
        assert r["lower_s"] < 1e-3
        assert r["gc_collections"] >= 0 and r["gc_s"] >= 0.0
        assert set(r["sections"]) <= {"fit/boundary", "fit/harvest",
                                      "fit/log", "fit/eval",
                                      "fit/checkpoint"}
        assert "fit/boundary" in r["sections"]
    # the collective measurement ran between two blocks: its span names
    # the next block, whose harvest section holds its seconds
    mc = next(r for r in spans if r["op"] == "fit/measure_comm")
    nxt = next(r for r in spans if r["span_id"] == mc["parent"])
    assert nxt["sections"]["fit/harvest"] >= mc["dur_ms"] / 1e3
    # after the last harvest: fit's tail, from it to fit's return
    tail = next(r for r in spans if r["op"] == "fit/tail")
    last = max((r for r in spans if r["op"] == "compute"),
               key=lambda r: r["epoch"])
    assert tail["t_start"] >= _end(last) - SLACK_S
    assert tail["compiles"] == 0 and "fit/harvest" in tail["sections"]


def test_no_train_traces_writes_no_set_up_span(tmp_path):
    args = create_parser().parse_args(ARGS + [
        "--n-epochs", "2", "--no-train-traces",
        "--partition-dir", str(tmp_path / "parts"),
        "--results-dir", str(tmp_path / "res"),
        "--metrics-out", str(tmp_path / "m.jsonl")])
    res = run(args)
    assert _spans(str(tmp_path / "m.jsonl")) == []
    assert set(res["setup_s"]) == {"graph_partition", "upload"}


def test_counters_land_on_the_innermost_span_only():
    import jax
    import jax.numpy as jnp

    recorder().install()
    x, y = jnp.ones(7), jnp.ones(5)
    with span("outer", annotate=False) as outer:
        with span("inner", annotate=False) as inner:
            jax.jit(lambda v: jnp.cos(v) * 3.0)(x).block_until_ready()
        jax.jit(lambda v: jnp.tan(v) - 1.0)(y).block_until_ready()
    assert inner.parent is outer and inner.counts["compiles"] == 1
    assert outer.counts["compiles"] == 1
    assert inner.counts["lower_s"] > 0 and outer.counts["lower_s"] > 0
    assert inner.programs == ["jit(<lambda>)"]
    assert outer.t0 <= inner.t0 and inner.t1 <= outer.t1


_SECOND = """
import sys
from pipegcn_tpu.cli.main import run
from pipegcn_tpu.cli.parser import create_parser
run(create_parser().parse_args(sys.argv[1:]))
"""


def test_a_second_process_loads_the_step_from_the_cache(tmp_path):
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_ENABLE_COMPILATION_CACHE", "XLA_FLAGS")}
    env.update(PYTHONPATH=REPO, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cc"),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")

    def one(name):
        out = str(tmp_path / f"{name}.jsonl")
        r = subprocess.run(
            [sys.executable, "-c", _SECOND, *ARGS, "--n-epochs", "2",
             "--partition-dir", str(tmp_path / "parts"), "--skip-partition",
             "--results-dir", str(tmp_path / "res"), "--metrics-out", out],
            env=env, capture_output=True, text=True, timeout=300)
        assert r.returncode == 0, r.stderr[-2000:]
        spans = _spans(out)
        return spans, next(r for r in spans if r["op"] == "fit/load")

    first_spans, first = one("first")
    second_spans, second = one("second")
    assert first["compiles"] >= 1 and first["cache_hits"] == 0
    assert "jit(multi)" in first["programs"]
    assert second["cache_hits"] >= 1 and second["compiles"] == 0
    assert second["cache_load_s"] > 0 and "jit(multi)" in second["programs"]
    # a process's first run: setup/process ends where its first span
    # starts
    for spans in (first_spans, second_spans):
        proc, rt = (next(r for r in spans if r["op"] == op)
                    for op in ("setup/process", "setup/runtime"))
        assert 0 < proc["dur_ms"] and _end(proc) <= rt["t_start"] + SLACK_S
    json.dumps(second)
