"""Training-path distributed-tracing tests (obs/trainspan.py +
trainer wiring + obs/live.py + obs/health.py + obs/timeline.py +
cli/report.py, docs/OBSERVABILITY.md "Training traces"):

  - TrainSpanPlane block emission: span conservation (counts match the
    sink), the compute span is the real dispatch->harvest window, and
    the armed comm tail sits back-to-back ENDING at the harvest
    barrier with grad_reduce last and halo cost apportioned by wire
    bytes;
  - estimate_offsets recovers planted per-rank clock skew from the
    tracesync barrier anchors (and from grad_reduce span ends when no
    tracesync landed);
  - fold_spans' interval-union overlap agrees with the profiler's
    fold_xplane on a shared interval fixture — one overlap definition,
    two sources;
  - straggler attribution names the rank whose compute window started
    last ON THE ALIGNED CLOCK (a big wall-clock skew must not fool it);
  - the straggler-skew alert fires once on a sustained skew, stays
    silent while red, and resolves when attribution moves off the rank
    (fake clock, through LiveAggregator + AlertEngine);
  - the timeline renders train spans on a dedicated per-rank track and
    stitches each epoch's MATCHING collectives across ranks into
    "collective" flows on the aligned clock;
  - pipegcn-report derives a measured overlap verdict from spans with
    NO profiler capture window, plus the divergence tripwire;
  - the live snapshot + /metrics gauges surface the span verdicts;
  - the zero-recompile pin: spans on vs off leaves the jitted step
    cache identical (the plane is host-side bookkeeping only);
  - the two-process slow-rank drill (faults+slow): a real pipelined
    CPU-mesh run with slow-rank@E:r1 injected must attribute the
    straggle to rank 1, fire the alert, stitch cross-rank flows, and
    keep every span on disk.

Marker: trainspan (scripts/chaos.sh runs the lane standalone); the
drill is additionally faults + slow so tier-1 skips it."""

import collections
import io
import json
import os
import socket
import subprocess
import sys
import time

import pytest

from pipegcn_tpu.obs.health import AlertEngine, load_rules, prometheus_text
from pipegcn_tpu.obs.live import LiveAggregator
from pipegcn_tpu.obs.metrics import MetricsLogger, read_metrics
from pipegcn_tpu.obs.profiler import fold_xplane
from pipegcn_tpu.obs.timeline import build_timeline
from pipegcn_tpu.obs.trainspan import (
    COMM_OPS,
    TrainSpanPlane,
    estimate_offsets,
    fold_spans,
    trace_id,
    train_spans,
)

pytestmark = pytest.mark.trainspan

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _records(buf):
    return [json.loads(line) for line in buf.getvalue().splitlines()]


# ---------------- emission: conservation + comm-tail geometry ---------


def test_block_span_conservation_and_comm_tail():
    """One block -> exactly the contracted spans: pre-arm a compute
    span + tracesync anchor only; post-arm additionally the comm tail
    back-to-back ending at the harvest barrier, grad_reduce LAST, halo
    cost split by wire bytes, every span tagged rank/generation."""
    clk = [100.0]
    buf = io.StringIO()
    ml = MetricsLogger(buf)
    plane = TrainSpanPlane(ml, rank=1, generation=2,
                           clock=lambda: clk[0],
                           now=lambda: clk[0] + 1000.0)

    # pre-arm: compute + tracesync, nothing else
    plane.block(epoch=0, chunk=1, dur_s=0.5, t_end=100.0)
    assert not plane.comm_armed
    recs = _records(buf)
    assert [r["event"] for r in recs] == ["span", "tracesync"]
    comp, sync = recs
    assert comp["op"] == "compute"
    assert comp["trace_id"] == trace_id(0) == "train-e0"
    assert comp["t_start"] == pytest.approx(1099.5)
    assert comp["dur_ms"] == pytest.approx(500.0)
    assert (comp["rank"], comp["generation"]) == (1, 2)
    assert (comp["epoch"], comp["epochs"]) == (0, 1)
    assert comp["comm_wait_s"] == 0.0
    assert comp["source"] == "r1"
    assert (sync["rank"], sync["epoch"]) == (1, 0)
    assert sync["t_anchor"] == pytest.approx(1100.0)
    assert sync["generation"] == 2

    # armed: the comm tail ends at the barrier, grad_reduce last
    plane.set_comm({"comm": 0.03, "reduce": 0.01, "bgrad": 0.02},
                   [(0, 100), (1, 300)], "bfloat16")
    assert plane.comm_armed
    plane.block(epoch=1, chunk=2, dur_s=0.5, t_end=101.0)
    spans = [r for r in _records(buf)[2:] if r["event"] == "span"]
    by_op = {}
    for r in spans:
        by_op.setdefault(r["op"], []).append(r)
    assert sorted(by_op) == ["bgrad_return", "compute", "grad_reduce",
                             "halo_exchange"]
    end = lambda r: r["t_start"] + r["dur_ms"] / 1e3  # noqa: E731
    barrier = 1101.0
    gr = by_op["grad_reduce"][0]
    assert end(gr) == pytest.approx(barrier)          # grad_reduce LAST
    assert gr["dur_ms"] == pytest.approx(20.0)        # reduce * chunk
    bg = by_op["bgrad_return"][0]
    assert end(bg) == pytest.approx(gr["t_start"])    # back-to-back
    assert bg["dur_ms"] == pytest.approx(40.0)
    halos = sorted(by_op["halo_exchange"], key=lambda r: r["layer"])
    # halo cost (0.03 * 2) apportioned 100:300 by wire bytes
    assert halos[0]["dur_ms"] == pytest.approx(15.0)
    assert halos[1]["dur_ms"] == pytest.approx(45.0)
    assert halos[0]["wire_bytes"] == 200              # bytes * chunk
    assert halos[1]["wire_bytes"] == 600
    assert all(h["dtype"] == "bfloat16" for h in halos)
    assert end(halos[1]) == pytest.approx(bg["t_start"])
    assert end(halos[0]) == pytest.approx(halos[1]["t_start"])
    for r in spans:
        assert (r["rank"], r["generation"]) == (1, 2)
        assert r["trace_id"] == "train-e1"

    # a window too short to hide the comm cost reads as exposed wait
    plane.block(epoch=3, chunk=1, dur_s=0.01, t_end=102.0)
    comp3 = [r for r in _records(buf) if r.get("op") == "compute"][-1]
    assert comp3["comm_wait_s"] == pytest.approx(0.05)

    # conservation: the plane's own counts match the sink exactly
    ml.close()
    sink_counts = collections.Counter(
        r["op"] for r in _records(buf) if r["event"] == "span")
    assert plane.counts == dict(sink_counts)
    assert plane.blocks == 3
    assert train_spans(_records(buf)) == [
        r for r in _records(buf) if r["event"] == "span"]


# ---------------- clock-offset recovery -------------------------------


def test_estimate_offsets_recovers_planted_skew():
    """Per-rank offsets recovered from tracesync anchors: three ranks
    share a barrier each epoch; their planted wall-clock skews come
    back (relative to the cross-rank median), and the grad_reduce
    span-end fallback recovers the same answer without tracesync."""
    planted = {0: 0.0, 1: 0.5, 2: -0.2}
    syncs, reduces = [], []
    for e in range(4):
        barrier = 1000.0 + e * 1.0
        for r, off in planted.items():
            syncs.append({"event": "tracesync", "rank": r, "epoch": e,
                          "t_anchor": barrier + off, "generation": 0})
            reduces.append({"event": "span", "trace_id": trace_id(e),
                            "span_id": f"s{e}{r}", "op": "grad_reduce",
                            "t_start": barrier + off - 0.01,
                            "dur_ms": 10.0, "status": "ok", "rank": r,
                            "epoch": e})
    got = estimate_offsets(syncs)
    for r, off in planted.items():
        assert got[r] == pytest.approx(off, abs=1e-9)
    # fallback path: no tracesync -> grad_reduce ends anchor the barrier
    got_fb = estimate_offsets(reduces)
    for r, off in planted.items():
        assert got_fb[r] == pytest.approx(off, abs=1e-9)
    # a single-rank run has no cross-rank barrier: no offsets
    assert estimate_offsets(syncs[:1]) == {}


# ---------------- overlap agrees with the profiler fold ---------------


def test_fold_spans_overlap_agrees_with_fold_xplane():
    """One overlap definition, two sources: the span fold and the
    device-trace fold produce the SAME fraction on the same intervals
    (compute [0,10]s; halo [6,8] covered; grad_reduce [9,11] half
    exposed -> 3 of 4 comm seconds covered = 0.75)."""
    spans = [
        {"event": "span", "trace_id": "train-e0", "span_id": "a",
         "op": "compute", "t_start": 0.0, "dur_ms": 10_000.0,
         "status": "ok", "rank": 0, "epoch": 0},
        {"event": "span", "trace_id": "train-e0", "span_id": "b",
         "op": "halo_exchange", "t_start": 6.0, "dur_ms": 2_000.0,
         "status": "ok", "rank": 0, "epoch": 0},
        {"event": "span", "trace_id": "train-e0", "span_id": "c",
         "op": "grad_reduce", "t_start": 9.0, "dur_ms": 2_000.0,
         "status": "ok", "rank": 0, "epoch": 0},
    ]
    fold = fold_spans(spans)
    assert fold["overlap_spans"] == pytest.approx(0.75)

    # the same intervals as a device's executor threads (ns): compute
    # on one line, the two collectives on lines of their own
    mod = "jit_step(1)"
    tr = {"host": [], "lines": [
        {"device": 0, "events": [["op.c", 0.0, 10e9, mod]]},
        {"device": 0, "events": [["op.h", 6e9, 2e9, mod]]},
        {"device": 0, "events": [["op.r", 9e9, 2e9, mod]]}]}
    prog = {"scan_length": 1, "module": "jit_step",
            "map": {"op.c": ("layer0/spmm/x", "fusion"),
                    "op.h": ("halo_exchange/x", "all-gather"),
                    "op.r": ("grad_reduce/x", "all-reduce")}}
    meas = fold_xplane(tr, [prog])
    assert meas["overlap_fraction"] == pytest.approx(
        fold["overlap_spans"])


# ---------------- straggler attribution on the aligned clock ----------


def _two_rank_records(n_epochs=2, wall_off=5.0, lag=0.2, t0=1000.0):
    """Two ranks sharing barriers: rank 1's wall clock is `wall_off`
    seconds ahead AND its compute window starts `lag` seconds late
    (physically). Returns (recs0, recs1)."""
    out = {0: [], 1: []}
    for e in range(n_epochs):
        barrier = t0 + (e + 1) * 1.0
        for r, (off, dur) in {0: (0.0, 0.8),
                              1: (wall_off, 0.8 - lag)}.items():
            out[r].append({"event": "tracesync", "rank": r, "epoch": e,
                           "t_anchor": barrier + off, "generation": 0})
            for op, d0, d1 in (("compute", dur, 0.0),
                               ("halo_exchange", 0.2, 0.1),
                               ("grad_reduce", 0.1, 0.0)):
                rec = {"event": "span", "trace_id": trace_id(e),
                       "span_id": f"{op[0]}{e}r{r}", "op": op,
                       "t_start": barrier + off - d0,
                       "dur_ms": (d0 - d1) * 1e3, "status": "ok",
                       "rank": r, "epoch": e, "source": f"r{r}"}
                if op == "halo_exchange":
                    rec["layer"] = 0
                out[r].append(rec)
    return out[0], out[1]


def test_straggler_attribution_survives_clock_skew():
    """Rank 1 really starts 0.2 s late, but its wall clock is 5 s
    AHEAD: raw timestamps would blame it by 5.2 s (or, re-signed,
    exonerate it). The tracesync-aligned fold names rank 1 with the
    physical gap (median-of-two halves it to 0.1 s)."""
    recs0, recs1 = _two_rank_records()
    fold = fold_spans(recs0 + recs1)
    # offsets symmetric around the 2-rank median: the RELATIVE skew
    # is what alignment needs, and it equals the planted 5 s
    assert (fold["offsets"][1] - fold["offsets"][0]
            == pytest.approx(5.0, abs=1e-6))
    assert fold["straggler_rank"] == 1
    assert fold["straggler_max_gap_s"] == pytest.approx(0.1, abs=1e-6)
    assert fold["straggler_gap_s_by_rank"][1] == pytest.approx(
        0.1, abs=1e-6)
    for e, pe in fold["per_epoch"].items():
        assert pe["straggler_rank"] == 1
        assert pe["gap_s"] == pytest.approx(0.1, abs=1e-6)
    # both ranks' comm is fully inside their compute windows here
    assert fold["overlap_spans"] == pytest.approx(1.0)
    assert fold["comm_wait_s_by_rank"] == {0: 0.0, 1: 0.0}


# ---------------- straggler-skew alert: fire / dedupe / resolve -------


def _write_epoch(ml, e, step=0.1):
    ml.write({"event": "epoch", "epoch": e, "loss": 1.0, "grad_norm": 0.5,
              "step_time_s": step, "halo_bytes": 1000, "staleness_age": 1,
              "memory": None, "time_unix": time.time()})


def _write_skewed_epoch(ml, e, late_rank, t0=2000.0, lag=0.2):
    """Both ranks' compute spans for epoch `e` into one stream;
    `late_rank` starts `lag` late (gap = lag/2 vs the 2-rank median)."""
    barrier = t0 + (e + 1) * 1.0
    for r in (0, 1):
        dur = 0.8 - (lag if r == late_rank else 0.0)
        ml.span(trace_id(e), f"c{e}r{r}", "compute", barrier - dur,
                dur * 1e3, rank=r, epoch=e)
    _write_epoch(ml, e)


def test_straggler_skew_alert_fire_dedupe_resolve(tmp_path):
    """A sustained one-rank skew fires straggler-skew ONCE for source
    r1, stays silent while red, and resolves once attribution moves
    off the rank — the edge-triggered contract every other rule keeps."""
    d = tmp_path / "run"
    d.mkdir()
    fake = [7000.0]
    agg = LiveAggregator(str(d), clock=lambda: fake[0])
    rules = [r for r in load_rules(None) if r["rule"] == "straggler-skew"]
    assert rules and rules[0]["sustain"] == 3
    eng = AlertEngine(rules, clock=lambda: fake[0])

    ml = MetricsLogger(d / "train.jsonl")
    # median epoch time 0.1 s -> threshold factor(0.5) * 0.1 = 0.05 s;
    # the planted gap (0.2 / 2 = 0.1 s) clears it
    for e in range(3):
        _write_skewed_epoch(ml, e, late_rank=1)
    ml.hard_flush()
    agg.poll()
    edges = eng.evaluate(agg)
    assert [(x["state"], x["rule"], x["source"]) for x in edges] == [
        ("fire", "straggler-skew", "r1")]
    assert "rank 1" in edges[0]["message"]

    # still red -> dedup: no further edges
    _write_skewed_epoch(ml, 3, late_rank=1)
    ml.hard_flush()
    fake[0] += 1.0
    agg.poll()
    assert eng.evaluate(agg) == []
    assert eng.firing() == [{"rule": "straggler-skew", "source": "r1"}]

    # attribution moves off rank 1 -> resolve once
    _write_skewed_epoch(ml, 4, late_rank=0)
    ml.hard_flush()
    agg.poll()
    edges = eng.evaluate(agg)
    assert [(x["state"], x["rule"], x["source"]) for x in edges] == [
        ("resolve", "straggler-skew", "r1")]
    assert eng.evaluate(agg) == []
    assert (eng.n_fired, eng.n_resolved) == (1, 1)
    ml.close()


# ---------------- timeline: train track + cross-rank flows ------------


def test_timeline_train_track_and_collective_flows():
    """Train spans land on the dedicated per-rank "train" track on the
    ALIGNED clock, and each epoch's MATCHING collectives across ranks
    become one "collective" flow; compute spans ride no flow."""
    recs0, recs1 = _two_rank_records(n_epochs=2)
    obj = build_timeline([(0, recs0), (1, recs1)])
    evs = [e for e in obj["traceEvents"] if e.get("ph") != "M"]
    slices = [e for e in evs if e["ph"] == "X"]
    assert {e["tid"] for e in slices} == {6}
    names = {e["name"] for e in slices}
    assert names == {"compute", "halo_exchange", "grad_reduce"}
    # the train thread is labeled on both rank processes
    meta = [e for e in obj["traceEvents"] if e.get("ph") == "M"
            and e.get("name") == "thread_name"
            and e["args"]["name"] == "train"]
    assert {m["pid"] for m in meta} == {0, 1}

    flows = [e for e in evs if e["ph"] in ("s", "t", "f")]
    assert flows and all(e["cat"] == "collective" for e in flows)
    # one flow per (epoch, collective op): 2 epochs x (halo L0 +
    # grad_reduce) = 4 flows, each an s -> f pair spanning both pids
    by_id = collections.defaultdict(list)
    for e in flows:
        by_id[e["id"]].append(e)
    assert len(by_id) == 4
    for sites in by_id.values():
        assert [e["ph"] for e in sites] == ["s", "f"]
        assert {e["pid"] for e in sites} == {0, 1}
        # aligned clock: the matching collectives coincide despite the
        # planted 5 s wall skew
        assert sites[0]["ts"] == pytest.approx(sites[1]["ts"], abs=1e-3)
    # compute spans are slices only, never flow endpoints
    comm_ts = {e["ts"] for e in evs if e["ph"] == "X"
               and e["name"] in COMM_OPS}
    for e in flows:
        assert e["ts"] in comm_ts


# ---------------- report: span fallback without a profiler window -----


def test_report_span_fallback_and_divergence(tmp_path):
    """summarize_run derives the measured overlap verdict from spans
    with NO profile record, exposes the contracted --json keys, prints
    the span rows, and trips the divergence flag against the host
    estimate at the shared 0.25 threshold."""
    from pipegcn_tpu.cli.report import format_summary, summarize_run

    recs0, recs1 = _two_rank_records(n_epochs=2)
    records = ([{"event": "summary", "epoch_time_s": 1.0,
                 "comm_cost": {"comm": 0.1}}]
               + recs0 + recs1)
    assert not any(r.get("event") == "profile" for r in records)
    out = summarize_run(records)
    assert "measured_overlap_fraction" not in out
    assert out["overlap_spans"] == pytest.approx(1.0)
    assert out["comm_wait_share_by_rank"] == {"r0": 0.0, "r1": 0.0}
    assert out["straggler_rank"] == 1
    assert out["straggler_max_gap_s"] == pytest.approx(0.1, abs=1e-6)
    assert set(out["trace_clock_offsets"]) == {"r0", "r1"}
    # spans say 1.0, the standalone estimate says 0.1 -> divergence
    assert out["comm_fraction"] == pytest.approx(0.1)
    assert out["overlap_divergence"] is True

    text = format_summary("run", out)
    assert "overlap (spans)" in text and "100.00%" in text
    assert "comm wait share (spans)" in text
    assert "straggler (spans)" in text and "r1" in text
    assert "!! overlap divergence" in text
    # the summary dict IS the --json payload: keys are the contract
    json.dumps(out)


# ---------------- live snapshot + prometheus gauges -------------------


def test_live_snapshot_and_prometheus_gauges(tmp_path):
    """The live plane folds train spans into snapshot()["trainspan"]
    and exports the three contracted gauges with per-rank labels."""
    d = tmp_path / "run"
    d.mkdir()
    ml = MetricsLogger(d / "train.jsonl")
    recs0, recs1 = _two_rank_records(n_epochs=2)
    for rec in recs0 + recs1:
        ml.write(rec)
    ml.close()

    agg = LiveAggregator(str(d))
    agg.poll()
    ts = agg.trainspan()
    assert ts is not None and ts["overlap_spans"] == pytest.approx(1.0)
    snap = agg.snapshot()
    tsnap = snap["trainspan"]
    assert tsnap["overlap_spans"] == pytest.approx(1.0)
    assert tsnap["straggler_rank"] == 1
    assert tsnap["straggler_max_gap_s"] == pytest.approx(0.1, abs=1e-6)
    assert set(tsnap["comm_wait_share_by_rank"]) == {0, 1}
    assert set(tsnap["clock_offsets"]) == {0, 1}

    prom = {}
    for line in prometheus_text(agg, None).splitlines():
        if line and not line.startswith("#"):
            name, val = line.rsplit(" ", 1)
            prom[name] = float(val)
    assert prom["pipegcn_overlap_fraction"] == pytest.approx(1.0)
    assert prom['pipegcn_comm_wait_seconds{rank="0"}'] == 0.0
    assert prom['pipegcn_comm_wait_seconds{rank="1"}'] == 0.0
    assert prom['pipegcn_straggler_gap_seconds{rank="1"}'] == \
        pytest.approx(0.1, abs=1e-6)


# ---------------- zero-recompile pin ----------------------------------


def test_zero_recompile_with_spans_hot(tmp_path):
    """The span plane is host-side bookkeeping only: an identical fit
    with train traces ON compiles exactly the same number of step
    variants as with traces OFF — and the ON run really emitted the
    armed comm tail (the pin covers the hot path, not a dormant one)."""
    from pipegcn_tpu.graph import synthetic_graph
    from pipegcn_tpu.models import ModelConfig
    from pipegcn_tpu.parallel import Trainer, TrainConfig
    from pipegcn_tpu.partition import ShardedGraph, partition_graph

    g = synthetic_graph(num_nodes=200, avg_degree=6, n_feat=8,
                        n_class=3, seed=3)
    parts = partition_graph(g, 2, seed=0)
    sg = ShardedGraph.build(g, parts, n_parts=2)
    mcfg = ModelConfig(layer_sizes=(sg.n_feat, 8, sg.n_class),
                       norm="layer", dropout=0.0,
                       train_size=sg.n_train_global)

    def _fit(name, traces):
        t = Trainer(sg, mcfg, TrainConfig(
            lr=0.01, n_epochs=7, enable_pipeline=True, seed=0,
            eval=False, train_traces=traces))
        ml = MetricsLogger(tmp_path / f"{name}.jsonl")
        t.fit(None, log_fn=lambda *a, **k: None, metrics=ml,
              measure_comm_cost=True)
        ml.close()
        return t

    t_on = _fit("on", True)
    t_off = _fit("off", False)
    recs_on = read_metrics(tmp_path / "on.jsonl")
    ops = {r["op"] for r in train_spans(recs_on)}
    assert "compute" in ops and "grad_reduce" in ops  # plane was hot
    assert any(r.get("event") == "tracesync" for r in recs_on)
    assert not train_spans(read_metrics(tmp_path / "off.jsonl"))
    assert t_on._step._cache_size() == t_off._step._cache_size()


# ---------------- the two-process slow-rank drill ---------------------


def _free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _spawn_rank(rank, port, tmp_path, extra, n_epochs):
    env = {
        **os.environ,
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
        "PYTHONPATH": REPO,
        "PYTHONUNBUFFERED": "1",
    }
    cmd = [
        sys.executable, os.path.join(REPO, "main.py"),
        "--dataset", "synthetic:400:6:8:3",
        "--n-partitions", "2", "--parts-per-node", "1",
        "--node-rank", str(rank),
        "--master-addr", "127.0.0.1", "--port", str(port),
        "--n-epochs", str(n_epochs), "--n-hidden", "16",
        "--dropout", "0.0", "--log-every", "1000",
        "--fix-seed", "--seed", "7", "--no-eval",
        "--partition-dir", str(tmp_path / "parts"),
        "--model-dir", str(tmp_path / f"model{rank}"),
        "--results-dir", str(tmp_path / f"results{rank}"),
        "--metrics-out", str(tmp_path / "mx" / f"metrics{rank}.jsonl"),
    ] + extra
    return subprocess.Popen(cmd, env=env, cwd=REPO,
                            stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT, text=True)


@pytest.mark.faults
@pytest.mark.slow
def test_two_process_slow_rank_drill(tmp_path):
    """The real thing: a two-process pipelined CPU-mesh run with
    slow-rank@3..6:r1:500 injected. The always-on span plane must (a)
    survive to disk on both ranks, (b) attribute the straggle to rank
    1 on the tracesync-aligned clock, (c) fire the straggler-skew
    alert naming r1 through the live plane, and (d) stitch cross-rank
    collective flows in the timeline."""
    (tmp_path / "mx").mkdir()
    port = _free_port()
    # epochs 3..6 slow on rank 1: comm arming lands after epoch 5, so
    # epoch 6 carries comm spans AND a 500 ms straggle; the last
    # `sustain`(3) attributed dispatches (4, 5, 6) all name rank 1
    plan = ",".join(f"slow-rank@{e}:r1:500" for e in range(3, 7))
    extra = ["--enable-pipeline", "--fault-plan", plan]
    procs = [_spawn_rank(r, port, tmp_path, extra, n_epochs=7)
             for r in (0, 1)]
    try:
        for p in procs:
            out, _ = p.communicate(timeout=600)
            assert p.returncode == 0, out[-4000:]
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()

    streams = [read_metrics(tmp_path / "mx" / f"metrics{r}.jsonl")
               for r in (0, 1)]
    merged = streams[0] + streams[1]

    # (a) spans survived on BOTH ranks, comm tail included
    for r, recs in enumerate(streams):
        ops = {s["op"] for s in train_spans(recs)}
        assert "compute" in ops, f"rank {r} lost its compute spans"
        assert "grad_reduce" in ops and "halo_exchange" in ops
        assert any(x.get("event") == "tracesync" for x in recs)

    # (b) attribution names the injected rank with a physical gap
    # (median-of-two halves the 500 ms sleep) on a same-host-aligned
    # clock (offsets must be ~0, not the sleep leaking into them)
    fold = fold_spans(merged)
    assert fold["straggler_rank"] == 1
    assert fold["straggler_gap_s_by_rank"][1] > 0.15
    for off in fold["offsets"].values():
        assert abs(off) < 0.2
    recent = [pe for _, pe in sorted(fold["per_epoch"].items())][-3:]
    assert all(pe["straggler_rank"] == 1 for pe in recent)

    # (c) the live plane fires straggler-skew for source r1
    agg = LiveAggregator(str(tmp_path / "mx"))
    agg.poll()
    eng = AlertEngine([r for r in load_rules(None)
                       if r["rule"] == "straggler-skew"])
    edges = eng.evaluate(agg)
    assert [(x["state"], x["source"]) for x in edges
            if x["rule"] == "straggler-skew"] == [("fire", "r1")]
    text = prometheus_text(agg, eng)
    assert 'pipegcn_straggler_gap_seconds{rank="1"}' in text

    # (d) the timeline stitches the epoch-6 collectives across ranks
    obj = build_timeline([(0, streams[0]), (1, streams[1])])
    flows = [e for e in obj["traceEvents"] if e.get("ph") in ("s", "f")
             and e.get("cat") == "collective"]
    by_id = collections.defaultdict(set)
    for e in flows:
        by_id[e["id"]].add(e["pid"])
    assert any(pids == {0, 1} for pids in by_id.values())

    # and the report's span verdict needs no profiler window
    from pipegcn_tpu.cli.report import summarize_run
    out = summarize_run(merged)
    assert out.get("overlap_spans") is not None
    assert out["straggler_rank"] == 1
