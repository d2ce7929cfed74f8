"""Live telemetry plane tests (obs/live.py + obs/health.py +
cli/monitor.py, docs/OBSERVABILITY.md "Live monitoring"):

  - stream discovery over run directories / stems / per-generation
    elastic files, and the deduped generation-ordered merge the report
    CLI shares;
  - TailReader torn-final-line tolerance, malformed-line counting, and
    truncation rewind;
  - LiveAggregator tail-follow across files that appear mid-run;
  - AlertEngine edge-triggering under a fake clock: fire once, stay
    silent while red, resolve once (the replica-dead + epoch-time
    drill);
  - span lifecycle conservation through the MicroBatcher (exactly one
    terminal span per sampled submit) and the timeline's Perfetto flow
    stitching;
  - /metrics scrape parity against the JSONL-derived values.

Everything here is host-side and jax-free except nothing — the marker
is `live` (scripts/chaos.sh monitor lane)."""

import json
import os
import time
import urllib.request

import numpy as np
import pytest

from pipegcn_tpu.obs.health import (
    AlertEngine,
    MonitorServer,
    health_json,
    load_rules,
    prometheus_text,
)
from pipegcn_tpu.obs.live import (
    LiveAggregator,
    TailReader,
    discover_streams,
    merge_streams,
    read_stream,
)
from pipegcn_tpu.obs.metrics import MetricsLogger, read_metrics

pytestmark = pytest.mark.live

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _write_epochs(ml, n, t0=0, step=0.1, src_extra=None):
    for e in range(t0, t0 + n):
        ml.write({"event": "epoch", "epoch": e, "loss": 1.0 - 0.01 * e,
                  "grad_norm": 0.5, "step_time_s": step,
                  "halo_bytes": 1000, "staleness_age": 1,
                  "memory": None, "time_unix": time.time(),
                  **(src_extra or {})})


def _run_header(ml):
    ml.write({"event": "run", "schema_version": 10, "config": {},
              "device": {}, "mesh": {}, "time_unix": time.time()})


# ---------------- discovery + merge ----------------------------------------


def test_discover_streams_stem_and_dir(tmp_path):
    d = tmp_path / "run"
    d.mkdir()
    (d / "train.jsonl").write_text('{"event": "bench"}\n')
    (d / "train.g0.m0.jsonl").write_text('{"event": "bench", "g": 0}\n')
    (d / "train.g1.m0.jsonl").write_text('{"event": "bench", "g": 1}\n')
    (d / "membership.jsonl").write_text('{"event": "bench", "m": 1}\n')
    (d / "notes.txt").write_text("not a stream\n")

    # stem target: base + per-generation files + the ledger beside them
    got = discover_streams(str(d / "train"))
    assert [os.path.basename(p) for p in got] == [
        "membership.jsonl", "train.jsonl", "train.g0.m0.jsonl",
        "train.g1.m0.jsonl"]
    # the .jsonl spelling of the stem finds the same set
    assert discover_streams(str(d / "train.jsonl")) == got
    # directory target: everything, recursively
    (d / "sub").mkdir()
    (d / "sub" / "replica-m0-i0-metrics.jsonl").write_text(
        '{"event": "bench", "r": 0}\n')
    got_dir = discover_streams(str(d))
    assert len(got_dir) == 5
    # a plain file with no per-generation siblings is just itself
    lone = tmp_path / "lone.jsonl"
    lone.write_text('{"event": "bench"}\n')
    assert discover_streams(str(lone)) == [str(lone)]
    # a typo'd stem matches nothing (and adopts no unrelated ledger)
    assert discover_streams(str(d / "nope")) == []


def test_merge_streams_dedups_and_orders(tmp_path):
    a = tmp_path / "t.jsonl"
    b = tmp_path / "t.g0.m0.jsonl"
    c = tmp_path / "t.g1.m0.jsonl"
    a.write_text('{"event": "bench", "n": 0}\n')
    # the run header duplicated into a per-generation file folds to one
    b.write_text('{"event": "bench", "n": 0}\n'
                 '{"event": "bench", "n": 1}\n')
    c.write_text('{"event": "bench", "n": 2}\n')
    recs = merge_streams([str(c), str(b), str(a)])
    assert [r["n"] for r in recs] == [0, 1, 2]


def test_tail_reader_torn_lines_and_truncation(tmp_path):
    p = tmp_path / "s.jsonl"
    p.write_text('{"event": "bench", "n": 0}\n{"event": "bench", "n"')
    r = TailReader(str(p))
    # the torn tail is invisible until its newline lands
    assert [x["n"] for x in r.poll()] == [0]
    assert r.poll() == []
    with open(p, "a") as f:
        f.write(': 1}\nnot json\n{"event": "bench", "n": 2}\n')
    assert [x["n"] for x in r.poll()] == [1, 2]
    assert r.n_malformed == 1
    # truncation rewinds to the start
    p.write_text('{"event": "bench", "n": 9}\n')
    assert [x["n"] for x in r.poll()] == [9]
    # final=True consumes a parseable unterminated tail (one-shot mode)
    p2 = tmp_path / "t.jsonl"
    p2.write_text('{"event": "bench", "n": 0}\n{"event": "bench", "n": 1}')
    assert [x["n"] for x in read_stream(str(p2))] == [0, 1]


def test_aggregator_follows_appearing_files(tmp_path):
    d = tmp_path / "run"
    d.mkdir()
    agg = LiveAggregator(str(d))
    assert agg.poll() == 0
    with MetricsLogger(d / "train.g0.m0.jsonl") as ml:
        _run_header(ml)
        _write_epochs(ml, 3)
    assert agg.poll() == 4
    # a new generation appears mid-run and joins the tail set live
    with MetricsLogger(d / "train.g1.m0.jsonl") as ml:
        _write_epochs(ml, 2, t0=3)
        ml.fault("rank-death", epoch=4, rank=0)
    assert agg.poll() == 3
    assert agg.poll() == 0
    snap = agg.snapshot()
    assert snap["n_streams"] == 2
    assert snap["n_records"] == 7
    assert snap["schema_version"] == 10
    assert agg.fault_counts == {"rank-death": 1}
    assert agg.latest("epoch")["train.g1.m0"]["epoch"] == 4
    # an invalid record is counted, kept out of state, never fatal
    with open(d / "train.g1.m0.jsonl", "a") as f:
        f.write('{"event": "epoch", "epoch": 99}\n')
    agg.poll()
    assert agg.n_invalid == 1
    assert agg.latest("epoch")["train.g1.m0"]["epoch"] == 4


# ---------------- alert engine ---------------------------------------------


def test_alert_rules_load_and_reject_typos(tmp_path):
    rules = load_rules(None)
    assert [r["rule"] for r in rules] == [
        "epoch-time-regression", "shed-rate", "staleness-age",
        "fault-rate", "silent-source", "straggler-skew"]
    p = tmp_path / "rules.json"
    p.write_text(json.dumps([
        {"rule": "epoch-time-regression", "factor": 2.0},
        {"rule": "fault-rate", "kind": "rank-death", "threshold": 2},
    ]))
    rules = load_rules(str(p))
    assert rules[0]["factor"] == 2.0
    assert rules[0]["min_points"] == 5  # default survives
    assert rules[1]["kind"] == "rank-death"
    p.write_text(json.dumps([{"rule": "epoch-time-regresion"}]))
    with pytest.raises(ValueError, match="unknown alert rule"):
        load_rules(str(p))
    p.write_text(json.dumps([{"rule": "shed-rate", "treshold": 0.5}]))
    with pytest.raises(ValueError, match="unknown parameter"):
        load_rules(str(p))


def test_epoch_time_alert_fires_and_resolves_exactly_once(tmp_path):
    """The drill the chaos monitor lane scripts: a step-time spike
    fires epoch-time-regression ONCE, stays silent while red, and
    resolves ONCE when the time recovers; alert records land in the
    sink deduped."""
    d = tmp_path / "run"
    d.mkdir()
    fake = [1000.0]
    agg = LiveAggregator(str(d), clock=lambda: fake[0])
    sink = MetricsLogger(tmp_path / "alerts.jsonl")
    eng = AlertEngine(
        [dict(load_rules(None)[0])], ml=sink, clock=lambda: fake[0])

    ml = MetricsLogger(d / "train.jsonl")
    _run_header(ml)
    _write_epochs(ml, 8, step=0.1)
    ml.hard_flush()
    agg.poll()
    assert eng.evaluate(agg) == []

    # spike: > factor (1.5) x rolling median 0.1
    _write_epochs(ml, 1, t0=8, step=0.5)
    ml.hard_flush()
    agg.poll()
    edges = eng.evaluate(agg)
    assert [(e["state"], e["rule"]) for e in edges] == [
        ("fire", "epoch-time-regression")]
    # still red across N ticks -> no further edges (dedup)
    for _ in range(3):
        fake[0] += 1.0
        agg.poll()
        assert eng.evaluate(agg) == []
    assert eng.firing() == [{"rule": "epoch-time-regression",
                             "source": "train"}]

    # recovery resolves once
    _write_epochs(ml, 1, t0=9, step=0.1)
    ml.hard_flush()
    agg.poll()
    edges = eng.evaluate(agg)
    assert [(e["state"], e["rule"]) for e in edges] == [
        ("resolve", "epoch-time-regression")]
    assert eng.evaluate(agg) == []
    assert (eng.n_fired, eng.n_resolved) == (1, 1)
    ml.close()
    sink.close()

    recs = read_metrics(tmp_path / "alerts.jsonl")
    assert [r["state"] for r in recs] == ["fire", "resolve"]
    for r in recs:
        assert r["rule"] == "epoch-time-regression"
        assert r["severity"] == "warn"


def test_fault_and_silence_alerts_under_fake_clock(tmp_path):
    """fault-rate fires on a fresh fault and resolves when the horizon
    passes quietly; silent-source covers the replica-dead case: a
    stream that stops producing fires after horizon_s and resolves
    when records resume."""
    d = tmp_path / "run"
    d.mkdir()
    fake = [5000.0]
    agg = LiveAggregator(str(d), clock=lambda: fake[0])
    rules = [r for r in load_rules(None)
             if r["rule"] in ("fault-rate", "silent-source")]
    eng = AlertEngine(rules, clock=lambda: fake[0])

    ml = MetricsLogger(d / "replica-m0-i0-metrics.jsonl")
    _run_header(ml)
    ml.hard_flush()
    agg.poll()
    assert eng.evaluate(agg) == []

    ml.fault("replica-dead", epoch=-1, replica=0)
    agg.poll()
    edges = eng.evaluate(agg)
    assert [(e["rule"], e["state"]) for e in edges] == [
        ("fault-rate", "fire")]

    # the replica goes silent past the 30s horizon -> silent-source
    # fires; past the 60s fault horizon -> fault-rate resolves
    fake[0] += 45.0
    edges = eng.evaluate(agg)
    assert [(e["rule"], e["state"]) for e in edges] == [
        ("silent-source", "fire")]
    fake[0] += 30.0
    edges = eng.evaluate(agg)
    assert [(e["rule"], e["state"]) for e in edges] == [
        ("fault-rate", "resolve")]

    # records resume -> silent-source resolves; each edge happened once
    ml.recovery("relaunch", epoch=-1, replica=0)
    agg.poll()
    edges = eng.evaluate(agg)
    assert [(e["rule"], e["state"]) for e in edges] == [
        ("silent-source", "resolve")]
    assert (eng.n_fired, eng.n_resolved) == (2, 2)
    ml.close()


# ---------------- spans ----------------------------------------------------


def test_span_lifecycle_conservation():
    """Rate-1 sampling through the MicroBatcher: every sampled submit
    lands EXACTLY one terminal span (dispatch | shed), dispatched ones
    a queue span too, and the engine span covers each flushed batch."""
    from pipegcn_tpu.serve.batcher import MicroBatcher
    from pipegcn_tpu.serve.tracing import SpanWriter, TraceSampler

    spans = []

    class _ML:
        def span(self, trace_id, span_id, op, t_start, dur_ms,
                 status="ok", **extra):
            spans.append({"trace_id": trace_id, "span_id": span_id,
                          "op": op, "t_start": t_start,
                          "dur_ms": dur_ms, "status": status})

    fake = [0.0]
    sw = SpanWriter(_ML(), clock=lambda: fake[0], source="t",
                    now=lambda: 2000.0 + fake[0])
    sampler = TraceSampler(1.0, seed=0, tag="t")
    mb = MicroBatcher(run=lambda ids: np.zeros((ids.size, 2)),
                      max_batch=8, max_delay_ms=0.0,
                      clock=lambda: fake[0], on_span=sw.emit,
                      max_queue=6)
    traced = []
    for i in range(4):
        tk = mb.submit(np.array([i]), trace_id=sampler.sample())
        traced.append(tk.trace_id)
        fake[0] += 0.001
        mb.pump(force=True)
    # overload: fill the queue, then shed one
    t5 = mb.submit(np.arange(6), trace_id=sampler.sample())
    shed = mb.submit(np.arange(3), trace_id=sampler.sample())
    assert shed.shed and shed.trace_id is not None
    fake[0] += 0.001
    mb.pump(force=True)

    assert sampler.n_sampled == 6
    by_trace = {}
    for s in spans:
        by_trace.setdefault(s["trace_id"], []).append(s["op"])
    # exactly one terminal span per sampled trace
    for tid, ops in by_trace.items():
        terminal = [op for op in ops if op in ("dispatch", "shed")]
        assert len(terminal) == 1, (tid, ops)
    assert by_trace[shed.trace_id] == ["shed"]
    for tid in traced + [t5.trace_id]:
        assert sorted(by_trace[tid]) == ["dispatch", "engine", "queue"]
    # span ids unique; t_start on the unix axis the writer was given
    ids = [s["span_id"] for s in spans]
    assert len(ids) == len(set(ids))
    assert all(s["t_start"] >= 2000.0 for s in spans)
    # rate 0: no ids minted at all
    assert TraceSampler(0.0).sample() is None


def test_timeline_stitches_spans_into_flows(tmp_path):
    """span records from two streams sharing a trace id become X
    slices bound by one Perfetto flow (s -> f with a common id), and
    the v5-v9 kinds render as counters/instants on the wall axis."""
    from pipegcn_tpu.obs.timeline import build_timeline

    t0 = 1000.0
    driver = [
        {"event": "span", "trace_id": "q1-t", "span_id": "s1",
         "op": "queue", "t_start": t0, "dur_ms": 2.0, "status": "ok"},
        {"event": "span", "trace_id": "q1-t", "span_id": "s2",
         "op": "rpc", "t_start": t0 + 0.002, "dur_ms": 5.0,
         "status": "ok", "replica": 0},
        {"event": "serving", "window_s": 1.0, "queries": 10,
         "qps": 10.0, "batch_fill": 1.0, "queue_depth": 2,
         "p50_ms": 1.0, "p95_ms": 2.0, "p99_ms": 3.0,
         "cache_hit_rate": None, "staleness_age": 0, "shed": 0,
         "param_generation": 0, "param_staleness": 0,
         "time_unix": t0 + 0.5},
        {"event": "fleet", "kind": "replica-dead", "replica": 0,
         "window": 1, "time_unix": t0 + 0.6},
    ]
    replica = [
        {"event": "span", "trace_id": "q1-t", "span_id": "s3",
         "op": "engine", "t_start": t0 + 0.004, "dur_ms": 1.5,
         "status": "ok"},
    ]
    obj = build_timeline([(0, driver), (1, replica)])
    evs = [e for e in obj["traceEvents"] if e.get("ph") != "M"]
    # contract: numeric ts >= 0, X dur >= 0, sorted
    last = -1.0
    for e in evs:
        assert e["ts"] >= 0
        assert e["ts"] >= last
        last = e["ts"]
        if e["ph"] == "X":
            assert e["dur"] >= 0
    slices = [e for e in evs if e["ph"] == "X"]
    assert sorted(e["name"] for e in slices) == [
        "engine", "queue", "rpc"]
    # wall anchor: earliest span at ts 0, engine span 4ms in
    assert min(e["ts"] for e in slices) == 0.0
    flows = [e for e in evs if e["ph"] in ("s", "t", "f")]
    assert [e["ph"] for e in flows] == ["s", "t", "f"]
    assert len({e["id"] for e in flows}) == 1
    assert flows[0]["pid"] == 0 and flows[-1]["pid"] == 1
    counters = [e for e in evs if e["ph"] == "C"
                and e["name"].startswith("serving_")]
    assert {e["name"] for e in counters} == {
        "serving_qps", "serving_p50_ms", "serving_p99_ms",
        "serving_queue_depth", "serving_shed"}
    instants = [e for e in evs if e["ph"] == "i"]
    assert any(e["name"] == "fleet:replica-dead" for e in instants)


# ---------------- /metrics scrape parity -----------------------------------


def _seed_run_dir(d):
    with MetricsLogger(d / "train.jsonl") as ml:
        _run_header(ml)
        _write_epochs(ml, 5)
        ml.fault("rank-death", epoch=3, rank=1)
        ml.recovery("restart", epoch=3, rank=1, downtime_s=0.5)
        ml.serving(window_s=1.0, queries=100, qps=100.0,
                   batch_fill=0.9, queue_depth=3, p50_ms=1.0,
                   p95_ms=2.0, p99_ms=3.0, cache_hit_rate=0.8,
                   staleness_age=2, shed=5, param_generation=1,
                   param_staleness=0,
                   shed_by_reason={"queue-full": 5})


def _parse_prom(text):
    out = {}
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        name, val = line.rsplit(" ", 1)
        out[name] = float(val)
    return out


def test_metrics_scrape_matches_jsonl(tmp_path):
    """/metrics over HTTP reports exactly the numbers the JSONL says:
    the scrape is a view of the same records the report CLI reads."""
    d = tmp_path / "run"
    d.mkdir()
    _seed_run_dir(d)
    recs = read_metrics(d / "train.jsonl")
    last_epoch = [r for r in recs if r["event"] == "epoch"][-1]
    serving = [r for r in recs if r["event"] == "serving"][-1]

    agg = LiveAggregator(str(d))
    eng = AlertEngine()
    agg.poll()
    eng.evaluate(agg)
    srv = MonitorServer(agg, eng, port=0).start()
    try:
        url = f"http://127.0.0.1:{srv.port}"
        text = urllib.request.urlopen(url + "/metrics",
                                      timeout=5).read().decode()
        health = json.loads(urllib.request.urlopen(
            url + "/health", timeout=5).read())
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(url + "/nope", timeout=5)
    finally:
        srv.stop()

    vals = _parse_prom(text)
    assert vals['pipegcn_loss{source="train"}'] == last_epoch["loss"]
    assert vals['pipegcn_epoch{source="train"}'] == last_epoch["epoch"]
    assert vals['pipegcn_serving_qps{source="train"}'] == serving["qps"]
    assert vals['pipegcn_serving_p99_ms{source="train"}'] == \
        serving["p99_ms"]
    assert vals['pipegcn_faults_total{kind="rank-death"}'] == 1
    assert vals['pipegcn_recoveries_total{kind="restart"}'] == 1
    assert vals['pipegcn_serving_shed_rows_total{reason="queue-full"}'] \
        == 5
    assert vals["pipegcn_records_total"] == len(recs)
    assert vals["pipegcn_schema_version"] == 10
    # the fresh fault fires the page-severity fault-rate rule
    assert vals['pipegcn_alert_firing{rule="fault-rate",source="*"}'] \
        == 1
    assert health["status"] == "critical"
    assert health["alerts_firing"] == [
        {"rule": "fault-rate", "source": "*"}]
    # text renderer matches what the server shipped (modulo the
    # wall-clock age gauge, which moves between the two renders)
    def _stable(d):
        return {k: v for k, v in d.items()
                if "last_seen_age" not in k}
    direct = prometheus_text(agg, eng)
    assert _stable(_parse_prom(direct)) == _stable(vals)


def test_monitor_cli_once(tmp_path, capsys):
    from pipegcn_tpu.cli.monitor import main as monitor_main

    d = tmp_path / "run"
    d.mkdir()
    with MetricsLogger(d / "train.jsonl") as ml:
        _run_header(ml)
        _write_epochs(ml, 3)
    rc = monitor_main([str(d), "--once", "--alerts-out", "-"])
    out = capsys.readouterr().out
    health = json.loads(out[out.index("{"):])
    assert rc == 0
    assert health["status"] == "ok"
    assert health["n_records"] == 4

    # a fault flips the fault-rate page rule -> rc 2 (scriptable
    # drill); MetricsLogger appends, so reopening extends the stream
    with MetricsLogger(d / "train.jsonl") as ml:
        ml.fault("rank-death", epoch=2, rank=0)
    rc = monitor_main([str(d), "--once"])
    out = capsys.readouterr().out
    assert rc == 2
    assert "ALERT FIRE fault-rate" in out
    # the alert sink landed next to the run
    recs = read_metrics(d / "alerts.jsonl")
    assert [r["state"] for r in recs] == ["fire"]


def test_report_cli_accepts_run_directory(tmp_path, capsys):
    """pipegcn-report on a directory merges every stream (deduped,
    generation-ordered) into one summary instead of demanding a single
    file."""
    from pipegcn_tpu.cli.report import main as report_main

    d = tmp_path / "run"
    d.mkdir()
    header = {"event": "run", "schema_version": 10, "config": {},
              "device": {}, "mesh": {},
              "time_unix": 1700000000.0}
    with MetricsLogger(d / "train.g0.m0.jsonl") as ml:
        ml.write(header)
        _write_epochs(ml, 3)
    with MetricsLogger(d / "train.g1.m0.jsonl") as ml:
        ml.write(header)  # duplicated header folds to one
        _write_epochs(ml, 2, t0=3)
    rc = report_main([str(d), "--json"])
    assert rc == 0
    s = json.loads(capsys.readouterr().out.strip())
    assert s["n_streams_merged"] == 2
    assert s["n_epoch_records"] == 5
    assert s["schema_version"] == 10
