"""Deep performance observability (docs/OBSERVABILITY.md):

  - profiling windows: a REAL jax.profiler trace captured on the CPU
    mesh during fit(), folded against the compiled step's HLO into
    measured per-phase device time + a comm/compute overlap fraction;
  - staleness probes: per-layer relative drift between the stale halo
    features the pipelined step consumed and the fresh ones it shipped;
  - epoch anatomy: per-phase FLOP/byte attribution of the compiled
    step (>= 90% of FLOPs must land in named phases);
  - cross-rank timeline CLI: two ranks' metrics JSONL merged into one
    structurally-valid Chrome-trace file;
  - report CLI: measured vs estimated overlap side by side + the
    pinned --json shape;
  - flush-on-death: the final fault record survives an os._exit(75)
    (subprocess-proven);
  - TPU-window preflight: entries with missing artifacts are skipped
    loudly instead of burning window time.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from pipegcn_tpu.cli.parser import create_parser
from pipegcn_tpu.cli.report import main as report_main
from pipegcn_tpu.cli.timeline import main as timeline_main
from pipegcn_tpu.obs import MetricsLogger, read_metrics, validate_record
from pipegcn_tpu.obs.profiler import (
    classify_op,
    fold_trace,
    hlo_op_map,
    parse_profile_epochs,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------- pure parser units ---------------------------------------

def test_parse_profile_epochs():
    assert parse_profile_epochs("1:3") == (1, 3)
    assert parse_profile_epochs(" 10:20 ") == (10, 20)
    with pytest.raises(ValueError, match="A:B"):
        parse_profile_epochs("3")
    with pytest.raises(ValueError, match="empty"):
        parse_profile_epochs("5:5")


def test_classify_op_phases():
    assert classify_op("jit(step)/layer0/spmm/dot_general") == "spmm"
    assert classify_op("jit(step)/layer1/dense/dot_general") == "dense"
    assert classify_op("jit(step)/layer0/halo_exchange/ppermute") \
        == "halo_comm"
    assert classify_op("transpose(jvp(f))/layer0/bgrad_return/x") \
        == "halo_comm"
    assert classify_op("jit(step)/grad_reduce/psum") == "grad_reduce"
    assert classify_op("jit(step)/adam_update/mul") == "optimizer"
    assert classify_op("jit(step)/layer0/dropout/threefry") \
        == "dropout_rng"
    assert classify_op("", "collective-permute") == "halo_comm"
    assert classify_op("jit(step)/something_else/add") == "other"


def test_fold_trace_overlap_math():
    """Synthetic timeline: comm [0, 10] with compute covering [0, 6] on
    the same pid -> 60% overlap; phases fold by classified scope."""
    op_map = {"cp.1": ("jit(s)/layer0/halo_exchange/ppermute",
                       "collective-permute"),
              "dot.1": ("jit(s)/layer0/spmm/dot_general", "dot"),
              "dot.2": ("jit(s)/layer0/dense/dot_general", "dot")}
    events = [
        {"ph": "X", "pid": 1, "tid": 7, "ts": 0.0, "dur": 10.0,
         "name": "cp.1", "args": {"hlo_op": "cp.1"}},
        {"ph": "X", "pid": 1, "tid": 8, "ts": 0.0, "dur": 4.0,
         "name": "dot.1", "args": {"hlo_op": "dot.1"}},
        {"ph": "X", "pid": 1, "tid": 9, "ts": 4.0, "dur": 2.0,
         "name": "dot.2", "args": {"hlo_op": "dot.2"}},
        # a different pid's compute must NOT count toward pid 1's comm
        {"ph": "X", "pid": 2, "tid": 1, "ts": 0.0, "dur": 100.0,
         "name": "dot.1", "args": {"hlo_op": "dot.1"}},
    ]
    out = fold_trace(events, op_map)
    assert out["overlap_fraction"] == pytest.approx(0.6)
    assert out["comm_s"] == pytest.approx(10.0 / 1e6)
    assert out["phases"]["halo_comm"] == pytest.approx(10.0 / 1e6)
    assert out["phases"]["spmm"] == pytest.approx(104.0 / 1e6)
    assert out["phases"]["dense"] == pytest.approx(2.0 / 1e6)
    assert out["n_device_events"] == 4


def test_hlo_op_map_parses_metadata():
    txt = (
        'HloModule jit_step, entry_computation_layout={()->f32[2]}\n\n'
        'ENTRY %main.5 () -> f32[2] {\n'
        '  %dot.1 = f32[2]{0} dot(f32[2,3]{1,0} %a, f32[3]{0} %b), '
        'lhs_contracting_dims={1}, rhs_contracting_dims={0}, '
        'metadata={op_name="jit(step)/layer0/spmm/dot_general" '
        'source_file="x.py" source_line=1}\n'
        '  ROOT %cp.2 = f32[2]{0} collective-permute(f32[2]{0} %dot.1), '
        'metadata={op_name="jit(step)/layer0/halo_exchange/ppermute"}\n'
        '}\n')
    m = hlo_op_map(txt)
    assert m["dot.1"] == ("jit(step)/layer0/spmm/dot_general", "dot")
    assert m["cp.2"][1] == "collective-permute"
    from pipegcn_tpu.obs.profiler import module_name
    assert module_name(txt) == "jit_step"


# ---------------- end-to-end CPU-mesh smoke (the acceptance gate) ---------

def _cli_args(tmp_path, extra):
    base = [
        "--dataset", "synthetic:600:8:16:4",
        "--n-partitions", "4",
        "--n-epochs", "2",
        "--n-layers", "2",
        "--n-hidden", "32",
        "--dropout", "0.2",
        "--log-every", "5",
        "--fix-seed", "--seed", "7",
        "--no-eval",
        "--partition-dir", str(tmp_path / "partitions"),
        "--model-dir", str(tmp_path / "model"),
        "--results-dir", str(tmp_path / "results"),
    ]
    return create_parser().parse_args(base + extra)


@pytest.fixture(scope="module")
def profiled_run(tmp_path_factory):
    """One pipelined 2-epoch CLI run capturing a REAL jax.profiler
    trace over epochs [1, 2) with staleness probes every epoch and an
    anatomy record — shared by the record-content, report-CLI and
    timeline tests below."""
    from pipegcn_tpu.cli.main import run

    tmp_path = tmp_path_factory.mktemp("profiled")
    mpath = tmp_path / "metrics.jsonl"
    args = _cli_args(tmp_path, [
        "--enable-pipeline",
        "--metrics-out", str(mpath),
        "--profile-dir", str(tmp_path / "trace"),
        "--profile-epochs", "1:2",
        "--staleness-probe-every", "1",
        "--anatomy",
    ])
    res = run(args)
    return tmp_path, mpath, res


@pytest.mark.profile
def test_profile_smoke_all_record_kinds(profiled_run):
    """The tier-1 acceptance gate: a 2-epoch CPU-mesh fit with
    --profile-epochs 1:2 + --staleness-probe-every 1 + --anatomy emits
    every new record kind, schema-valid."""
    tmp_path, mpath, _ = profiled_run
    recs = read_metrics(mpath)
    for r in recs:
        validate_record(r)
    kinds = {r["event"] for r in recs}
    assert {"run", "epoch", "summary",
            "profile", "anatomy", "staleness"} <= kinds
    # the trace really hit the disk in TensorBoard layout
    sessions = os.listdir(os.path.join(tmp_path, "trace", "plugins",
                                       "profile"))
    assert sessions


@pytest.mark.profile
def test_profile_record_measures_overlap(profiled_run):
    """The profile record carries a measured overlap fraction in
    [0, 1], a phase decomposition with real device time in the comm
    phases (P=4 -> halo collectives exist), and the capture window."""
    _, mpath, res = profiled_run
    profs = [r for r in read_metrics(mpath) if r["event"] == "profile"]
    assert len(profs) == 1
    p = profs[0]
    assert 0.0 <= p["overlap_fraction"] <= 1.0
    assert p["comm_s"] > 0          # P=4: collective-permutes ran
    assert p["compute_s"] > 0
    assert p["phases"].get("halo_comm", 0) > 0
    assert sum(p["phases"].values()) == pytest.approx(
        p["comm_s"] + p["compute_s"], rel=1e-6)
    assert (p["epoch_start"], p["epoch_end"]) == (1, 2)
    assert p["n_matched_events"] > 0
    # the same record rides the fit result
    assert res is not None


@pytest.mark.profile
def test_staleness_records_per_layer_drift(profiled_run):
    """Probe epochs log per-layer relative drift: 1.0 at epoch 0 (the
    carry is zeros, drift is total) and a finite value once warm."""
    _, mpath, _ = profiled_run
    stale = [r for r in read_metrics(mpath)
             if r["event"] == "staleness"]
    by_epoch = {r["epoch"]: r for r in stale}
    assert set(by_epoch) == {0, 1}
    for r in stale:
        assert set(r["layers"]) == {"0", "1"}  # both graph layers
        for v in r["layers"].values():
            assert np.isfinite(v["rel_drift"])
            assert v["rel_drift"] >= 0
        assert r["max_rel_drift"] == pytest.approx(
            max(v["rel_drift"] for v in r["layers"].values()))
    assert by_epoch[0]["max_rel_drift"] == pytest.approx(1.0)
    assert 0.0 < by_epoch[1]["max_rel_drift"] < 10.0


@pytest.mark.profile
def test_anatomy_attributes_flops(profiled_run):
    """>= 90% of the compiled step's estimated FLOPs land in a named
    (non-'other') phase, and the spmm+dense phases dominate."""
    _, mpath, _ = profiled_run
    recs = [r for r in read_metrics(mpath) if r["event"] == "anatomy"]
    assert len(recs) == 1
    a = recs[0]
    assert a["attributed_flops_fraction"] >= 0.90
    assert a["est_flops"] > 0
    ph = a["phases"]
    assert ph["dense"]["flops"] > 0 and ph["spmm"]["flops"] > 0
    # XLA's own total rides along on backends that expose it
    assert a["flops"] is None or a["flops"] > 0


@pytest.mark.profile
def test_report_prints_measured_vs_estimated(profiled_run, capsys):
    _, mpath, _ = profiled_run
    assert report_main([str(mpath)]) == 0
    out = capsys.readouterr().out
    assert "overlap (measured)" in out
    assert "staleness rel drift" in out
    assert "anatomy flop shares" in out


def test_report_json_shape_pinned(profiled_run, capsys):
    """The --json summary is a single JSON object whose key set is a
    consumable contract for benches/CI: pin the core keys."""
    _, mpath, _ = profiled_run
    assert report_main([str(mpath), "--json"]) == 0
    s = json.loads(capsys.readouterr().out)
    required = {
        "file", "n_epoch_records", "n_eval_records", "schema_version",
        "device", "n_devices", "pipeline", "median_epoch_s",
        "loss_first", "loss_last", "loss_delta", "grad_norm_last",
        "halo_bytes_per_epoch", "staleness_age_max",
        "measured_overlap_fraction", "profile_phases", "profile_comm_s",
        "profile_compute_s", "profile_window",
        "staleness_probes", "staleness_max_rel_drift",
        "staleness_last_rel_drift",
        "anatomy_attributed_flops_fraction", "anatomy_flop_shares",
        "n_epochs", "best_val",
    }
    missing = required - set(s)
    assert not missing, f"--json summary lost keys: {sorted(missing)}"
    assert 0.0 <= s["measured_overlap_fraction"] <= 1.0
    assert s["staleness_probes"] == 2
    # estimated + measured exist together -> the divergence verdict too
    if "overlapped_comm_fraction" in s or "comm_fraction" in s:
        assert "overlap_divergence" in s


# ---------------- timeline CLI --------------------------------------------

def _write_rank_jsonl(path, rank, n_epochs=4, fault_at=None):
    with MetricsLogger(path) as ml:
        ml.run_header(config={}, device={}, mesh={"n_parts": 2})
        for e in range(n_epochs):
            rec = {"event": "epoch", "epoch": e,
                   "step_time_s": 0.5 + 0.05 * rank,
                   "loss": 1.0 - 0.1 * e, "grad_norm": 0.5,
                   "halo_bytes": 64, "staleness_age": int(e > 0),
                   "memory": None, "rank": rank}
            ml.write(rec)  # no time_unix: exercises dispatch alignment
        if fault_at is not None:
            ml.fault(kind="divergence", epoch=fault_at, rank=rank,
                     reason="synthetic")
            ml.recovery(kind="divergence", epoch=fault_at + 1,
                        rank=rank)
        ml.staleness(epoch=2, layers={"0": {"rel_drift": 0.4,
                                            "fresh_norm": 2.0}},
                     max_rel_drift=0.4, rank=rank)
        ml.profile(phases={"spmm": 0.3, "halo_comm": 0.1}, comm_s=0.1,
                   compute_s=0.4, overlap_fraction=0.75,
                   epoch_start=1, epoch_end=3, rank=rank)


def test_timeline_merges_two_ranks_chrome_valid(tmp_path, capsys):
    """Two synthetic rank streams -> one structurally-valid Chrome
    trace: sorted ts, X events with numeric dur >= 0, both ranks as
    processes, faults as instants, profile spans inside the window."""
    r0 = tmp_path / "r0.jsonl"
    r1 = tmp_path / "r1.jsonl"
    _write_rank_jsonl(r0, 0, fault_at=2)
    _write_rank_jsonl(r1, 1)
    out = tmp_path / "trace.json"
    assert timeline_main([str(r0), str(r1), "--out", str(out)]) == 0
    obj = json.load(open(out))
    assert set(obj) >= {"traceEvents", "displayTimeUnit"}
    evs = obj["traceEvents"]
    assert isinstance(evs, list) and evs
    meta = [e for e in evs if e.get("ph") == "M"]
    body = [e for e in evs if e.get("ph") != "M"]
    # both ranks present as named processes
    pnames = {e["args"]["name"] for e in meta
              if e.get("name") == "process_name"}
    assert pnames == {"rank 0", "rank 1"}
    # structural validity (the chrome://tracing loader's hard rules)
    last_ts = -1.0
    for e in body:
        assert e.get("ph") in ("X", "i", "C")
        assert isinstance(e.get("ts"), (int, float)) and e["ts"] >= 0
        if e["ph"] == "X":
            assert isinstance(e.get("dur"), (int, float))
            assert e["dur"] >= 0
        assert e["ts"] >= last_ts
        last_ts = e["ts"]
    assert {e["pid"] for e in body} == {0, 1}
    # epochs aligned at dispatch boundaries: both ranks' epoch 1 starts
    # at the same ts (the slower rank sets the boundary)
    e1 = [e for e in body if e.get("name") == "epoch 1"]
    assert len(e1) == 2
    assert e1[0]["ts"] == pytest.approx(e1[1]["ts"])
    # fault instant + profile spans made it
    assert any(e["ph"] == "i" and "fault" in e["name"] for e in body)
    assert any(e.get("tid") == 2 and e["ph"] == "X" for e in body)


def test_timeline_cli_rank_override_and_errors(tmp_path, capsys):
    r0 = tmp_path / "a.jsonl"
    _write_rank_jsonl(r0, 0)
    out = tmp_path / "t.json"
    assert timeline_main([str(r0), "--ranks", "5",
                          "--out", str(out)]) == 0
    obj = json.load(open(out))
    names = {e["args"]["name"] for e in obj["traceEvents"]
             if e.get("name") == "process_name"}
    assert names == {"rank 5"}
    capsys.readouterr()
    assert timeline_main([str(r0), "--ranks", "1,2",
                          "--out", str(out)]) == 2
    assert timeline_main([str(tmp_path / "nope.jsonl")]) == 1


# ---------------- flush-on-death ------------------------------------------

def test_fault_record_survives_hard_exit(tmp_path):
    """PR 3's watchdog exits via os._exit(75), which skips atexit and
    io teardown: the final fault record explaining the death must
    already be fsynced to disk when the process dies."""
    mpath = tmp_path / "death.jsonl"
    code = (
        "import os, sys\n"
        "sys.path.insert(0, {repo!r})\n"
        "from pipegcn_tpu.obs import MetricsLogger\n"
        "ml = MetricsLogger({path!r})\n"
        "ml.run_header(config={{}}, device={{}}, mesh={{}})\n"
        "ml.fault(kind='peer-lost', epoch=7, rank=0, peer_rank=1,\n"
        "         silent_s=61.0, hard_deadline=True)\n"
        "os._exit(75)\n"
    ).format(repo=REPO, path=str(mpath))
    r = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                       capture_output=True, timeout=120)
    assert r.returncode == 75, r.stderr.decode()
    recs = read_metrics(mpath)
    faults = [x for x in recs if x["event"] == "fault"]
    assert len(faults) == 1
    assert faults[0]["kind"] == "peer-lost"
    assert faults[0]["epoch"] == 7
    for x in recs:
        validate_record(x)


def test_hard_flush_tolerates_stringio():
    import io

    ml = MetricsLogger(io.StringIO())
    ml.fault(kind="divergence", epoch=1, rank=0)  # auto hard_flush
    ml.hard_flush()  # explicit call: no fileno -> still fine
