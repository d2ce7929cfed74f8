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
    ANCHOR_SPAN,
    classify_op,
    fold_xplane,
    hlo_op_map,
    module_name,
    parse_profile_epochs,
    scope_path,
    self_times,
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
    # the scopes this step gained: the loss, the tripwire's counts and
    # the gradient norm, the stale concat; a kernel's inner scopes stay
    # in their kernel's phase
    assert classify_op("jit(step)/shard_map/jvp(loss)/reduce_sum") \
        == "loss"
    assert classify_op("jit(step)/jvp(layer1)/tripwire/reduce_sum") \
        == "numerics"
    assert classify_op("jit(step)/grad_norm/sqrt") == "numerics"
    assert classify_op("jit(step)/jvp(layer1)/halo_concat/concatenate") \
        == "halo_concat"
    assert classify_op(
        "jit(multi)/while/body/transpose(jvp(layer1))/spmm/bwd/"
        "rem_gather/jit(_take)/gather") == "spmm"
    # a primitive named like a scope is no scope
    assert classify_op("jit(step)/jvp(layer1)/loss_weights") == "other"


@pytest.mark.parametrize("op_name, path", [
    ("jit(step)/shard_map/transpose(jvp(layer1))/spmm/bwd/gather/"
     "jit(_take)/gather", "spmm/bwd/gather"),
    ("jit(multi)/shard_map/while/body/closed_call/jvp(layer0)/spmm/"
     "reduce/reduce_sum", "spmm/reduce"),
    # the last component is the primitive, whatever its name
    ("jit(step)/shard_map/jvp(layer1)/jit(_take)/gather", ""),
    ("jit(step)/shard_map/jvp(layer1)/dense/dot_general", "dense"),
    ("jit(step)/shard_map/jvp(loss)/jit(log_softmax)/exp", "loss"),
    # a fusion that lists two instructions is named by the first
    ("transpose(jvp())/norm/mul;transpose(jvp())/broadcast_in_dim",
     "norm"),
    ("", ""),
])
def test_scope_path(op_name, path):
    assert scope_path(op_name) == path


def _program(scan_length, ops, module="jit_multi"):
    return {"scan_length": scan_length, "module": module,
            "map": {k: (v, k.split(".")[0]) for k, v in ops.items()}}


def test_fold_xplane_overlap_math():
    """Synthetic lines: comm [0, 10] with compute covering [0, 6] on
    the same device -> 60% overlap; phases fold by classified scope;
    seconds are the mean over the two devices."""
    prog = _program(1, {
        "collective-permute.1": "jit(s)/layer0/halo_exchange/ppermute",
        "dot.1": "jit(s)/layer0/spmm/tile/dot_general",
        "dot.2": "jit(s)/layer0/dense/dot_general"}, module="jit_s")
    tr = {"host": [], "lines": [
        {"device": 0, "events": [
            ["collective-permute.1", 0.0, 10.0, "jit_s(1)"]]},
        {"device": 0, "events": [["dot.1", 0.0, 4.0, "jit_s(1)"],
                                 ["dot.2", 4.0, 2.0, "jit_s(1)"]]},
        # another device's compute must NOT cover device 0's comm
        {"device": 1, "events": [["dot.1", 0.0, 100.0, "jit_s(1)"]]},
    ]}
    out = fold_xplane(tr, [prog])
    assert out["overlap_fraction"] == pytest.approx(0.6)
    assert out["comm_s"] == pytest.approx(10.0 / 2 * 1e-9)
    assert out["phases"]["halo_comm"] == pytest.approx(5e-9)
    assert out["phases"]["spmm"] == pytest.approx(52e-9)
    assert out["phases"]["dense"] == pytest.approx(1e-9)
    assert out["paths"]["spmm/tile"] == pytest.approx(52e-9)
    assert out["n_device_events"] == 4 and out["n_matched_events"] == 4
    assert out["n_devices"] == 2
    # device 0 is busy over [0, 10], device 1 over [0, 100]
    assert out["busy_s"] == pytest.approx(55e-9)
    assert out["window_s"] == pytest.approx(100e-9)


def test_hlo_op_map_parses_metadata():
    txt = (
        'HloModule jit_step, entry_computation_layout={()->f32[2]}\n\n'
        'ENTRY %main.5 () -> f32[2] {\n'
        '  %dot.1 = f32[2]{0} dot(f32[2,3]{1,0} %a, f32[3]{0} %b), '
        'lhs_contracting_dims={1}, rhs_contracting_dims={0}, '
        'metadata={op_name="jit(step)/layer0/spmm/tile/dot_general" '
        'source_file="x.py" source_line=1}\n'
        '  ROOT %cp.2 = f32[2]{0} collective-permute(f32[2]{0} %dot.1), '
        'metadata={op_name="jit(step)/layer0/halo_exchange/ppermute"}\n'
        '}\n')
    m = hlo_op_map(txt)
    assert m["dot.1"] == ("jit(step)/layer0/spmm/tile/dot_general", "dot")
    assert scope_path(m["dot.1"][0]) == "spmm/tile"
    assert m["cp.2"][1] == "collective-permute"
    assert module_name(txt) == "jit_step"


# ---------------- the fold on a nested op line ----------------------------
# A chip's op line as recorded: a `while` spans its body, whose fusions
# span nothing. Times in ns; [op, start, duration, module].

_SCAN_OPS = {
    "while.1": "jit(multi)/while",
    "fusion.1": "jit(multi)/while/body/jvp(layer1)/spmm/gather/"
                "jit(_take)/gather",
    "fusion.2": "jit(multi)/while/body/jvp(layer1)/spmm/reduce/reduce_sum",
    "fusion.3": "jit(multi)/while/body/transpose(jvp(layer1))/spmm/bwd/"
                "gather/jit(_take)/gather",
    "copy.1": "",
}


def _scan_line(start, module, n_body):
    """One run of a scan program: copy.1, then while.1 over `n_body`
    rounds of fusion.1 (30), fusion.2 (20), fusion.3 (40) with 10 of
    the loop's own time between rounds."""
    evs = [["copy.1", start, 50.0, module]]
    t = start + 60.0
    w0 = t
    body = []
    for _ in range(n_body):
        t += 10.0
        for name, dur in (("fusion.1", 30.0), ("fusion.2", 20.0),
                          ("fusion.3", 40.0)):
            body.append([name, t, dur, module])
            t += dur
    evs.append(["while.1", w0, t - w0, module])
    return evs + body, t


def test_fold_nested_while_self_times_and_union():
    evs, end = _scan_line(1000.0, "jit_multi(7)", 2)
    selfs, leaves = self_times(evs)
    by_name = {}
    for e, ns in zip(evs, selfs):
        by_name[e[0]] = by_name.get(e[0], 0.0) + ns
    # the while's own time is what its body leaves: 2 x 10
    assert by_name["while.1"] == pytest.approx(20.0)
    assert by_name["fusion.1"] == pytest.approx(60.0)
    assert leaves[evs.index(next(e for e in evs if e[0] == "while.1"))] \
        is False
    out = fold_xplane({"host": [], "lines": [{"device": 0, "events": evs}]},
                      [_program(2, _SCAN_OPS)])
    # busy is the union: the while and its body are counted once
    assert out["busy_s"] == pytest.approx((50.0 + 200.0) * 1e-9)
    assert out["window_s"] == pytest.approx((end - 1000.0) * 1e-9)
    assert out["busy_s"] <= out["window_s"]
    assert sum(out["phases"].values()) + out["other_programs_s"] \
        == pytest.approx(out["busy_s"])
    assert out["paths"] == pytest.approx({
        "spmm/gather": 60e-9, "spmm/reduce": 40e-9,
        "spmm/bwd/gather": 80e-9})
    assert out["phases"]["spmm"] == pytest.approx(180e-9)
    # the while and the copy name no scope of the program
    assert out["unscoped_s"] == pytest.approx(70e-9)
    assert out["programs"][0]["matched"] == 1.0


def test_fold_two_programs_with_clashing_instruction_numbers():
    """A scan of 2 and a scan of 3 epochs are two programs in which
    `fusion.2` is another operation: each module is read through the
    map of its own program, told apart by the rounds its body ran."""
    ops3 = dict(_SCAN_OPS)
    ops3["fusion.2"] = _SCAN_OPS["fusion.3"]      # the numbers clash
    ops3["fusion.3"] = _SCAN_OPS["fusion.2"]
    a, end_a = _scan_line(0.0, "jit_multi(11)", 2)
    b, _ = _scan_line(end_a + 100.0, "jit_multi(22)", 3)
    out = fold_xplane(
        {"host": [], "lines": [{"device": 0, "events": a + b}]},
        [_program(2, _SCAN_OPS), _program(3, ops3)])
    progs = {p["scan_length"]: p for p in out["programs"]}
    assert progs[2]["modules"] == ["jit_multi(11)"]
    assert progs[3]["modules"] == ["jit_multi(22)"]
    assert progs[2]["matched"] == progs[3]["matched"] == 1.0
    # scan 2: reduce 2 x 20; scan 3: its `fusion.3` is the reduce, 3 x 40
    assert out["paths"]["spmm/reduce"] == pytest.approx((40 + 120) * 1e-9)
    assert out["paths"]["spmm/bwd/gather"] == pytest.approx(
        (80 + 60) * 1e-9)


def test_fold_leaves_another_modules_events_out():
    """An eval program or the eager key building inside the window:
    busy, but in no phase of the step."""
    evs, end = _scan_line(0.0, "jit_multi(7)", 2)
    foreign = [["fusion.1", end + 50.0, 500.0, "jit_eval(9)"],
               ["threefry.4", end + 600.0, 30.0, "jit__threefry(3)"]]
    out = fold_xplane(
        {"host": [], "lines": [{"device": 0, "events": evs + foreign}]},
        [_program(2, _SCAN_OPS)])
    assert out["other_programs_s"] == pytest.approx(530e-9)
    assert out["phases"]["spmm"] == pytest.approx(180e-9)
    assert out["busy_s"] == pytest.approx((250.0 + 530.0) * 1e-9)
    assert out["busy_s"] <= out["window_s"]
    assert out["programs"][0]["modules"] == ["jit_multi(7)"]
    assert out["n_device_events"] == len(evs) + 2


def test_fold_idle_gaps_named_by_program_span():
    """A gap is named by the innermost span of the program open at its
    middle; a runtime event nested deeper stands beside it."""
    a, end_a = _scan_line(0.0, "jit_multi(7)", 2)
    b, _ = _scan_line(end_a + 1000.0, "jit_multi(7)", 2)
    mid = end_a + 500.0
    host = [[ANCHOR_SPAN, 5.0, 1.0],
            ["step", end_a + 300.0, 5000.0],
            ["fit/keys", mid - 100.0, 300.0],
            ["PjitFunction(_threefry_fold_in)", mid - 10.0, 50.0],
            ["fit/harvest", end_a + 10.0, 200.0]]
    out = fold_xplane({"host": host,
                       "lines": [{"device": 0, "events": a + b}]},
                      [_program(2, _SCAN_OPS)])
    top = out["idle_gaps"][0]
    assert top["span"] == "fit/keys"
    assert top["inner"] == "PjitFunction(_threefry_fold_in)"
    assert top["s"] == pytest.approx(1000e-9) and top["n"] == 1
    assert out["anchor_s"] == pytest.approx(5e-9)


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
    phases (P=4 -> halo collectives exist), busy time inside the
    window, and the capture window."""
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
    assert 0 < p["busy_s"] <= p["window_s"]
    assert p["n_devices"] == 4
    # one epoch inside the window: the single-epoch step, joined
    assert [q["scan_length"] for q in p["programs"]] == [1]
    assert p["programs"][0]["matched"] > 0.9
    assert p["paths"].get("halo_exchange", 0) > 0
    # the trace clock's zero lies on the stream's clock, in this run
    spans = [r for r in read_metrics(mpath) if r["event"] == "span"]
    assert spans and abs(p["t0_unix"] - spans[-1]["t_start"]) < 600
    # the same record rides the fit result
    assert res is not None


@pytest.fixture(scope="module")
def fused_run(tmp_path_factory):
    """The same job over 12 epochs in scans of 3 (blocks of 3, 2, 3, 2,
    2 under --log-every 5), once with a window over epochs [3, 9) and
    once with profiling off."""
    from pipegcn_tpu.cli.main import run

    out = {}
    for name, extra in (("off", []),
                        ("on", ["--profile-epochs", "3:9"])):
        tmp_path = tmp_path_factory.mktemp("fused_" + name)
        mpath = tmp_path / "metrics.jsonl"
        if extra:
            extra += ["--profile-dir", str(tmp_path / "trace")]
        args = _cli_args(tmp_path, [
            "--enable-pipeline", "--n-epochs", "12",
            "--fused-epochs", "3", "--spmm-impl", "bucket",
            "--metrics-out", str(mpath)] + extra)
        out[name] = (mpath, run(args))
    return out


@pytest.mark.profile
def test_profile_window_traces_the_fused_scans(fused_run):
    """The window is cut at 3 and at 9 and otherwise dispatched as the
    run would be: blocks [3, 5), [5, 8), [8, 9), so the record joins
    the scans of 2 and 3 epochs and the single step, each against its
    own compiled text, and reads the kernels' scopes."""
    mpath, res = fused_run["on"]
    profs = [r for r in read_metrics(mpath) if r["event"] == "profile"]
    assert len(profs) == 1
    p = profs[0]
    validate_record(p)
    assert (p["epoch_start"], p["epoch_end"]) == (3, 9)
    progs = {q["scan_length"]: q for q in p["programs"]}
    assert set(progs) == {1, 2, 3}
    for q in progs.values():
        assert q["n_events"] > 0 and q["matched"] > 0.9
        assert len(q["modules"]) == 1
    assert p["paths"]["spmm/gather"] > 0
    assert any(k.startswith("spmm/bwd/") for k in p["paths"])
    under = sum(v for k, v in p["paths"].items() if k.startswith("spmm"))
    # (each path is rounded to a nanosecond)
    assert under == pytest.approx(p["phases"]["spmm"], abs=1e-7)
    assert 0 < p["busy_s"] <= p["window_s"]
    # the gaps are named by this loop's host spans
    assert p["idle_gaps"]
    assert {g["span"] for g in p["idle_gaps"]} & {
        "fit/dispatch", "fit/keys", "fit/wait", "fit/harvest",
        "fit/log", "fit/boundary"}


def test_profiling_off_compiles_what_the_plan_needs(fused_run):
    """With profiling off the run dispatches scans of 3 and 2 and never
    the single step: two compiled step programs, as before the scopes
    and the spans were there; no profile record."""
    mpath, res = fused_run["off"]
    t = res["trainer"]
    assert t._multi_step._cache_size() == 2
    assert t._step._cache_size() == 0
    assert not [r for r in read_metrics(mpath) if r["event"] == "profile"]
    # the window adds one program: the single step of block [8, 9)
    t_on = fused_run["on"][1]["trainer"]
    assert t_on._multi_step._cache_size() == 2
    assert t_on._step._cache_size() == 1


def test_report_prints_paths_under_the_phase_table(fused_run, capsys):
    mpath, _ = fused_run["on"]
    assert report_main([str(mpath)]) == 0
    out = capsys.readouterr().out
    assert out.index("profiled device time") \
        < out.index("device busy (profiled)") < out.index("spmm/gather")
    assert "scans of [1, 2, 3]" in out
    assert "device idle (profiled)" in out
    assert report_main([str(mpath), "--json"]) == 0
    s = json.loads(capsys.readouterr().out)
    assert s["profile_scan_lengths"] == [1, 2, 3]
    assert s["profile_paths"]["spmm/gather"] > 0


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
