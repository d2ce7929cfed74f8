"""chip_smoke.py's phase functions at a tiny shape on the CPU mesh, its
failure branches, and the two entry points that must refuse to measure
without a TPU (chip_smoke.py, bench.py)."""

import json
import os
import shutil
import subprocess
import sys
from types import SimpleNamespace

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

TINY = dict(nodes=3000, degree=12, n_feat=32, n_class=8, hidden=32,
            n_layers=3)


@pytest.fixture(scope="module")
def legs(tmp_path_factory):
    """P=1, then P=4 pipelined and vanilla on the virtual mesh, through
    the same function main() calls on the chip."""
    root = tmp_path_factory.mktemp("smoke")
    out, part = str(root / "out"), str(root / "parts")
    os.makedirs(out)
    return out, [
        chip_smoke.train_leg(chip_smoke.smoke_args(
            out, part, n_parts=p, pipeline=pipe, **TINY),
            log=lambda *_: None)
        for p, pipe in ((1, True), (4, True), (4, False))]


def test_smoke_legs_pass_at_tiny_shape(legs):
    _, (p1, p4, p4v) = legs
    for leg in (p1, p4, p4v):
        assert leg["loss_last"] < leg["loss_first"]
        assert leg["kernel"] == leg["winner"]["name"]
        # 20 epochs dispatch as 4,4,2,4,4,2 (main.py's 10-epoch cadence)
        assert [n for n, _ in leg["dispatches_s"]] == [4, 4, 2, 4, 4, 2]
        assert set(leg["setup_s"]) >= {"graph_partition", "tables",
                                       "upload", "pp_precompute"}
    assert p1["artifact_source"] == p1["tables_source"] \
        == p4["artifact_source"] == "built in this run"
    assert p4v["artifact_source"].startswith("loaded from ")
    assert "placement" not in p1
    assert p4["pipeline"] and not p4v["pipeline"]
    # the vanilla leg found what the pipelined one left under partitions/
    assert p4v["tuning_source"] == "artifact"
    assert p4v["tables_source"].startswith("loaded from ")
    for leg in (p4, p4v):
        assert len(set(leg["placement"]["devices"])) == 4
        assert leg["placement"]["ici_bytes_per_epoch"] > 0


def _fake_trainer(impl, fallbacks=()):
    return SimpleNamespace(fallbacks=list(fallbacks),
                           _current_impl=lambda: impl)


def _rewrite(src, dst, edit):
    recs = [json.loads(line) for line in open(src)]
    with open(dst, "w") as f:
        for r in edit(recs):
            f.write(json.dumps(r) + "\n")


def test_check_stream_failure_branches(legs, tmp_path):
    out, (p1, _, _) = legs
    good = os.path.join(out, "metrics-p1-pipelined.jsonl")
    impl = p1["winner"]["impl"]
    facts = chip_smoke.check_stream(good, _fake_trainer(impl))
    assert facts["kernel"] == impl
    bad = str(tmp_path / "bad.jsonl")

    # a fallback record in the stream
    _rewrite(good, bad, lambda rs: rs + [
        {"event": "fallback", "epoch": 3, "from_impl": "block",
         "to_impl": "bucket", "reason": "INTERNAL: TPU backend error"}])
    with pytest.raises(chip_smoke.SmokeFailure, match="downgraded"):
        chip_smoke.check_stream(bad, _fake_trainer(impl))
    # ... or only on the trainer (a run without a metrics sink record)
    with pytest.raises(chip_smoke.SmokeFailure, match="downgraded"):
        chip_smoke.check_stream(good, _fake_trainer(
            impl, [{"from_impl": "block", "to_impl": "bucket"}]))

    # a tuner candidate that did not compile
    def break_candidate(rs):
        for r in rs:
            if r["event"] == "tuning":
                r["costs"][2]["error"] = "XlaRuntimeError('INTERNAL')"
        return rs

    _rewrite(good, bad, break_candidate)
    with pytest.raises(chip_smoke.SmokeFailure, match="did not compile"):
        chip_smoke.check_stream(bad, _fake_trainer(impl))

    # a fault record (the sentinel rolled back and carried on)
    _rewrite(good, bad, lambda rs: rs + [
        {"event": "fault", "kind": "divergence", "epoch": 7}])
    with pytest.raises(chip_smoke.SmokeFailure, match="fault records"):
        chip_smoke.check_stream(bad, _fake_trainer(impl))

    # a loss that does not fall
    def flat_loss(rs):
        for r in rs:
            if r["event"] == "epoch":
                r["loss"] = 1.0
        return rs

    _rewrite(good, bad, flat_loss)
    with pytest.raises(chip_smoke.SmokeFailure, match="did not fall"):
        chip_smoke.check_stream(bad, _fake_trainer(impl))

    # another kernel dispatched than the tuner chose
    with pytest.raises(chip_smoke.SmokeFailure, match="dispatched"):
        chip_smoke.check_stream(good, _fake_trainer("xla"))


def test_reference_agreement_small_input(legs):
    _, (p1, _, _) = legs
    ref = chip_smoke.check_reference(p1["winner"], nodes=2000, degree=40,
                                     width=64)
    assert ref["fwd_median_rel_err"] < chip_smoke.REF_FWD_MEDIAN_REL
    assert ref["bwd_median_rel_err"] < chip_smoke.REF_BWD_MEDIAN_REL


def _run(argv, **env):
    full = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu", **env)
    return subprocess.run([sys.executable, *argv], cwd=REPO, env=full,
                          capture_output=True, text=True, timeout=600)


@pytest.mark.parametrize("script", ["chip_smoke.py", "bench.py"])
def test_measurement_entry_points_refuse_the_cpu(script):
    r = _run([script])
    assert r.returncode != 0
    # says what it found, prints no result
    assert "platform='cpu'" in r.stderr
    assert '"metric"' not in r.stdout and '"ok"' not in r.stdout


def test_bench_cpu_dry_run_is_labelled():
    # bench.py anchors its artifact under <repo>/partitions (gitignored)
    art = os.path.join(REPO, "partitions", "bench-small-1-c2-s1024")
    mine = not os.path.exists(art)
    try:
        r = _run(["bench.py", "--cpu", "--small", "--parts", "1",
                  "--blocks", "2", "--no-compare", "--reorder", "none"],
                 XLA_FLAGS="")
    finally:
        if mine:
            shutil.rmtree(art, ignore_errors=True)
    assert r.returncode == 0, r.stderr[-2000:]
    res = json.loads(r.stdout.strip().splitlines()[-1])
    assert res["metric"] == "cpu_dryrun_small_epoch_time"
    assert res["backend"] == "cpu" and res["device"] == "cpu"
    # no chip number: nothing relative to the reference, no utilization,
    # no older measurement attached
    assert not {"vs_baseline", "mfu_pct", "last_tpu_measurement",
                "degraded", "stage"} & set(res)
    assert "devices: 1 x cpu (platform=cpu)" in r.stderr
