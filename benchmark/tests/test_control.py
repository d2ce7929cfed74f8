"""The comparison that decides `correct` has to fail what it should: the
control (the reference in fp8 with a per-tensor scale, put in the program's
place) and each fault a one-chip training cell can have, planted under the
harness. At the tiny shape the limits are the tiny shape's own, set from CPU
readings of both cells over seeds 1-6 (program: `mu_dir` 0.056-0.086,
control 0.129-0.178, half-batch fault 0.315-0.417; the other numbers'
largest program readings times 2 to 3); the cells' limits come from chip
readings and are in `benchmark/limits/`."""

import pytest

from conftest import make_tiny_root, run_cell

CELL = "reddit_p1_block"
TIGHT = {"loss1_gap": 1e-3, "gnorm1_gap": 0.006, "loss_gap": 0.004,
         "gnorm_gap": 0.04, "mu_gap": 0.08, "delta_gap": 0.15,
         "mu_dir": 0.105}


@pytest.fixture
def tight_root(tmp_path):
    return make_tiny_root(tmp_path, limits=TIGHT)


def _reading(root, seed, kind):
    from benchmark import control, harness

    spec = harness.load_spec(root, CELL)
    (row,) = control.readings([spec], seed, [kind], log=lambda m: None)
    return row


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_control_in_lower_precision_is_not_correct(tight_root, seed):
    row = _reading(tight_root, seed, "control_fp8")
    assert not row["correct"], row["compared"]
    # the number that sees it is the direction of Adam's first moment
    assert row["numbers"]["mu_dir"] > TIGHT["mu_dir"]


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_half_batch_in_the_reference_is_not_correct(tight_root, seed):
    row = _reading(tight_root, seed, "fault_half_batch")
    assert not row["correct"], row["compared"]
    assert row["numbers"]["mu_dir"] > 2 * TIGHT["mu_dir"]


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_reference_in_the_configurations_precision_is_correct(tight_root,
                                                               seed):
    row = _reading(tight_root, seed, "own_precision_bf16")
    assert row["correct"], row["compared"]


def test_sound_run_is_correct_under_the_tight_limits(tight_root,
                                                     monkeypatch):
    rc, line, _ = run_cell(tight_root, CELL, 0, monkeypatch)
    assert rc == 0 and line["correct"] is True, line["compared"]


def _state_unchanged(monkeypatch):
    import pipegcn_tpu.parallel.trainer as trainer_mod

    monkeypatch.setattr(trainer_mod, "adam_update",
                        lambda grads, opt, params, **kw: (params, opt))


def _half_batch(monkeypatch):
    import jax.numpy as jnp

    import pipegcn_tpu.parallel.trainer as trainer_mod

    whole = trainer_mod.cross_entropy_sum

    def half(logits, labels, mask):
        kept = mask * (jnp.arange(mask.shape[0]) % 2 == 0)
        # the mean over the rest: the step divides by the whole count
        return whole(logits, labels, kept) * (mask.sum() / kept.sum())

    monkeypatch.setattr(trainer_mod, "cross_entropy_sum", half)


@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch],
                         ids=["state_unchanged", "half_batch"])
def test_fault_under_the_timed_path_is_not_correct(tight_root, monkeypatch,
                                                   fault):
    rc, line, _ = run_cell(tight_root, CELL, 0, monkeypatch, patch=fault)
    assert rc == 0
    assert line["correct"] is False, line["compared"]
    row = line["compared"]["mu_dir"]
    assert row["value"] > row["limit"]


def test_readings_of_two_cells_in_one_process(tight_root, monkeypatch,
                                              capsys):
    """`control.py` as the chip call runs it: the program's seeds of both
    cells of a configuration, the control on the first seed only."""
    import json

    from benchmark import control, harness

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", tight_root + "/cache")
    monkeypatch.setattr(harness, "require_device", lambda chips: None)
    rc = control.main(["--workload", "reddit_p1_block", "reddit_p1_auto",
                       "--seeds", "4", "5", "--kinds", "program",
                       "control_fp8", "--in-place-seeds", "1"], tight_root)
    rows = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
            if ln.startswith("{")]
    assert rc == 0
    assert [(r["workload"][7:], r["seed"], r["kind"][:7]) for r in rows] == [
        ("p1_block", 4, "program"), ("p1_block", 4, "control"),
        ("p1_auto", 4, "program"), ("p1_auto", 4, "control"),
        ("p1_block", 5, "program"), ("p1_auto", 5, "program")]
    assert all(r["correct"] == (r["kind"] == "program") for r in rows)
