"""Numerical-robustness tests (docs/RESILIENCE.md "Numerics"): the
in-graph non-finite tripwire and its provenance, the loss-scale state
machine (backoff / step-skip / regrowth), the kernel fallback ladder,
the amax-clamped fp8 transport cast, and the products-shape NaN
regression — all tier-1-safe on the CPU mesh.
"""

import io
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pipegcn_tpu.graph import synthetic_graph
from pipegcn_tpu.models import ModelConfig
from pipegcn_tpu.obs import MetricsLogger, validate_record
from pipegcn_tpu.ops.bucket_spmm import (
    amax_transport_cast,
    transport_cast,
)
from pipegcn_tpu.parallel import Trainer, TrainConfig
from pipegcn_tpu.partition import ShardedGraph, partition_graph
from pipegcn_tpu.resilience import (
    DivergenceSentinel,
    FaultPlan,
    KernelFallbackError,
    LossScaleConfig,
    LossScaler,
    SentinelConfig,
)
from pipegcn_tpu.resilience.numerics import (
    PHASES,
    epoch_nonfinite_counts,
    fallback_ladder,
    first_nonfinite_phase,
    is_kernel_error,
    sanitize_for_sentinel,
    summarize_numerics,
)

pytestmark = pytest.mark.numerics


@pytest.fixture(scope="module")
def sharded():
    g = synthetic_graph(num_nodes=300, avg_degree=6, n_feat=8, n_class=3,
                        seed=1)
    parts = partition_graph(g, 2, seed=0)
    return ShardedGraph.build(g, parts, n_parts=2)


def _trainer(sg, *, mkw=None, **tkw):
    mkw = dict(mkw or {})
    mkw.setdefault("layer_sizes", (sg.n_feat, 16, sg.n_class))
    mkw.setdefault("dropout", 0.0)
    mkw.setdefault("train_size", sg.n_train_global)
    tkw.setdefault("n_epochs", 10)
    tkw.setdefault("log_every", 50)
    return Trainer(sg, ModelConfig(**mkw), TrainConfig(**tkw))


# ---------------- loss-scale state machine (host) ---------------------


def test_loss_scale_config_parse():
    assert not LossScaleConfig.parse("off").enabled
    assert not LossScaleConfig.parse("").enabled
    auto = LossScaleConfig.parse("auto")
    assert auto.mode == "auto" and auto.enabled
    stat = LossScaleConfig.parse("1024")
    assert stat.mode == "static" and stat.init_scale == 1024.0
    with pytest.raises(ValueError, match="auto"):
        LossScaleConfig.parse("warp9")
    with pytest.raises(ValueError, match="positive"):
        LossScaleConfig.parse("-4")
    with pytest.raises(ValueError, match="positive"):
        LossScaleConfig.parse("inf")


def test_loss_scaler_backoff_skip_and_regrow():
    s = LossScaler(LossScaleConfig(mode="auto", init_scale=1024.0,
                                   growth_interval=3))
    assert s.scale == 1024.0
    # clean epochs: no events until the growth interval fills
    assert s.update(0, [0, 0]) == []
    # an overflow halves the scale and counts the skipped step
    evs = s.update(2, [1])
    assert [e["kind"] for e in evs] == ["overflow"]
    assert evs[0]["skipped"] and evs[0]["new_scale"] == 512.0
    assert s.scale == 512.0 and s.n_skipped == 1 and s.n_backoffs == 1
    # the overflow reset the clean streak; 3 clean epochs regrow
    evs = s.update(3, [0, 0, 0])
    assert [e["kind"] for e in evs] == ["growth"]
    assert s.scale == 1024.0 and s.n_growths == 1
    # static mode: skips counted, scale never moves
    st = LossScaler(LossScaleConfig(mode="static", init_scale=64.0))
    evs = st.update(0, [1])
    assert evs[0]["kind"] == "overflow" and "new_scale" not in evs[0]
    assert st.scale == 64.0 and st.n_skipped == 1
    # disabled: flags are ignored entirely
    off = LossScaler(LossScaleConfig())
    assert off.update(0, [1, 1]) == [] and off.scale == 1.0


def test_loss_scaler_respects_scale_bounds():
    s = LossScaler(LossScaleConfig(mode="auto", init_scale=2.0,
                                   min_scale=1.0, max_scale=4.0,
                                   growth_interval=1))
    s.update(0, [1])          # 2 -> 1
    assert s.scale == 1.0
    evs = s.update(1, [1])    # would go below min: skip counted, no halve
    assert evs[0]["kind"] == "overflow" and "new_scale" not in evs[0]
    assert s.scale == 1.0
    s.update(2, [0])          # 1 -> 2
    s.update(3, [0])          # 2 -> 4
    s.update(4, [0])          # at max: stays
    assert s.scale == 4.0


def test_sanitize_for_sentinel_masks_overflow_epochs():
    losses = [1.0, np.nan, 0.8]
    gn = [0.5, np.inf, 0.4]
    sl, sg_ = sanitize_for_sentinel(losses, gn, [0, 1, 0])
    assert np.isfinite(sl).all() and np.isfinite(sg_).all()
    assert sl[1] == 1.0 and sg_[1] == 0.5   # nearest preceding clean
    # a block that STARTS flagged borrows the first clean value
    sl, _ = sanitize_for_sentinel([np.nan, 2.0], [np.inf, 1.0], [1, 0])
    assert sl[0] == 2.0
    # fully-flagged block: nothing for the sentinel to check
    assert sanitize_for_sentinel([np.nan], [np.nan], [1]) == (None, None)


# ---------------- tripwire provenance (host helpers) ------------------


def test_first_nonfinite_phase_dataflow_order():
    assert first_nonfinite_phase({}) is None
    assert first_nonfinite_phase({ph: 0 for ph in PHASES}) is None
    # contamination cascades downstream; the FIRST phase is the cause
    assert first_nonfinite_phase(
        {"loss": 1, "spmm": 12, "dense": 3, "grads": 99}) == "spmm"
    assert first_nonfinite_phase({"grads": 4}) == "grads"
    # fused-block [k]-arrays count as tripped when any epoch tripped
    assert first_nonfinite_phase({"dense": [0, 2, 0]}) == "dense"


def test_epoch_nonfinite_counts_slices_fused_blocks():
    nm = {"spmm": [0, 7, 0], "loss": [0, 1, 0], "dense": 0}
    assert epoch_nonfinite_counts(nm, 1) == {"spmm": 7, "loss": 1}
    assert epoch_nonfinite_counts(nm, 0) == {}


# ---------------- kernel fallback ladder (host helpers) ---------------


def test_fallback_ladder_order():
    assert fallback_ladder("block") == ["bucket", "xla"]
    # unknown/retired kernel names degrade straight to the workhorse
    assert fallback_ladder("pallas") == ["xla"]
    assert fallback_ladder("bucket") == ["xla"]
    assert fallback_ladder("gat-bucket") == ["xla"]
    assert fallback_ladder("xla") == []


def test_is_kernel_error_classification():
    assert is_kernel_error(RuntimeError("INTERNAL: TPU backend error"))
    assert is_kernel_error(RuntimeError("RESOURCE EXHAUSTED: vmem"))
    assert is_kernel_error(RuntimeError(
        "fault-injected kernel dispatch failure"))
    assert not is_kernel_error(ValueError("bad flag"))
    assert not is_kernel_error(KeyboardInterrupt())


# ---------------- amax-clamped fp8 cast -------------------------------


def test_amax_cast_avoids_saturation_and_underflow():
    # large activations: the static e4m3 clamp saturates at +-448 and
    # biases the mean; the amax cast rescales into range
    x = jnp.asarray(np.linspace(-4000.0, 4000.0, 64, dtype=np.float32))
    y_static = transport_cast(x, jnp.float8_e4m3fn).astype(jnp.float32)
    assert float(jnp.max(jnp.abs(y_static))) <= 448.0  # saturated
    y, inv = amax_transport_cast(x, jnp.float8_e4m3fn)
    back = y.astype(jnp.float32) * inv
    assert np.allclose(np.asarray(back), np.asarray(x), rtol=0.08)
    # tiny cotangents: e5m2's smallest subnormal is ~1.5e-5 — the
    # static cast flushes to zero, the amax cast preserves them
    t = jnp.asarray(np.full(8, 3e-7, np.float32))
    flushed = transport_cast(t, jnp.float8_e5m2).astype(jnp.float32)
    assert float(jnp.max(jnp.abs(flushed))) == 0.0
    y, inv = amax_transport_cast(t, jnp.float8_e5m2)
    back = np.asarray(y.astype(jnp.float32) * inv)
    assert np.all(back > 0) and np.allclose(back, 3e-7, rtol=0.3)
    # degenerate inputs stay degenerate, never a NaN scale
    z, invz = amax_transport_cast(jnp.zeros(4), jnp.float8_e4m3fn)
    assert float(invz) == 1.0 and not np.any(np.asarray(z))
    n, _ = amax_transport_cast(jnp.asarray([np.nan, 1.0]),
                               jnp.float8_e4m3fn)
    assert np.isnan(np.asarray(n.astype(jnp.float32))[0])
    # non-fp8 targets fall back to the plain saturating cast
    b, invb = amax_transport_cast(x, jnp.bfloat16)
    assert invb is None and b.dtype == jnp.bfloat16


# ---------------- tripwire in the jitted step -------------------------


def test_tripwire_counts_ride_step_metrics(sharded):
    t = _trainer(sharded, enable_pipeline=True)
    t.train_epoch(0)
    nm = {k: int(v) for k, v in t._last_metrics["numerics"].items()}
    assert set(nm) == set(PHASES)
    assert all(v == 0 for v in nm.values())
    # fused blocks carry [k]-arrays of counts
    t.train_epochs(1, 3)
    nm = t._last_metrics["numerics"]
    assert all(np.asarray(v).shape == (3,) for v in nm.values())


def test_tripwire_names_birth_phase_on_poisoned_input(sharded):
    t = _trainer(sharded, enable_pipeline=True)
    feat = np.array(np.asarray(t.data["feat"]))
    feat[0, 3, 1] = np.nan
    t.data["feat"] = jax.device_put(jnp.asarray(feat), t._shard)
    loss = t.train_epoch(0)
    assert not np.isfinite(loss)
    nm = {k: int(v) for k, v in t._last_metrics["numerics"].items()}
    assert nm["input"] == 1            # exactly the poisoned element
    assert nm["loss"] >= 1 and nm["grads"] >= 1
    assert first_nonfinite_phase(nm) == "input"


def test_tripwire_off_drops_counts(sharded):
    t = _trainer(sharded, numerics_tripwire=False)
    t.train_epoch(0)
    assert "numerics" not in t._last_metrics


def test_fit_fault_record_names_phase(sharded):
    """A REAL in-graph NaN (not an injected host-side one) trips the
    sentinel AND the fault record carries the tripwire's birth phase,
    plus a contracted `numerics` kind="tripwire" record."""
    t = _trainer(sharded, enable_pipeline=True, n_epochs=6)
    feat = np.array(np.asarray(t.data["feat"]))
    feat[1, 2, 0] = np.inf
    t.data["feat"] = jax.device_put(jnp.asarray(feat), t._shard)
    buf = io.StringIO()
    with pytest.raises(Exception):  # retries re-hit the poisoned input
        t.fit(eval_graphs=None, log_fn=lambda s: None,
              metrics=MetricsLogger(buf),
              sentinel=DivergenceSentinel(SentinelConfig(max_retries=1)))
    recs = [json.loads(line) for line in buf.getvalue().splitlines()]
    faults = [r for r in recs if r["event"] == "fault"]
    assert faults and faults[0]["phase"] == "input"
    trip = [r for r in recs if r["event"] == "numerics"
            and r["kind"] == "tripwire"]
    assert trip and trip[0]["phase"] == "input"
    assert trip[0]["counts"].get("input") == 1
    for r in trip:
        validate_record(r)
    assert summarize_numerics(recs)["first_nan_phase"] == "input"


# ---------------- loss scaling in the jitted step ---------------------


def test_static_loss_scale_matches_unscaled_trajectory(sharded):
    """Scaling multiplies the loss before backward and divides the
    reduced grads after — in f32, a power-of-two scale must reproduce
    the unscaled trajectory almost exactly."""
    t0 = _trainer(sharded, enable_pipeline=True, seed=3)
    t1 = _trainer(sharded, enable_pipeline=True, seed=3,
                  loss_scale="1024")
    for e in range(4):
        l0 = t0.train_epoch(e)
        l1 = t1.train_epoch(e)
        assert abs(l0 - l1) < 1e-4 * max(1.0, abs(l0))
    assert int(t1._last_metrics["overflow"]) == 0


def test_overflow_skips_step_and_backs_off_in_fit(sharded):
    """Injected overflow: the scaler halves the scale, counts the
    skip, emits a contracted `numerics` record — and the sentinel does
    NOT mistake the handled overflow for divergence."""
    t = _trainer(sharded, enable_pipeline=True, n_epochs=8,
                 loss_scale="auto")
    buf = io.StringIO()
    logs = []
    t.fit(eval_graphs=None, log_fn=logs.append,
          metrics=MetricsLogger(buf),
          sentinel=DivergenceSentinel(SentinelConfig()),
          fault_plan=FaultPlan.parse("overflow@3"))
    recs = [json.loads(line) for line in buf.getvalue().splitlines()]
    ovf = [r for r in recs if r["event"] == "numerics"
           and r["kind"] == "overflow"]
    assert len(ovf) == 1 and ovf[0]["epoch"] == 3
    assert ovf[0]["skipped"] and ovf[0]["new_scale"] == ovf[0]["scale"] / 2
    for r in ovf:
        validate_record(r)
    # no divergence fault, no rollback — the overflow was handled
    assert not any(r["event"] == "fault" for r in recs)
    assert t.loss_scaler.n_skipped == 1
    assert t.loss_scaler.scale == LossScaleConfig.parse("auto").init_scale / 2
    s = summarize_numerics(recs)
    assert s["loss_scale_skips"] == 1 and s["loss_scale_backoffs"] == 1
    assert any("step skipped" in line for line in logs)


# ---------------- kernel fallback ladder (trainer) --------------------


def test_kernel_crash_downgrades_and_completes(sharded, tmp_path, capsys):
    """Acceptance: a simulated kernel-dispatch failure completes
    training via an automatic logged fallback instead of crashing, and
    the error that forced it is on stderr whoever reads the metrics."""
    t = _trainer(sharded, mkw={"spmm_impl": "block", "block_tile": 16},
                 enable_pipeline=True, n_epochs=6)
    assert t._current_impl() == "block"
    buf = io.StringIO()
    logs = []
    res = t.fit(eval_graphs=None, log_fn=logs.append,
                metrics=MetricsLogger(buf),
                fault_plan=FaultPlan.parse("kernel-crash@2"))
    assert t._current_impl() == "bucket"     # one rung down, not two
    assert t.last_epoch == t.tcfg.n_epochs
    assert res["history"] or True
    recs = [json.loads(line) for line in buf.getvalue().splitlines()]
    falls = [r for r in recs if r["event"] == "fallback"]
    assert len(falls) == 1
    assert falls[0]["from_impl"] == "block"
    assert falls[0]["to_impl"] == "bucket"
    assert "fault-injected" in falls[0]["reason"]
    for r in falls:
        validate_record(r)
    # every epoch record is finite: the downgraded kernel trained on
    losses = [r["loss"] for r in recs if r["event"] == "epoch"]
    assert len(losses) == 6 and np.isfinite(losses).all()
    assert any("kernel fallback: block -> bucket" in line
               for line in logs)
    assert summarize_numerics(recs)["kernel_fallbacks"] == \
        ["block->bucket"]
    err = capsys.readouterr().err
    assert "KERNEL DOWNGRADE block -> bucket" in err
    assert "fault-injected kernel dispatch failure" in err


def test_fallback_ladder_exhaustion_raises(sharded):
    t = _trainer(sharded, mkw={"spmm_impl": "xla"})
    t._inject_kernel_crash = True
    with pytest.raises(KernelFallbackError, match="no fallback rung"):
        t.train_epoch(0)


def test_bucket_crash_falls_straight_to_xla(sharded):
    """Under spmm_impl="bucket" the FIRST fallback is the impl ladder's
    next rung (no same-kernel rung stands before it) and the retried
    epoch trains on."""
    t = _trainer(sharded, mkw={"spmm_impl": "bucket"})
    t._inject_kernel_crash = True
    loss = t.train_epoch(0)
    assert t.fallbacks[0]["from_impl"] == "bucket"
    assert t.fallbacks[0]["to_impl"] == "xla"
    assert np.isfinite(loss)


def test_downgraded_trainer_keeps_trajectory(sharded):
    """The fallback rebuilds tables + step but restores the
    pre-dispatch state: the downgraded run's losses stay finite and
    the retried epoch re-runs (bucket and block kernels are
    numerically equivalent formulations of the same mean)."""
    ref = _trainer(sharded, mkw={"spmm_impl": "bucket"},
                   enable_pipeline=True, seed=11)
    ref_losses = [ref.train_epoch(e) for e in range(3)]
    t = _trainer(sharded, mkw={"spmm_impl": "block", "block_tile": 16},
                 enable_pipeline=True, seed=11)
    t._inject_kernel_crash = True
    losses = [t.train_epoch(e) for e in range(3)]
    assert t._current_impl() == "bucket"
    np.testing.assert_allclose(losses, ref_losses, rtol=1e-4)


# ---------------- kernel-table bounds validation ----------------------


@pytest.mark.parametrize("where", ["first", "last"])
@pytest.mark.parametrize("side", ["fwd", "bwd"])
def test_bucket_table_validation_catches_oob(sharded, side, where):
    """The kernels gather with mode='clip' on the strength of the
    host-side bounds check: an out-of-bounds index (build bug, rotted
    cache) must raise a NAMED error at build/load time — under the old
    fill-mode gathers it minted NaN silently mid-epoch. Holds in the
    slot-major layout, forward and transpose tables, first slot of the
    first row and last slot of the last (a padding column), and for an
    inverse permutation that points past the buckets' rows."""
    from pipegcn_tpu.ops.bucket_spmm import (
        ROW_TILE,
        build_sharded_bucket_tables,
        validate_bucket_tables,
    )

    sg = sharded
    tables = build_sharded_bucket_tables(sg)  # validates internally
    n_src = sg.n_max + sg.halo_size
    validate_bucket_tables(tables, sg.n_max, n_src)
    bound = n_src if side == "fwd" else sg.n_max
    at = 0 if where == "first" else -1
    plain = [k for k in tables if k.startswith(f"bkt_{side}_")
             and not k.endswith("inv")]
    assert all(tables[k].shape[-1] % ROW_TILE == 0 for k in plain)
    bad = {k: np.array(v) for k, v in tables.items()}
    key = plain[at]
    bad[key].reshape(-1)[at] = bound + 7
    with pytest.raises(ValueError, match="out-of-bounds"):
        validate_bucket_tables(bad, sg.n_max, n_src)
    bad[key].reshape(-1)[at] = -3
    with pytest.raises(ValueError, match="out-of-bounds"):
        validate_bucket_tables(bad, sg.n_max, n_src)
    # the sentinel is in bounds: over a padding slot it changes
    # nothing, over an edge it drops that edge, which the count of each
    # direction's entries against the other's (or the graph's) names
    real = int(tables[key].reshape(-1)[at]) != bound
    assert real == (where == "first")
    bad[key].reshape(-1)[at] = bound
    n_edges = [int(np.count_nonzero(d < sg.n_max)) for d in sg.edge_dst]
    for count in (None, n_edges):
        if real:
            with pytest.raises(ValueError, match="dropped or overwritten"):
                validate_bucket_tables(bad, sg.n_max, n_src, n_edges=count)
        else:
            validate_bucket_tables(bad, sg.n_max, n_src, n_edges=count)
    bad[key].reshape(-1)[at] = tables[key].reshape(-1)[at]
    rows = sum(tables[k].shape[-1] for k in plain)
    bad[f"bkt_{side}_inv"].reshape(-1)[at] = rows + 1
    with pytest.raises(ValueError, match="out-of-bounds"):
        validate_bucket_tables(bad, sg.n_max, n_src)


# ---------------- products-shape NaN regression -----------------------


@pytest.fixture(scope="module")
def products_shape():
    """Reduced-node-count synthetic with the ogbn-products SHAPE
    statistics (deg ~51, F=100, 47 classes) — the config family whose
    full-scale run trained to loss=nan on chip (VERDICT r5)."""
    g = synthetic_graph(num_nodes=6000, avg_degree=51, n_feat=100,
                        n_class=47, seed=0)
    parts = partition_graph(g, 1, seed=0)
    return ShardedGraph.build(g, parts, n_parts=1)


def test_products_shape_f8_config_trains_finite(products_shape):
    """Regression pin for the products-shape NaN config: use_pp + bf16
    + fp8 remainder + bucket kernel, hidden 128 — must train with
    finite, DECREASING loss, with the tripwire confirming every phase
    finite."""
    sg = products_shape
    cfg = ModelConfig(
        layer_sizes=(sg.n_feat, 128, 128, sg.n_class),
        use_pp=True, norm="layer", dropout=0.3,
        train_size=sg.n_train_global, spmm_chunk=2_097_152,
        dtype="bfloat16", spmm_impl="bucket", rem_dtype="float8",
    )
    tcfg = TrainConfig(lr=0.003, n_epochs=8, enable_pipeline=True,
                       eval=False, fused_epochs=1)
    t = Trainer(sg, cfg, tcfg)
    losses = [t.train_epoch(e) for e in range(8)]
    assert np.isfinite(losses).all(), f"non-finite losses: {losses}"
    assert losses[-1] < losses[0]
    nm = {k: int(np.sum(np.asarray(v)))
          for k, v in t._last_metrics["numerics"].items()}
    assert first_nonfinite_phase(nm) is None, nm
