"""Epoch anatomy: attribute the compiled step's FLOPs/bytes to phases.

VERDICT round 5's top open item is a measurement problem: the ~0.5 s
non-SpMM epoch floor is known only as a residual. This module answers
it structurally: walk the compiled train step's optimized HLO, estimate
each instruction's FLOPs and bytes from its shapes, and attribute them
to the same phase vocabulary the profiler uses (obs/profiler.py:
spmm / dense / halo_comm / grad_reduce / optimizer / norm /
dropout_rng / other) via the `named_phase` scope metadata. Combined
with XLA's own ``.cost_analysis()`` total and ``memory_analysis()``,
that yields the contracted ``anatomy`` record (obs/schema.py v2).

The FLOP model is deliberately simple — dots dominate a GNN step:

  dot          2 * prod(output shape) * prod(contracted dims)
  elementwise/
  fusion/etc   prod(output shape)  (one op per output element)
  data movement (copy/transpose/broadcast/slice/gather/tuple plumbing)
               0 FLOPs, but bytes = out + operand bytes

``attributed_flops_fraction`` is the share of the estimated total that
landed in a NAMED phase (anything but "other") — the acceptance gate
is >= 90%, i.e. the scope annotations cover the compiled program.

``time_config`` is the on-chip ablation timer promoted out of
scripts/epoch_anatomy.py (which is now a thin wrapper): time the SAME
production config with one ingredient removed at a time; the deltas
attribute the wall-clock floor the way the HLO walk attributes FLOPs.
"""

from __future__ import annotations

import re
import time
from typing import Any, Dict, List, Tuple

from .profiler import (PHASES, _INSTR_RE, _OPNAME_RE, classify_op,
                       scope_path)

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")

# opcodes that move or rename data without arithmetic
_ZERO_FLOP = {
    "parameter", "constant", "copy", "copy-start", "copy-done",
    "bitcast", "bitcast-convert", "transpose", "reshape", "broadcast",
    "slice", "dynamic-slice", "dynamic-update-slice", "concatenate",
    "gather", "tuple", "get-tuple-element", "pad", "reverse", "iota",
    "after-all", "partition-id", "replica-id", "domain", "custom-call",
    "collective-permute", "all-gather", "send", "recv", "send-done",
    "recv-done", "infeed", "outfeed", "rng-bit-generator", "optimization-barrier",
}


def _parse_shapes(type_str: str) -> List[Tuple[str, List[int]]]:
    """Every dtype[dims] occurrence in an HLO type string (tuple types
    yield several)."""
    out = []
    for m in _SHAPE_RE.finditer(type_str):
        dims = [int(d) for d in m.group(2).split(",") if d]
        out.append((m.group(1), dims))
    return out


def _numel(dims: List[int]) -> int:
    n = 1
    for d in dims:
        n *= d
    return n


def _shape_bytes(shapes: List[Tuple[str, List[int]]]) -> int:
    return sum(_DTYPE_BYTES.get(dt, 4) * _numel(dims)
               for dt, dims in shapes)


_CONTRACT_RE = re.compile(r"lhs_contracting_dims=\{([0-9,]*)\}")
_OPERANDS_RE = re.compile(
    r"\(((?:[a-z0-9]+\[[^\]]*\](?:\{[^}]*\})?\s*%?[\w.\-]+(?:,\s*)?)+)\)")


def _instr_flops(kind: str, line: str,
                 out_shapes: List[Tuple[str, List[int]]]) -> float:
    out_elems = sum(_numel(dims) for _, dims in out_shapes)
    if kind == "dot":
        m = _OPERANDS_RE.search(line)
        cm = _CONTRACT_RE.search(line)
        if m and cm:
            ops = _parse_shapes(m.group(1))
            if ops:
                lhs_dims = ops[0][1]
                cdims = [int(d) for d in cm.group(1).split(",") if d]
                k = 1
                for d in cdims:
                    if d < len(lhs_dims):
                        k *= lhs_dims[d]
                return 2.0 * out_elems * k
        return 2.0 * out_elems
    if kind in _ZERO_FLOP:
        return 0.0
    if kind == "all-reduce" or kind == "reduce-scatter":
        return float(out_elems)  # one add per element per hop-combine
    # fusions, elementwise, reduce, scatter, compare, select, rng, ...
    return float(out_elems)


def hlo_anatomy(compiled_text: str) -> Dict[str, Any]:
    """Walk an optimized HLO module's text; returns per-phase estimated
    {flops, bytes} plus totals and the attributed-flops fraction."""
    phases: Dict[str, Dict[str, float]] = {}
    total_flops = 0.0
    total_bytes = 0.0
    n_ops = 0
    in_entry = False
    for line in compiled_text.splitlines():
        # count the ENTRY computation only: fusion/reduce bodies would
        # double-count their calling op's output elements
        if line.startswith("ENTRY"):
            in_entry = True
            continue
        if in_entry and line.startswith("}"):
            in_entry = False
            continue
        if not in_entry:
            continue
        m = _INSTR_RE.match(line)
        if not m:
            continue
        kind = m.group("kind")
        out_shapes = _parse_shapes(m.group("type"))
        om = _OPNAME_RE.search(line)
        op_name = om.group("op") if om else ""
        phase = classify_op(op_name, kind)
        fl = _instr_flops(kind, line, out_shapes)
        by = float(_shape_bytes(out_shapes))
        mo = _OPERANDS_RE.search(line)
        if mo:
            by += float(_shape_bytes(_parse_shapes(mo.group(1))))
        slot = phases.setdefault(phase, {"flops": 0.0, "bytes": 0.0,
                                         "n_ops": 0})
        slot["flops"] += fl
        slot["bytes"] += by
        slot["n_ops"] += 1
        total_flops += fl
        total_bytes += by
        n_ops += 1
    named = sum(v["flops"] for k, v in phases.items() if k != "other")
    return {
        "phases": phases,
        "est_flops": total_flops,
        "est_bytes": total_bytes,
        "n_ops": n_ops,
        "attributed_flops_fraction": (
            named / total_flops if total_flops > 0 else None),
    }


# instructions that hold other instructions or only rename values
_CONTAINERS = {"parameter", "constant", "tuple", "get-tuple-element",
               "bitcast", "while", "call", "conditional"}
_CALLED_RE = re.compile(r"(?:calls|to_apply)=%?([\w.\-]+)")
_COMPUTATION_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\{\s*$")


def scope_coverage(compiled_text: str, under: str = "spmm"
                   ) -> Dict[str, Dict[str, float]]:
    """How far the kernels' own scopes reach below the named scope
    `under`: of the bytes that the instructions under it read and write
    (every computation but fusion and reduction bodies, so a scan's body
    counts), the share whose scope path goes on below `under`
    (`spmm/gather`, not bare `spmm`). Forward and backward
    (`spmm/bwd/...`) apart: {"fwd" | "bwd": {bytes, covered, fraction,
    n_ops}}. Where no trace is taken this stands for the share of self
    time that carries a second-level scope."""
    bodies = set()
    for line in compiled_text.splitlines():
        if " fusion(" in line or "to_apply=" in line:
            bodies.update(_CALLED_RE.findall(line))
    out = {d: {"bytes": 0.0, "covered": 0.0, "n_ops": 0}
           for d in ("fwd", "bwd")}
    skip = False
    for line in compiled_text.splitlines():
        cm = _COMPUTATION_RE.match(line)
        if cm:
            skip = cm.group(1) in bodies
            continue
        m = _INSTR_RE.match(line)
        if skip or not m or m.group("kind") in _CONTAINERS:
            continue
        om = _OPNAME_RE.search(line)
        toks = scope_path(om.group("op")).split("/") if om else []
        if under not in toks:
            continue
        below = toks[toks.index(under) + 1:]
        slot = out["bwd" if below[:1] == ["bwd"] else "fwd"]
        by = float(_shape_bytes(_parse_shapes(m.group("type"))))
        mo = _OPERANDS_RE.search(line)
        if mo:
            by += float(_shape_bytes(_parse_shapes(mo.group(1))))
        slot["bytes"] += by
        slot["n_ops"] += 1
        if [t for t in below if t != "bwd"]:
            slot["covered"] += by
    for slot in out.values():
        slot["fraction"] = (slot["covered"] / slot["bytes"]
                            if slot["bytes"] else None)
    return out


def step_anatomy(trainer) -> Dict[str, Any]:
    """The full ``anatomy`` record body for a Trainer's single-epoch
    compiled step: the HLO walk above + XLA's own cost analysis and
    (where the backend exposes one) memory analysis. Costs one compile
    of the single-epoch program when the trainer has only run fused
    blocks so far; cached otherwise."""
    import jax

    import jax.numpy as jnp

    rng = jax.random.fold_in(trainer._epoch_rng_base(), 0)
    compiled = trainer._step.lower(
        trainer.state, trainer.data, rng,
        jnp.float32(trainer.loss_scaler.scale)).compile()
    # the text past the persistent cache: an entry written by an older
    # checkout would name that checkout's scopes
    rec = hlo_anatomy(trainer.step_compiled_text())
    try:
        ca = trainer.step_cost_analysis()
    except Exception:  # noqa: BLE001 — backend without analysis
        ca = {}
    rec["flops"] = float(ca["flops"]) if ca.get("flops") else None
    rec["bytes_accessed"] = (float(ca["bytes accessed"])
                             if ca.get("bytes accessed") else None)
    try:
        ma = compiled.memory_analysis()
        rec["memory"] = {
            k: int(getattr(ma, k))
            for k in ("temp_size_in_bytes", "argument_size_in_bytes",
                      "output_size_in_bytes", "generated_code_size_in_bytes")
            if hasattr(ma, k)
        } or None
    except Exception:  # noqa: BLE001
        rec["memory"] = None
    return rec


# ---------------- on-chip ablation timing -----------------------------


def time_config(sg, cfg, tcfg, reps: int, blk: int,
                trainer_cls=None) -> Tuple[float, float, float]:
    """Median per-epoch seconds of (sg, cfg, tcfg) over `reps` fused
    blocks of `blk` epochs, excluding setup and both compiles. The
    scripts/epoch_anatomy.py ablation clock, importable so window
    tooling and tests share one implementation."""
    import numpy as np

    if trainer_cls is None:
        from ..parallel.trainer import Trainer as trainer_cls

    t0 = time.perf_counter()
    tr = trainer_cls(sg, cfg, tcfg)
    setup = time.perf_counter() - t0
    t0 = time.perf_counter()
    tr.train_epochs(0, 1)
    compile_s = time.perf_counter() - t0
    if blk > 1:
        tr.train_epochs(1, blk)  # fused-program compile, off the clock
    times = []
    e = 1 + blk
    for _ in range(reps):
        t0 = time.perf_counter()
        tr.train_epochs(e, blk)
        times.append((time.perf_counter() - t0) / blk)
        e += blk
    del tr
    return float(np.median(times)), setup, compile_s


__all__ = ["PHASES", "hlo_anatomy", "scope_coverage", "step_anatomy",
           "time_config"]
