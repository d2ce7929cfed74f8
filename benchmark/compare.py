"""The numbers that decide `correct` for a training cell, and their limits.

Every number is a gap between what the timed program produced over its
first dispatch (the fused steps `Trainer.fit` ran from the seed) and what
the plain reference produces over the same steps. A cell's limits file
holds those of them that separate the program from the control and the
faults at the cell's own size (PERF.md section 2); all are logged.

- `sign1_gap` (two-step dispatches): the share of the reference's first
  gradient, by magnitude, whose sign the program's first Adam update does
  not carry. The first update is worked out from the state after the two
  steps. It sees no later step's dynamics and grows with the square of the
  noise, so it is the one that tells half of the batch from the whole.
- `mu_dir`: Adam's first moment after the dispatch: the norm of the
  difference over the reference's norm, all leaves as one vector. It sees
  what the signs do not: a gradient's size, and the second step.
- `loss1_gap`, `gnorm1_gap`: the first step's loss and the global norm of
  the first gradient as the optimizer gets it.
- `loss_gap`, `gnorm_gap`: the widest of the same over all the steps.
- `mu_gap`, `delta_gap`: the first moment and the parameters' change by the
  worst leaf, as gaps of norms: |norm(program) - norm(reference)| over the
  larger of the reference's norm of that leaf and of the median leaf.

Leaves whose first reference gradient is under a thousandth of the median
leaf's move under Adam by round-off alone and are left out of `delta_gap`.
A gap of norms is one projection of the difference: half of a batch of 10^5
rows, or fp8 with a per-tensor scale, moves it no more than bfloat16 does,
which is why the limits stand on the first two.
"""

from __future__ import annotations

import json
import math

import numpy as np


def _leaves(tree, prefix=""):
    if isinstance(tree, dict):
        for k in sorted(tree):
            yield from _leaves(tree[k], f"{prefix}/{k}")
    elif isinstance(tree, (list, tuple)):
        for i, v in enumerate(tree):
            yield from _leaves(v, f"{prefix}/{i}")
    else:
        yield prefix, np.asarray(tree, np.float64)


def leaf_norms(tree) -> dict:
    return {k: float(np.linalg.norm(v)) for k, v in _leaves(tree)}


def worst_norm_gap(prog: dict, ref: dict, keep=None):
    """(gap, leaf) over the leaves of `ref` (in `keep`, if given)."""
    names = [k for k in ref if keep is None or k in keep]
    if set(prog) != set(ref):
        return 1.0, "leaf-set-differs"
    floor = float(np.median([ref[k] for k in ref]))
    worst, where = 0.0, ""
    for k in names:
        gap = abs(prog[k] - ref[k]) / max(ref[k], floor, 1e-30)
        if not math.isfinite(gap):
            return float("inf"), k
        if gap >= worst:
            worst, where = gap, k
    return worst, where


def direction_gap(prog: dict, ref: dict) -> float:
    """Norm of the difference over the reference's norm, all leaves taken
    as one vector. A gap of norms is one projection of the difference;
    this is all of it, and concentrates where that swings."""
    if set(prog) != set(ref):
        return 1.0
    num = sum(float(np.sum((prog[k] - ref[k]) ** 2)) for k in ref)
    den = sum(float(np.sum(ref[k] ** 2)) for k in ref)
    gap = math.sqrt(num / max(den, 1e-300))
    return gap if math.isfinite(gap) else float("inf")


def first_sign_gap(prog: dict, ref: dict, lr: float, b1=0.9, b2=0.999,
                   eps=1e-8) -> float:
    """After TWO Adam steps: the share of the reference's first gradient,
    by magnitude, whose sign the program's first update does not carry.

    Adam's first update is -lr * g / (|g| + eps), the gradient's sign, and
    its second is -lr * (mu / bc1) / (sqrt(nu / bc2) + eps) of the moments
    it leaves, so the state after two steps gives the first update as the
    parameters' whole change less the second. No step lies before the
    first gradient, so this number carries none of the later steps'
    dynamics, and a sign flips only where the noise passes the gradient:
    it grows with the SQUARE of the noise."""
    bc1, bc2 = 1 - b1 ** 2, 1 - b2 ** 2
    init = dict(_leaves(ref["init"]))
    g1 = dict(_leaves(ref["first_grad"]))
    p, mu, nu = (dict(_leaves(prog[k])) for k in ("params", "mu", "nu"))
    if not set(p) == set(mu) == set(nu) == set(g1):
        return 1.0
    wrong = total = 0.0
    for k, g in g1.items():
        second = (mu[k] / bc1) / (np.sqrt(nu[k] / bc2) + eps)
        first = -(p[k] - init[k]) / lr - second
        carried = np.sign(g) * first > 0.5      # a sign is +-1; 0 is none
        wrong += float(np.abs(g)[~carried].sum())
        total += float(np.abs(g).sum())
    return wrong / max(total, 1e-300)


def _tree_sub(a, b):
    if isinstance(a, dict):
        return {k: _tree_sub(a[k], b[k]) for k in a}
    if isinstance(a, (list, tuple)):
        return [_tree_sub(x, y) for x, y in zip(a, b)]
    return np.asarray(a, np.float64) - np.asarray(b, np.float64)


def compared_numbers(prog: dict, ref: dict, lr=None) -> dict:
    """`prog`: loss and grad_norm per step (lists), params, mu, nu after
    the steps. `ref`: the reference's `train` result. `lr`: the
    configuration's learning rate. Returns name -> value (and `where`
    notes under '_where')."""
    n = len(ref["loss"])
    out, where = {}, {}
    if len(prog["loss"]) < n or len(prog["grad_norm"]) < n:
        return {"_where": {"all": "program reported too few steps"}}

    def rel(p, r):
        return (abs(p - r) / max(abs(r), 1e-30) if math.isfinite(p)
                else float("inf"))

    loss = [rel(prog["loss"][k], ref["loss"][k]) for k in range(n)]
    gnorm = [rel(prog["grad_norm"][k], ref["grad_norm"][k])
             for k in range(n)]
    out.update(loss1_gap=loss[0], gnorm1_gap=gnorm[0], loss_gap=max(loss),
               gnorm_gap=max(gnorm))
    out["mu_gap"], where["mu_gap"] = worst_norm_gap(
        leaf_norms(prog["mu"]), leaf_norms(ref["mu"]))
    g1 = leaf_norms(ref["first_grad"])
    floor = 1e-3 * float(np.median(list(g1.values())))
    moving = {k for k, v in g1.items() if v >= floor}
    where["left_out_of_delta_gap"] = sorted(set(g1) - moving)
    out["delta_gap"], where["delta_gap"] = worst_norm_gap(
        leaf_norms(_tree_sub(prog["params"], ref["init"])),
        leaf_norms(_tree_sub(ref["params"], ref["init"])), keep=moving)
    out["mu_dir"] = direction_gap(dict(_leaves(prog["mu"])),
                                  dict(_leaves(ref["mu"])))
    if n == 2 and lr and "nu" in prog:
        out["sign1_gap"] = first_sign_gap(prog, ref, lr)
    out["_where"] = where
    return out


def load_limits(path: str) -> dict:
    with open(path) as f:
        return {k: float(v) for k, v in json.load(f)["limits"].items()}


def judge(numbers: dict, limits: dict):
    """(correct, {name: {"value", "limit"}}): every limit's number has to
    be there, finite and at or under its limit."""
    table, ok = {}, True
    for name, limit in limits.items():
        value = numbers.get(name, float("inf"))
        table[name] = {"value": value, "limit": limit}
        ok = ok and math.isfinite(value) and value <= limit
    return ok, table
