"""Operations and bytes one training epoch needs, from shapes alone.

Counts what the mathematics needs, whatever implements it, so a kernel PR
cannot make it stale: a linear layer is 2*N*in*out forward, the same again
for the weight gradient and, where its input carries a gradient, for the
input gradient; a mean aggregation over E directed edges of width F is
2*E*F forward and 2*E*F backward (its transpose applied to the cotangent).
The first aggregation is left out under `use_pp`: it is computed once in
set-up, not in the step. Recomputed operations never count.

Compulsory bytes of one aggregation pass: the operand read once, the
result written once, and the adjacency read once in the most compact
encoding the repository has (4-byte column indices plus row pointers, or
one bit per cell of the N x N matrix where that is smaller). Never
E x F x bytes: a tiled kernel legitimately reads less than that.
"""

from __future__ import annotations

import json
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def peaks_for(kind: str) -> dict:
    with open(os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)
    if kind not in table:
        raise LookupError(
            f"no published peaks for device_kind {kind!r} in "
            f"benchmark/peaks.json (known: {sorted(table)})")
    return table[kind]


def in_step_aggregations(layer_sizes, n_linear: int, use_pp: bool) -> list:
    """Widths of the aggregations the step itself performs."""
    n_graph = len(layer_sizes) - 1 - n_linear
    return [layer_sizes[i] for i in range(n_graph)
            if not (use_pp and i == 0)]


def linear_flops(n_nodes: int, layer_sizes, n_linear: int,
                 use_pp: bool) -> int:
    n_layers = len(layer_sizes) - 1
    n_graph = n_layers - n_linear
    total = 0
    for i in range(n_layers):
        d_in, d_out = layer_sizes[i], layer_sizes[i + 1]
        if i < n_graph and use_pp and i == 0:
            mats = [2 * d_in]          # one product over concat(x, agg x)
        elif i < n_graph:
            mats = [d_in, d_in]        # self and neighbour products
        else:
            mats = [d_in]
        passes = 2 if i == 0 else 3    # the input features need no gradient
        total += sum(2 * n_nodes * m * d_out for m in mats) * passes
    return total


def aggregation_flops(n_edges: int, widths) -> int:
    return sum(2 * n_edges * w * 2 for w in widths)   # forward + backward


def aggregation_min_bytes(n_nodes: int, n_edges: int, width: int,
                          itemsize: int) -> int:
    adjacency = min(4 * n_edges + 4 * (n_nodes + 1),
                    (n_nodes * n_nodes + 7) // 8)
    return 2 * n_nodes * width * itemsize + adjacency


def epoch_work(n_nodes: int, n_edges: int, layer_sizes, n_linear: int,
               use_pp: bool, itemsize: int) -> dict:
    widths = in_step_aggregations(layer_sizes, n_linear, use_pp)
    agg = aggregation_flops(n_edges, widths)
    lin = linear_flops(n_nodes, layer_sizes, n_linear, use_pp)
    return {
        "flops": agg + lin, "aggregation_flops": agg, "linear_flops": lin,
        # one entry per aggregation pass of the step, forward and backward
        "aggregation_passes": [
            {"flops": 2 * n_edges * w,
             "min_bytes": aggregation_min_bytes(n_nodes, n_edges, w,
                                                itemsize)}
            for w in widths for _ in ("forward", "backward")],
    }


def aggregation_least_s(work: dict, peaks: dict) -> dict:
    """The least time the chip could take over the step's aggregation
    passes, and which bound holds."""
    by_flops = sum(p["flops"] for p in work["aggregation_passes"]) \
        / peaks["bf16_flops_per_s"]
    by_bytes = sum(p["min_bytes"] for p in work["aggregation_passes"]) \
        / peaks["hbm_bytes_per_s"]
    least = sum(max(p["flops"] / peaks["bf16_flops_per_s"],
                    p["min_bytes"] / peaks["hbm_bytes_per_s"])
                for p in work["aggregation_passes"])
    return {"least_s": least,
            "bound": "bytes" if by_bytes >= by_flops else "flops"}
