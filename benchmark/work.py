"""What is the same for every model when a step's work is held against
the chip: the table of peaks, the compulsory bytes of one aggregation pass
and the least time the chip could take over a step's passes.

The count of a step's operations is the MODEL's and lives in a file of its
own, `model_work/<name>.py`, found by the configuration's `work` key or,
without one, by its `model` (harness.load_spec). Its `epoch_work(facts,
flags, itemsize)` returns `flops`, `linear_flops`, `aggregation_flops` and
`aggregation_passes`, one `{flops, min_bytes}` per aggregation pass of the
step. It counts what the mathematics needs, whatever implements it, so a
kernel PR cannot make it stale; recomputed operations never count.

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


def aggregation_min_bytes(n_nodes: int, n_edges: int, width: int,
                          itemsize: int) -> int:
    adjacency = min(4 * n_edges + 4 * (n_nodes + 1),
                    (n_nodes * n_nodes + 7) // 8)
    return 2 * n_nodes * width * itemsize + adjacency


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
