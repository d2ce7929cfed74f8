"""Readings for the limits of `correct`, many seeds in one process:

- `program`: the cell's own first dispatch from the seed (needs the chip),
- `control_fp8`: the reference computed in fp8 e4m3 with a per-tensor scale,
  the precision below the configuration's bfloat16, in the program's place,
- `fault_half_batch`: every second training row left out, the mean taken
  over the rest, planted in the reference put in the program's place,
- `own_precision_bf16`: the reference rounded through bfloat16, which has
  to pass,

each compared with the float32 reference by the numbers of `compare.py` and
judged by the cell's limits. Run it on the chip at the cell's own size:

    python3 benchmark/control.py --workload <cell> [<cell> ...] \
        --seeds 1 2 3 --kinds program control_fp8 fault_half_batch

One JSON line per cell, seed and kind on stdout. Cells of one configuration
share the reference's runs. Without `program` no chip is needed and the
rows are fed in node order. The benchmark's own runs never call this. A
state left unchanged needs no run: it reads 1 by `mu_dir`.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
IN_PLACE = {"control_fp8": "quant_fp8", "own_precision_bf16": "quant_bf16"}


def node_order_facts(spec: dict, args) -> dict:
    """What the reference needs where no program ran: one partition, the
    rows in node order."""
    import numpy as np

    from benchmark import graphgen, harness

    shape = graphgen.parse_dataset(args.dataset)
    n = shape["num_nodes"]
    hidden = [args.n_hidden] * (args.n_layers - 1)
    return {"num_parts": 1, "row_of_node": np.arange(n), "n_rows": n,
            "part_of_node": np.zeros(n, np.int64),
            "n_steps": harness.cycle_plan(args)[2],
            "layer_sizes": (shape["n_feat"], *hidden, shape["n_class"]),
            "multilabel": shape["multilabel"]}


def readings(specs: list, seed: int, kinds, log=print) -> list:
    """Rows {"workload", "seed", "kind", "numbers", "where", "correct",
    "compared"} for every cell in `specs` (of ONE configuration) and kind."""
    import numpy as np

    from benchmark import compare, harness

    def timed(what, fn):
        t0 = time.perf_counter()
        out = fn()
        log(f"  {what}: {time.perf_counter() - t0:.1f} s, "
            f"loss {out['loss']}")
        return out

    in_place, shared, rows = {}, None, []
    for spec in specs:
        name = spec["cell"]["name"]
        limits = compare.load_limits(spec["limits_file"])
        if "program" in kinds:
            fd = harness.first_dispatch(spec, seed, log)
            args, prog = fd["args"], fd["facts"]
            del fd
            gc.collect()
        else:
            from pipegcn_tpu.cli.parser import create_parser

            args = create_parser().parse_args(
                harness.program_argv(spec, seed, "", "", 1))
            prog = node_order_facts(spec, args)
        facts = {k: prog[k] for k in ("num_parts", "row_of_node", "n_rows",
                                      "n_steps", "layer_sizes",
                                      "multilabel")}
        key = (facts["n_rows"], facts["n_steps"],
               facts["row_of_node"].tobytes())
        if shared != key:         # another feed: the reference runs anew
            shared, in_place = key, {}
            ref = timed("float32 reference", lambda: harness.reference_run(
                spec, args, seed, facts))
        for kind in kinds:
            if kind == "program":
                got = prog
            elif kind not in in_place:
                kw = {}
                if kind == "fault_half_batch":
                    mask = harness.reference_graph(spec, args)["train_mask"]
                    kw["train_rows"] = mask.copy()
                    kw["train_rows"][np.nonzero(mask)[0][1::2]] = False
                else:
                    kw["quant"] = getattr(harness.load_reference(spec),
                                          IN_PLACE[kind])
                in_place[kind] = timed(kind, lambda: harness.reference_run(
                    spec, args, seed, facts, **kw))
            got = prog if kind == "program" else in_place[kind]
            numbers = compare.compared_numbers(got, ref, lr=args.lr)
            where = numbers.pop("_where")
            ok, table = compare.judge(numbers, limits)
            rows.append({"workload": name, "seed": seed, "kind": kind,
                         "numbers": numbers, "where": where, "correct": ok,
                         "compared": table})
    return rows


def main(argv, root: str = ROOT) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", nargs="+", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--kinds", nargs="+",
                    default=["control_fp8", "fault_half_batch"])
    ap.add_argument("--in-place-seeds", type=int, default=None,
                    help="read the control and the fault on the first N "
                         "seeds only (the program on all)")
    opts = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    from benchmark import harness

    def log(msg):
        print(msg, file=sys.stderr, flush=True)

    specs = [harness.load_spec(root, w) for w in opts.workload]
    if len({s["cell"]["config"] for s in specs}) != 1:
        raise SystemExit("the cells of one call share one configuration")
    if "program" in opts.kinds:
        harness.require_device(max(int(s["cell"]["chips"]) for s in specs))
    for i, seed in enumerate(opts.seeds):
        kinds = opts.kinds
        if opts.in_place_seeds is not None and i >= opts.in_place_seeds:
            kinds = [k for k in kinds if k == "program"]
        log(f"seed {seed}: {kinds}")
        for row in readings(specs, seed, kinds, log):
            print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
