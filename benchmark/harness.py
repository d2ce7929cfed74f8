"""The benchmark of the training path: one cell, one process, one run.

`run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>` reads the
cell from `BENCHMARK.json`, its configuration from the file the cell's
`configs` entry names, its job from `benchmark/jobs/<traffic>.json`, builds
the argv of `main.py` from them and drives
`pipegcn_tpu.cli.main.run(args)` -> `Trainer.fit`, the entry users drive.
It never calls `train_epochs`, `_dispatch` or a kernel itself.

Set-up (`setup_s`, process start to the end of warm-up): the partition
artifact (built once per checkout and configuration with the
configuration's graph seed, so the row layout does not follow `--seed`),
`run(args)` over the first dispatch from the seed, which is the shortest
scan of the dispatch cycle (its state after those steps is what `correct`
compares), then one `fit` call for every other scan length the window
uses. The window continues the SAME trainer through `fit` for whole
dispatch cycles until `--seconds` have passed; `epoch_s` is its wall time
over all its epochs. Then the peak memory is read, the program's state is
freed, and the cell's plain reference follows the first dispatch's steps.

Everything the program prints goes to stderr; the last line of stdout is
the one JSON object the contract asks for.
"""

from __future__ import annotations

import argparse
import gc
import importlib.util
import json
import math
import os
import shutil
import sys
import time

# keys of a configuration file that are not flags of the program
CONFIG_META = {"name", "source", "graph_seed", "published", "assumed",
               "notes", "work"}


class BenchmarkError(RuntimeError):
    """The run cannot produce a result line."""


# ------------------------------------------------------------------ the cell


def load_spec(root: str, workload: str) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise BenchmarkError(f"no workload {workload!r} in BENCHMARK.json "
                             f"(has: {sorted(cells)})")
    cell = cells[workload]
    cfg_entry = next(c for c in bench["configs"]
                     if c["name"] == cell["config"])
    with open(os.path.join(root, cfg_entry["file"])) as f:
        config = json.load(f)
    bench_dir = os.path.join(root, bench["paths"][0])
    with open(os.path.join(bench_dir, "jobs",
                           cell["traffic"] + ".json")) as f:
        job = json.load(f)

    def applies(metric: dict, others: list) -> bool:
        if "workloads" in metric:
            return workload in metric["workloads"]
        moved = metric.get("moves")
        return moved is None or any(m["name"] == moved for m in others)

    e2e = [m for m in bench["end_to_end"] if applies(m, [])]
    per_layer = [m for m in bench["per_layer"] if applies(m, e2e)]
    # the plain reference is a file of its own, found by the name the job
    # gives (`reference`) or, without one, by the configuration's model
    ref_name = job.get("reference", config.get("model", "graphsage"))
    # the count of a step's work is the model's, a file of its own too,
    # found by the configuration's `work` key or by its model. A model
    # without one gets no result line: never another model's count
    work_name = config.get("work", config.get("model"))
    work_file = os.path.join(bench_dir, "model_work", f"{work_name}.py")
    if not os.path.exists(work_file):
        raise BenchmarkError(
            f"no count of a step's work for configuration "
            f"{cell['config']!r} (work {config.get('work')!r}, model "
            f"{config.get('model')!r}): add {work_file} with "
            f"epoch_work(facts, flags, itemsize) (benchmark/README.md, "
            f"point 6)")
    return {"cell": cell, "config": config, "job": job, "end_to_end": e2e,
            "per_layer": per_layer, "bench_dir": bench_dir, "root": root,
            "work_file": work_file,
            "reference_file": os.path.join(bench_dir, "references",
                                           ref_name + ".py"),
            "limits_file": os.path.join(bench_dir, "limits",
                                        workload + ".json")}


def program_argv(spec: dict, seed: int, part_dir: str, out_dir: str,
                 n_epochs: int) -> list:
    """The argv of `main.py` for this cell: the configuration's flags, the
    job's over them, then what every run of the benchmark fixes."""
    flags = {k: v for k, v in spec["config"].items()
             if k not in CONFIG_META}
    flags.update(spec["job"].get("args", {}))
    flags.update({"n_epochs": n_epochs, "seed": seed, "fix_seed": True,
                  "skip_partition": True, "partition_dir": part_dir,
                  "results_dir": out_dir,
                  "metrics_out": os.path.join(out_dir, "metrics.jsonl")})
    argv = []
    for key, value in flags.items():
        flag = "--" + key.replace("_", "-")
        if value is True:
            argv.append(flag)
        elif value is not False and value is not None:
            argv += [flag, str(value)]
    return argv


def dispatch_plan(start: int, end: int, fused: int, log_every: int) -> list:
    """(epoch, length) of the blocks `Trainer.fit` dispatches over
    [start, end) with reference logs on: a block never crosses a multiple
    of `log_every` or of 10."""
    out, e = [], start
    while e < end:
        chunk = min(max(fused, 1), end - e, log_every - e % log_every,
                    10 - e % 10)
        out.append((e, chunk))
        e += chunk
    return out


def cycle_plan(args):
    """(epochs of a dispatch cycle, its blocks as `fit` dispatches them,
    the length of the first dispatch from the seed). That is the cycle's
    shortest scan: the state `correct` compares then lies as few steps
    from the seed as a program of the window allows, and the reference
    follows as few."""
    cycle = math.lcm(int(args.log_every), 10)
    plan = dispatch_plan(cycle, 2 * cycle, args.fused_epochs,
                         args.log_every)
    return cycle, plan, min(n for _, n in plan)


# ---------------------------------------------------------------- the device


def require_device(chips: int) -> dict:
    """Platform, kind and count as JAX reports them; raises unless the
    platform is a TPU with at least `chips` chips. Nothing falls back."""
    from pipegcn_tpu.backend import WrongBackend, require_tpu

    try:
        dev = require_tpu()
    except WrongBackend as exc:
        raise BenchmarkError(str(exc)) from exc
    if dev["count"] < chips:
        raise BenchmarkError(f"the cell needs {chips} chip(s), JAX found "
                             f"{dev['count']}")
    return dev


def memory_peak_bytes() -> int:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.local_devices()]
    return int(max(peaks or [0]) or 0)


class CompileCounter:
    """Compile requests JAX makes (cache hits among them) and backend
    compiles, from its own monitoring events."""

    def __init__(self):
        import jax

        self.requests = 0
        self.backend = 0
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)

    def _on_event(self, name, **_kw):
        if name == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1

    def _on_duration(self, name, _secs, **_kw):
        if name == "/jax/core/compile/backend_compile_duration":
            self.backend += 1

    def total(self) -> int:
        return self.requests + self.backend


# ------------------------------------------------------------- program calls


def read_stream(path: str) -> list:
    if not os.path.exists(path):
        return []
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


def fit_more(trainer, args, start: int, end: int, rfile: str):
    """Continue `trainer` through `Trainer.fit` over [start, end) the way
    `cli.main.run` calls it, on its own metrics sink (appending)."""
    from pipegcn_tpu.obs import MetricsLogger
    from pipegcn_tpu.resilience import DivergenceSentinel, SentinelConfig

    sentinel = None
    if getattr(args, "sentinel", True):
        sentinel = DivergenceSentinel(SentinelConfig(
            loss_factor=args.sentinel_loss_factor,
            grad_norm_max=args.sentinel_grad_max,
            max_retries=args.sentinel_max_retries,
            lr_backoff=args.sentinel_lr_backoff,
            snapshot_every=args.sentinel_snapshot_every,
            flush_on_trip=args.sentinel_flush))
    metrics = MetricsLogger(args.metrics_out)
    trainer.tcfg.n_epochs = end
    try:
        # the standalone collective cost is measured once per run, by
        # run()'s own fit call: not again in every continuation
        return trainer.fit(
            None, start_epoch=start, reference_logs=True,
            result_file=rfile, inductive=args.inductive,
            measure_comm_cost=False, sharded_eval=args.sharded_eval,
            async_eval=not args.sync_eval, metrics=metrics,
            sentinel=sentinel)
    finally:
        metrics.close()


def first_dispatch_facts(trainer, n_steps: int) -> dict:
    """What `correct` needs of the program after its first dispatch, as
    host copies: parameters and Adam's moments, the partition and the row
    each node is fed at, and the padded row count of a partition."""
    import jax
    import numpy as np

    sg = trainer.sg
    nid = np.asarray(sg.global_nid)               # [P, rows], -1 = padding
    part, row = np.nonzero(nid >= 0)
    n_nodes = part.size
    part_of_node = np.zeros(n_nodes, np.int64)
    row_of_node = np.zeros(n_nodes, np.int64)
    part_of_node[nid[part, row]] = part
    row_of_node[nid[part, row]] = row
    return {
        "params": jax.device_get(trainer.state["params"]),
        "mu": jax.device_get(trainer.state["opt"]["mu"]),
        "nu": jax.device_get(trainer.state["opt"]["nu"]),
        "opt_step": int(jax.device_get(trainer.state["opt"]["step"])),
        "num_parts": int(sg.num_parts),
        "part_of_node": part_of_node,
        "row_of_node": row_of_node,
        "n_rows": int(sg.n_max + sg.halo_size),
        "n_steps": n_steps,
        "n_nodes": n_nodes,
        "n_edges": int(np.asarray(sg.edge_count).sum()),
        "layer_sizes": tuple(int(x) for x in trainer.cfg.layer_sizes),
        "multilabel": bool(sg.multilabel),
        "kernel": (trainer.tuning["winner"]["name"] if trainer.tuning
                   else trainer._current_impl()),
    }


def load_module(path: str, name: str):
    """A file of the benchmark's data-driven parts (a per-layer metric's
    reader, a plain reference), loaded by its path."""
    if not os.path.exists(path):
        raise BenchmarkError(f"no {path}")
    loaded = sys.modules.get(name)
    if loaded is not None and getattr(loaded, "__file__", None) == path:
        return loaded
    mod_spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(mod_spec)
    sys.modules[name] = mod        # dataclasses look their module up
    mod_spec.loader.exec_module(mod)
    return mod


def load_named(path: str, prefix: str):
    """`load_module` under a module name made of `prefix` and the file's
    own name."""
    stem = os.path.splitext(os.path.basename(path))[0]
    return load_module(path, prefix + stem.replace(".", "_")
                       .replace("-", "_"))


def load_reference(spec: dict):
    return load_named(spec["reference_file"], "benchmark_reference_")


def reference_graph(spec: dict, args) -> dict:
    """The configuration's graph as the reference reads it, made once per
    checkout by the benchmark's own generator."""
    from . import graphgen

    return graphgen.reference_graph(
        args.dataset, int(spec["config"].get("graph_seed", 0)),
        os.path.join(config_dir(spec), "refgraph"))


def reference_run(spec: dict, args, seed: int, facts: dict, **planted):
    """The cell's plain reference over the first dispatch's steps.
    `planted` (`quant`, `train_rows`) puts the control or a fault in."""
    return load_reference(spec).follow(
        args, seed, facts, reference_graph(spec, args), **planted)


def config_dir(spec: dict) -> str:
    """What the configuration alone fixes and every cell of it shares: the
    partition artifact as prepared, and the reference's graph."""
    return os.path.join(spec["root"], "partitions", "bench",
                        spec["cell"]["config"])


def link_tree(src: str, dst: str) -> None:
    """Hard links (copies, where the file system has none) of the files
    under `src` at `dst`, leaving what is there. What a cell then writes
    beside them (kernel tables, `tuning.json`) is its own."""
    for base, _, files in os.walk(src):
        out = os.path.join(dst, os.path.relpath(base, src))
        os.makedirs(out, exist_ok=True)
        for name in files:
            target = os.path.join(out, name)
            if not os.path.exists(target):
                try:
                    os.link(os.path.join(base, name), target)
                except OSError:
                    shutil.copy2(os.path.join(base, name), target)


# ------------------------------------------------------------------- the run


def load_reader(bench_dir: str, name: str):
    return load_named(os.path.join(bench_dir, "layer_metrics",
                                   name + ".py"), "layer_metric_").read


def first_dispatch(spec: dict, seed: int, log) -> dict:
    """The artifact (once per checkout) and `run(args)` over the first
    dispatch from the seed: the trainer, its parsed flags, the dispatch
    plan of a cycle and what `correct` compares."""
    from pipegcn_tpu.cli.main import prepare, run
    from pipegcn_tpu.cli.parser import create_parser

    cell = spec["cell"]
    tag = f"{cell['config']}-{cell['traffic']}"
    part_dir = os.path.join(spec["root"], "partitions", "bench", tag)
    out_dir = os.path.join(spec["bench_dir"], "out", cell["name"])
    shutil.rmtree(out_dir, ignore_errors=True)
    os.makedirs(out_dir)

    parser = create_parser()
    cycle, plan, first = cycle_plan(
        parser.parse_args(program_argv(spec, seed, part_dir, out_dir, 1)))
    args = parser.parse_args(
        program_argv(spec, seed, part_dir, out_dir, first))

    # the artifact, once per checkout and configuration, laid out from the
    # configuration's graph seed and not from --seed: every run trains the
    # same rows. It is prepared under the configuration's directory and
    # linked into the cell's, so a second cell of the configuration does
    # not pay the graph again and no cell reads another's tables
    prepare_s = 0.0
    marker = os.path.join(part_dir, "prepared.json")
    if not os.path.exists(marker):
        t0 = time.perf_counter()
        pristine = os.path.join(config_dir(spec), "artifact")
        prep = parser.parse_args(program_argv(
            spec, int(spec["config"].get("graph_seed", 0)), pristine,
            out_dir, first))
        prepare(prep)               # builds what is not there, else loads
        link_tree(pristine, part_dir)
        prepare_s = time.perf_counter() - t0
        with open(marker, "w") as f:
            json.dump({"graph_seed": prep.seed, "seconds": prepare_s}, f)
        gc.collect()

    t0 = time.perf_counter()
    res = run(args)                  # the first dispatch from the seed
    run_s = time.perf_counter() - t0
    facts = first_dispatch_facts(res["trainer"], first)
    records = epoch_records(args.metrics_out)[:first]
    facts["loss"] = [float(r["loss"]) for r in records]
    facts["grad_norm"] = [float(r["grad_norm"]) for r in records]
    log(f"first dispatch of {first}: artifact {prepare_s:.2f} s, run() "
        f"{run_s:.2f} s: {res['setup_s']}; kernel {facts['kernel']}; "
        f"cycle {[n for _, n in plan]}")
    return {"trainer": res["trainer"], "args": args, "facts": facts,
            "cycle": cycle, "plan": plan, "out_dir": out_dir,
            "split": dict(res["setup_s"], prepare_s=prepare_s)}


def set_up(spec: dict, seed: int, t_start: float, log) -> dict:
    """Everything before the window: the first dispatch from the seed, then
    one `fit` call for every other scan length of the cycle. Returns what
    the window and the judgement need; `setup_s` runs from `t_start` to
    the end of this function's work."""
    import jax
    import jax.numpy as jnp

    su = first_dispatch(spec, seed, log)
    trainer, args, cycle = su["trainer"], su["args"], su["cycle"]
    first = su["facts"]["n_steps"]
    rfile = os.path.join(su["out_dir"], "result.txt")
    warm_epochs, lengths = first, {first}
    for epoch, length in reversed(su.pop("plan")):   # every other scan
        if length not in lengths:                    # length, once
            lengths.add(length)
            fit_more(trainer, args, epoch - cycle, epoch - cycle + length,
                     rfile)
            warm_epochs += length
    # `train_epochs` builds a block's epoch keys with eager `jnp.arange`,
    # whose add for a non-zero first epoch compiles at first use per
    # length: in a user's run that is the second block of each length,
    # here it would be the window's first. Evaluate it in set-up.
    for length in lengths:
        jax.block_until_ready(jnp.arange(cycle, cycle + length))
    jax.block_until_ready(trainer.state)
    su.update(lengths=sorted(lengths), rfile=rfile, warm_epochs=warm_epochs,
              n_warm_records=len(epoch_records(args.metrics_out)),
              setup_s=time.perf_counter() - t_start)
    log(f"set-up {su['setup_s']:.2f} s; warm-up epochs {warm_epochs} in "
        f"scans of {su['lengths']}")
    return su


def epoch_records(path: str) -> list:
    return [r for r in read_stream(path) if r.get("event") == "epoch"]


def measure_window(su: dict, seconds: float, trace_dir) -> dict:
    """Continue the trainer through `fit` for whole dispatch cycles until
    `seconds` have passed (one traced cycle where `trace_dir` is given).
    Host clock from the first call to after `block_until_ready`."""
    import jax

    trainer, args, cycle = su["trainer"], su["args"], su["cycle"]
    epoch, n_epochs = cycle, 0
    if trace_dir:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0     # a Python trace is a million events
        opts.host_tracer_level = 2
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    t0 = time.perf_counter()
    try:
        while True:
            n_cycles = 1
            if n_epochs:
                per_cycle = (time.perf_counter() - t0) / (n_epochs / cycle)
                n_cycles = max(math.ceil(
                    (seconds - (time.perf_counter() - t0)) / per_cycle), 1)
            fit_more(trainer, args, epoch, epoch + n_cycles * cycle,
                     su["rfile"])
            jax.block_until_ready(trainer.state)
            epoch += n_cycles * cycle
            n_epochs += n_cycles * cycle
            if trace_dir or time.perf_counter() - t0 >= seconds:
                break
        window_s = time.perf_counter() - t0
    finally:
        if trace_dir:
            jax.profiler.stop_trace()
    return {"window_s": window_s, "n_epochs": n_epochs}


def reader_context(spec: dict, su: dict, win: dict, red: dict, dev: dict,
                   records: list) -> dict:
    """What a per-layer metric's reader is given (README, point 5): the
    reduced trace, the set-up split, the cell as the program built it and
    its work as its model's file counts it."""
    from . import work

    args = su["args"]
    flags = dict(vars(args))
    # the scalars and shapes of the cell: not the per-node arrays, not the
    # parameters
    facts = {k: v for k, v in su["facts"].items()
             if isinstance(v, (bool, int, float, str, tuple))}
    stream: dict = {}
    for r in records:
        stream.setdefault(r.get("event"), []).append(r)
    epochs = stream.get("epoch", [])
    return {
        "setup": su["split"], "trace": red, "epochs_traced": win["n_epochs"],
        "work": load_named(spec["work_file"], "model_work_").epoch_work(
            facts, flags, 2 if args.dtype == "bfloat16" else 4),
        "work_module": work,
        "peaks": work.peaks_for(dev["kind"]),   # an unknown kind raises
        "chips": int(spec["cell"]["chips"]),
        "warm_epochs": su["warm_epochs"],
        "warm_dispatch_s": sum(float(r["step_time_s"])
                               for r in epochs[:su["n_warm_records"]]),
        "epoch_s": win["window_s"] / win["n_epochs"],
        "facts": facts, "flags": flags, "cell": spec["cell"],
        "config": spec["config"], "job": spec["job"], "stream": stream,
    }


def traced_metrics(spec: dict, su: dict, win: dict, trace_dir: str,
                   hlo_texts: list, dev: dict, records: list, log) -> dict:
    """The per-layer metrics of the cell, `busy_s` / `window_s` and the
    breakdown, from the trace of the window and the set-up split."""
    from . import trace_reduce

    path = trace_reduce.newest_xplane(trace_dir)
    if path is None:
        raise BenchmarkError(f"no .xplane.pb under {trace_dir}")
    t0 = time.perf_counter()
    raw = trace_reduce.load_xplane(path)
    t1 = time.perf_counter()
    red = trace_reduce.reduce_trace(
        raw, int(spec["cell"]["chips"]),
        [trace_reduce.hlo_scope_map(t) for t in hlo_texts])
    scopes = {k: round(v, 4) for k, v in red.get("scope_s", {}).items()}
    log(f"trace: {os.path.getsize(path) / 1e6:.1f} MB read in "
        f"{t1 - t0:.1f} s, reduced in {time.perf_counter() - t1:.1f} s, "
        f"{red.get('n_events')} op events; self seconds by scope {scopes}")
    log("self seconds per epoch by scope path " + json.dumps(
        {k: round(v / win["n_epochs"], 6) for k, v in sorted(
            red.get("path_s", {}).items(), key=lambda kv: -kv[1])}))
    shutil.rmtree(trace_dir, ignore_errors=True)   # hundreds of MB
    if not red or not red["busy_s"] > 0:
        raise BenchmarkError("the trace holds no device operation")
    ctx = reader_context(spec, su, win, red, dev, records)
    tuning = (ctx["stream"].get("tuning") or [{}])[-1]
    est, spmm_s = tuning.get("est_epoch_spmm_s"), red["scope_s"].get("spmm")
    if est and spmm_s:
        log(f"the tuner's est_epoch_spmm_s {est:.4f} over the traced "
            f"spmm_s {spmm_s / win['n_epochs']:.4f}: "
            f"{est / (spmm_s / win['n_epochs']):.3f}")
    metrics = {}
    for m in spec["per_layer"]:
        value = load_reader(spec["bench_dir"], m["name"])(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return {"metrics": metrics,
            "device": {"busy_s": red["busy_s"], "window_s": red["window_s"]},
            "breakdown": {"device_ops": red["ops"][:10],
                          "idle_gaps": red["idle_gaps"][:10]}}


def run_cell(spec: dict, seed: int, seconds: float, trace: bool,
             t_start: float, log) -> dict:
    import jax

    from . import compare

    dev = require_device(int(spec["cell"]["chips"]))
    counter = CompileCounter()
    su = set_up(spec, seed, t_start, log)
    args, facts, first = su["args"], su["facts"], su["facts"]["n_steps"]

    trace_dir = os.path.join(su["out_dir"], "trace") if trace else None
    compiles_before = counter.total()
    win = measure_window(su, seconds, trace_dir)
    compiles_in_window = counter.total() - compiles_before
    peak = memory_peak_bytes()

    trainer = su.pop("trainer")
    records = read_stream(args.metrics_out)
    epochs = [r for r in records if r.get("event") == "epoch"]
    window_losses = [float(r["loss"])
                     for r in epochs[su["n_warm_records"]:]]
    bad = [r for r in records if r.get("event") in ("fallback", "fault")]
    bad += list(trainer.fallbacks)
    failed = (sum(not math.isfinite(x) for x in window_losses)
              + len(bad) + compiles_in_window)
    t0 = time.perf_counter()
    hlo_texts = [t for t in (multi_step_hlo(trainer, n)
                             for n in su["lengths"]) if t] if trace else []
    if trace:
        log(f"HLO text of {len(hlo_texts)} program(s) for the scope join: "
            f"{time.perf_counter() - t0:.1f} s")

    # free the program's state, then the reference: a process's peak never
    # falls again, and the reference needs the room
    del trainer
    gc.collect()
    in_use = [(d.memory_stats() or {}).get("bytes_in_use")
              for d in jax.local_devices()]
    epoch_s = win["window_s"] / win["n_epochs"]
    log(f"window {win['window_s']:.3f} s over {win['n_epochs']} epochs: "
        f"epoch_s {epoch_s:.5f}; compile requests inside "
        f"{compiles_in_window}; peak {peak / 1e9:.3f} GB; fallback/fault "
        f"records {len(bad)}; bytes in use after freeing the program's "
        f"state {in_use}")

    t0 = time.perf_counter()
    ref = reference_run(spec, args, seed, facts)
    numbers = compare.compared_numbers(facts, ref, lr=args.lr)
    correct, compared = compare.judge(
        numbers, compare.load_limits(spec["limits_file"]))
    log(f"reference {time.perf_counter() - t0:.2f} s over {first} steps; "
        f"program loss {facts['loss']} reference {ref['loss']}; "
        f"notes {numbers.get('_where')}")
    # every number, also those this cell's limits file does not hold
    log("numbers " + json.dumps({k: v for k, v in numbers.items()
                                 if k != "_where"}))

    result = {"correct": bool(correct and facts["opt_step"] == first
                              and not bad and compiles_in_window == 0),
              "attempted": win["n_epochs"], "failed": int(failed)}
    device = dict(dev, memory_peak_bytes=peak)
    if trace:
        traced = traced_metrics(spec, su, win, trace_dir, hlo_texts, dev,
                                records, log)
        result["metrics"] = traced["metrics"]
        device.update(traced["device"])
        result["breakdown"] = traced["breakdown"]
    else:
        e2e = {"epoch_s": epoch_s, "setup_s": su["setup_s"]}
        result["metrics"] = {
            m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
            for m in spec["end_to_end"]}
    result["device"] = device
    result["compared"] = compared      # last: each number beside its limit
    return result


def multi_step_hlo(trainer, length: int):
    """Optimized HLO text of the fused program the window ran, for the
    join from trace events to named scopes where the trace itself carries
    no scope. The trainer compiles it past the persistent cache (one real
    compile): a cache hit would hand back the text, and so the `op_name`
    scopes, of whichever checkout compiled the program first. Returns
    None if that cannot be done."""
    try:
        return trainer.step_compiled_text(length)
    except Exception as exc:  # noqa: BLE001 - the join is optional
        print(f"benchmark: no HLO text for the scope join: {exc!r}",
              file=sys.stderr)
        return None


def main(argv, root: str, t_start: float) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    opts = ap.parse_args(argv)

    # the result line owns stdout: everything else, the program's prints
    # and the runtime's logging included, goes to stderr
    sys.stdout.flush()
    result_out = os.fdopen(os.dup(1), "w")
    os.dup2(2, 1)
    sys.stdout = sys.stderr

    def log(msg):
        print(f"benchmark: {msg}", file=sys.stderr, flush=True)

    try:
        spec = load_spec(root, opts.workload)
        result = run_cell(spec, opts.seed, opts.seconds, bool(opts.trace),
                          t_start, log)
    except BenchmarkError as exc:
        log(f"no result: {exc}")
        return 3
    for name, row in result["compared"].items():
        print(f"compared {name} {row['value']:.6g} limit {row['limit']:.6g}",
              file=sys.stderr)
    sys.stderr.flush()
    result_out.write(json.dumps(result) + "\n")
    result_out.flush()
    return 0
