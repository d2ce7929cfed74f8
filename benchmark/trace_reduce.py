"""From a profiler trace (`.xplane.pb`) to device busy time, per-operation
self times, the named scope and the scope path of each operation and the
longest idle gaps.

Kept with the benchmark so that every PR computes the same numbers in the
same way. Reading is split from reducing: `load_xplane` turns the file into
plain lists (and can be swapped for `load_recorded`, which reads the small
recorded chip trace under `testdata/`), `reduce_trace` does the arithmetic.

A TPU plane carries several lines (steps, modules, ops) that cover the same
time, and its op line nests (a `while` spans its body). So busy time is the
UNION of one device's op-line intervals, clipped to the window, and an
operation's time is its SELF time: its duration less what its direct
children cover. Neither can pass the window by construction.
"""

from __future__ import annotations

import bisect
import functools
import glob
import gzip
import json
import os
import re
from typing import Dict, List, Optional

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OP_LINE = "XLA Ops"
MODULE_LINE = "XLA Modules"
# a TPU op event is named by its whole HLO instruction, "%fusion.7 = ..."
_INSTRUCTION = re.compile(r"^%?([\w.\-]+)\s*=")
# the program's named scopes (models/sage.py, parallel/trainer.py), most
# specific first; an operation belongs to the first one its metadata names
SCOPES = ("spmm", "dense", "dropout", "norm", "adam_update", "grad_reduce",
          "halo", "bgrad")
_TEXT_STATS = ("tf_op", "long_name", "hlo_op", "name", "tf_op_name",
               "source")
# the components of an `op_name` that JAX itself puts there, beside those
# with parentheses (`jit(..)`, `jvp(..)`, `transpose(..)`): its structural
# names, and the spec `jnp.einsum` names its call by (`rduts,rusf->rdtf`).
# Whatever else a path holds is a scope the program named. JAX's list, not
# the program's: a scope a later PR opens shows in `path_s` with no edit
_JAX_STRUCTURE = re.compile(
    r"^(while|body|cond|branch_\w+|closed_call|shard_map|checkpoint|"
    r"custom_vjp_call\w*|custom_jvp_call|pjit|.*->.*)$")


def newest_xplane(trace_dir: str) -> Optional[str]:
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return max(files, key=os.path.getmtime) if files else None


def scope_of(text: str) -> str:
    for s in SCOPES:
        if re.search(rf"(^|[/(\s\"]){s}([/)\s\"]|$)", text):
            return s
    return "other"


@functools.lru_cache(maxsize=None)   # a few thousand names, 10^5 events
def scope_path(op_name: str) -> str:
    """`spmm/bwd/rem_gather` of `jit(multi)/while/body/closed_call/
    jvp(layer1)/spmm/bwd/rem_gather/jit(_take)/gather`: the named scopes
    an instruction's `op_name` metadata carries, in order. The last
    component is the primitive and goes; so does every component with
    parentheses, JAX's own structural names and an einsum's spec. "" where
    the program named none."""
    first = op_name.split(";", 1)[0].strip()   # a fusion may list several
    return "/".join(c for c in first.split("/")[:-1]
                    if c and "(" not in c and ")" not in c
                    and not _JAX_STRUCTURE.match(c))


def path_seconds(path_s: Dict[str, float], under: str, last) -> float:
    """Seconds of `path_s` on the paths that have `under` among their
    components and end in one of `last`."""
    return sum(s for path, s in path_s.items()
               if under in path.split("/") and path.split("/")[-1] in last)


def hlo_scope_map(hlo_text: str) -> Dict[str, str]:
    """instruction name -> op_name metadata, from compiled HLO text."""
    out = {}
    pat = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=.*?"
                     r"op_name=\"([^\"]*)\"")
    for line in hlo_text.splitlines():
        m = pat.match(line)
        if m:
            out[m.group(1)] = m.group(2)
    return out


def short_name(event_name: str) -> str:
    m = _INSTRUCTION.match(event_name)
    return m.group(1) if m else event_name


def load_xplane(path: str, max_host_events: int = 200_000) -> dict:
    """{"devices": {id: [[op, start_ns, dur_ns], ...]}, "modules": {id:
    [[module, start_ns, dur_ns], ...]}, "op_text": {op: what the trace
    says of it}, "host": [[name, start_ns, dur_ns], ...], "layout":
    [plane / line / event counts and stat keys, for a look by hand]}.
    `op` is the instruction's name (`fusion.7`)."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices: Dict[int, list] = {}
    modules: Dict[int, list] = {}
    op_text: Dict[str, str] = {}
    host: list = []
    layout: list = []
    cpu_ops: list = []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            n = 0
            for ev in line.events:
                n += 1
                if m and line.name == OP_LINE:
                    op = short_name(ev.name)
                    devices.setdefault(int(m.group(1)), []).append(
                        [op, float(ev.start_ns), float(ev.duration_ns)])
                    if op not in op_text:
                        op_text[op] = _stat_text(ev)
                    if n == 1:
                        layout.append(f"  stats of the first op event: "
                                      f"{[(k, str(v)[:60]) for k, v in ev.stats]}")
                elif m and line.name == MODULE_LINE:
                    modules.setdefault(int(m.group(1)), []).append(
                        [ev.name, float(ev.start_ns), float(ev.duration_ns)])
                elif plane.name.startswith("/host:"):
                    if ev.name.startswith("$"):
                        # a call event of the Python tracer (`$file.py:12
                        # fn`): a million of them where it is on. The
                        # thread's line is named by the interpreter
                        # (`python`, `python3`) and is the main thread's
                        # own: its other events (`fit/...`) are kept
                        continue
                    if _has_stat(ev, "hlo_op"):
                        # the CPU backend runs its thunks on host threads:
                        # they stand in for a device line (tests only)
                        cpu_ops.append([ev.name, float(ev.start_ns),
                                        float(ev.duration_ns)])
                        op_text.setdefault(ev.name, _stat_text(ev))
                        mod = next((v for k, v in ev.stats
                                    if k == "hlo_module"), None)
                        if mod:
                            cpu_ops[-1].append(str(mod))
                    elif ev.duration_ns > 0 and len(host) < max_host_events:
                        host.append([ev.name, float(ev.start_ns),
                                     float(ev.duration_ns)])
            layout.append(f"{plane.name} | {line.name} | {n} events")
    if not devices and cpu_ops:
        devices[0] = cpu_ops
    return {"devices": devices, "modules": modules, "op_text": op_text,
            "host": host, "layout": layout}


def _has_stat(ev, key: str) -> bool:
    return any(k == key for k, _ in ev.stats)


def _stat_text(ev) -> str:
    parts = [ev.name[:300]]
    for k, v in ev.stats:
        if k in _TEXT_STATS and isinstance(v, str):
            parts.append(v[:400])
    return " ".join(parts)


def load_recorded(path: str) -> dict:
    with gzip.open(path, "rt") as f:
        tr = json.load(f)
    tr["devices"] = {int(k): v for k, v in tr["devices"].items()}
    tr["modules"] = {int(k): v for k, v in tr.get("modules", {}).items()}
    return tr


def merge_intervals(intervals: List[tuple]) -> List[list]:
    out: List[list] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def self_times(events: List[list]) -> List[float]:
    """Self time of each event of one nested line, in input order."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][1], -events[i][2]))
    self_ns = [events[i][2] for i in range(len(events))]
    stack: List[int] = []
    for i in order:
        s, d = events[i][1], events[i][2]
        while stack and events[stack[-1]][1] + events[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            p = stack[-1]
            p_end = events[p][1] + events[p][2]
            self_ns[p] -= max(min(s + d, p_end) - s, 0.0)
        stack.append(i)
    return [max(x, 0.0) for x in self_ns]


def module_of_events(events: List[list], modules: List[list]) -> List[str]:
    """The module event each op event falls in ("" where none does). A CPU
    op event carries its module as a fourth field."""
    if events and len(events[0]) > 3:
        return [e[3] if len(e) > 3 else "" for e in events]
    spans = sorted((s, s + d, name) for name, s, d in modules)
    starts = [s for s, _, _ in spans]
    out = []
    for _, s, d in events:
        i = bisect.bisect_right(starts, s + 0.5 * d) - 1
        out.append(spans[i][2] if i >= 0 and s + 0.5 * d <= spans[i][1]
                   else "")
    return out


def pick_scope_maps(events, mods, scope_maps) -> Dict[str, Dict[str, str]]:
    """module -> the map (of `scope_maps`, one per compiled program) that
    names most of the ops seen in that module: two scan lengths are two
    programs whose instruction numbers differ."""
    seen: Dict[str, set] = {}
    for ev, mod in zip(events, mods):
        seen.setdefault(mod, set()).add(ev[0])
    out = {}
    for mod, names in seen.items():
        best = max(scope_maps, key=lambda m: len(names & m.keys()),
                   default=None)
        if best is not None and names & best.keys():
            out[mod] = best
    return out


def reduce_trace(tr: dict, n_devices: int,
                 scope_maps: Optional[List[Dict[str, str]]] = None) -> dict:
    """window_s / busy_s (mean over the devices used), self seconds by op,
    by scope and by scope path (`path_s`, keyed by `scope_path` of the
    joined `op_name`; mean over devices), and the longest idle gaps
    labelled by what the host was doing. `scope_maps`: `hlo_scope_map` of
    each program the window ran, for traces that carry no scope
    themselves."""
    ids = sorted(tr["devices"])[:n_devices]
    if not ids:
        return {}
    text = dict(tr["op_text"])
    lo = min(e[1] for i in ids for e in tr["devices"][i])
    hi = max(e[1] + e[2] for i in ids for e in tr["devices"][i])
    busy_ns, op_ns, scope_ns, path_ns, gaps = 0.0, {}, {}, {}, []
    for i in ids:
        evs = tr["devices"][i]
        merged = merge_intervals([(e[1], e[1] + e[2]) for e in evs
                                  if e[2] > 0])
        busy_ns += sum(e - s for s, e in merged)
        mods = module_of_events(evs, tr.get("modules", {}).get(i, []))
        maps = pick_scope_maps(evs, mods, scope_maps or [])
        for ev, mod, ns in zip(evs, mods, self_times(evs)):
            op_name = maps.get(mod, {}).get(ev[0], "")
            sc = scope_of(text.get(ev[0], ev[0]) + " " + op_name)
            key = (ev[0], sc)
            op_ns[key] = op_ns.get(key, 0.0) + ns
            scope_ns[sc] = scope_ns.get(sc, 0.0) + ns
            path = scope_path(op_name)
            path_ns[path] = path_ns.get(path, 0.0) + ns
        for (_, e0), (s1, _) in zip(merged, merged[1:]):
            gaps.append((s1 - e0, e0, s1))
    k = len(ids)
    gap_s: Dict[str, float] = {}
    for dur, g0, g1 in sorted(gaps, reverse=True)[:200]:
        label = _host_label(tr["host"], 0.5 * (g0 + g1))
        gap_s[label] = gap_s.get(label, 0.0) + dur / k * 1e-9
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": busy_ns / k * 1e-9,
        "scope_s": {s: v / k * 1e-9 for s, v in scope_ns.items()},
        "path_s": {p: v / k * 1e-9 for p, v in path_ns.items()},
        "ops": sorted(([f"{n} [{sc}] {_shape_of(text.get(n, ''))}".strip(),
                        v / k * 1e-9] for (n, sc), v in op_ns.items()),
                      key=lambda x: -x[1]),
        "idle_gaps": sorted(([lbl, v] for lbl, v in gap_s.items()),
                            key=lambda x: -x[1]),
        "n_events": sum(len(tr["devices"][i]) for i in ids),
    }


def _shape_of(text: str) -> str:
    """`bf16[262132,128] fusion kCustom` of an instruction's text."""
    m = re.search(r"=\s*\(?(\w+\[[\d,]*\])[^ ]*\s+([\w\-]+)\(", text)
    kind = re.search(r"kind=(\w+)", text)
    return (f"{m.group(1)} {m.group(2)}"
            + (f" {kind.group(1)}" if kind else "")) if m else ""


def _host_label(host: List[list], t: float) -> str:
    best = None
    for name, s, d in host:
        if s <= t <= s + d and (best is None or d < best[1]):
            best = (name, d)
    return f"host: {best[0][:80]}" if best else "host: no span"
