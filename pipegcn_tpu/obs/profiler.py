"""Device-trace profiling windows: measured phase time, not estimates.

``jax.profiler`` writes one ``<host>.xplane.pb`` per capture under
``<dir>/plugins/profile/<time>/``. This module turns it into the
contracted ``profile`` record (obs/schema.py):

  1. ``load_xplane`` reads the file through ``jax.profiler.ProfileData``
     into plain lists. A TPU plane carries several lines (steps,
     modules, ops) that cover the same time; only the OP line counts.
     An op event there is named by its whole HLO instruction
     (``%fusion.7 = ...``) and carries no scope. On the CPU backend the
     thunks run on host threads and carry ``hlo_op`` / ``hlo_module``
     stats: they stand in for a device's op line (the tier-1 path).
  2. The op names alone are anonymous, but the COMPILED program's HLO
     text carries ``metadata={op_name="jit(step)/.../layer0/spmm/
     gather/..."}``, the `named_phase` scopes of the model stack and
     the kernels. ``hlo_op_map`` reads them; one map per compiled
     program (a scan of 2 epochs and one of 4 are two programs whose
     instruction numbers clash), chosen per traced module by the names
     it matches.
  3. ``fold_xplane`` does the arithmetic. The op line nests (a `while`
     spans its body), so busy time is the UNION of a device's
     intervals and an operation's time is its SELF time: its duration
     less what its direct children cover. Neither can pass the window.
     Self seconds fold into phases (``classify_op``: spmm / dense /
     halo_comm / ...) and into scope paths (``scope_path``:
     ``spmm/bwd/gather``), the overlap fraction is the share of the
     communication leaves' time that computing leaves of the same
     device cover, and the longest gaps in the busy time are named by
     the program's host span (``trace_span``: ``step``, ``fit/...``)
     open at the gap's middle.

Seconds are per device (the mean over the devices traced). Only
``load_xplane`` imports jax, lazily; the fold runs on plain lists.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Any, Dict, List, Optional, Sequence, Tuple

# phase vocabulary — the contract the profile/anatomy records share
PHASES = ("spmm", "dense", "halo_comm", "grad_reduce", "optimizer",
          "norm", "dropout_rng", "halo_concat", "loss", "numerics",
          "eval", "other")

# communication phases whose device time the overlap fraction measures
COMM_PHASES = ("halo_comm", "grad_reduce")

# HLO opcode prefixes that are communication wherever they appear,
# even outside a named scope (shard_map lowers ppermute/psum to these)
_COMM_KINDS = ("collective-permute", "all-reduce", "all-gather",
               "all-to-all", "reduce-scatter", "collective-broadcast",
               "send", "recv")

# every `named_phase` the train step opens (models/sage.py,
# parallel/trainer.py, parallel/halo.py) and, below `spmm`, the
# aggregation kernels (ops/bucket_spmm.py, ops/block_spmm.py): what a
# scope path is made of. `layer{i}` and JAX's transform wrappers
# (jit(..), jvp(..), transpose(..), while/body) are not part of a path.
SCOPE_NAMES = frozenset((
    "spmm", "dense", "dropout", "norm", "loss", "tripwire", "grad_norm",
    "halo_concat", "halo_exchange", "halo_prefetch", "bgrad_return",
    "grad_reduce", "adam_update", "eval", "eval_metric_reduce",
    # inside spmm
    "bwd", "gather", "reduce", "unpermute", "relayout", "cast", "scale",
    "unpack", "tile", "rem_gather", "rem_reduce", "rem_unpermute",
    "rem_relayout"))

# names of the program's own host spans (`trace_span`): the
# PhaseTimer's annotated `step` and its `fit/...` children
PROGRAM_SPANS = ("step", "fit/")
# opened right after `start_trace` returns: lays the trace's zero on
# the wall clock (fit reads `time.time()` inside it)
ANCHOR_SPAN = "fit/trace_anchor"


def classify_op(op_name: str, hlo_kind: str = "") -> str:
    """Bucket one HLO op into a phase by its metadata scope path (the
    `named_phase` names: layer{i}/spmm, halo_exchange, grad_reduce,
    adam_update, ...) with the opcode as a fallback for collectives.
    Backward ops keep the forward scope inside jax's transpose(...)
    wrapper, so substring matching covers both directions."""
    s = op_name.lower()
    k = hlo_kind.lower()
    if "halo_exchange" in s or "bgrad_return" in s \
            or "halo_prefetch" in s:
        return "halo_comm"
    if "grad_reduce" in s:
        return "grad_reduce"
    if any(k.startswith(c) for c in _COMM_KINDS):
        # an unscoped collective: the gradient psum is scoped, so bare
        # collectives are halo traffic (stale-concat exchange blocks)
        return "halo_comm"
    if "adam_update" in s:
        return "optimizer"
    if "/spmm" in s or "spmm" in s:
        return "spmm"
    if "dropout" in s:
        return "dropout_rng"
    if "/norm" in s or "layer_norm" in s or "batch_norm" in s:
        return "norm"
    if "/dense" in s:
        return "dense"
    if "halo_concat" in s:
        return "halo_concat"
    toks = _scope_tokens(s)
    if "tripwire" in toks or "grad_norm" in toks:
        return "numerics"
    if "loss" in toks:
        return "loss"
    if "eval" in s:
        return "eval"
    return "other"


_TOKEN_RE = re.compile(r"[\w.\-]+")


def _scope_tokens(op_name: str) -> List[str]:
    first = op_name.split(";", 1)[0]       # a fusion may list several
    parts = first.split("/")[:-1]          # the last is the primitive
    return [t for part in parts for t in _TOKEN_RE.findall(part)
            if t in SCOPE_NAMES]


def scope_path(op_name: str) -> str:
    """``spmm/bwd/gather`` of ``jit(step)/shard_map/transpose(jvp(
    layer1))/spmm/bwd/gather/jit(_take)/gather``: the SCOPE_NAMES an
    instruction's `op_name` metadata names, in order. The last
    component is the primitive (`gather`, `reduce_sum`) and never a
    scope; "" where no scope of the program is named."""
    return "/".join(_scope_tokens(op_name))


# one optimized-HLO instruction: "%name = type opcode(...), ...,
# metadata={op_name="..."}". Tuple-typed outputs and missing metadata
# both occur; keep the regex tolerant and skip what it cannot read.
_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?(?P<name>[\w.\-]+)\s*=\s*"
    r"(?P<type>\([^=]*?\)|[a-z0-9]+\[[^\]]*\](?:\{[^}]*\})?)\s*"
    r"(?P<kind>[\w\-]+)\(")
_OPNAME_RE = re.compile(r'metadata=\{[^}]*op_name="(?P<op>[^"]*)"')


def hlo_op_map(compiled_text: str) -> Dict[str, Tuple[str, str]]:
    """{hlo op name -> (scope op_name, opcode)} from a compiled
    module's text (``jitted.lower(...).compile().as_text()``). The op
    names here are what a trace's op events are named by, so this is
    the join key between the anonymous timeline and the named
    phases."""
    out: Dict[str, Tuple[str, str]] = {}
    for line in compiled_text.splitlines():
        m = _INSTR_RE.match(line)
        if not m:
            continue
        om = _OPNAME_RE.search(line)
        out[m.group("name")] = (om.group("op") if om else "",
                                m.group("kind"))
    return out


def module_name(compiled_text: str) -> str:
    """The HloModule name (a traced module is named by it, with the
    program's id in brackets behind it)."""
    m = re.match(r"HloModule\s+([\w.\-]+)", compiled_text)
    return m.group(1) if m else ""


# ---------------- trace loading ---------------------------------------

_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_OP_LINE = "XLA Ops"
_MODULE_LINE = "XLA Modules"
# a TPU op event is named by its whole HLO instruction, "%fusion.7 = ..."
_INSTRUCTION = re.compile(r"^%?([\w.\-]+)\s*=")


def newest_xplane(profile_dir: str) -> Optional[str]:
    """The newest ``*.xplane.pb`` under a ``jax.profiler`` output dir."""
    files = glob.glob(os.path.join(profile_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return max(files, key=os.path.getmtime) if files else None


def load_xplane(path: str, max_host_events: int = 200_000
                ) -> Dict[str, Any]:
    """{"lines": [{"device": id, "events": [[op, start_ns, dur_ns,
    module], ...]}, ...], "host": [[name, start_ns, dur_ns], ...]}.
    One entry of `lines` is one nested line: a TPU's op line, or on
    the CPU backend one executor thread's thunks of one virtual
    device. `op` is the instruction's name (`fusion.7`), `module` the
    traced program the event ran in."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    lines: List[Dict[str, Any]] = []
    host: List[list] = []
    for plane in pd.planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m:
            ops, mods = [], []
            for line in plane.lines:
                if line.name == _OP_LINE:
                    for ev in line.events:
                        im = _INSTRUCTION.match(ev.name)
                        ops.append([im.group(1) if im else ev.name,
                                    float(ev.start_ns),
                                    float(ev.duration_ns), ""])
                elif line.name == _MODULE_LINE:
                    mods = [(float(ev.start_ns),
                             float(ev.start_ns + ev.duration_ns), ev.name)
                            for ev in line.events]
            _assign_modules(ops, sorted(mods))
            if ops:
                lines.append({"device": int(m.group(1)), "events": ops})
        elif plane.name.startswith("/host:"):
            for li, line in enumerate(plane.lines):
                by_dev: Dict[int, list] = {}
                for ev in line.events:
                    stats = dict(ev.stats)
                    if "hlo_op" in stats:
                        mod = (f"{stats.get('hlo_module', '')}"
                               f"({stats.get('program_id', '')})")
                        by_dev.setdefault(
                            int(stats.get("device_ordinal", 0)), []
                        ).append([str(stats["hlo_op"]),
                                  float(ev.start_ns),
                                  float(ev.duration_ns), mod])
                    elif ev.duration_ns > 0 and \
                            len(host) < max_host_events:
                        host.append([ev.name, float(ev.start_ns),
                                     float(ev.duration_ns)])
                lines.extend({"device": d, "events": evs}
                             for d, evs in sorted(by_dev.items()))
    return {"lines": lines, "host": host}


def _assign_modules(ops: List[list], mods: List[tuple]) -> None:
    """The module event each op event's middle falls in."""
    starts = [s for s, _, _ in mods]
    for ev in ops:
        mid = ev[1] + 0.5 * ev[2]
        i = bisect.bisect_right(starts, mid) - 1
        if i >= 0 and mid <= mods[i][1]:
            ev[3] = mods[i][2]


# ---------------- the fold --------------------------------------------


def merge_intervals(intervals: Sequence[Tuple[float, float]]
                    ) -> List[List[float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _overlap_with_union(iv: Tuple[float, float],
                        union: Sequence[Sequence[float]]) -> float:
    a, b = iv
    tot = 0.0
    for ua, ub in union:
        if ub <= a:
            continue
        if ua >= b:
            break
        tot += min(b, ub) - max(a, ua)
    return tot


def self_times(events: Sequence[Sequence]) -> Tuple[List[float],
                                                    List[bool]]:
    """(self time, is a leaf) of each event of ONE nested line, in
    input order: an event's duration less what its direct children
    cover, never below 0."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][1], -events[i][2]))
    self_ns = [float(e[2]) for e in events]
    leaf = [True] * len(events)
    stack: List[int] = []
    for i in order:
        s, d = events[i][1], events[i][2]
        while stack and events[stack[-1]][1] + events[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            p = stack[-1]
            p_end = events[p][1] + events[p][2]
            self_ns[p] -= max(min(s + d, p_end) - s, 0.0)
            leaf[p] = False
        stack.append(i)
    return [max(x, 0.0) for x in self_ns], leaf


def _pick_programs(lines, programs):
    """module -> index into `programs` of the map that names most of
    the operations seen in that module, among those compiled under the
    module's name; modules that no program names are left out. Two
    scans of one step differ in little but their trip count, so a tie
    goes to the program whose scan length the module's events show:
    the operations of the scan's body ran that many times for each
    time the operations around it ran once."""
    seen: Dict[str, Dict[str, int]] = {}
    first = lines[0]["device"]
    for line in lines:
        for ev in line["events"]:
            counts = seen.setdefault(ev[3], {})
            if line["device"] == first:
                counts[ev[0]] = counts.get(ev[0], 0) + 1
            else:
                counts.setdefault(ev[0], 0)
    out: Dict[str, int] = {}
    for mod, counts in seen.items():
        stem = mod.split("(", 1)[0]
        reps = sorted({c for c in counts.values() if c})
        trips = round(reps[1] / reps[0]) if len(reps) > 1 else 1
        best, best_key = None, (0, False)
        for i, prog in enumerate(programs):
            if stem and prog["module"] and stem != prog["module"]:
                continue
            key = (len(counts.keys() & prog["map"].keys()),
                   prog["scan_length"] == trips)
            if key > best_key:
                best, best_key = i, key
        # a foreign module may share an instruction's name (`copy.1`)
        if best is not None and 2 * best_key[0] >= len(counts):
            out[mod] = best
    return out


def _host_spans(host, t):
    """(innermost span of the program, innermost host event of any
    kind) open at `t`; "" where none is."""
    prog = inner = None
    for name, s, d in host:
        if s <= t <= s + d:
            if inner is None or d < inner[1]:
                inner = (name, d)
            if name != ANCHOR_SPAN and (
                    name == PROGRAM_SPANS[0]
                    or name.startswith(PROGRAM_SPANS[1])) and \
                    (prog is None or d < prog[1]):
                prog = (name, d)
    return (prog[0] if prog else ""), (inner[0][:80] if inner else "")


def fold_xplane(tr: Dict[str, Any], programs: Sequence[Dict[str, Any]],
                n_gaps: int = 10) -> Dict[str, Any]:
    """Fold a loaded trace into the body of a ``profile`` record.

    `programs`: one entry per compiled program the window dispatched,
    {"scan_length": k, "module": HloModule name, "map": hlo_op_map of
    its text}. Events of modules that no program names (an eval
    program, the eager key building) count in `busy_s` but in no
    phase, so they cannot pass for overlap.

    Overlap: per device, the non-communication LEAVES form a union;
    each communication leaf is split into covered/exposed against it.
    fraction = covered / total (0.0 when the capture saw no
    communication at all: P=1 runs)."""
    lines = [ln for ln in tr["lines"] if ln["events"]]
    if not lines:
        return {}
    devices = sorted({ln["device"] for ln in lines})
    k = len(devices)
    lo = min(e[1] for ln in lines for e in ln["events"])
    hi = max(e[1] + e[2] for ln in lines for e in ln["events"])
    picked = _pick_programs(lines, programs)
    n_events = [0] * len(programs)
    n_matched = [0] * len(programs)
    mods_of: List[set] = [set() for _ in programs]

    phase_ns: Dict[str, float] = {}
    path_ns: Dict[str, float] = {}
    unscoped_ns = other_ns = 0.0
    named: Dict[Tuple[int, str], Tuple[bool, str, str]] = {}
    busy_iv: Dict[int, list] = {d: [] for d in devices}
    comm_iv: Dict[int, list] = {d: [] for d in devices}
    comp_iv: Dict[int, list] = {d: [] for d in devices}
    for ln in lines:
        dev = ln["device"]
        selfs, leaves = self_times(ln["events"])
        for ev, ns, is_leaf in zip(ln["events"], selfs, leaves):
            if ev[2] > 0:
                busy_iv[dev].append((ev[1], ev[1] + ev[2]))
            pi = picked.get(ev[3])
            if pi is None:
                other_ns += ns
                continue
            n_events[pi] += 1
            mods_of[pi].add(ev[3])
            # (matched, phase, path) of an instruction, read once: a scan
            # runs each of its few thousand instructions many times
            info = named.get((pi, ev[0]))
            if info is None:
                hit = programs[pi]["map"].get(ev[0])
                op_name, kind = hit or ("", "")
                info = named[(pi, ev[0])] = (
                    hit is not None, classify_op(op_name, kind or ev[0]),
                    scope_path(op_name))
            matched, phase, path = info
            n_matched[pi] += matched
            phase_ns[phase] = phase_ns.get(phase, 0.0) + ns
            if path:
                path_ns[path] = path_ns.get(path, 0.0) + ns
            else:
                unscoped_ns += ns
            if is_leaf and ev[2] > 0:
                tgt = comm_iv if phase in COMM_PHASES else comp_iv
                tgt[dev].append((ev[1], ev[1] + ev[2]))

    busy_ns, gaps = 0.0, []
    for d in devices:
        merged = merge_intervals(busy_iv[d])
        busy_ns += sum(e - s for s, e in merged)
        gaps.extend((s1 - e0, e0, s1)
                    for (_, e0), (s1, _) in zip(merged, merged[1:]))
    covered = total_comm = 0.0
    for d in devices:
        union = merge_intervals(comp_iv[d])
        for iv in comm_iv[d]:
            total_comm += iv[1] - iv[0]
            covered += _overlap_with_union(iv, union)
    frac = min(max(covered / total_comm, 0.0), 1.0) if total_comm else 0.0

    gap_s: Dict[Tuple[str, str], List[float]] = {}
    for dur, g0, g1 in sorted(gaps, reverse=True)[:200]:
        span, inner = _host_spans(tr["host"], 0.5 * (g0 + g1))
        slot = gap_s.setdefault((span, inner if inner != span else ""),
                                [0.0, 0])
        slot[0] += dur / k * 1e-9
        slot[1] += 1

    def sec(ns):
        return round(ns / k * 1e-9, 9)

    comm_ns = sum(phase_ns.get(p, 0.0) for p in COMM_PHASES)
    anchor = next((e for e in tr["host"] if e[0] == ANCHOR_SPAN), None)
    return {
        "phases": {p: sec(v) for p, v in sorted(phase_ns.items())},
        "comm_s": sec(comm_ns),
        "compute_s": sec(sum(phase_ns.values()) - comm_ns),
        "overlap_fraction": round(frac, 6),
        "paths": {p: sec(v) for p, v in sorted(path_ns.items())},
        "unscoped_s": sec(unscoped_ns),
        "other_programs_s": sec(other_ns),
        "window_s": round((hi - lo) * 1e-9, 9),
        "busy_s": sec(busy_ns),
        "programs": [
            {"scan_length": p["scan_length"],
             "modules": sorted(mods_of[i]),
             "n_events": n_events[i],
             "matched": (round(n_matched[i] / n_events[i], 4)
                         if n_events[i] else 0.0)}
            for i, p in enumerate(programs)],
        "idle_gaps": [
            {"span": span, "inner": inner, "s": round(v[0], 9),
             "n": v[1]}
            for (span, inner), v in sorted(
                gap_s.items(), key=lambda kv: -kv[1][0])[:n_gaps]],
        "first_event_s": round(lo * 1e-9, 9),
        "anchor_s": round(anchor[1] * 1e-9, 9) if anchor else None,
        "n_devices": k,
        "n_device_events": sum(len(ln["events"]) for ln in lines),
        "n_matched_events": sum(n_matched),
    }


def analyze_trace_dir(profile_dir: str, compiled_texts: Dict[int, str]
                      ) -> Optional[Dict[str, Any]]:
    """Fold the newest capture under `profile_dir` against the
    compiled HLO text of every scan length the window dispatched
    ({scan length: text}); returns the body of a ``profile`` record
    (event/epoch fields added by the caller) or None when the capture
    left no device operation to read."""
    path = newest_xplane(profile_dir)
    if path is None:
        return None
    tr = load_xplane(path)
    programs = [{"scan_length": int(n), "module": module_name(txt),
                 "map": hlo_op_map(txt)}
                for n, txt in sorted(compiled_texts.items())]
    body = fold_xplane(tr, programs)
    if not body or not body["n_device_events"]:
        return None
    body["trace_files"] = [os.path.relpath(path, profile_dir)]
    body["trace_bytes"] = os.path.getsize(path)
    return body


# ---------------- CLI flag parsing ------------------------------------


def parse_profile_epochs(spec: str) -> Tuple[int, int]:
    """'A:B' -> (A, B): capture a device trace around the dispatched
    blocks of epochs [A, B). Raises ValueError on malformed or empty
    windows so the CLI fails before burning a run."""
    m = re.fullmatch(r"(\d+):(\d+)", spec.strip())
    if not m:
        raise ValueError(
            f"--profile-epochs expects 'A:B' (epoch window [A, B)), "
            f"got {spec!r}")
    a, b = int(m.group(1)), int(m.group(2))
    if b <= a:
        raise ValueError(
            f"--profile-epochs window [{a}, {b}) is empty")
    return a, b
