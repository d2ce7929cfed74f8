"""Automated postmortem diagnosis: from artifacts to a root cause.

`collect_bundle(run_dir)` gathers everything a dead (or finished) run
left behind — black-box flight-recorder dumps (obs/flight.py), every
metrics JSONL stream (reusing `obs/live.discover_streams`, so
per-generation files, the membership ledger metrics and window.jsonl
all fold in), child-process log tails, checkpoint metadata, and an
environment fingerprint — into one JSON-able bundle. `diagnose(bundle)`
then runs an ORDERED, evidence-citing rule set and returns a
confidence-ranked verdict:

  wedged-collective  a rank blocked in a dead collective (watchdog
                     dumps, peer-lost hard-deadline faults, open
                     dispatch/collective spans)
  oom                RESOURCE_EXHAUSTED / out-of-memory text anywhere
  fallback-exhausted the kernel fallback ladder ran out of rungs
  corrupt-artifact   digest/CRC-verification failures killed the run
  config-error       a setup-phase ValueError/argument error
  desync             cross-rank parameter desync without a resync
  storage-fault      durable writes degraded and never recovered
  recompile-storm    repeated recompiles dominated the run
  divergence         sentinel retries exhausted / NaN death
  preemption         a requested, checkpointed, resumable exit
  topo-rollback      a resume rolled the delta journal back past the
                     checkpoint watermark (journal op="truncate"
                     records with dropped entries): topology deltas
                     applied after the last durable checkpoint were
                     un-committed and re-delivered by the stream plan
  crash              an uncaught exception not matching the above
  clean-exit         the run completed after the last recorded trouble
  unknown            nothing matched (pipegcn-debug exits 4)

Three classes are DETERMINISTIC — relaunching reproduces the failure,
so the elastic supervisor fails fast on them instead of burning
``--max-restarts``: corrupt-artifact, config-error,
fallback-exhausted. Everything else keeps the restart/backoff policy
(docs/RESILIENCE.md "Fail fast vs restart").

The verdict dict validates as the schema-v11 ``diagnosis`` record
kind. `pipegcn_tpu.cli.debug` is the CLI (`pipegcn-debug explain
<run-dir>`); the elastic supervisor calls :func:`diagnose_run`
directly.
"""

from __future__ import annotations

import glob
import json
import os
import platform
import re
import sys
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

from .live import discover_streams, merge_streams

# classes where a relaunch deterministically reproduces the failure:
# the supervisor fails fast instead of retrying (docs/RESILIENCE.md)
DETERMINISTIC_CLASSES = ("corrupt-artifact", "config-error",
                         "fallback-exhausted")

_MAX_LOG_TAIL = 4000        # chars kept per log file
_MAX_LOG_FILES = 24
_MAX_BLACKBOXES = 16
_TIMELINE_EVENTS = 40


# ---------------------------------------------------------------------
# bundle collection
# ---------------------------------------------------------------------


def _rel(path: str, root: str) -> str:
    try:
        return os.path.relpath(path, root)
    except ValueError:
        return path


def collect_bundle(run_dir: str) -> Dict[str, Any]:
    """Everything the run left behind, as one JSON-able dict. Tolerant
    by construction: unreadable/corrupt files become entries with an
    ``error`` key, never exceptions — a postmortem must work on
    exactly the broken artifacts a crash leaves."""
    run_dir = os.path.abspath(os.fspath(run_dir))
    bundle: Dict[str, Any] = {"run_dir": run_dir,
                              "collected_unix": time.time()}

    # black-box dumps (obs/flight.py), anywhere under the run dir
    boxes: List[Dict[str, Any]] = []
    for path in sorted(glob.glob(os.path.join(
            run_dir, "**", "blackbox-r*.json"), recursive=True)):
        entry: Dict[str, Any] = {"path": _rel(path, run_dir)}
        try:
            with open(path, encoding="utf-8") as fh:
                entry["data"] = json.load(fh)
        except (OSError, ValueError) as exc:
            entry["error"] = repr(exc)
        boxes.append(entry)
        if len(boxes) >= _MAX_BLACKBOXES:
            break
    bundle["blackboxes"] = boxes

    # every metrics stream the live plane would discover
    paths = discover_streams(run_dir)
    bundle["streams"] = [_rel(p, run_dir) for p in paths]
    bundle["records"] = merge_streams(paths)

    # child / rank log tails (elastic supervisor children, window runs)
    tails: Dict[str, str] = {}
    for path in sorted(glob.glob(os.path.join(run_dir, "**", "*.log"),
                                 recursive=True))[:_MAX_LOG_FILES]:
        try:
            with open(path, "rb") as fh:
                fh.seek(max(0, os.path.getsize(path) - _MAX_LOG_TAIL))
                tails[_rel(path, run_dir)] = fh.read().decode(
                    "utf-8", "replace")
        except OSError as exc:
            tails[_rel(path, run_dir)] = f"<unreadable: {exc!r}>"
    bundle["log_tails"] = tails

    # checkpoint metadata (never the payloads)
    cks = []
    for path in sorted(glob.glob(os.path.join(
            run_dir, "**", "state-*.npz"), recursive=True)):
        try:
            st = os.stat(path)
            cks.append({"path": _rel(path, run_dir),
                        "bytes": st.st_size, "mtime_unix": st.st_mtime})
        except OSError:
            continue
    bundle["checkpoints"] = cks

    # environment / config fingerprint
    fp: Dict[str, Any] = {
        "python": sys.version.split()[0],
        "platform": platform.platform(),
    }
    try:
        from importlib.metadata import version

        fp["jax"] = version("jax")
    except Exception:  # noqa: BLE001 — fingerprint is best-effort
        pass
    try:
        from .schema import SCHEMA_VERSION

        fp["schema_version"] = SCHEMA_VERSION
    except Exception:  # noqa: BLE001
        pass
    run_hdr = next((r for r in bundle["records"]
                    if r.get("event") == "run"), None)
    if run_hdr is not None:
        cfg = run_hdr.get("config") or {}
        fp["config"] = {k: cfg[k] for k in sorted(cfg)
                        if isinstance(cfg[k], (str, int, float, bool,
                                               type(None)))}
    bundle["fingerprint"] = fp
    return bundle


# ---------------------------------------------------------------------
# rule helpers
# ---------------------------------------------------------------------


def _faults(bundle: Dict[str, Any], kind: str) -> List[Dict[str, Any]]:
    return [r for r in bundle.get("records", ())
            if r.get("event") == "fault" and r.get("kind") == kind]


def _recoveries(bundle: Dict[str, Any], kind: str) -> List[Dict]:
    return [r for r in bundle.get("records", ())
            if r.get("event") == "recovery" and r.get("kind") == kind]


def _boxes(bundle: Dict[str, Any]) -> List[Dict[str, Any]]:
    return [b for b in bundle.get("blackboxes", ()) if "data" in b]


def _corpus(bundle: Dict[str, Any]) -> List[Tuple[str, str]]:
    """(source, text) pairs to grep for error signatures: log tails,
    black-box error fields and stack captures."""
    out: List[Tuple[str, str]] = list(bundle.get("log_tails",
                                                 {}).items())
    for b in _boxes(bundle):
        d = b["data"]
        for key in ("error", "stacks"):
            if d.get(key):
                out.append((f"{b['path']}:{key}", str(d[key])))
    return out


def _grep(bundle: Dict[str, Any], pattern: str,
          max_hits: int = 4) -> List[str]:
    """Evidence strings ``source: matched line`` for a regex."""
    rx = re.compile(pattern)
    hits: List[str] = []
    for source, text in _corpus(bundle):
        for line in text.splitlines():
            if rx.search(line):
                hits.append(f"{source}: {line.strip()[:160]}")
                break  # one citation per source is plenty
        if len(hits) >= max_hits:
            break
    return hits


def _last_summary_time(bundle: Dict[str, Any]) -> Optional[float]:
    ts = [r.get("time_unix") for r in bundle.get("records", ())
          if r.get("event") == "summary"
          and isinstance(r.get("time_unix"), (int, float))]
    has_summary = any(r.get("event") == "summary"
                      for r in bundle.get("records", ()))
    if not has_summary:
        return None
    return max([t for t in ts if t is not None], default=0.0)


def _newest_box_time(bundle: Dict[str, Any]) -> Optional[float]:
    # stall dumps are NON-terminal by design (the stall detector
    # captures stacks and the process keeps running), so they must not
    # make a completed run look like it died after its summary
    ts = [b["data"].get("time_unix") for b in _boxes(bundle)
          if isinstance(b["data"].get("time_unix"), (int, float))
          and b["data"].get("reason") != "stall"]
    return max(ts) if ts else None


# ---------------------------------------------------------------------
# the rule set (ordered: ties in confidence resolve to the earlier
# rule — the ordering IS part of the contract, pinned by tests)
# ---------------------------------------------------------------------


def _rule_clean_exit(b: Dict) -> Optional[Dict]:
    t_sum = _last_summary_time(b)
    if t_sum is None:
        return None
    t_box = _newest_box_time(b)
    if t_box is not None and t_box > t_sum:
        return None  # something died AFTER the last completed run
    ev = ["summary record present: the run (or its clean resume) "
          "completed"]
    n_f = sum(1 for r in b.get("records", ())
              if r.get("event") == "fault")
    if n_f:
        ev.append(f"{n_f} fault record(s) all predate the final "
                  f"summary (recovered in-run)")
    return {"confidence": 0.9, "evidence": ev,
            "remediation": "nothing to do — the run completed; any "
                           "faults along the way were recovered"}


def _rule_wedged(b: Dict) -> Optional[Dict]:
    ev: List[str] = []
    for box in _boxes(b):
        d = box["data"]
        if d.get("reason") == "watchdog":
            ann = d.get("annotation") or {}
            ctx = ", ".join(f"{k}={ann[k]}" for k in sorted(ann)
                            if k not in ("t", "seq", "kind"))
            ev.append(f"{box['path']}: watchdog-trip dump (rank "
                      f"{d.get('rank')}"
                      + (f", {ctx}" if ctx else "") + ")")
            if d.get("stacks"):
                ev.append(f"{box['path']}: all-thread stacks captured "
                          f"while wedged")
    for r in _faults(b, "peer-lost"):
        ev.append(f"fault record: peer-lost at epoch {r.get('epoch')} "
                  f"(peer rank {r.get('peer_rank')}"
                  + (", hard deadline" if r.get("hard_deadline")
                     else "") + ")")
    for box in _boxes(b):
        for sp in box["data"].get("open_spans") or ():
            if sp.get("kind") in ("dispatch-enter", "collective-enter"):
                ev.append(f"{box['path']}: span {sp.get('kind')} "
                          f"(epoch {sp.get('epoch')}"
                          + (f", phase {sp['phase']}"
                             if sp.get("phase") else "")
                          + ") never exited")
    if not ev:
        return None
    strong = any("watchdog" in e or "peer-lost" in e for e in ev)
    return {"confidence": 0.9 if strong and len(ev) >= 2 else 0.6,
            "evidence": ev,
            "remediation": "a rank stopped making progress inside a "
                           "collective; restart the pod from the "
                           "emergency checkpoint (--resume) and check "
                           "the dead peer's host/network"}


def _rule_oom(b: Dict) -> Optional[Dict]:
    ev = _grep(b, r"RESOURCE_EXHAUSTED|Out of memory|bad_alloc"
                  r"|MemoryError|OOM[ :-]|oom-kill")
    if not ev:
        return None
    return {"confidence": 0.85, "evidence": ev,
            "remediation": "the device or host ran out of memory: "
                           "shrink --spmm-chunk / --n-hidden, raise "
                           "--n-partitions, or move to a larger "
                           "topology"}


def _rule_fallback_exhausted(b: Dict) -> Optional[Dict]:
    ev = _grep(b, r"KernelFallbackError|fallback ladder|every rung")
    fbs = [r for r in b.get("records", ())
           if r.get("event") == "fallback"]
    for r in fbs[:3]:
        ev.append(f"fallback record: {r.get('from_impl')} -> "
                  f"{r.get('to_impl')} at epoch {r.get('epoch')}")
    if not _grep(b, r"KernelFallbackError|fallback ladder|every rung"):
        return None
    return {"confidence": 0.85, "evidence": ev,
            "remediation": "every aggregation-kernel rung failed — "
                           "this reproduces on relaunch; pin "
                           "--spmm-impl xla and file the kernel crash"}


def _rule_corrupt_artifact(b: Dict) -> Optional[Dict]:
    ev = _grep(b, r"CheckpointCorrupt|LedgerCorrupt|digest mismatch"
                  r"|CRC mismatch|every generation corrupt"
                  r"|partition artifact .* corrupt")
    if not ev:
        return None
    return {"confidence": 0.85, "evidence": ev,
            "remediation": "a persisted artifact fails verification — "
                           "relaunching reproduces this; delete or "
                           "restore the corrupt generation/artifact "
                           "before restarting"}


def _rule_config_error(b: Dict) -> Optional[Dict]:
    ev: List[str] = []
    for box in _boxes(b):
        err = str(box["data"].get("error") or "")
        if re.match(r"(ValueError|NotImplementedError|TypeError"
                    r"|KeyError|ArgumentError)", err):
            ev.append(f"{box['path']}: setup/config exception: "
                      f"{err[:160]}")
    ev += _grep(b, r"error: (unrecognized|invalid|argument)"
                   r"|usage: pipegcn")
    if not ev:
        return None
    return {"confidence": 0.8, "evidence": ev,
            "remediation": "the configuration itself is rejected — "
                           "relaunching reproduces this; fix the "
                           "flag/config named above"}


def _rule_desync(b: Dict) -> Optional[Dict]:
    fs = _faults(b, "desync")
    if not fs:
        return None
    rec = _recoveries(b, "desync")
    ev = [f"fault record: cross-rank desync at epoch "
          f"{r.get('epoch')} (source rank {r.get('source_rank')})"
          for r in fs[:3]]
    if rec:
        ev.append(f"{len(rec)} desync recovery record(s): resync "
                  f"adopted rank 0's state")
        conf = 0.5  # recovered; only relevant if nothing else matched
    else:
        ev += _grep(b, r"cross-rank parameter desync", max_hits=2)
        conf = 0.8
    return {"confidence": conf, "evidence": ev,
            "remediation": "replicated params drifted across ranks; "
                           "resume from the crash checkpoint and "
                           "enable --desync-resync (or investigate "
                           "nondeterministic kernels)"}


def _rule_sdc(b: Dict) -> Optional[Dict]:
    """Silent data corruption (resilience/integrity.py): sdc fault
    records, integrity mismatch records, quarantine requests. A
    quarantine (or an unrecovered detection) is high-confidence — the
    run named a defective member; a recovered one-off detection is
    background context only."""
    fs = _faults(b, "sdc")
    mism = [r for r in b.get("records", ())
            if r.get("event") == "integrity"
            and r.get("outcome") == "mismatch"]
    if not fs and not mism:
        return None
    ev = [f"fault record: sdc ({r.get('target')}) at epoch "
          f"{r.get('epoch')} (rank {r.get('rank')}, "
          f"{r.get('strikes', 1)} strike(s))" for r in fs[:3]]
    ev += [f"integrity record: {r.get('check')} mismatch on "
           f"{r.get('target')} at epoch {r.get('epoch')} "
           f"({str(r.get('detail', ''))[:60]})" for r in mism[:3]]
    quarantined = (_faults(b, "quarantine-request")
                   or _grep(b, r"quarantine requested for member",
                            max_hits=2))
    if quarantined:
        ev += [f"fault record: quarantine-request (member "
               f"{r.get('member')}, {r.get('strikes')} strikes)"
               for r in _faults(b, "quarantine-request")[:2]]
        ev += [h for h in quarantined if isinstance(h, str)]
        conf = 0.9
    else:
        recovered = bool(_recoveries(b, "sdc"))
        conf = 0.5 if recovered else 0.8
        if recovered:
            ev.append("sdc recovery record present: rollback/flush/"
                      "rebuild completed")
    return {"confidence": conf, "evidence": ev,
            "remediation": "silent data corruption detected; if one "
                           "rank keeps tripping (quarantined), pull "
                           "that host for screening — rejoin only via "
                           "an explicit rejoin request after clearing "
                           "its quarantine marker"}


def _rule_storage_fault(b: Dict) -> Optional[Dict]:
    fs = _faults(b, "io-degraded")
    ev = [f"fault record: io-degraded at epoch {r.get('epoch')} "
          f"({str(r.get('component', r.get('reason', '')))[:80]})"
          for r in fs[:3]]
    ev += _grep(b, r"ENOSPC|EROFS|No space left|Read-only file system"
                   r"|CHECKPOINT SAVE FAILED", max_hits=3)
    if not ev:
        return None
    recovered = bool(_recoveries(b, "io-degraded"))
    if recovered:
        ev.append("io-degraded recovery record present: the writer "
                  "caught back up")
    return {"confidence": 0.45 if recovered else 0.8, "evidence": ev,
            "remediation": "durable writes degraded (disk full / "
                           "read-only / torn); free space or fix the "
                           "mount, then --resume — the previous "
                           "checkpoint generation is authoritative"}


def _rule_recompile_storm(b: Dict) -> Optional[Dict]:
    repads = [r for r in b.get("records", ())
              if r.get("event") == "stream" and r.get("repadded")]
    hits = _grep(b, r"re-padded: recompile|recompil", max_hits=3)
    ev = [f"stream record: delta seq {r.get('seq')} re-padded "
          f"(recompile) at epoch {r.get('epoch')}" for r in repads[:4]]
    ev += hits
    if len(ev) < 3:
        return None
    return {"confidence": 0.7, "evidence": ev,
            "remediation": "shape changes forced repeated recompiles; "
                           "raise --stream-slack (or pre-pad) so "
                           "deltas land without growing shapes"}


def _rule_divergence(b: Dict) -> Optional[Dict]:
    fs = _faults(b, "divergence")
    if not fs:
        return None
    exhausted = _grep(b, r"retries were exhausted|DivergenceError",
                      max_hits=2)
    ev = [f"fault record: divergence at epoch {r.get('epoch')} "
          f"(retry {r.get('retry')}, reason "
          f"{str(r.get('reason', ''))[:60]})" for r in fs[:3]]
    ev += exhausted
    recovered = bool(_recoveries(b, "divergence"))
    if recovered and not exhausted:
        ev.append("divergence recovery record present: rollback + "
                  "retry succeeded")
    return {"confidence": 0.85 if exhausted
            else (0.45 if recovered else 0.7),
            "evidence": ev,
            "remediation": "training diverged; lower --lr, raise "
                           "--sentinel-loss-factor, or enable "
                           "--loss-scale dynamic before resuming"}


def _rule_preemption(b: Dict) -> Optional[Dict]:
    ev: List[str] = []
    for box in _boxes(b):
        if box["data"].get("reason") == "preemption":
            ev.append(f"{box['path']}: preemption dump (epoch "
                      f"{box['data'].get('epoch')})")
    ev += [f"fault record: preemption at epoch {r.get('epoch')} "
           f"({str(r.get('reason', ''))[:60]})"
           for r in _faults(b, "preemption")[:3]]
    ev += _grep(b, r"resumable — rerun with --resume|\[exit 75\]",
                max_hits=2)
    if not ev:
        return None
    return {"confidence": 0.75, "evidence": ev,
            "remediation": "a requested, checkpointed stop — rerun "
                           "with --resume --checkpoint-dir; no "
                           "investigation needed"}


def _rule_crash(b: Dict) -> Optional[Dict]:
    ev: List[str] = []
    for box in _boxes(b):
        d = box["data"]
        if d.get("reason") in ("exception", "fault"):
            ev.append(f"{box['path']}: {d.get('reason')} dump "
                      f"({str(d.get('error', ''))[:120]})")
    ev += _grep(b, r"Traceback \(most recent call last\)", max_hits=2)
    if not ev:
        return None
    return {"confidence": 0.65, "evidence": ev,
            "remediation": "an uncaught exception killed the run; the "
                           "crash checkpoint (if any) is resumable — "
                           "read the cited error before retrying"}


def _rule_topo_rollback(b: Dict) -> Optional[Dict]:
    """Crash-consistent streaming (stream/journal.py): a resume found
    journal entries PAST the checkpoint watermark — deltas applied
    after the last durable checkpoint — and rolled them back
    (op="truncate" with dropped records) for re-delivery by the
    stream plan. Moderate confidence: the rollback itself is the
    designed recovery, so a completed run's clean-exit outranks it;
    it becomes the verdict only when the run died around the
    rollback."""
    truncs = [r for r in b.get("records", ())
              if r.get("event") == "journal"
              and r.get("op") == "truncate"
              and int(r.get("n_records", 0)) > 0]
    if not truncs:
        return None
    ev = []
    for r in truncs[:3]:
        ev.append(f"journal record: {int(r.get('n_records', 0))} "
                  f"entr{'y' if int(r.get('n_records', 0)) == 1 else 'ies'} "
                  f"past watermark seq {r.get('seq')} rolled back "
                  f"(journal at generation {r.get('topo_generation')})")
    replays = [r for r in b.get("records", ())
               if r.get("event") == "journal"
               and r.get("op") == "replay"]
    for r in replays[:2]:
        ev.append(f"journal record: replay of "
                  f"{int(r.get('n_records', 0))} entr"
                  f"{'y' if int(r.get('n_records', 0)) == 1 else 'ies'}"
                  f" (+{int(r.get('rederived', 0))} re-derived from "
                  f"the plan) up to watermark seq {r.get('seq')}")
    return {
        "confidence": 0.6, "evidence": ev,
        "remediation": "topology deltas newer than the checkpoint "
                       "watermark were un-committed on resume and "
                       "re-delivered at their scheduled epochs — "
                       "verify the run's journal op=\"verify\" record "
                       "shows tables_match; checkpoint more often "
                       "(or fsync the journal) to shrink the "
                       "watermark gap"}


# (name, matcher) in priority order; confidence breaks ties the other
# way, so the order only matters between equal-confidence matches
_RULES: List[Tuple[str, Callable[[Dict], Optional[Dict]]]] = [
    ("clean-exit", _rule_clean_exit),
    ("wedged-collective", _rule_wedged),
    ("oom", _rule_oom),
    ("fallback-exhausted", _rule_fallback_exhausted),
    ("corrupt-artifact", _rule_corrupt_artifact),
    ("config-error", _rule_config_error),
    ("desync", _rule_desync),
    ("sdc", _rule_sdc),
    ("storage-fault", _rule_storage_fault),
    ("recompile-storm", _rule_recompile_storm),
    ("divergence", _rule_divergence),
    ("preemption", _rule_preemption),
    ("topo-rollback", _rule_topo_rollback),
    ("crash", _rule_crash),
]


# ---------------------------------------------------------------------
# diagnosis
# ---------------------------------------------------------------------


def _timeline(bundle: Dict[str, Any]) -> List[str]:
    """The last minutes of the run, rendered: contracted records and
    black-box breadcrumbs merged on their timestamps."""
    events: List[Tuple[float, str]] = []
    for r in bundle.get("records", ()):
        t = r.get("time_unix")
        if not isinstance(t, (int, float)):
            continue
        ev = r.get("event")
        if ev == "epoch":
            desc = f"epoch {r.get('epoch')} loss={r.get('loss')}"
        elif ev in ("fault", "recovery", "numerics", "fleet"):
            desc = f"{ev}:{r.get('kind')} epoch={r.get('epoch', '?')}"
        elif ev == "membership":
            desc = (f"membership gen {r.get('generation')} "
                    f"({r.get('trigger')})")
        elif ev in ("run", "summary", "alert", "stream", "fallback",
                    "blackbox", "diagnosis", "soak"):
            desc = ev
        else:
            continue
        events.append((float(t), desc))
    for box in _boxes(bundle):
        d = box["data"]
        for c in d.get("crumbs") or ():
            t = c.get("t")
            if isinstance(t, (int, float)):
                keys = ", ".join(
                    f"{k}={c[k]}" for k in sorted(c)
                    if k not in ("t", "seq", "kind"))
                events.append((float(t),
                               f"r{d.get('rank', '?')} crumb "
                               f"{c.get('kind')}"
                               + (f" ({keys[:80]})" if keys else "")))
        t = d.get("time_unix")
        if isinstance(t, (int, float)):
            events.append((float(t),
                           f"BLACKBOX DUMP r{d.get('rank', '?')} "
                           f"reason={d.get('reason')}"))
    events.sort(key=lambda e: e[0])
    events = events[-_TIMELINE_EVENTS:]
    if not events:
        return []
    t0 = events[-1][0]
    return [f"t-{t0 - t:7.1f}s  {desc}" for t, desc in events]


def diagnose(bundle: Dict[str, Any]) -> Dict[str, Any]:
    """Run the rule set over a collected bundle; returns the verdict
    dict (validates as a schema ``diagnosis`` record), including the
    full ranked candidate list."""
    matches: List[Dict[str, Any]] = []
    for i, (name, fn) in enumerate(_RULES):
        try:
            m = fn(bundle)
        except Exception as exc:  # noqa: BLE001 — a broken rule must
            #                       not kill the whole postmortem
            m = {"confidence": 0.0, "evidence": [f"rule error: {exc!r}"],
                 "remediation": ""}
        if m is not None:
            matches.append({"verdict": name, "order": i, **m})
    matches.sort(key=lambda m: (-m["confidence"], m["order"]))
    if matches:
        top = matches[0]
        verdict, confidence = top["verdict"], float(top["confidence"])
        evidence, remediation = top["evidence"], top["remediation"]
    else:
        verdict, confidence = "unknown", 0.0
        n_box = len(_boxes(bundle))
        evidence = [("no rule matched despite "
                     f"{n_box} black-box dump(s) — inspect them "
                     "directly (the timeline below folds them in)")
                    if n_box else
                    ("no rule matched: no dumps, no fault records, no "
                     "recognizable error text")]
        remediation = ("collect more: enable --metrics-out, keep the "
                       "coordination dir, and rerun with PIPEGCN_"
                       "STALL_S set for stall forensics")
    return {
        "event": "diagnosis",
        "verdict": verdict,
        "confidence": confidence,
        "evidence": list(evidence),
        "remediation": remediation,
        "deterministic": verdict in DETERMINISTIC_CLASSES,
        "candidates": [{"verdict": m["verdict"],
                        "confidence": float(m["confidence"])}
                       for m in matches],
        "run_dir": bundle.get("run_dir", ""),
        "n_blackboxes": len(_boxes(bundle)),
        "timeline": _timeline(bundle),
    }


def diagnose_run(run_dir: str) -> Dict[str, Any]:
    """collect_bundle + diagnose in one call (supervisor / tooling
    entry point)."""
    return diagnose(collect_bundle(run_dir))


def render(verdict: Dict[str, Any]) -> str:
    """Human-readable report: verdict, evidence, remediation, and the
    last-minutes timeline."""
    lines = [
        f"verdict: {verdict['verdict']} "
        f"(confidence {verdict['confidence']:.2f}"
        + (", deterministic — do not blind-restart"
           if verdict.get("deterministic") else "") + ")",
        f"run dir: {verdict.get('run_dir', '?')}",
        "",
        "evidence:",
    ]
    for e in verdict.get("evidence", ()):
        lines.append(f"  - {e}")
    others = [c for c in verdict.get("candidates", ())[1:3]]
    if others:
        lines.append("also considered: " + ", ".join(
            f"{c['verdict']} ({c['confidence']:.2f})" for c in others))
    lines += ["", f"remediation: {verdict.get('remediation', '')}"]
    tl = verdict.get("timeline") or []
    if tl:
        lines += ["", "last-minutes timeline:"]
        lines += [f"  {ln}" for ln in tl]
    return "\n".join(lines) + "\n"
