"""Seeded full-stack chaos soak: composed fault schedules + invariants.

The unit tests in tests/ each drill ONE recovery path; this module
drills their COMPOSITION. A soak run is a sequence of episodes; each
episode derives a deterministic fault schedule from
``seed * 1000003 + episode`` (same seed -> same schedules -> same
verdict), runs an elastic-supervised trainer (cli.elastic subprocess)
with a streaming delta plan and the schedule as ``--fault-plan``, then
a final clean ``--resume`` (cli.main subprocess), and checks five
structural invariants over the artifacts left behind:

  checkpoint  the newest digest-valid generation exists and verifies
              (utils/checkpoint.py per-leaf CRCs); after the clean
              resume it sits at the nominal epoch count
  ledger      membership generations are contiguous from 0 and every
              record is CRC-clean (resilience/elastic.py)
  metrics     every metrics JSONL parses (a torn FINAL line is the one
              legal wound — SIGKILL mid-write) and the union of epoch
              records across generations + the resume covers every
              epoch exactly 0..n_epochs-1: nothing silently lost, even
              through the io-degraded ring-buffer path (obs/metrics.py)
  tickets     (``serve`` episodes only) the serving fleet drill's
              summary reports conserved=drained=True — zero accepted
              tickets lost (serve/fleet.py)
  autoscale   (``autoscale`` episodes only) the closed-loop drill —
              flash-crowd traffic over a 1-replica fleet with one
              replica-kill and one mid-crowd net-partition — ends with
              the replica-count trajectory matching the ledger's
              spawn/retire records one-to-one with the ``autoscale``
              decision records, at least one crowd-provoked scale-up,
              and tickets conserved through the scale events
              (serve/autoscale.py, check_autoscale)
  journal     (invariant #9) the clean resume's post-run rebuild audit
              — the ``journal`` op="verify" record — shows the
              trainer's topo_generation at the NOMINAL delta count
              (every scheduled delta applied exactly once through any
              composition of WAL replay, plan re-derivation, and live
              delivery) and the patched device tables digest-matching
              a from-scratch ShardedGraph.build (stream/journal.py)
  resume      the final clean ``--resume`` exits 0 and reaches
              n_epochs
  diagnosis   the automated postmortem (obs/postmortem.py) over the
              episode dir reaches the RIGHT verdict: ``clean-exit``
              when the first five invariants are green (every injected
              fault was recovered and the resume completed), or a
              class consistent with the injected schedule when they
              are red — every red episode must yield an explained
              black-box bundle, not just a pile of artifacts. The run
              summary reports ``diagnosis_accuracy`` (matched
              fraction across episodes).

Schedule composition rules (all deterministic per episode seed):

  * terminal kinds (kill / sigterm / crash) land only on checkpoint-
    boundary epochs, so the boundary-kind retirement in FaultPlan
    .skip_before stops them from re-firing forever on resume — every
    terminal fault costs exactly one restart budget unit (plus one
    more when a corrupt-ckpt forces the loader one generation back)
  * the streaming delta epoch is UNCONSTRAINED: the write-ahead delta
    journal (stream/journal.py) makes deltas durable before they are
    applied, and every resume path replays seqs at-or-under the
    checkpoint watermark before training continues — so a delta may
    land before, between, or after restart boundaries (the PR-14
    "after the last terminal epoch" rule is retired)
  * hang / desync / replica-kill / rejoin are excluded from the
    default pool — the episodes run one member (streaming is single-
    process), where those kinds either stall on the watchdog horizon
    or are inert; force them via ``force_faults`` when running a
    multi-member config
  * the storage kinds (resilience/storage.py) ride the same grammar;
    ``force_faults=("enospc@4",)`` is the acceptance proof that the
    previous checkpoint generation stays loadable and the re-drained
    metrics records survive

Each episode emits a schema-contracted ``soak`` record
(obs/schema.py) and the run writes ``soak-seed<seed>.json`` next to
the episode dirs.
"""

from __future__ import annotations

import dataclasses
import glob
import json
import os
import random
import shutil
import subprocess
import sys
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from .storage import IO_KINDS

# terminal kinds end the generation; the supervisor relaunches
TERMINAL_KINDS = ("kill", "sigterm", "crash")
# in-process kinds: the run recovers without a restart (slow-rank is
# a pure perturbation — a host-side sleep at one dispatch boundary
# that the training-span plane must attribute, obs/trainspan.py)
SOFT_KINDS = ("nan-loss", "kernel-crash", "corrupt-ckpt",
              "graph-delta", "slow-rank", "journal-torn") + IO_KINDS

_REPO = os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))


@dataclasses.dataclass(frozen=True)
class SoakConfig:
    """One soak run: `episodes` episodes derived from `seed`."""

    seed: int = 0
    episodes: int = 5
    n_epochs: int = 8
    n_parts: int = 2
    checkpoint_every: int = 2
    out_dir: str = os.path.join("results", "soak")
    dataset: str = "synthetic:300:6:8:3"
    # entries prepended VERBATIM to every episode's schedule (e.g.
    # ("enospc@4",) for the storage-fault acceptance proof)
    force_faults: Tuple[str, ...] = ()
    # adds the serving-fleet ticket-conservation drill to each episode
    serve: bool = False
    # adds the closed-loop autoscale drill: flash-crowd traffic over a
    # 1-replica fleet with --autoscale, one replica-kill and one mid-
    # crowd net-partition; invariant #7 (check_autoscale) demands the
    # replica-count trajectory match the ledger's spawn/retire records
    # and ticket conservation hold through the scale events
    autoscale: bool = False
    # adds the silent-data-corruption drill: one seeded bitflip per
    # episode (random target class: params / carry / tables / halo)
    # with --enable-pipeline and --integrity-check-every; invariant #8
    # (check_integrity) demands every injected flip be detected within
    # the cadence, attributed to the right class, and the episode
    # still resume green
    integrity: bool = False
    integrity_every: int = 2
    max_restarts: int = 6
    episode_timeout_s: float = 900.0
    keep_dirs: bool = False  # keep green episode dirs for inspection


def episode_seed(cfg: SoakConfig, episode: int) -> int:
    return cfg.seed * 1000003 + episode


def compose_schedule(cfg: SoakConfig, episode: int) \
        -> Tuple[List[str], int]:
    """(fault entries, stream-delta epoch) for one episode — a pure
    function of (cfg.seed, episode), never of wall clock or pid."""
    rng = random.Random(episode_seed(cfg, episode))
    entries = list(cfg.force_faults)
    boundaries = list(range(cfg.checkpoint_every,
                            cfg.n_epochs - 1, cfg.checkpoint_every))
    n_term = rng.randint(0, min(2, len(boundaries)))
    term_epochs = sorted(rng.sample(boundaries, n_term))
    for b in term_epochs:
        entries.append(f"{rng.choice(TERMINAL_KINDS)}@{b}")
    for kind in rng.sample(SOFT_KINDS, rng.randint(1, 2)):
        if kind == "corrupt-ckpt":
            e = rng.choice(boundaries)
        else:
            e = rng.randrange(1, cfg.n_epochs - 1)
        if kind == "slow-fs":
            entries.append(f"slow-fs@{e}:{rng.choice((5, 20))}")
        elif kind == "slow-rank":
            # ms of injected dispatch-boundary straggle (slow-rank@E:ms)
            entries.append(f"slow-rank@{e}:{rng.choice((50, 200))}")
        else:
            entries.append(f"{kind}@{e}")
    if cfg.integrity:
        # drawn AFTER the base kinds so non-integrity schedules stay
        # bit-identical for a given seed; one flip per episode keeps
        # the per-process strike count below the quarantine threshold
        e = rng.randrange(1, cfg.n_epochs - 1)
        cls = rng.choice(("params", "carry", "tables", "halo"))
        entries.append(f"bitflip@{e}:{cls}")
    # delta placement is unconstrained: the WAL journal + watermark
    # replay make a delta before (or between) restart boundaries
    # exactly as recoverable as one after them
    stream_epoch = rng.randrange(1, cfg.n_epochs - 1)
    return entries, stream_epoch


# ---------------------------------------------------------------------
# invariants
# ---------------------------------------------------------------------


def _inv(ok: bool, **detail) -> Dict:
    return {"ok": bool(ok), **detail}


def check_checkpoint(ck_dir: str,
                     want_epoch: Optional[int] = None) -> Dict:
    """Newest digest-valid generation verifies; optionally it must sit
    at `want_epoch` (after the clean resume)."""
    from ..utils.checkpoint import CheckpointCorrupt, verify_checkpoint

    gens = sorted(glob.glob(os.path.join(ck_dir, "state-*.npz")),
                  reverse=True)
    if not gens:
        return _inv(False, error="no checkpoint generations on disk")
    for path in gens:
        try:
            epoch = verify_checkpoint(path)
        except CheckpointCorrupt as exc:
            # a corrupt-ckpt fault may leave the newest torn; the walk
            # below must find a valid older generation
            last_err = repr(exc)
            continue
        ok = want_epoch is None or epoch == want_epoch
        return _inv(ok, path=os.path.basename(path), epoch=epoch,
                    **({} if ok else {"error": f"epoch {epoch} != "
                                               f"{want_epoch}"}))
    return _inv(False, error=f"every generation corrupt ({last_err})")


def check_ledger(coord_dir: str) -> Dict:
    """Generations contiguous from 0, every record CRC-clean."""
    from .elastic import LedgerCorrupt, MembershipLedger

    led = MembershipLedger(coord_dir)
    gens = led.generations()
    if gens != list(range(len(gens))) or not gens:
        return _inv(False, generations=gens,
                    error="generations not contiguous from 0")
    prev = -1
    for g in gens:
        try:
            rec = led.read(g)
        except LedgerCorrupt as exc:
            return _inv(False, generations=gens, error=repr(exc))
        if rec["generation"] <= prev:
            return _inv(False, generations=gens,
                        error=f"generation {g} not monotonic")
        prev = rec["generation"]
    return _inv(True, generations=gens)


def check_metrics(paths: Sequence[str], n_epochs: int) -> Dict:
    """Every line parses (one torn FINAL line per file tolerated —
    SIGKILL lands mid-write) and epoch records cover 0..n_epochs-1."""
    seen: set = set()
    torn = 0
    n_files = 0
    for path in paths:
        if not os.path.exists(path):
            continue
        n_files += 1
        with open(path, encoding="utf-8") as fh:
            lines = fh.read().splitlines()
        for i, line in enumerate(lines):
            if not line.strip():
                continue
            try:
                rec = json.loads(line)
            except json.JSONDecodeError:
                if i == len(lines) - 1:
                    torn += 1  # the one legal wound
                    continue
                return _inv(False, file=os.path.basename(path),
                            error=f"unparseable line {i + 1} (not the "
                                  f"file tail)")
            if rec.get("event") == "epoch":
                seen.add(int(rec["epoch"]))
    if not n_files:
        return _inv(False, error="no metrics files found")
    missing = sorted(set(range(n_epochs)) - seen)
    return _inv(not missing, files=n_files, torn_tails=torn,
                epochs_seen=len(seen),
                **({"missing": missing} if missing else {}))


# injected fault kind -> postmortem verdict classes that correctly
# explain it (obs/postmortem.py). Several kinds legitimately map to
# more than one class: a SIGKILL'd member leaves either a generic
# crash picture or (when a peer's watchdog dumped first) a
# wedged-collective one.
_KIND_TO_CLASS: Dict[str, Tuple[str, ...]] = {
    "corrupt-ckpt": ("corrupt-artifact",),
    "nan-loss": ("divergence",),
    "kernel-crash": ("fallback-exhausted", "crash"),
    "hang": ("wedged-collective",),
    "desync": ("desync",),
    "enospc": ("storage-fault",),
    "torn-write": ("storage-fault", "corrupt-artifact"),
    "ro-dir": ("storage-fault",),
    "slow-fs": ("storage-fault",),
    "kill": ("crash", "wedged-collective", "preemption"),
    "sigterm": ("preemption", "crash"),
    "crash": ("crash", "preemption"),
    "bitflip": ("sdc",),
    # a torn journal tail alone is recoverable (replay falls back to
    # the plan's delta files); if the episode still went red, the
    # rollback picture is the consistent explanation
    "journal-torn": ("topo-rollback", "crash"),
}


def expected_classes(schedule: Sequence[str]) -> List[str]:
    """Postmortem verdicts that would correctly explain a red episode
    running `schedule` (sorted; never empty — an unscheduled death is
    still a crash)."""
    out: set = set()
    for entry in schedule:
        out.update(_KIND_TO_CLASS.get(entry.split("@", 1)[0], ()))
    return sorted(out) if out else ["crash"]


def check_diagnosis(ep_dir: str, pre_verdict: str,
                    schedule: Sequence[str]) -> Dict:
    """Invariant #6: the automated postmortem over the episode dir
    reaches the right verdict — ``clean-exit`` on a green episode
    (dumps from recovered faults must NOT outrank the completed
    resume), a schedule-consistent class on a red one."""
    try:
        from ..obs.postmortem import diagnose_run

        diag = diagnose_run(ep_dir)
    except Exception as exc:  # noqa: BLE001
        return _inv(False, error=f"postmortem failed: {exc!r}")
    expected = (["clean-exit"] if pre_verdict == "green"
                else expected_classes(schedule))
    ok = diag["verdict"] in expected
    return _inv(ok, verdict=diag["verdict"],
                confidence=round(float(diag["confidence"]), 3),
                deterministic=diag["deterministic"],
                expected=expected,
                **({} if ok else
                   {"error": f"verdict {diag['verdict']!r} not in "
                             f"{expected}",
                    "evidence": list(diag["evidence"])[:4]}))


def check_tickets(fleet_summary: Optional[Dict]) -> Dict:
    """Zero accepted tickets lost in the serving drill (skipped —
    vacuously green — when the episode did not serve)."""
    if fleet_summary is None:
        return _inv(True, skipped=True)
    ok = (fleet_summary.get("conserved") is True
          and fleet_summary.get("drained") is True
          and fleet_summary.get("n_submitted")
          == fleet_summary.get("n_served", 0)
          + fleet_summary.get("n_shed", 0))
    return _inv(ok, conserved=fleet_summary.get("conserved"),
                drained=fleet_summary.get("drained"),
                n_submitted=fleet_summary.get("n_submitted"),
                n_served=fleet_summary.get("n_served"),
                n_shed=fleet_summary.get("n_shed"))


def check_autoscale(fleet_summary: Optional[Dict],
                    fleet_jsonl: str,
                    initial_replicas: int = 1) -> Dict:
    """Invariant #7 (``autoscale`` episodes): the replica-count
    trajectory is explained by the ledger — every ``spawn``/``retire``
    fleet record pairs with a ``scale-up``/``scale-down`` autoscale
    decision record, the final active count equals
    ``initial + spawns - retires``, the flash crowd provoked at least
    one scale-up, and ticket conservation held through the scale
    events. Vacuously green when the episode did not run the drill."""
    if fleet_summary is None:
        return _inv(False, error="autoscale drill crashed (no summary)")
    spawns = retires = ups = downs = 0
    if os.path.exists(fleet_jsonl):
        with open(fleet_jsonl, encoding="utf-8") as fh:
            for line in fh:
                try:
                    rec = json.loads(line)
                except json.JSONDecodeError:
                    continue
                ev = rec.get("event")
                if ev == "fleet":
                    if rec.get("kind") == "spawn":
                        spawns += 1
                    elif rec.get("kind") == "retire":
                        retires += 1
                elif ev == "autoscale":
                    if rec.get("action") == "scale-up":
                        ups += 1
                    elif rec.get("action") == "scale-down":
                        downs += 1
    detail = dict(spawns=spawns, retires=retires,
                  decisions_up=ups, decisions_down=downs,
                  n_spawned=fleet_summary.get("n_spawned"),
                  n_retired=fleet_summary.get("n_retired"),
                  replicas_active=fleet_summary.get("replicas_active"),
                  conserved=fleet_summary.get("conserved"),
                  drained=fleet_summary.get("drained"),
                  n_submitted=fleet_summary.get("n_submitted"),
                  n_served=fleet_summary.get("n_served"),
                  n_shed=fleet_summary.get("n_shed"))
    errors = []
    if spawns != fleet_summary.get("n_spawned"):
        errors.append(f"ledger spawns {spawns} != summary "
                      f"{fleet_summary.get('n_spawned')}")
    if retires != fleet_summary.get("n_retired"):
        errors.append(f"ledger retires {retires} != summary "
                      f"{fleet_summary.get('n_retired')}")
    if ups != spawns:
        errors.append(f"scale-up decisions {ups} != spawns {spawns}")
    if downs != retires:
        errors.append(f"scale-down decisions {downs} != retires "
                      f"{retires}")
    want = initial_replicas + spawns - retires
    if fleet_summary.get("replicas_active") != want:
        errors.append(f"replicas_active "
                      f"{fleet_summary.get('replicas_active')} != "
                      f"{initial_replicas} + {spawns} - {retires}")
    if spawns < 1:
        errors.append("flash crowd provoked no scale-up")
    if not (fleet_summary.get("conserved") is True
            and fleet_summary.get("drained") is True
            and fleet_summary.get("n_submitted")
            == fleet_summary.get("n_served", 0)
            + fleet_summary.get("n_shed", 0)):
        errors.append("tickets not conserved through scale events")
    return _inv(not errors, **detail,
                **({"error": "; ".join(errors)} if errors else {}))


def check_integrity(metric_files: Sequence[str],
                    schedule: Sequence[str],
                    cadence: int) -> Dict:
    """Invariant #8 (``integrity`` episodes): every scheduled bitflip
    actually fired (an episode that completes to n_epochs must have
    crossed the injection epoch in some generation), and every
    ``fault kind=injected reason=bitflip:<class>`` record has a
    matching detection — an ``integrity`` mismatch record or an
    ``sdc`` fault record naming the SAME target class — within
    ``cadence`` epochs of the injection. Vacuously green when the
    schedule holds no bitflips."""
    scheduled = [e for e in schedule if e.startswith("bitflip@")]
    if not scheduled:
        return _inv(True, skipped=True)
    injected: List[Tuple[int, str]] = []
    detected: List[Tuple[int, str]] = []
    for path in metric_files:
        if not os.path.exists(path):
            continue
        with open(path, encoding="utf-8") as fh:
            for line in fh:
                try:
                    rec = json.loads(line)
                except json.JSONDecodeError:
                    continue
                ev = rec.get("event")
                if (ev == "fault" and rec.get("kind") == "injected"
                        and str(rec.get("reason", ""))
                        .startswith("bitflip:")):
                    injected.append((int(rec.get("epoch", -1)),
                                     str(rec["reason"]).split(":", 1)[1]))
                elif ev == "integrity" and rec.get("outcome") == "mismatch":
                    detected.append((int(rec.get("epoch", -1)),
                                     str(rec.get("target") or "")))
                elif ev == "fault" and rec.get("kind") == "sdc":
                    detected.append((int(rec.get("epoch", -1)),
                                     str(rec.get("target") or "")))
    errors = []
    fired_classes = {cls for _, cls in injected}
    for entry in scheduled:
        cls = entry.rsplit(":", 1)[-1]
        if cls not in fired_classes:
            errors.append(f"scheduled {entry} never injected")
    for e, cls in injected:
        hit = any(dcls == cls and e <= de <= e + max(cadence, 1)
                  for de, dcls in detected)
        if not hit:
            errors.append(f"bitflip:{cls}@{e} undetected within "
                          f"cadence {cadence}")
    return _inv(not errors, scheduled=list(scheduled),
                injected=sorted(set(injected)),
                detected=sorted(set(detected))[:8],
                **({"error": "; ".join(errors)} if errors else {}))


def check_journal(resume_metrics: str, n_batches: int) -> Dict:
    """Invariant #9 (journaled streaming): the clean resume's post-run
    rebuild audit — the ``journal`` op="verify" record in the resume
    metrics stream — reports the trainer's topo_generation at the
    NOMINAL delta count (every scheduled delta applied exactly once,
    whether by WAL replay, plan re-derivation after a torn tail, or
    live delivery) and ``tables_match`` true: the patched device
    tables are bitwise-identical to a from-scratch rebuild."""
    if not os.path.exists(resume_metrics):
        return _inv(False, error="no resume metrics stream")
    verify = None
    replayed = truncated = 0
    with open(resume_metrics, encoding="utf-8") as fh:
        for line in fh:
            try:
                rec = json.loads(line)
            except json.JSONDecodeError:
                continue
            if rec.get("event") != "journal":
                continue
            op = rec.get("op")
            if op == "verify":
                verify = rec
            elif op == "replay":
                replayed += (int(rec.get("n_records", 0))
                             + int(rec.get("rederived", 0)))
            elif op == "truncate":
                truncated += int(rec.get("n_records", 0))
    if verify is None:
        return _inv(False,
                    error="no journal verify record in the resume "
                          "stream (journaled resume did not run)")
    errors = []
    if verify.get("tables_match") is not True:
        errors.append(f"device tables diverge from rebuild: "
                      f"{verify.get('mismatch')}")
    if int(verify.get("topo_generation", -1)) != n_batches:
        errors.append(f"topo_generation "
                      f"{verify.get('topo_generation')} != nominal "
                      f"{n_batches}")
    return _inv(not errors,
                topo_generation=verify.get("topo_generation"),
                tables_match=verify.get("tables_match"),
                replayed=replayed, truncated=truncated,
                **({"error": "; ".join(errors)} if errors else {}))


# ---------------------------------------------------------------------
# episode driver
# ---------------------------------------------------------------------


def _episode_env() -> Dict[str, str]:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    env["PYTHONPATH"] = _REPO + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    env["PYTHONUNBUFFERED"] = "1"
    return env


def _train_argv(cfg: SoakConfig, ep_dir: str, delta_path: str,
                stream_epoch: int) -> List[str]:
    argv = [
        "--dataset", cfg.dataset,
        "--n-partitions", str(cfg.n_parts),
        "--parts-per-node", str(cfg.n_parts),  # one member: streaming
        #                                        is single-process
        "--n-epochs", str(cfg.n_epochs),
        "--n-hidden", "8", "--dropout", "0.0",
        "--log-every", "1000", "--no-eval",
        "--fix-seed", "--seed", "7",
        "--local-reorder", "none",
        "--partition-dir", os.path.join(ep_dir, "parts"),
        "--checkpoint-dir", os.path.join(ep_dir, "ck"),
        "--checkpoint-every", str(cfg.checkpoint_every),
        "--checkpoint-keep", "0",  # keep every generation: the
        #                            invariants audit the full history
        "--stream-plan", f"{delta_path}@{stream_epoch}",
        "--metrics-out", os.path.join(ep_dir, "metrics.jsonl"),
    ]
    if cfg.integrity:
        # pipeline on so the carry/halo target classes are injectable
        argv += ["--enable-pipeline",
                 "--integrity-check-every", str(cfg.integrity_every)]
    return argv


def _write_delta_file(cfg: SoakConfig, episode: int, path: str) -> None:
    """One small CRC-guarded delta batch, deterministic per episode.
    The base graph comes from the same dataset string the episode
    trains on (synthetic loads are seed-stable), so the batch is valid
    against every generation's rebuild of the graph."""
    from ..graph import load_data
    from ..graph.synthetic import synthetic_delta_schedule
    from ..stream.deltas import save_deltas

    g = load_data(cfg.dataset)
    batches = synthetic_delta_schedule(
        g, n_batches=1, edges_per_batch=4, dels_per_batch=2,
        nodes_per_batch=1, seed=episode_seed(cfg, episode))
    save_deltas(path, batches)


def _run_fleet_drill(cfg: SoakConfig, episode: int, ep_dir: str,
                     log: Callable[[str], None]) -> Optional[Dict]:
    """Short serving-fleet load drill; returns the driver's summary
    dict (None on a crash, which check_tickets turns red)."""
    rng = random.Random(episode_seed(cfg, episode) ^ 0x5EA5)
    cmd = [
        sys.executable, "-m", "pipegcn_tpu.cli.fleet",
        "--dataset", cfg.dataset, "--n-partitions", str(cfg.n_parts),
        "--n-hidden", "8", "--fix-seed",
        "--partition-dir", os.path.join(ep_dir, "parts-serve"),
        "--serve-build", "--replicas", "2", "--fleet-policy", "hash",
        "--serve-duration", "6", "--serve-qps", "40",
        "--serve-report-every", "0.5",
        "--metrics-out", os.path.join(ep_dir, "fleet.jsonl"),
    ]
    if rng.random() < 0.5:
        cmd += ["--fault-plan", "replica-kill@2:m1",
                "--fleet-retry-timeout", "15"]
    env = _episode_env()
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    try:
        proc = subprocess.run(cmd, env=env, cwd=_REPO,
                              timeout=cfg.episode_timeout_s,
                              capture_output=True, text=True)
    except subprocess.TimeoutExpired:
        log("  fleet drill timed out")
        return None
    tails = [ln for ln in proc.stdout.splitlines()
             if '"fleet": true' in ln]
    if proc.returncode != 0 or not tails:
        log(f"  fleet drill rc={proc.returncode}, no summary")
        return None
    return json.loads(tails[-1])


def _run_autoscale_drill(cfg: SoakConfig, episode: int, ep_dir: str,
                         log: Callable[[str], None]) -> Optional[Dict]:
    """Closed-loop autoscale drill: a 1-replica fleet under a
    flash-crowd arrival schedule with --autoscale, plus one
    replica-kill (the lone replica, pre-crowd — queue pressure during
    the relaunch is what provokes the scale-up) and one mid-crowd
    net-partition. Windows are 0.5 s wide; the kill/partition windows
    are drawn deterministically from the episode seed. Returns the
    driver's summary dict (None on a crash, which check_autoscale
    turns red)."""
    rng = random.Random(episode_seed(cfg, episode) ^ 0xA5CA)
    kill_w = rng.choice((2, 3))        # t ~ 1.0-2.0 s, before the crowd
    part_w = rng.choice((7, 8))        # t ~ 3.5-4.5 s, mid-crowd
    faults = (f"replica-kill@{kill_w}:m0,"
              f"net-partition@{part_w}:m0:1")
    cmd = [
        sys.executable, "-m", "pipegcn_tpu.cli.fleet",
        "--dataset", cfg.dataset, "--n-partitions", str(cfg.n_parts),
        "--n-hidden", "8", "--fix-seed",
        "--partition-dir", os.path.join(ep_dir, "parts-serve"),
        "--serve-build", "--replicas", "1",
        "--autoscale", "--autoscale-max", "3",
        "--autoscale-cooldown", "1.5",
        "--traffic", "flash-crowd:4:0.25:0.625",
        "--serve-duration", "8", "--serve-qps", "30",
        "--serve-max-batch", "32", "--serve-max-queue", "96",
        "--serve-report-every", "0.5",
        "--fault-plan", faults,
        "--fleet-retry-timeout", "20",
        "--metrics-out", os.path.join(ep_dir, "autoscale.jsonl"),
    ]
    log(f"  autoscale drill: kill@{kill_w} partition@{part_w}")
    env = _episode_env()
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    try:
        proc = subprocess.run(cmd, env=env, cwd=_REPO,
                              timeout=cfg.episode_timeout_s,
                              capture_output=True, text=True)
    except subprocess.TimeoutExpired:
        log("  autoscale drill timed out")
        return None
    tails = [ln for ln in proc.stdout.splitlines()
             if '"fleet": true' in ln]
    if proc.returncode != 0 or not tails:
        log(f"  autoscale drill rc={proc.returncode}, no summary")
        log(f"  tail:\n{(proc.stdout + proc.stderr)[-1500:]}")
        return None
    return json.loads(tails[-1])


def run_episode(cfg: SoakConfig, episode: int,
                log: Callable[[str], None] = print) -> Dict:
    """Run one episode end-to-end and return its soak record body."""
    schedule, stream_epoch = compose_schedule(cfg, episode)
    ep_dir = os.path.abspath(os.path.join(
        cfg.out_dir, f"ep{cfg.seed:04d}-{episode:03d}"))
    shutil.rmtree(ep_dir, ignore_errors=True)
    os.makedirs(ep_dir)
    delta_path = os.path.join(ep_dir, "deltas.jsonl")
    _write_delta_file(cfg, episode, delta_path)
    argv = _train_argv(cfg, ep_dir, delta_path, stream_epoch)
    log(f"episode {episode}: faults={schedule} "
        f"stream@{stream_epoch}")

    env = _episode_env()
    sup_cmd = [
        sys.executable, "-m", "pipegcn_tpu.cli.elastic",
        "--max-restarts", str(cfg.max_restarts),
        "--backoff-base", "0.1",
        "--metrics-out", os.path.join(ep_dir, "sup.jsonl"),
        "--", *argv,
    ]
    if schedule:
        sup_cmd += ["--fault-plan", ",".join(schedule)]
    try:
        sup = subprocess.run(sup_cmd, env=env, cwd=_REPO,
                             timeout=cfg.episode_timeout_s,
                             capture_output=True, text=True)
        sup_rc: Optional[int] = sup.returncode
        sup_tail = (sup.stdout + sup.stderr)[-2000:]
    except subprocess.TimeoutExpired as exc:
        sup_rc, sup_tail = None, f"TIMEOUT: {exc}"
    log(f"  supervised phase rc={sup_rc}")

    # final clean resume: no fault plan, fresh metrics file
    resume_argv = [a for a in argv]
    mi = resume_argv.index("--metrics-out")
    resume_metrics = os.path.join(ep_dir, "metrics-resume.jsonl")
    resume_argv[mi + 1] = resume_metrics
    res_cmd = [sys.executable, "-m", "pipegcn_tpu.cli.main",
               *resume_argv, "--resume"]
    try:
        res = subprocess.run(res_cmd, env=env, cwd=_REPO,
                             timeout=cfg.episode_timeout_s,
                             capture_output=True, text=True)
        res_rc: Optional[int] = res.returncode
        res_tail = (res.stdout + res.stderr)[-2000:]
    except subprocess.TimeoutExpired as exc:
        res_rc, res_tail = None, f"TIMEOUT: {exc}"
    log(f"  clean resume rc={res_rc}")

    fleet_summary = (_run_fleet_drill(cfg, episode, ep_dir, log)
                     if cfg.serve else None)
    autoscale_summary = (_run_autoscale_drill(cfg, episode, ep_dir, log)
                         if cfg.autoscale else None)

    ck_dir = os.path.join(ep_dir, "ck")
    coord_dir = os.path.join(ep_dir, "parts", "coord-elastic")
    metric_files = sorted(glob.glob(
        os.path.join(ep_dir, "metrics*.jsonl")))
    invariants = {
        "checkpoint": check_checkpoint(ck_dir, want_epoch=cfg.n_epochs),
        "ledger": check_ledger(coord_dir),
        "metrics": check_metrics(metric_files, cfg.n_epochs),
        "tickets": (check_tickets(fleet_summary) if cfg.serve
                    else _inv(True, skipped=True)),
        "autoscale": (check_autoscale(
            autoscale_summary, os.path.join(ep_dir, "autoscale.jsonl"))
            if cfg.autoscale else _inv(True, skipped=True)),
        # invariant #8: every injected bitflip detected within cadence,
        # attributed to the right target class
        "integrity": (check_integrity(metric_files, schedule,
                                      cfg.integrity_every)
                      if cfg.integrity else _inv(True, skipped=True)),
        # invariant #9: post-resume topo_generation at nominal, device
        # tables digest-match a from-scratch rebuild (one delta batch
        # per episode, see _write_delta_file)
        "journal": check_journal(resume_metrics, n_batches=1),
        "resume": _inv(res_rc == 0,
                       rc=res_rc,
                       **({} if res_rc == 0
                          else {"tail": res_tail[-500:]})),
    }
    # invariant #6 rides on the other five's verdict (green episodes
    # must diagnose clean-exit, red ones a schedule-consistent class)
    # and must run BEFORE the green-episode dir cleanup below
    pre_verdict = ("green" if all(v["ok"] for v in invariants.values())
                   else "red")
    invariants["diagnosis"] = check_diagnosis(ep_dir, pre_verdict,
                                              schedule)
    verdict = ("green" if all(v["ok"] for v in invariants.values())
               else "red")
    for name, v in invariants.items():
        log(f"  invariant {name}: {'ok' if v['ok'] else 'RED ' + str(v)}")
    if verdict == "red":
        log(f"  supervised tail:\n{sup_tail}")
    elif not cfg.keep_dirs:
        shutil.rmtree(ep_dir, ignore_errors=True)
    return {
        "episode": episode,
        "seed": episode_seed(cfg, episode),
        "schedule": list(schedule),
        "stream_epoch": stream_epoch,
        "supervised_rc": sup_rc,
        "invariants": invariants,
        "verdict": verdict,
    }


def run_soak(cfg: SoakConfig,
             log: Callable[[str], None] = print) -> Dict:
    """Run every episode, write the soak JSONL + summary JSON, return
    the summary (verdict 'green' iff every episode is green)."""
    from ..obs.metrics import MetricsLogger

    os.makedirs(cfg.out_dir, exist_ok=True)
    records = []
    soak_jsonl = os.path.join(cfg.out_dir,
                              f"soak-seed{cfg.seed}.jsonl")
    m = MetricsLogger(soak_jsonl)
    try:
        for i in range(cfg.episodes):
            rec = run_episode(cfg, i, log=log)
            records.append(rec)
            m.soak(episode=rec["episode"], seed=rec["seed"],
                   schedule=rec["schedule"],
                   invariants=rec["invariants"],
                   verdict=rec["verdict"],
                   supervised_rc=rec["supervised_rc"])
    finally:
        m.close()
    verdict = ("green" if records and
               all(r["verdict"] == "green" for r in records)
               else "red")
    # fraction of episodes whose automated postmortem matched the
    # expected class (invariant #6) — the headline forensics number
    diag_ok = [bool(r["invariants"].get("diagnosis", {}).get("ok"))
               for r in records]
    summary = {"seed": cfg.seed, "episodes": records,
               "n_episodes": len(records), "verdict": verdict,
               "diagnosis_accuracy": (round(sum(diag_ok)
                                            / len(diag_ok), 4)
                                      if diag_ok else None)}
    out = os.path.join(cfg.out_dir, f"soak-seed{cfg.seed}.json")
    from .storage import write_text_atomic

    write_text_atomic(out, json.dumps(summary, indent=1), fsync=False)
    log(f"soak seed {cfg.seed}: {len(records)} episode(s), "
        f"verdict {verdict}, diagnosis accuracy "
        f"{summary['diagnosis_accuracy']} -> {out}")
    return summary
