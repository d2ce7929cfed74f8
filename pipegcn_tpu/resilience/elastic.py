"""Elastic membership: train THROUGH rank loss, not just survive it.

PRs 2-3 made every rank exit 75 with a digest-verified checkpoint when
a peer dies; a human still had to notice and relaunch with the SAME
world size. This module closes the loop with a supervisor
(``python -m pipegcn_tpu.cli.elastic -- <train flags>``) that

  1. launches the rank processes of a multi-host run,
  2. watches for death (SIGKILL/OOM/crash), resumable exits (75) and
     completion (0),
  3. on a membership change computes a new partition->rank assignment
     (P partitions over the R' survivors, each process owning
     ceil(P/R') shards through the existing multi-shard SPMD
     machinery — a node's mesh slice is just "more local devices"),
  4. relaunches the survivors from the last good checkpoint
     generation. The dead rank's comm carry needs NO explicit remap:
     checkpoints always hold the FULL [P, ...] carry (host_state's
     allgather), and ``Trainer.restore_state`` re-device_puts it under
     the NEW mesh's shardings, so partition i's rows land on whoever
     owns partition i now.

Membership is durable: a CRC-guarded ``membership-<gen>.json`` ledger
in the coord dir records every generation (members, assignment,
trigger, restart latency). The generation counter is monotonic across
supervisor restarts — a new supervisor resumes at latest+1 with the
last recorded membership. Rejoin is ledger-driven too: a returning
rank drops a ``rejoin-r<k>.json`` request (or the fault plan schedules
``rejoin@G``) and the supervisor folds it into the next generation's
assignment, rebalancing shards back.

Crash-looping fleets degrade gracefully instead of thrashing:
exponential backoff between relaunches, a hard ``--max-restarts`` cap,
and a restart-storm circuit breaker (too many restarts inside a
sliding window). Both stop paths leave the last resumable checkpoint
untouched and exit 75 so an outer scheduler can still resume later.

Every member death is additionally DIAGNOSED (obs/postmortem.py runs
over the coord dir's black-box dumps, rank logs and metrics streams);
the verdict rides the next ledger generation and the membership
metrics record. Deterministic verdict classes — corrupt-artifact,
config-error, fallback-exhausted, failures a relaunch reproduces —
get ONE gated retry and then stop the supervisor hard (rc 1, not 75)
instead of burning ``--max-restarts`` (docs/RESILIENCE.md "Fail fast
vs restart").
"""

from __future__ import annotations

import dataclasses
import glob
import json
import math
import os
import signal
import subprocess
import sys
import time
import zlib
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from .preemption import EXIT_PREEMPTED, classify_exit
from .storage import IO_DEGRADED, write_text_atomic

# env vars the supervisor sets on every child; cli/main.py reads the
# generation into CoordConfig so heartbeat files are generation-keyed
# (stale-heartbeat poisoning fix) and MEMBER tells a relaunched process
# which ledger identity it carries (node ranks are re-dealt per gen)
GENERATION_ENV = "PIPEGCN_MEMBERSHIP_GEN"
MEMBER_ENV = "PIPEGCN_ELASTIC_MEMBER"

LEDGER_PREFIX = "membership-"
REJOIN_PREFIX = "rejoin-r"


# ---------------------------------------------------------------------------
# assignment math
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Assignment:
    """Partition->member mapping for one membership generation.

    ``members`` is the sorted member-id list; the first ``n_nodes`` of
    them get node ranks 0..n_nodes-1 (the contiguous-block ownership
    the mesh construction implies: node i owns partitions
    [i*parts_per_node, min((i+1)*parts_per_node, n_parts))). Members
    beyond ``n_nodes`` are idle spares this generation — they exist
    when ceil-division needs fewer nodes than there are members.
    """

    n_parts: int
    members: Tuple[int, ...]
    parts_per_node: int
    n_nodes: int

    def node_rank_of(self, member: int) -> Optional[int]:
        """Node rank this member runs at, None when idle this gen."""
        i = self.members.index(member)
        return i if i < self.n_nodes else None

    def parts_of_node(self, node_rank: int) -> Tuple[int, ...]:
        lo = node_rank * self.parts_per_node
        hi = min(lo + self.parts_per_node, self.n_parts)
        return tuple(range(lo, hi))

    def active_members(self) -> Tuple[int, ...]:
        return self.members[: self.n_nodes]

    def as_json(self) -> Dict[str, object]:
        """JSON shape recorded in the ledger and the `membership`
        metrics record (docs/OBSERVABILITY.md schema v6)."""
        return {
            "n_parts": self.n_parts,
            "parts_per_node": self.parts_per_node,
            "n_nodes": self.n_nodes,
            "members": list(self.members),
            "parts": {str(m): list(self.parts_of_node(i))
                      for i, m in enumerate(self.active_members())},
            "idle": list(self.members[self.n_nodes:]),
        }


def plan_assignment(n_parts: int, members: Sequence[int]) -> Assignment:
    """P partitions over the surviving members: each active node owns
    ceil(P/R') contiguous partitions. Contiguity is load-bearing, not
    cosmetic — ``make_mesh`` assigns the first P devices in
    process-major order, so node i's local devices ARE partitions
    [i*k, (i+1)*k) and the v3 mmap artifact's per-rank edge files can
    be opened without any shuffle."""
    ms = sorted(set(int(m) for m in members))
    if not ms:
        raise ValueError("cannot plan an assignment with zero members")
    if n_parts < 1:
        raise ValueError(f"n_parts must be >= 1, got {n_parts}")
    k = math.ceil(n_parts / len(ms))
    n_nodes = math.ceil(n_parts / k)
    return Assignment(n_parts=int(n_parts), members=tuple(ms),
                      parts_per_node=k, n_nodes=n_nodes)


# ---------------------------------------------------------------------------
# durable membership ledger
# ---------------------------------------------------------------------------

class LedgerCorrupt(RuntimeError):
    """A membership record failed its CRC or JSON parse."""


def _crc_of(payload: Dict) -> int:
    blob = json.dumps(payload, sort_keys=True,
                      separators=(",", ":")).encode("utf-8")
    return zlib.crc32(blob) & 0xFFFFFFFF


class MembershipLedger:
    """CRC-guarded ``membership-<gen>.json`` records in the coord dir.

    One file per generation, written atomically (tmp + rename) as
    ``{"crc32": ..., "payload": {...}}`` where the CRC covers the
    canonical-JSON payload bytes. Generations are monotonic: a write
    must strictly exceed the latest on-disk generation, ACROSS
    supervisor restarts — the counter lives in the filenames, not in
    any process.

    Rejoin requests ride the same directory: ``rejoin-r<k>.json``,
    dropped by a returning rank (or the fault plan's ``rejoin@G``
    schedule) and consumed by the supervisor at the next membership
    event.
    """

    def __init__(self, directory: str):
        self.dir = directory
        os.makedirs(directory, exist_ok=True)

    def path_for(self, generation: int) -> str:
        return os.path.join(self.dir, f"{LEDGER_PREFIX}{generation:06d}.json")

    def generations(self) -> List[int]:
        out = []
        for p in glob.glob(os.path.join(self.dir, LEDGER_PREFIX + "*.json")):
            stem = os.path.basename(p)[len(LEDGER_PREFIX):-len(".json")]
            try:
                out.append(int(stem))
            except ValueError:
                continue
        return sorted(out)

    def latest_generation(self) -> int:
        gens = self.generations()
        return gens[-1] if gens else -1

    def read(self, generation: int) -> Dict:
        path = self.path_for(generation)
        try:
            with open(path, "r", encoding="utf-8") as f:
                rec = json.load(f)
        except (OSError, ValueError) as exc:
            raise LedgerCorrupt(
                f"membership record {path} unreadable: {exc}") from exc
        payload = rec.get("payload")
        if not isinstance(payload, dict) or "crc32" not in rec:
            raise LedgerCorrupt(f"membership record {path} malformed")
        if int(rec["crc32"]) != _crc_of(payload):
            raise LedgerCorrupt(
                f"membership record {path} failed CRC "
                f"(stored {rec['crc32']}, computed {_crc_of(payload)})")
        return payload

    def latest(self) -> Optional[Dict]:
        """Newest record that passes its CRC, walking backwards past
        corrupt generations (same fallback discipline as checkpoint
        loading)."""
        for gen in reversed(self.generations()):
            try:
                return self.read(gen)
            except LedgerCorrupt:
                continue
        return None

    def append(self, *, generation: int, members: Sequence[int],
               assignment: Assignment, trigger: str,
               restart_latency_s: Optional[float] = None,
               diagnosis: Optional[Dict] = None) -> Dict:
        latest = self.latest_generation()
        if generation <= latest:
            raise ValueError(
                f"membership generation must be monotonic: {generation} "
                f"<= latest on-disk generation {latest}")
        payload = {
            "generation": int(generation),
            "members": sorted(int(m) for m in members),
            "assignment": assignment.as_json(),
            "trigger": str(trigger),
            "time_unix": time.time(),
        }
        if restart_latency_s is not None:
            payload["restart_latency_s"] = float(restart_latency_s)
        if diagnosis is not None:
            # the postmortem verdict that explains why this generation
            # exists (obs/postmortem.py) — slim form, evidence lives in
            # the metrics stream's diagnosis record
            payload["diagnosis"] = dict(diagnosis)
        rec = {"crc32": _crc_of(payload), "payload": payload}
        path = self.path_for(generation)
        # temp+rename through the storage-fault seams: a torn or failed
        # append leaves no membership-<gen>.json at all, so latest()
        # keeps answering with the previous durable generation
        write_text_atomic(path, json.dumps(rec, sort_keys=True))
        return payload

    # -- rejoin requests ---------------------------------------------------

    def rejoin_path(self, member: int) -> str:
        return os.path.join(self.dir, f"{REJOIN_PREFIX}{int(member)}.json")

    def request_rejoin(self, member: int) -> str:
        """Register a returning rank; the supervisor folds it into the
        next generation's assignment."""
        path = self.rejoin_path(member)
        write_text_atomic(
            path,
            json.dumps({"member": int(member), "time_unix": time.time()}),
            fsync=False)
        return path

    def pending_rejoins(self) -> List[int]:
        out = []
        for p in glob.glob(os.path.join(self.dir, REJOIN_PREFIX + "*.json")):
            stem = os.path.basename(p)[len(REJOIN_PREFIX):-len(".json")]
            try:
                out.append(int(stem))
            except ValueError:
                continue
        return sorted(out)

    def clear_rejoin(self, member: int) -> None:
        try:
            os.unlink(self.rejoin_path(member))
        except FileNotFoundError:
            pass


# ---------------------------------------------------------------------------
# restart policy: backoff + cap + storm circuit breaker
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class RestartDecision:
    action: str            # "restart" | "stop"
    delay_s: float = 0.0   # backoff before the relaunch
    reason: str = ""       # "max-restarts" | "restart-storm" on stop


class RestartPolicy:
    """Decides whether (and after how long) a membership event may
    relaunch the fleet. Three independent brakes:

      * exponential backoff: base * 2^(consecutive-1), capped; the
        consecutive counter resets once a generation survives
        ``stable_s`` (note_stable), so one long-lived fleet doesn't
        pay for last week's crash loop
      * hard cap: more than ``max_restarts`` total restarts -> stop
      * storm breaker: ``storm_threshold`` restarts inside a sliding
        ``storm_window_s`` -> stop, even below the hard cap — the
        signature of a config that kills every generation instantly

    Both stop paths are RESUMABLE stops: the supervisor exits 75 with
    the last good checkpoint intact.
    """

    def __init__(self, max_restarts: int = 8, backoff_base_s: float = 1.0,
                 backoff_max_s: float = 30.0, storm_window_s: float = 120.0,
                 storm_threshold: int = 5, stable_s: float = 60.0,
                 clock: Callable[[], float] = time.monotonic):
        self.max_restarts = int(max_restarts)
        self.backoff_base_s = float(backoff_base_s)
        self.backoff_max_s = float(backoff_max_s)
        self.storm_window_s = float(storm_window_s)
        self.storm_threshold = int(storm_threshold)
        self.stable_s = float(stable_s)
        self._clock = clock
        self.total = 0
        self.consecutive = 0
        self._recent: List[float] = []

    def note_stable(self, ran_s: float) -> None:
        """The last generation ran `ran_s` before its membership event;
        a long-enough run resets the backoff exponent (not the total
        cap — max_restarts bounds lifetime restarts)."""
        if ran_s >= self.stable_s:
            self.consecutive = 0

    def decide(self) -> RestartDecision:
        now = self._clock()
        self.total += 1
        self.consecutive += 1
        self._recent = [t for t in self._recent
                        if now - t <= self.storm_window_s]
        self._recent.append(now)
        if self.total > self.max_restarts:
            return RestartDecision("stop", reason="max-restarts")
        if len(self._recent) >= self.storm_threshold:
            return RestartDecision("stop", reason="restart-storm")
        delay = min(self.backoff_base_s * (2 ** (self.consecutive - 1)),
                    self.backoff_max_s)
        return RestartDecision("restart", delay_s=delay)


# ---------------------------------------------------------------------------
# supervisor
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class ElasticConfig:
    max_restarts: int = 8
    backoff_base_s: float = 1.0
    backoff_max_s: float = 30.0
    storm_window_s: float = 120.0
    storm_threshold: int = 5
    stable_s: float = 60.0
    poll_s: float = 0.25
    # extra seconds past the watchdog horizon to wait for survivors to
    # notice a dead peer and exit 75 on their own before being culled
    grace_extra_s: float = 60.0
    metrics_out: str = ""  # default: <coord_dir>/membership.jsonl


def _strip_flag(argv: List[str], flag: str, has_value: bool = True) -> List[str]:
    out, i = [], 0
    while i < len(argv):
        a = argv[i]
        if a == flag:
            i += 2 if has_value else 1
            continue
        if has_value and a.startswith(flag + "="):
            i += 1
            continue
        out.append(a)
        i += 1
    return out


def _flag_value(argv: List[str], flag: str) -> Optional[str]:
    for i, a in enumerate(argv):
        if a == flag and i + 1 < len(argv):
            return argv[i + 1]
        if a.startswith(flag + "="):
            return a.split("=", 1)[1]
    return None


def _member_metrics_path(base: str, generation: int, member: int) -> str:
    """Per-(generation, member) metrics file: a relaunch must never
    clobber a previous generation's epoch records — the drill's
    epoch-continuity check merges across all of them."""
    stem, ext = os.path.splitext(base)
    return f"{stem}.g{generation}.m{member}{ext or '.jsonl'}"


def _cpu_device_flags(env: Dict[str, str], parts_per_node: int) -> None:
    """On the CPU backend a 'node' gets its devices from
    --xla_force_host_platform_device_count; keep it in sync with the
    generation's parts_per_node (this IS the redistribution mechanism
    on the test mesh: fewer processes, more virtual devices each)."""
    if "cpu" not in env.get("JAX_PLATFORMS", ""):
        return
    kept = [t for t in env.get("XLA_FLAGS", "").split()
            if not t.startswith("--xla_force_host_platform_device_count")]
    kept.append(f"--xla_force_host_platform_device_count={parts_per_node}")
    env["XLA_FLAGS"] = " ".join(kept)


def _free_port() -> int:
    import socket

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class _Child:
    """One launched rank process plus its ledger identity."""

    def __init__(self, member: int, node_rank: int, handle, log_path: str):
        self.member = member
        self.node_rank = node_rank
        self.handle = handle
        self.log_path = log_path
        self.outcome: Optional[str] = None  # completed|resumable|dead|culled

    def poll(self) -> Optional[int]:
        return self.handle.poll()


def _default_popen(cmd: List[str], env: Dict[str, str], log_path: str):
    # children inherit nothing interactive; stdout/stderr land in a
    # per-(gen, member) file so a post-mortem never depends on the
    # supervisor having drained pipes
    logf = open(log_path, "ab")
    try:
        return subprocess.Popen(cmd, env=env, stdout=logf, stderr=logf,
                                start_new_session=True)
    finally:
        logf.close()


class ElasticSupervisor:
    """Launch, watch, redistribute, relaunch — the membership loop.

    ``train_argv`` is everything after the CLI's ``--`` separator: a
    verbatim ``cli.main`` flag list. The supervisor owns and overrides
    ``--node-rank``, ``--parts-per-node``, ``--port``,
    ``--watchdog-dir`` and ``--metrics-out`` per child; every other
    flag passes through untouched.
    """

    def __init__(self, train_argv: Sequence[str],
                 cfg: Optional[ElasticConfig] = None,
                 popen: Callable = _default_popen,
                 log: Callable[[str], None] = None):
        from ..cli.parser import create_parser

        self.cfg = cfg or ElasticConfig()
        self.train_argv = list(train_argv)
        self.popen = popen
        self._log = log or (lambda s: print(f"[elastic] {s}", flush=True))
        args = create_parser().parse_args(self.train_argv)
        if not args.checkpoint_dir:
            raise ValueError(
                "elastic supervision requires --checkpoint-dir in the "
                "train flags: redistribution resumes survivors from the "
                "last good checkpoint generation")
        self.args = args
        self.n_parts = int(args.n_partitions)
        # the ledger home must be STABLE across generations while the
        # coordination port changes per relaunch, so never leave the
        # coord dir keyed on the port: pin one and pass it down
        self.coord_dir = args.watchdog_dir or os.path.join(
            args.partition_dir, "coord-elastic")
        self.ledger = MembershipLedger(self.coord_dir)
        self.policy = RestartPolicy(
            max_restarts=self.cfg.max_restarts,
            backoff_base_s=self.cfg.backoff_base_s,
            backoff_max_s=self.cfg.backoff_max_s,
            storm_window_s=self.cfg.storm_window_s,
            storm_threshold=self.cfg.storm_threshold,
            stable_s=self.cfg.stable_s)
        self._metrics = None
        self._children: List[_Child] = []
        self._shutdown: Optional[int] = None
        self._stopping = False
        # generations whose ledger append failed (disk full / read-only
        # coord dir), queued for in-order retry: the last DURABLE
        # generation stays authoritative — a supervisor restart resumes
        # from ledger.latest(), never from progress that was only
        # acked in memory
        self._ledger_pending: List[Dict] = []
        # postmortem fail-fast state: per deterministic verdict class,
        # how many member deaths diagnosed as it. One gated retry is
        # allowed (the diagnosis could be wrong); a recurrence stops
        # the supervisor instead of burning --max-restarts on a
        # failure that reproduces every launch (docs/RESILIENCE.md
        # "Fail fast vs restart")
        self._det_seen: Dict[str, int] = {}
        self._pending_diag: Optional[Dict] = None
        # rejoin@G entries in the fault plan are the supervisor's to
        # honor (inert in the trainer): member rank rejoins at gen G
        self._rejoin_schedule: List[Tuple[int, Optional[int]]] = []
        if args.fault_plan:
            from .faults import FaultPlan

            self._rejoin_schedule = list(
                FaultPlan.parse(args.fault_plan).schedule("rejoin"))

    # -- helpers -----------------------------------------------------------

    def _metrics_logger(self):
        if self._metrics is None:
            from ..obs.metrics import MetricsLogger

            path = self.cfg.metrics_out or os.path.join(
                self.coord_dir, "membership.jsonl")
            self._metrics = MetricsLogger(path)
        return self._metrics

    def _clear_stale_heartbeats(self) -> None:
        # stale-heartbeat poisoning fix, half 2 (half 1 is the
        # generation-keyed filenames in coord.py): a relaunched fleet
        # must never see ghosts of the previous incarnation
        for p in glob.glob(os.path.join(self.coord_dir, "heartbeat-*")):
            try:
                os.unlink(p)
            except OSError:
                # genuinely-optional (storage-fault audit): heartbeat
                # filenames are generation-keyed, so a ghost that
                # refuses to unlink can never be mistaken for a live
                # peer of the NEXT generation anyway
                pass

    def _watchdog_horizon_s(self) -> float:
        wd = float(getattr(self.args, "watchdog_timeout", 0) or 0)
        # mirrors the hard-deadline factor in coord.py: survivors get
        # the full watchdog escalation path before the supervisor culls
        return (wd * 5 if wd > 0 else 120.0) + self.cfg.grace_extra_s

    def _child_argv(self, assignment: Assignment, node_rank: int,
                    member: int, generation: int, port: int,
                    resume: bool) -> List[str]:
        argv = list(self.train_argv)
        for flag in ("--node-rank", "--parts-per-node", "--port",
                     "--watchdog-dir"):
            argv = _strip_flag(argv, flag)
        metrics_base = _flag_value(argv, "--metrics-out")
        if metrics_base:
            argv = _strip_flag(argv, "--metrics-out")
            argv += ["--metrics-out",
                     _member_metrics_path(metrics_base, generation, member)]
        argv += ["--node-rank", str(node_rank),
                 "--parts-per-node", str(assignment.parts_per_node),
                 "--port", str(port),
                 "--watchdog-dir", self.coord_dir]
        if resume and "--resume" not in argv:
            argv.append("--resume")
        return argv

    def _launch_generation(self, generation: int,
                           assignment: Assignment) -> None:
        from ..utils.checkpoint import latest_checkpoint_path

        self._clear_stale_heartbeats()
        port = _free_port()
        resume = (latest_checkpoint_path(self.args.checkpoint_dir)
                  is not None)
        self._children = []
        for node_rank, member in enumerate(assignment.active_members()):
            argv = self._child_argv(assignment, node_rank, member,
                                    generation, port, resume)
            env = dict(os.environ)
            env[GENERATION_ENV] = str(generation)
            env[MEMBER_ENV] = str(member)
            _cpu_device_flags(env, assignment.parts_per_node)
            cmd = [sys.executable, "-m", "pipegcn_tpu.cli.main"] + argv
            log_path = os.path.join(
                self.coord_dir, f"rank-g{generation}-m{member}.log")
            handle = self.popen(cmd, env, log_path)
            self._children.append(_Child(member, node_rank, handle, log_path))
            self._log(f"gen {generation}: launched member {member} as "
                      f"node-rank {node_rank}/{assignment.n_nodes} "
                      f"(parts {list(assignment.parts_of_node(node_rank))}, "
                      f"port {port}, resume={resume})")

    def _signal_children(self, sig: int) -> None:
        for c in self._children:
            if c.poll() is None:
                try:
                    c.handle.send_signal(sig)
                except (OSError, ProcessLookupError):
                    pass

    def _watch_generation(self) -> Tuple[Optional[int], float]:
        """Block until every child of the current generation exits,
        classifying each. Returns (victim_member, death_time): the
        FIRST child to die abnormally (None when the generation ended
        without a death — all completed/resumable). Once a death is
        seen, survivors get the watchdog horizon to notice and exit 75
        themselves before being culled (SIGTERM -> SIGKILL) — a peer
        wedged in a dead collective would otherwise stall the
        relaunch forever."""
        victim: Optional[int] = None
        death_t = 0.0
        deadline: Optional[float] = None
        while True:
            alive = 0
            for c in self._children:
                rc = c.poll()
                if rc is None:
                    alive += 1
                    continue
                if c.outcome is None:
                    c.outcome = classify_exit(rc)
                    self._log(f"member {c.member} exited rc={rc} "
                              f"({c.outcome})")
                    if c.outcome == "dead" and victim is None:
                        victim = c.member
                        death_t = time.monotonic()
                        deadline = death_t + self._watchdog_horizon_s()
            if alive == 0:
                return victim, death_t
            if self._shutdown is not None and not self._stopping:
                # forward once, then keep waiting for the children's
                # own preemption checkpoints to land
                self._stopping = True
                self._signal_children(signal.SIGTERM)
            if deadline is not None and time.monotonic() > deadline:
                self._log("culling survivors stuck past the watchdog "
                          "horizon")
                self._signal_children(signal.SIGTERM)
                t0 = time.monotonic()
                while (any(c.poll() is None for c in self._children)
                       and time.monotonic() - t0 < 10):
                    time.sleep(self.cfg.poll_s)
                self._signal_children(signal.SIGKILL)
                for c in self._children:
                    if c.outcome is None and c.poll() is not None:
                        rc = c.handle.returncode
                        # a culled survivor was alive, just wedged: it
                        # stays a member (resumable), it is not the
                        # victim
                        c.outcome = ("resumable"
                                     if classify_exit(rc) != "dead"
                                     else "culled")
                deadline = None
                continue
            time.sleep(self.cfg.poll_s)

    def _next_members(self, members: List[int], victim: Optional[int],
                      generation: int) -> Tuple[List[int], str]:
        """Survivor set for the next generation plus its trigger tag.
        Exactly one victim per membership event (the first death); a
        total wipe-out keeps the full membership — a full-fleet
        restart beats training on nothing."""
        outcomes = {c.member: c.outcome for c in self._children}
        survivors = [m for m in members
                     if outcomes.get(m) not in ("dead",) and m != victim]
        if victim is not None and not survivors:
            self._log(f"every member died with member {victim}; retrying "
                      f"the full membership")
            return list(members), "restart-all"
        if victim is not None:
            trigger = "rank-death"
            members = survivors
        else:
            trigger = "preempt-resume"
        rejoining = set(self.ledger.pending_rejoins())
        due = [(g, m) for (g, m) in self._rejoin_schedule
               if g <= generation + 1]
        for g, m in due:
            self._rejoin_schedule.remove((g, m))
            if m is not None:
                rejoining.add(m)
        for m in sorted(rejoining):
            self.ledger.clear_rejoin(m)
        if rejoining:
            members = sorted(set(members) | rejoining)
            trigger = "rejoin" if victim is None else trigger
            self._log(f"rejoin: members {sorted(rejoining)} fold back in "
                      f"at generation {generation + 1}")
        members, stripped = self._strip_quarantined(members, rejoining)
        if stripped:
            trigger = "quarantine"
        return members, trigger

    def _strip_quarantined(self, members: List[int],
                           rejoining=frozenset()
                           ) -> Tuple[List[int], bool]:
        """Drop quarantined members (resilience/integrity.py markers —
        recurring silent data corruption on that rank) from the
        candidate set at every replan. A pending explicit rejoin
        request is the operator's release valve: it clears the marker
        and the member stays in. Quarantining EVERY member keeps the
        full set with a loud log — a fleet of zero trains nothing."""
        from .integrity import clear_quarantine, read_quarantines

        q = read_quarantines(self.coord_dir)
        if not q:
            return members, False
        for m in sorted(set(rejoining) & set(q)):
            clear_quarantine(self.coord_dir, m)
            q.pop(m, None)
            self._log(f"member {m} released from quarantine by "
                      f"explicit rejoin request")
        banned = [m for m in members if m in q]
        if not banned:
            return members, False
        keep = [m for m in members if m not in q]
        if not keep:
            self._log(f"every member ({banned}) is quarantined; "
                      f"keeping the full membership — an operator must "
                      f"clear the markers to make progress")
            return members, False
        reasons = ", ".join(
            f"m{m}: {q[m].get('reason', '?')}" for m in banned)
        self._log(f"quarantine: excluding members {banned} from the "
                  f"next generation ({reasons})")
        return keep, True

    def _flush_ledger_pending(self) -> bool:
        """Retry queued ledger appends in generation order, stopping at
        the first failure — appending a LATER generation while an
        earlier one is still pending would make the earlier one
        permanently unappendable (the ledger enforces monotonicity).
        True when the queue fully drained."""
        drained = 0
        while self._ledger_pending:
            kw = self._ledger_pending[0]
            try:
                self.ledger.append(**kw)
            except OSError as exc:
                self._log(f"ledger append for generation "
                          f"{kw['generation']} still failing ({exc}); "
                          f"{len(self._ledger_pending)} generations "
                          f"pending")
                return False
            self._ledger_pending.pop(0)
            drained += 1
        if drained:
            self._metrics_logger().recovery(
                IO_DEGRADED, -1, redrained=drained,
                component="membership-ledger")
            self._log(f"ledger recovered: {drained} pending "
                      f"generations appended")
        return True

    def _diagnose_death(self, generation: int,
                        victim: int) -> Optional[Dict]:
        """Run the postmortem rule engine over the coordination dir
        (black-box dumps, rank logs, the membership metrics stream all
        live there) after a member death. Returns the verdict dict, or
        None when diagnosis itself failed — forensics must never take
        the supervisor down."""
        try:
            from ..obs.postmortem import diagnose_run

            v = diagnose_run(self.coord_dir)
        except Exception as exc:  # noqa: BLE001
            self._log(f"postmortem for member {victim} failed: {exc!r}")
            return None
        self._log(f"postmortem for member {victim}: {v['verdict']} "
                  f"(confidence {v['confidence']:.2f}"
                  + (", deterministic" if v["deterministic"] else "")
                  + ")")
        try:
            self._metrics_logger().diagnosis(
                verdict=v["verdict"], confidence=v["confidence"],
                evidence=list(v["evidence"])[:6],
                remediation=v["remediation"],
                deterministic=v["deterministic"],
                generation=generation, victim=victim)
        except OSError:
            pass  # a degraded metrics sink must not block the verdict
        return v

    @staticmethod
    def _diag_slim(v: Dict) -> Dict:
        return {"verdict": v["verdict"],
                "confidence": v["confidence"],
                "deterministic": v["deterministic"]}

    def _record(self, generation: int, members: List[int],
                assignment: Assignment, trigger: str,
                latency: Optional[float],
                diagnosis: Optional[Dict] = None) -> None:
        kw = dict(generation=generation, members=list(members),
                  assignment=assignment, trigger=trigger,
                  restart_latency_s=latency,
                  diagnosis=(self._diag_slim(diagnosis)
                             if diagnosis else None))
        appended = False
        if self._flush_ledger_pending():
            try:
                self.ledger.append(**kw)
                appended = True
            except OSError as exc:
                self._log(f"LEDGER WRITE FAILED for generation "
                          f"{generation} ({exc}); the last durable "
                          f"generation {self.ledger.latest_generation()} "
                          f"stays authoritative — queuing for retry at "
                          f"the next membership event")
                self._metrics_logger().fault(
                    IO_DEGRADED, -1, reason=repr(exc),
                    generation=generation,
                    component="membership-ledger")
        if not appended:
            self._ledger_pending.append(kw)
        extra = ({"diagnosis": diagnosis["verdict"]}
                 if diagnosis else {})
        # surface the durable stream watermark with every membership
        # generation: the checkpoint's (__stream_seq__,
        # __topo_generation__) pair tells the reader exactly which
        # topology the relaunched fleet will replay to before training
        from ..utils.checkpoint import peek_watermark

        try:
            wm_seq, wm_gen = peek_watermark(self.args.checkpoint_dir)
        except Exception:  # noqa: BLE001 — observability must not kill
            wm_seq, wm_gen = -1, 0
        if wm_seq >= 0 or wm_gen > 0:
            extra["stream_seq"] = int(wm_seq)
            extra["topo_generation"] = int(wm_gen)
        self._metrics_logger().membership(
            generation=generation, assignment=assignment.as_json(),
            trigger=trigger, restart_latency_s=latency,
            n_members=len(members), **extra)

    # -- main loop ---------------------------------------------------------

    def run(self) -> int:
        def _on_term(signum, frame):  # noqa: ARG001
            self._shutdown = signum

        try:
            signal.signal(signal.SIGTERM, _on_term)
            signal.signal(signal.SIGINT, _on_term)
        except ValueError:
            pass  # not the main thread (unit tests)

        generation = self.ledger.latest_generation() + 1
        prev = self.ledger.latest()
        if prev is not None:
            members = list(prev["members"])
            trigger = "supervisor-resume"
            self._log(f"resuming ledger at generation {generation} "
                      f"with members {members}")
        else:
            n_nodes0 = math.ceil(
                self.n_parts / max(int(self.args.parts_per_node), 1))
            members = list(range(max(n_nodes0, 1)))
            trigger = "start"
        # quarantine markers survive a supervisor restart: excluded
        # members stay out until the operator clears them
        members, stripped = self._strip_quarantined(members)
        if stripped:
            trigger = "quarantine"
        latency: Optional[float] = None

        while True:
            assignment = plan_assignment(self.n_parts, members)
            self._record(generation, members, assignment, trigger, latency,
                         diagnosis=self._pending_diag)
            self._pending_diag = None
            t_launch = time.monotonic()
            self._launch_generation(generation, assignment)
            victim, death_t = self._watch_generation()
            ran_s = time.monotonic() - t_launch
            event_t = death_t if victim is not None else time.monotonic()
            outcomes = [c.outcome for c in self._children]
            if victim is None and all(o == "completed" for o in outcomes):
                self._log(f"generation {generation} completed; "
                          f"{self.policy.total} restarts total")
                return 0
            if self._stopping:
                self._log("supervisor shutdown requested; children "
                          "checkpointed — exiting resumable")
                return EXIT_PREEMPTED
            members, trigger = self._next_members(members, victim,
                                                  generation)
            if victim is not None:
                self._pending_diag = self._diagnose_death(generation,
                                                          victim)
            diag = self._pending_diag
            if diag is not None and diag.get("deterministic"):
                v = diag["verdict"]
                self._det_seen[v] = self._det_seen.get(v, 0) + 1
                if self._det_seen[v] >= 2:
                    # the gated retry died the same way: relaunching
                    # reproduces this — stop HARD (rc 1, not 75; a
                    # blind outer-scheduler resume would loop too)
                    self._log(
                        f"stopping: deterministic failure "
                        f"'{v}' recurred after its one gated retry — "
                        f"{diag['remediation']}")
                    try:
                        self.ledger.append(
                            generation=generation + 1,
                            members=list(members),
                            assignment=assignment,
                            trigger=f"deterministic:{v}",
                            diagnosis=self._diag_slim(diag))
                    except (OSError, ValueError) as exc:
                        self._log(f"final ledger append failed: {exc}")
                    self._metrics_logger().membership(
                        generation=generation + 1,
                        assignment=assignment.as_json(),
                        trigger=f"deterministic:{v}",
                        restart_latency_s=None,
                        n_members=len(members), diagnosis=v)
                    return 1
                self._log(f"postmortem verdict '{v}' is deterministic: "
                          f"allowing ONE gated retry, then failing "
                          f"fast")
            self.policy.note_stable(ran_s)
            decision = self.policy.decide()
            if decision.action == "stop":
                self._log(f"stopping: {decision.reason} after "
                          f"{self.policy.total - 1} restarts; last "
                          f"resumable checkpoint left in "
                          f"{self.args.checkpoint_dir}")
                self._metrics_logger().membership(
                    generation=generation, assignment=assignment.as_json(),
                    trigger=decision.reason, restart_latency_s=None,
                    n_members=len(members))
                return EXIT_PREEMPTED
            self._log(f"membership event ({trigger}); backing off "
                      f"{decision.delay_s:.1f}s before generation "
                      f"{generation + 1}")
            time.sleep(decision.delay_s)
            # death-detect -> next-generation-launch wall time: the
            # headline the acceptance criteria bound (watchdog horizon
            # + one backoff interval)
            latency = time.monotonic() - event_t
            generation += 1
