"""Silent-data-corruption (SDC) defense: the integrity plane.

Every defense before this PR catches *loud* failures — non-finite
values (the numerics tripwire), cross-rank divergence (the desync
detector), corruption *at rest* on disk (checkpoint CRC digests).
Nothing catches **wrong-but-finite** device state: a flipped bit in an
uploaded gather table, a corrupted halo payload, a defective core
computing plausible garbage ("Cores that don't count", HotOS '21).
This module adds three independent detectors plus the containment
bookkeeping, all cadence-gated by ``--integrity-check-every N``:

  fletcher digests   order-independent two-accumulator bit sums
                     (uint32 wraparound — any single bit flip changes
                     the sum with certainty) computed by a tiny jitted
                     program on device and by bit-identical numpy on
                     the host, so device state can be compared against
                     host-built references and across time
  IntegrityPlane     the per-trainer orchestrator fit() drives at
                     check boundaries: scrubs static device tables
                     against their baselines, verifies the pipelined
                     carry (halo features attributed separately from
                     the rest) and the replicated params against
                     digests captured when they were last produced,
                     and runs the Freivalds-style SpMM verification
  freivalds check    probabilistic algebraic verification of the
                     production aggregation kernel: project the
                     feature matrix onto a per-epoch random +-1 vector
                     r, aggregate the single-column result through the
                     PRODUCTION kernel (tables and all), and compare
                     against an independent raw-edge host reference —
                     O(nnz + n*d) instead of re-running the epoch.
                     A flipped gather-table index routes the wrong row
                     and the projections disagree; a defective core
                     miscomputing the kernel disagrees the same way.

Coverage window, stated honestly (docs/RESILIENCE.md): the digest
scrub compares state at dispatch boundaries, so it catches corruption
of boundary-resident state (exactly where host-side bit-flip injection
lands, and where DMA'd state sits between programs); mid-scan HBM is
ECC territory. The wire checksum lane (parallel/halo.py) covers the
ICI transport inside the step; Freivalds covers the compute datapath.

Recovery is per target class: ``tables`` rebuilds the dirty shard's
device tables from the host artifact (the PR-13 dirty-shard path),
``halo``/``carry`` flush the pipelined carry (epoch-0 warmup
semantics), ``params`` roll back to the last good snapshot — agreed
across ranks through the widened FaultConsensus word so the pod moves
in lockstep. Recurring SDC on one rank writes a quarantine request
marker the elastic supervisor consumes (resilience/elastic.py).

Host-side orchestration; the only device work is the small jitted
digest/projection programs, dispatched at cadence only.
"""

from __future__ import annotations

import dataclasses
import json
import os
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

# target classes the chaos grammar can flip and the records attribute
TARGETS = ("params", "carry", "tables", "halo")

# SDC codes riding the consensus word (coord.IDX_SDC_CODE): 0 = none
SDC_CODES = {t: i + 1 for i, t in enumerate(TARGETS)}
SDC_NAMES = {v: k for k, v in SDC_CODES.items()}

# a member whose run detects this many SDC events is asked to leave
# the fleet (quarantine marker, consumed by the elastic supervisor)
QUARANTINE_STRIKES = 2


# ---------------- fletcher digests ------------------------------------

def _as_u32(a: np.ndarray) -> np.ndarray:
    """Host bit view of any array as a flat uint32 vector (sub-word
    dtypes zero-extend per element, so the view — and therefore the
    digest — is identical to the device program's)."""
    a = np.ascontiguousarray(a)
    if a.dtype == np.bool_:
        a = a.astype(np.uint8)
    size = a.dtype.itemsize
    if size == 1:
        return a.reshape(-1).view(np.uint8).astype(np.uint32)
    if size == 2:
        return a.reshape(-1).view(np.uint16).astype(np.uint32)
    if size == 4:
        return a.reshape(-1).view(np.uint32)
    # 8-byte dtypes: fold the two 32-bit halves
    u = a.reshape(-1).view(np.uint32)
    return u


def host_digest(a: np.ndarray) -> np.ndarray:
    """[2] uint32 fletcher-style digest of an array's bits: a plain
    wraparound sum and an odd-weighted sum. Order-independent (integer
    wraparound addition commutes), so the device reduction — whatever
    order XLA picks — produces the identical pair. Any single bit flip
    changes the plain sum by +-2^k != 0 (mod 2^32), so detection of
    the one-flip fault model is certain, not probabilistic."""
    u = _as_u32(np.asarray(a))
    with np.errstate(over="ignore"):
        n = u.shape[0]
        w = (np.arange(n, dtype=np.uint32) << np.uint32(1)) | np.uint32(1)
        s1 = np.add.reduce(u, dtype=np.uint32) if n else np.uint32(0)
        s2 = (np.add.reduce(u * w, dtype=np.uint32) if n
              else np.uint32(0))
    return np.asarray([s1, s2], np.uint32)


def device_digest(x):
    """Jittable counterpart of :func:`host_digest` — same bit view,
    same two wraparound sums, returned as a [2] uint32 array."""
    import jax
    import jax.numpy as jnp

    x = x.reshape(-1)
    if x.dtype == jnp.bool_:
        x = x.astype(jnp.uint8)
    size = jnp.dtype(x.dtype).itemsize
    # bitcast to the same-width unsigned view, then widen to uint32
    if size == 1:
        u = jax.lax.bitcast_convert_type(x, jnp.uint8).astype(jnp.uint32)
    elif size == 2:
        u = jax.lax.bitcast_convert_type(x, jnp.uint16).astype(jnp.uint32)
    elif size == 4:
        u = jax.lax.bitcast_convert_type(x, jnp.uint32)
    else:
        u = jax.lax.bitcast_convert_type(x, jnp.uint32).reshape(-1)
    n = u.shape[0]
    if n == 0:
        return jnp.zeros((2,), jnp.uint32)
    w = (jnp.arange(n, dtype=jnp.uint32) << jnp.uint32(1)) | jnp.uint32(1)
    s1 = jnp.sum(u, dtype=jnp.uint32)
    s2 = jnp.sum(u * w, dtype=jnp.uint32)
    return jnp.stack([s1, s2])


def _spans_processes(a) -> bool:
    """True for a jax.Array whose shards live partly on OTHER
    processes (fetching it whole would need a collective). Each rank
    then digests only its addressable shards — it guards its own
    rows, and the fault consensus aggregates detection across ranks."""
    import jax

    return (isinstance(a, jax.Array)
            and not a.is_fully_addressable
            and not a.is_fully_replicated)


def digest_tree(tree: Any) -> Dict[str, np.ndarray]:
    """{path: [2] uint32} device digests of every leaf of a pytree of
    device (or host) arrays — one jitted program per distinct leaf
    structure, cached by jax's own jit cache. Leaves sharded across
    processes fold the wraparound digests of the LOCAL shards only
    (order-independent, so the fold is stable across time as long as
    the sharding is — which is exactly the comparison window)."""
    import jax

    leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
    out: Dict[str, np.ndarray] = {}
    fn = _digest_many()
    keys = [jax.tree_util.keystr(p) for p, _ in leaves]
    vals = [v for _, v in leaves]
    whole = [(k, v) for k, v in zip(keys, vals)
             if not _spans_processes(v)]
    if whole:
        for (k, _), d in zip(whole, fn([v for _, v in whole])):
            out[k] = np.asarray(d)
    one = _digest_one()
    for k, v in zip(keys, vals):
        if not _spans_processes(v):
            continue
        acc = np.zeros(2, np.uint32)
        for sh in v.addressable_shards:
            acc = acc + np.asarray(one(sh.data))  # uint32 wraps
        out[k] = acc
    return out


_DIGEST_FN = None


def _digest_many():
    """The shared jitted list-of-arrays digest program."""
    global _DIGEST_FN
    if _DIGEST_FN is None:
        import jax

        _DIGEST_FN = jax.jit(
            lambda arrs: [device_digest(a) for a in arrs])
    return _DIGEST_FN


_DIGEST_ONE = None


def _digest_one():
    """Jitted single-array digest — the per-local-shard program the
    multi-process paths use (a shard is a plain one-device array)."""
    global _DIGEST_ONE
    if _DIGEST_ONE is None:
        import jax

        _DIGEST_ONE = jax.jit(device_digest)
    return _DIGEST_ONE


def shard_digests(a) -> np.ndarray:
    """[P, 2] uint32 per-leading-index digests of a stacked [P, ...]
    device array — the dirty-shard attribution the table scrubber
    needs (which shard's rows rotted decides which shard rebuilds).
    When the array spans processes, only this rank's rows are digested
    (the rest stay zero in BOTH the baseline and the current pass, so
    they always compare equal): every shard is still guarded, by the
    rank that owns it."""
    fn = _shard_digest_fn()
    if _spans_processes(a):
        out = np.zeros((int(a.shape[0]), 2), np.uint32)
        for sh in a.addressable_shards:
            start = sh.index[0].start or 0
            d = np.asarray(fn(sh.data))
            out[start:start + d.shape[0]] = d
        return out
    return np.asarray(fn(a))


_SHARD_DIGEST_FN = None


def _shard_digest_fn():
    global _SHARD_DIGEST_FN
    if _SHARD_DIGEST_FN is None:
        import jax

        _SHARD_DIGEST_FN = jax.jit(
            lambda a: jax.vmap(device_digest)(a))
    return _SHARD_DIGEST_FN


# ---------------- bit-flip injection (chaos) --------------------------

def _local_rows(a) -> Tuple[List[int], np.ndarray]:
    """(global row indices, host rows) of the process-local shards of
    a stacked [P, ...] array — the multi-process-safe fetch. Single
    process (or replicated): every row."""
    if _spans_processes(a):
        pairs = []
        for sh in a.addressable_shards:
            start = sh.index[0].start or 0
            data = np.asarray(sh.data)
            for i in range(data.shape[0]):
                pairs.append((start + i, data[i]))
        pairs.sort(key=lambda t: t[0])
        return ([p for p, _ in pairs],
                np.stack([d for _, d in pairs]))
    arr = np.asarray(a)
    return list(range(arr.shape[0])), arr


def flip_bit(a: np.ndarray, *, bit: int = 0, index: int = 0) -> np.ndarray:
    """Return a copy of `a` with one bit flipped in the element at flat
    position `index` — the chaos lane's host-side SDC model. `bit`
    counts from the element's LSB; out-of-range values wrap."""
    a = np.array(a, copy=True)
    flat = a.reshape(-1)
    if flat.size == 0:
        return a
    index = int(index) % flat.size
    view = _as_u32_inplace(flat)
    width = 8 * min(a.dtype.itemsize, 4)
    view[index % view.size] ^= np.uint32(1) << np.uint32(bit % width)
    return a


def _as_u32_inplace(flat: np.ndarray) -> np.ndarray:
    size = flat.dtype.itemsize
    if flat.dtype == np.bool_:
        return flat.view(np.uint8)
    if size == 1:
        return flat.view(np.uint8)
    if size == 2:
        return flat.view(np.uint16)
    return flat.view(np.uint32)


# ---------------- quarantine markers ----------------------------------

def quarantine_marker_path(coord_dir: str, member: int) -> str:
    return os.path.join(coord_dir, f"quarantine-m{int(member)}.json")


def request_quarantine(coord_dir: str, member: int, *, reason: str,
                       strikes: int, targets: List[str]) -> str:
    """Durable quarantine request for `member`, consumed by the
    elastic supervisor at its next membership replan. Written with the
    temp+rename discipline every durable artifact here uses."""
    os.makedirs(coord_dir, exist_ok=True)
    path = quarantine_marker_path(coord_dir, member)
    payload = {"member": int(member), "reason": str(reason),
               "strikes": int(strikes),
               "targets": sorted(set(targets)),
               "time_unix": time.time()}
    tmp = f"{path}.{os.getpid()}.tmp"
    with open(tmp, "w") as f:
        json.dump(payload, f, sort_keys=True)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)
    return path


def read_quarantines(coord_dir: str) -> Dict[int, Dict[str, Any]]:
    """{member: marker payload} for every quarantine marker present.
    Unreadable markers still quarantine (fail-closed: a half-written
    marker means the member WAS asking to leave)."""
    out: Dict[int, Dict[str, Any]] = {}
    try:
        names = os.listdir(coord_dir)
    except OSError:
        return out
    for name in names:
        if not (name.startswith("quarantine-m")
                and name.endswith(".json")):
            continue
        try:
            member = int(name[len("quarantine-m"):-len(".json")])
        except ValueError:
            continue
        try:
            with open(os.path.join(coord_dir, name)) as f:
                out[member] = json.load(f)
        except (OSError, ValueError):
            out[member] = {"member": member, "reason": "unreadable marker"}
    return out


def clear_quarantine(coord_dir: str, member: int) -> bool:
    """Operator-initiated release: remove the marker so the next
    rejoin request can fold the member back in. Returns True when a
    marker was actually removed."""
    try:
        os.remove(quarantine_marker_path(coord_dir, member))
        return True
    except OSError:
        return False


# ---------------- the plane -------------------------------------------

@dataclasses.dataclass
class CheckResult:
    """One detector's verdict at one check boundary."""

    check: str                   # scrub | freivalds | wire
    outcome: str                 # ok | mismatch
    target: Optional[str] = None  # params | carry | tables | halo
    detail: str = ""
    dirty_shards: Tuple[int, ...] = ()
    overhead_s: float = 0.0


class IntegrityPlane:
    """Per-trainer SDC detector set, driven by fit() at cadence.

    Lifecycle: ``baseline(trainer)`` captures the static-data digests
    once (and again after any table rebuild / graph delta);
    ``note_dynamic(trainer)`` captures params+carry digests right
    after a dispatch lands (the state's production point);
    ``check(trainer, epoch)`` at the NEXT boundary re-digests and
    compares, plus scrubs the static tables and runs Freivalds.
    """

    # relative tolerance for the Freivalds projection comparison: the
    # kernel accumulates in f32 while the host reference uses f64, so
    # exact equality is not the contract — a flipped table index
    # mis-routes whole rows and lands orders of magnitude above this
    FREIVALDS_RTOL = 5e-2

    def __init__(self, check_every: int, *, rank: int = 0,
                 log: Callable[[str], None] = print):
        self.check_every = max(int(check_every), 0)
        self.rank = int(rank)
        self.log = log
        self._static_refs: Optional[Dict[str, np.ndarray]] = None
        self._dynamic_refs: Optional[Dict[str, Dict[str, np.ndarray]]] = None
        # detection counters for containment (quarantine strikes)
        self.detections: Dict[str, int] = {}
        self.checks_run = 0

    @property
    def enabled(self) -> bool:
        return self.check_every > 0

    def due(self, epoch: int) -> bool:
        return (self.enabled and epoch > 0
                and epoch % self.check_every == 0)

    # ---------------- baselines ---------------------------------------

    @staticmethod
    def _static_keys(trainer) -> List[str]:
        """Every static device array the scrubber guards: kernel
        gather tables, CSR slabs, send-lists, masks, degrees, features
        — everything in trainer.data (all of it is static between
        graph deltas; params/opt/carry live in trainer.state)."""
        return sorted(trainer.data.keys())

    def baseline(self, trainer) -> float:
        """(Re)capture the static-data digest baseline. Called at
        plane arm time and after any legitimate table rebuild."""
        t0 = time.perf_counter()
        self._static_refs = {
            k: shard_digests(trainer.data[k])
            for k in self._static_keys(trainer)
        }
        return time.perf_counter() - t0

    def note_dynamic(self, trainer) -> float:
        """Capture params + carry digests at their production point
        (right after a dispatch at a check boundary). The next
        boundary's check() compares against these."""
        t0 = time.perf_counter()
        refs: Dict[str, Dict[str, np.ndarray]] = {
            "params": digest_tree(trainer.state["params"]),
        }
        comm = trainer.state.get("comm") or {}
        if comm:
            refs["halo"] = digest_tree(comm.get("halo", {}))
            rest = {k: v for k, v in comm.items() if k != "halo"}
            refs["carry"] = digest_tree(rest)
        self._dynamic_refs = refs
        return time.perf_counter() - t0

    def drop_dynamic(self) -> None:
        """Forget the params/carry baselines (rollback, carry flush,
        restore — the state legitimately changed outside a dispatch)."""
        self._dynamic_refs = None

    # ---------------- checks ------------------------------------------

    def scrub_static(self, trainer) -> CheckResult:
        """Compare every static device table against its baseline;
        mismatches name the dirty shards for the rebuild path."""
        t0 = time.perf_counter()
        if self._static_refs is None:
            self.baseline(trainer)
            return CheckResult("scrub", "ok", target="tables",
                               detail="baseline captured",
                               overhead_s=time.perf_counter() - t0)
        bad: List[str] = []
        dirty: set = set()
        for k in self._static_keys(trainer):
            ref = self._static_refs.get(k)
            if ref is None:  # new key (table rebuild added it)
                continue
            cur = shard_digests(trainer.data[k])
            if cur.shape != ref.shape:
                bad.append(k)
                dirty.update(range(cur.shape[0]))
                continue
            rows = np.nonzero(np.any(cur != ref, axis=-1))[0]
            if rows.size:
                bad.append(k)
                dirty.update(int(r) for r in rows)
        dt = time.perf_counter() - t0
        if not bad:
            return CheckResult("scrub", "ok", target="tables",
                               overhead_s=dt)
        return CheckResult(
            "scrub", "mismatch", target="tables",
            detail="digest mismatch in " + ", ".join(sorted(bad)[:6]),
            dirty_shards=tuple(sorted(dirty)), overhead_s=dt)

    def verify_dynamic(self, trainer) -> List[CheckResult]:
        """Compare params and carry digests against their production
        baselines — the boundary-resident at-rest window."""
        t0 = time.perf_counter()
        if self._dynamic_refs is None:
            return []
        out: List[CheckResult] = []
        cur: Dict[str, Dict[str, np.ndarray]] = {
            "params": digest_tree(trainer.state["params"]),
        }
        comm = trainer.state.get("comm") or {}
        if comm and "halo" in self._dynamic_refs:
            cur["halo"] = digest_tree(comm.get("halo", {}))
            rest = {k: v for k, v in comm.items() if k != "halo"}
            cur["carry"] = digest_tree(rest)
        dt = time.perf_counter() - t0
        for target, refs in self._dynamic_refs.items():
            now = cur.get(target)
            if now is None:
                continue
            bad = [k for k, v in refs.items()
                   if not np.array_equal(now.get(k), v)]
            if bad:
                out.append(CheckResult(
                    "scrub", "mismatch", target=target,
                    detail="digest mismatch in "
                           + ", ".join(sorted(bad)[:6]),
                    overhead_s=dt))
            else:
                out.append(CheckResult("scrub", "ok", target=target,
                                       overhead_s=dt))
        return out

    def freivalds(self, trainer, epoch: int) -> Optional[CheckResult]:
        """Randomized algebraic verification of the production SpMM:
        aggregate the feature matrix projected onto a random +-1
        vector through the PRODUCTION kernel (gather tables, slab
        plans and all), and compare against an independent raw-edge
        reference computed on the host from the partition artifact.
        O(nnz + n*d). GAT aggregation is parameter-dependent and is
        covered by the scrubber only."""
        if getattr(trainer.cfg, "model", "") == "gat" or \
                getattr(trainer, "_gat_tables", None) is not None:
            return None
        t0 = time.perf_counter()
        sg = trainer.sg
        rng = np.random.default_rng(
            (int(epoch) * 1000003 + 12345) & 0xFFFFFFFF)
        feat_w = int(trainer.data["feat"].shape[-1])
        r = rng.integers(0, 2, size=feat_w).astype(np.float32) * 2 - 1
        try:
            u, w_fbuf = self._freivalds_device(trainer, r)
        except Exception as exc:  # noqa: BLE001 — detector, not a crash
            return CheckResult(
                "freivalds", "ok", target="tables",
                detail=f"skipped: {exc!r}"[:160],
                overhead_s=time.perf_counter() - t0)
        # multi-process runs verify the LOCAL shards only (each rank
        # guards its own; the consensus word aggregates detection)
        rows, u = _local_rows(u)               # [k, n_max]
        _, w_fbuf = _local_rows(w_fbuf)        # [k, n_src_rows]
        u = u.astype(np.float64)
        w_fbuf = w_fbuf.astype(np.float64)
        # host reference: raw-edge mean aggregation per shard from the
        # partition artifact (an independent code path end to end)
        es = np.asarray(sg.edge_src)
        ed = np.asarray(sg.edge_dst)
        deg = np.asarray(sg.in_deg, np.float64)
        n_max = sg.n_max
        worst = 0.0
        for j, p in enumerate(rows):
            acc = np.zeros(n_max + 1, np.float64)
            np.add.at(acc, ed[p], w_fbuf[j][es[p]])
            v = acc[:n_max] / deg[p]
            scale = max(float(np.max(np.abs(v))), 1.0)
            worst = max(worst, float(np.max(np.abs(u[j] - v))) / scale)
        dt = time.perf_counter() - t0
        if worst > self.FREIVALDS_RTOL:
            return CheckResult(
                "freivalds", "mismatch", target="tables",
                detail=f"projection residual {worst:.3e} "
                       f"(rtol {self.FREIVALDS_RTOL:g})",
                overhead_s=dt)
        return CheckResult("freivalds", "ok", target="tables",
                           detail=f"residual {worst:.3e}",
                           overhead_s=dt)

    def _freivalds_device(self, trainer, r: np.ndarray):
        """Device half of the Freivalds check: project, halo-exchange
        the projection, aggregate through the production kernel.
        Returns (u [P, n_max], w_fbuf [P, n_src_rows]) on host."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec

        from ..parallel.halo import halo_exchange
        from ..parallel.mesh import PARTS_AXIS
        from ..ops.spmm import spmm_mean

        sg = trainer.sg
        n_max, P = sg.n_max, trainer.P
        n_src = n_max + sg.halo_size
        data = trainer.data
        use_tables = ("bkt_fwd_inv" in data) or ("blk_fwd_inv" in data)
        keys = ["feat", "in_deg", "send_idx", "send_mask"]
        if use_tables:
            keys += [k for k in data
                     if k.startswith(("bkt_", "blk_", "blkrem_"))]
        else:
            keys += ["edge_src", "edge_dst"]
        d_in = {k: data[k] for k in keys}
        r_dev = jnp.asarray(r)

        def body(d):
            d = {k: v[0] for k, v in d.items()}
            w = (d["feat"].astype(jnp.float32) @ r_dev)[:, None]
            wb = halo_exchange(w, d["send_idx"], d["send_mask"],
                               PARTS_AXIS, P)
            if use_tables:
                # transport=False: the verification must exercise the
                # table STRUCTURE in clean precision, not the narrowed
                # gather transport (whose quantization is by design)
                spmm = trainer.make_device_spmm_closure(
                    d, n_max=n_max, n_src_rows=n_src, transport=False)
                agg = spmm(wb)
            else:
                agg = spmm_mean(
                    wb, d["edge_src"], d["edge_dst"], d["in_deg"],
                    n_max, trainer.cfg.spmm_chunk,
                    trainer.cfg.sorted_edges)
            return agg[:, 0][None], wb[:, 0][None]

        spec = PartitionSpec(PARTS_AXIS)
        if trainer.emulated:
            tm = jax.tree_util.tree_map

            def vbody(d):
                a, b = body(tm(lambda v: v[None], d))
                return a[0], b[0]

            fn = jax.jit(jax.vmap(vbody, axis_name=PARTS_AXIS))
        else:
            fn = jax.jit(jax.shard_map(
                body, mesh=trainer.mesh,
                in_specs=(jax.tree_util.tree_map(lambda _: spec, d_in),),
                out_specs=(spec, spec)))
        u, wb = fn(d_in)
        return jax.device_get(u), jax.device_get(wb)

    # ---------------- the per-boundary driver -------------------------

    def run_checks(self, trainer, epoch: int, *,
                   deep: bool = True) -> List[CheckResult]:
        """Detectors in attribution order. The dynamic digest compare
        is cheap and runs at EVERY boundary (the params/carry refs are
        re-captured after every dispatch, so any boundary can verify
        them); the static-table scrub and the Freivalds projection are
        the expensive half and run only when ``deep`` (the cadence
        boundaries). Mismatch counters feed the quarantine-strike
        policy."""
        self.checks_run += 1
        results: List[CheckResult] = []
        results.extend(self.verify_dynamic(trainer))
        if deep:
            results.append(self.scrub_static(trainer))
            fr = self.freivalds(trainer, epoch)
            if fr is not None:
                results.append(fr)
        for res in results:
            if res.outcome == "mismatch" and res.target:
                self.detections[res.target] = \
                    self.detections.get(res.target, 0) + 1
        return results

    def total_detections(self) -> int:
        return sum(self.detections.values())

    def should_quarantine(self) -> bool:
        return self.total_detections() >= QUARANTINE_STRIKES
