"""Compiled-once sharded inference engine.

The serving counterpart of `parallel/evaluator.py`: one donated-buffer,
shard_map'd forward program over the partitioned graph — no dropout, no
grads, no metric reduce — plus five tiny companion programs (full halo
exchange, incremental dirty-row exchange, in-place feature patch,
changed-slot halo flush, and the replicated query gather). All are
built ONCE per engine and
traced once per input shape; the batcher's power-of-two ladder keeps
the shape population finite, so after `warmup()` steady-state traffic
never recompiles (pinned by the TRACE_COUNTS test in test_serve.py).

Serving inherits every training-side kernel win by construction: the
forward program aggregates through `trainer.make_device_spmm_closure`
(the tuner's measured kernel choice)
and exchanges boundaries through the same send-lists as training.

State owned by the engine (per device, sharded over PARTS_AXIS):
  _feat   [P, n_max, F]      mutable feature shard (donated on patch)
  _halo0  [P, (P-1)*B, F]    layer-0 halo cache in the SEND VIEW —
                             compute dtype, GCN degree pre-scale
                             applied — exactly the buffer forward()
                             would exchange at layer 0
  _logits [P, n_max, C]      f32 logits of every owned node

Staleness ledger (docs/SERVING.md): `staleness_age` counts applied
update batches whose effects the served logits do not yet reflect.
apply_updates bumps it; refresh() collapses it to the halo lag (logits
now see current features, boundary as-of the halo cache);
refresh_boundary() zeroes the halo lag. age == 0 ⇔ fully fresh ⇔ a
cache hit.
"""

from __future__ import annotations

import time
from typing import Dict, Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec

from ..models.sage import forward
from ..obs.trace import named_phase
from ..parallel.halo import exchange_blocks, halo_exchange
from ..parallel.mesh import PARTS_AXIS
from ..utils.checkpoint import (CheckpointCorrupt, _generations,
                                load_checkpoint)
from .batcher import MicroBatcher, ServingStats, bucket_for, bucket_ladder
from .cache import Layer0Cache
from .freshness import FreshnessTracker, dirty_exchange_blocks

# Incremented at TRACE time inside each program body: a jit cache hit
# leaves them untouched, so the delta across a traffic window counts
# recompiles exactly. The no-recompile acceptance test pins these.
TRACE_COUNTS: Dict[str, int] = {
    "exchange": 0, "inc": 0, "refresh": 0, "patch": 0, "query": 0,
    "flush": 0,
}


def trace_counts() -> Dict[str, int]:
    return dict(TRACE_COUNTS)


# data keys the inference program must NOT close over as static input:
# feat is the mutable serving carry, the rest are training-only
_NON_STATIC = ("feat", "label", "train_mask", "val_mask", "test_mask",
               "row_mask")


class ServingEngine:
    """Persistent sharded inference over one Trainer's mesh + artifact.

    Built once per (trainer, batch-shape-ladder); `for_trainer` caches
    instances so repeated construction (bench legs, warm restarts in
    the same process) reuses the compiled programs."""

    def __init__(self, trainer, *, max_batch: int = 64,
                 ladder_min: int = 8, max_update_rows: int = 256):
        if trainer.emulated:
            raise ValueError(
                "serving requires a real device mesh; emulated trainers "
                "stack partitions on one device and cannot serve")
        self.trainer = trainer
        sg = trainer.sg
        self.sg = sg
        self.cfg = trainer.cfg
        self.P = trainer.P
        self.n_max = sg.n_max
        self.halo_size = sg.halo_size
        self.n_class = sg.n_class
        self.n_feat_raw = sg.n_feat
        self.num_global_nodes = int((sg.global_nid >= 0).sum())
        self.ladder = bucket_ladder(ladder_min, max_batch)
        self.update_ladder = bucket_ladder(ladder_min, max_update_rows)
        self.params_version = 0
        # parameter-generation axis (schema v7): the checkpoint epoch
        # the served params came from (-1 = fresh init), and how many
        # newer PUBLISHED generations the fleet has not swapped in yet
        self.param_generation = -1
        self.param_staleness = 0
        self._last_corrupt_gen = -1  # dedupe corrupt-gen fault records

        # ---------------- host-side routing ---------------------------
        # global nid -> (partition, local row); -1 rows are padding
        nid = np.asarray(sg.global_nid)
        self._q_part = np.full(self.num_global_nodes, -1, np.int32)
        self._q_local = np.zeros(self.num_global_nodes, np.int32)
        for p in range(self.P):
            own = np.nonzero(nid[p] >= 0)[0]
            self._q_part[nid[p, own]] = p
            self._q_local[nid[p, own]] = own.astype(np.int32)

        self.freshness = FreshnessTracker(self.P, self.n_max)
        self.cache = Layer0Cache(sg.send_idx, sg.send_mask)
        self._feat_lag = 0   # update batches not yet in _logits
        self._halo_lag = 0   # update batches whose boundary rows are
        #                      not yet in _halo0
        # topology-generation axis (schema v8): count of graph delta
        # batches this engine's topology reflects (docs/STREAMING.md)
        self.topo_generation = 0

        # ---------------- device state --------------------------------
        # private copy of the feature shard: serving patches it under
        # donation, the trainer's training/eval buffer must stay intact
        self._feat = jax.jit(
            lambda x: x + jnp.zeros((), x.dtype),
            out_shardings=trainer._shard)(trainer.data["feat"])
        self._static = {k: v for k, v in trainer.data.items()
                        if k not in _NON_STATIC}
        self._params = trainer.state["params"]
        self._norm = trainer.state["norm"]
        self._logits = None

        # ---------------- compiled programs ---------------------------
        P, n_max, cfg = self.P, self.n_max, self.cfg
        mesh = trainer.mesh
        spec = PartitionSpec(PARTS_AXIS)
        repl = PartitionSpec()
        tm = jax.tree_util.tree_map
        st_spec = tm(lambda _: spec, self._static)
        params_spec = tm(lambda _: repl, self._params)
        norm_spec = tm(lambda _: repl, self._norm)
        is_gcn = cfg.model == "gcn"
        cdt = cfg.compute_dtype

        def send_view(f, in_deg):
            # exactly forward()'s transform on the buffer it hands to
            # comm_update at layer 0: cast to the compute dtype, then
            # (GCN) the f32 symmetric-norm pre-scale cast back — the
            # op sequence must match bit-for-bit or the cached halo
            # diverges from a live exchange
            h = f.astype(cdt)
            if is_gcn:
                d_sqrt = jnp.sqrt(in_deg.astype(jnp.float32))
                h = (h.astype(jnp.float32)
                     / d_sqrt[: h.shape[0], None]).astype(cdt)
            return h

        def exchange_fn(feat, d):
            TRACE_COUNTS["exchange"] += 1
            d = {k: v[0] for k, v in d.items()}
            h = send_view(feat[0], d["in_deg"])
            return exchange_blocks(h, d["send_idx"], d["send_mask"],
                                   PARTS_AXIS, P)[None]

        self._exchange_prog = jax.jit(jax.shard_map(
            exchange_fn, mesh=mesh, in_specs=(spec, st_spec),
            out_specs=spec))

        # wire-integrity guard on the dirty-row exchange: a trace-time
        # choice (guard off compiles the historical byte-identical
        # program), so the no-recompile pin holds either way — the inc
        # program still traces exactly once per engine
        wire_guard = int(getattr(trainer.tcfg, "integrity_check_every",
                                 0) or 0) > 0
        self._wire_guard = wire_guard
        self.wire_bad_total = 0

        def inc_fn(feat, halo0, dirty, d):
            TRACE_COUNTS["inc"] += 1
            d = {k: v[0] for k, v in d.items()}
            h = send_view(feat[0], d["in_deg"])
            if wire_guard:
                new, bad = dirty_exchange_blocks(
                    h, halo0[0], dirty[0], d["send_idx"],
                    d["send_mask"], PARTS_AXIS, P, guard=True)
                return new[None], jax.lax.psum(bad, PARTS_AXIS)
            new = dirty_exchange_blocks(
                h, halo0[0], dirty[0], d["send_idx"], d["send_mask"],
                PARTS_AXIS, P)
            return new[None]

        self._inc_prog = jax.jit(jax.shard_map(
            inc_fn, mesh=mesh, in_specs=(spec, spec, spec, st_spec),
            out_specs=(spec, repl) if wire_guard else spec),
            donate_argnums=(1,))

        def refresh_fn(params, norm, feat, halo0, d):
            TRACE_COUNTS["refresh"] += 1
            d = {k: v[0] for k, v in d.items()}
            f, h0 = feat[0], halo0[0]
            # the first exchanged layer consumes the resident halo
            # cache (the freshness carry); deeper layers exchange live
            # exactly like the evaluator. Under use_pp layer 0 never
            # exchanges, so every comm_update call is live.
            first = None if cfg.use_pp else 0

            def comm_update(i, h):
                if i == first:
                    return jnp.concatenate(
                        [h, h0.astype(h.dtype)], axis=0)
                return halo_exchange(h, d["send_idx"], d["send_mask"],
                                     PARTS_AXIS, P)

            spmm = trainer.make_device_spmm_closure(
                d, n_max=n_max, n_src_rows=n_max + self.halo_size,
                transport=False)
            gat = trainer.make_device_gat_closure(
                d, n_max=n_max, n_src_rows=n_max + self.halo_size,
                transport=False)
            with named_phase("serve_refresh"):
                logits, _ = forward(
                    params, cfg, f, d["edge_src"], d["edge_dst"],
                    d["in_deg"], n_max, training=False, halo_eval=True,
                    comm_update=comm_update, norm_state=norm,
                    spmm_fn=spmm, gat_fn=gat)
            return logits[None]

        self._refresh_prog = jax.jit(jax.shard_map(
            refresh_fn, mesh=mesh,
            in_specs=(params_spec, norm_spec, spec, spec, st_spec),
            out_specs=spec))

        def patch_fn(feat, up, ul, uv):
            TRACE_COUNTS["patch"] += 1
            f = feat[0]
            r = jax.lax.axis_index(PARTS_AXIS)
            # rows owned elsewhere (and -1 padding) map out of bounds
            # and are dropped by the scatter
            idx = jnp.where(up == r, ul, f.shape[0])
            f = f.at[idx].set(uv.astype(f.dtype), mode="drop")
            return f[None]

        self._patch_prog = jax.jit(jax.shard_map(
            patch_fn, mesh=mesh, in_specs=(spec, repl, repl, repl),
            out_specs=spec), donate_argnums=(0,))

        def flush_fn(halo0, m):
            # zero receiver-side halo slots whose send-list entry a
            # topology delta moved or removed: a removed entry's slot
            # must read zero (what a full exchange produces for a
            # masked-off slot), a moved entry's slot is re-shipped by
            # the next incremental refresh
            TRACE_COUNTS["flush"] += 1
            return jnp.where(m, jnp.zeros((), halo0.dtype), halo0)

        self._flush_prog = jax.jit(jax.shard_map(
            flush_fn, mesh=mesh, in_specs=(spec, spec),
            out_specs=spec), donate_argnums=(0,))

        def query_fn(logits, qp, ql):
            TRACE_COUNTS["query"] += 1
            lg = logits[0]
            r = jax.lax.axis_index(PARTS_AXIS)
            rows = jnp.take(lg, ql, axis=0, mode="clip")
            rows = jnp.where((qp == r)[:, None], rows,
                             jnp.zeros((), rows.dtype))
            with named_phase("serve_query"):
                # each queried row is non-zero on exactly its owner, so
                # the psum both routes and replicates the answer
                return jax.lax.psum(rows, PARTS_AXIS)

        self._query_prog = jax.jit(jax.shard_map(
            query_fn, mesh=mesh, in_specs=(spec, repl, repl),
            out_specs=repl))

        # the layer-0 halo cache starts fully fresh
        self._halo0 = self._exchange_prog(self._feat, self._static)

    # ------------------------------------------------------------------
    @classmethod
    def for_trainer(cls, trainer, **kw) -> "ServingEngine":
        cache = getattr(trainer, "_serving_engines", None)
        if cache is None:
            cache = trainer._serving_engines = {}
        key = tuple(sorted(kw.items()))
        if key not in cache:
            cache[key] = cls(trainer, **kw)
        return cache[key]

    # ---------------- params / warmup ---------------------------------

    def load_params(self, params=None, norm=None,
                    generation: Optional[int] = None) -> None:
        """Swap serving weights (e.g. after a checkpoint restore on the
        trainer); logits are stale until the next refresh().
        `generation` records the checkpoint epoch the params came from
        (the v7 parameter-generation axis on serving records)."""
        self._params = self.trainer.state["params"] \
            if params is None else params
        self._norm = self.trainer.state["norm"] if norm is None else norm
        self.params_version += 1
        self._logits = None
        if generation is not None:
            self.param_generation = int(generation)

    def load_from_checkpoint(self, directory: str, ml=None) -> Dict:
        """CRC-hardened zero-downtime weight swap from a checkpoint
        directory (the fleet hot-swap path, docs/SERVING.md "Fleet").

        Loads only the serving subset {params, norm} of the newest
        generation that passes digest verification (load_pytree reads
        only the template's paths, so optimizer moments never leave
        disk). A corrupt/truncated newest generation walks back to an
        older good one — and if nothing newer than what we already
        serve survives verification, the OLD params keep serving and a
        ``serve-ckpt-corrupt`` fault record is emitted (once per bad
        generation, not once per poll). Returns a swap report:
        {swapped, param_generation, param_staleness, swap_ms?, reason?}.
        """
        newest = max((e for e, _ in _generations(directory) if e >= 0),
                     default=-1)
        t0 = time.monotonic()
        template = {"params": self._params, "norm": self._norm}
        try:
            state, epoch = load_checkpoint(directory, template)
        except FileNotFoundError:
            return {"swapped": False, "reason": "no-checkpoint",
                    "param_generation": self.param_generation,
                    "param_staleness": self.param_staleness}
        except CheckpointCorrupt as exc:
            self.param_staleness = sum(
                1 for e, _ in _generations(directory)
                if e > self.param_generation)
            if ml is not None and newest != self._last_corrupt_gen:
                ml.fault("serve-ckpt-corrupt", epoch=newest,
                         reason=str(exc)[:200])
            self._last_corrupt_gen = newest
            return {"swapped": False, "reason": "all-corrupt",
                    "param_generation": self.param_generation,
                    "param_staleness": self.param_staleness}
        # count published generations the served params still trail
        stale_after = sum(1 for e, _ in _generations(directory)
                          if e > epoch)
        if epoch <= self.param_generation:
            # nothing newer was READABLE; if something newer was
            # PUBLISHED, the newest generation(s) failed verification
            self.param_staleness = sum(
                1 for e, _ in _generations(directory)
                if e > self.param_generation)
            if newest > self.param_generation:
                if ml is not None and newest != self._last_corrupt_gen:
                    ml.fault("serve-ckpt-corrupt", epoch=newest,
                             reason="newest generation failed "
                                    "verification; kept serving "
                                    f"generation {self.param_generation}")
                self._last_corrupt_gen = newest
                reason = "newer-generation-corrupt"
            else:
                reason = "no-newer-generation"
            return {"swapped": False, "reason": reason,
                    "param_generation": self.param_generation,
                    "param_staleness": self.param_staleness}
        if epoch < newest and ml is not None \
                and newest != self._last_corrupt_gen:
            # walked back: swapping to an older-than-newest good gen
            ml.fault("serve-ckpt-corrupt", epoch=newest,
                     reason=f"walked back to generation {epoch}")
            self._last_corrupt_gen = newest
        self.load_params(state["params"], state["norm"],
                         generation=epoch)
        self.refresh()  # retrace-free: same shapes, compiled programs
        swap_ms = (time.monotonic() - t0) * 1000.0
        self.param_staleness = stale_after
        return {"swapped": True, "param_generation": epoch,
                "param_staleness": stale_after,
                "swap_ms": float(swap_ms)}

    def warmup(self, buckets=None) -> float:
        """Trace the refresh program and every query-ladder bucket so
        steady-state traffic replays compiled code. Returns seconds."""
        t0 = time.monotonic()
        if self._logits is None:
            self.refresh()
        for b in (buckets or self.ladder):
            qp = np.full(b, -1, np.int32)
            ql = np.zeros(b, np.int32)
            np.asarray(self._query_prog(self._logits, qp, ql))
        # trace the topology-delta flush with an all-clear mask so the
        # first live delta replays compiled code (no-op on the values)
        m = jax.device_put(
            jnp.zeros((self.P, (self.P - 1) * self.sg.b_max, 1), bool),
            self.trainer._shard)
        self._halo0 = self._flush_prog(self._halo0, m)
        return time.monotonic() - t0

    # ---------------- freshness path ----------------------------------

    @property
    def staleness_age(self) -> int:
        return self._feat_lag

    @property
    def fully_fresh(self) -> bool:
        return self._feat_lag == 0

    def apply_updates(self, node_ids, values) -> int:
        """Patch owned-node features in place (donated scatter), mark
        the dirty-row bitmap, and invalidate layer-0 cache slots off
        the send-lists. Returns the number of halo slots invalidated."""
        if self.cfg.use_pp:
            raise ValueError(
                "feature updates are unsupported under use_pp: the "
                "precompute folds raw features into a trainer-side "
                "aggregate; serve with use_pp off (or rebuild the "
                "engine) to ingest updates")
        ids = np.atleast_1d(np.asarray(node_ids, np.int64))
        vals = np.atleast_2d(np.asarray(values, np.float32))
        if vals.shape != (ids.size, self.n_feat_raw):
            raise ValueError(
                f"values must be [{ids.size}, {self.n_feat_raw}], "
                f"got {vals.shape}")
        if ids.size and (ids.min() < 0
                         or ids.max() >= self.num_global_nodes):
            raise ValueError("node id out of range")
        parts = self._q_part[ids]
        local = self._q_local[ids]
        touched = 0
        top = self.update_ladder[-1]
        for i0 in range(0, ids.size, top):
            sl = slice(i0, min(i0 + top, ids.size))
            n = sl.stop - sl.start
            b = bucket_for(n, self.update_ladder)
            up = np.full(b, -1, np.int32)
            ul = np.zeros(b, np.int32)
            uv = np.zeros((b, vals.shape[1]), np.float32)
            up[:n], ul[:n], uv[:n] = parts[sl], local[sl], vals[sl]
            self._feat = self._patch_prog(self._feat, up, ul, uv)
        self.freshness.mark(parts, local)
        touched = self.cache.invalidate_rows(parts, local)
        self._feat_lag += 1
        if touched:
            self._halo_lag += 1
        return touched

    def apply_graph_deltas(self, report) -> int:
        """Sync the engine with a topology delta the TRAINER just
        applied (Trainer.apply_graph_deltas -> PatchReport): re-bind
        the patched static inputs (send-lists, degrees, kernel tables),
        extend the query routing for new nodes, feed new-node features
        through the compiled patch ladder, zero the layer-0 cache slots
        whose send-list entry moved or vanished, and mark the moved /
        degree-changed rows dirty so the next refresh_boundary() merge
        is bit-identical to a full exchange. No retracing: every shape
        is unchanged (the patcher's slack guarantee). Returns the
        number of halo cache slots invalidated.

        A re-padded report means every compiled program's shapes grew;
        the engine cannot be patched and must be rebuilt."""
        if self.cfg.use_pp:
            raise ValueError(
                "topology deltas are unsupported under use_pp (the "
                "precomputed layer-0 aggregate bakes in the old "
                "topology); serve with use_pp off")
        if report.repadded:
            cache = getattr(self.trainer, "_serving_engines", None)
            if cache:
                cache.clear()
            raise RuntimeError(
                "graph delta re-padded the sharded graph: compiled "
                "serving shapes grew; rebuild the engine via "
                "ServingEngine.for_trainer")
        from ..stream.patch import flush_masks

        sg = self.trainer.sg
        self.sg = sg
        # the trainer re-uploaded every patched array + rebuilt kernel
        # tables; same shapes, so the compiled programs replay
        self._static = {k: v for k, v in self.trainer.data.items()
                        if k not in _NON_STATIC}
        # ---- host routing: new nodes become queryable -----------------
        nid = np.asarray(sg.global_nid)
        self.num_global_nodes = int((nid >= 0).sum())
        self._q_part = np.full(self.num_global_nodes, -1, np.int32)
        self._q_local = np.zeros(self.num_global_nodes, np.int32)
        for p in range(self.P):
            own = np.nonzero(nid[p] >= 0)[0]
            self._q_part[nid[p, own]] = p
            self._q_local[nid[p, own]] = own.astype(np.int32)
        # ---- new-node features -> private feature shard ---------------
        if report.new_rows is not None and report.new_rows.any():
            pp, rr = np.nonzero(report.new_rows)
            vals = np.asarray(sg.feat)[pp, rr].astype(np.float32)
            top = self.update_ladder[-1]
            for i0 in range(0, pp.size, top):
                sl = slice(i0, min(i0 + top, pp.size))
                n = sl.stop - sl.start
                b = bucket_for(n, self.update_ladder)
                up = np.full(b, -1, np.int32)
                ul = np.zeros(b, np.int32)
                uv = np.zeros((b, vals.shape[1]), np.float32)
                up[:n], ul[:n] = pp[sl].astype(np.int32), \
                    rr[sl].astype(np.int32)
                uv[:n] = vals[sl]
                self._feat = self._patch_prog(self._feat, up, ul, uv)
        # ---- layer-0 cache: rebuild the ledger on the patched
        # send-lists, carrying over hit accounting and still-valid
        # stale bits (slot positions are unchanged where the entry is) -
        old = self.cache
        self.cache = Layer0Cache(sg.send_idx, sg.send_mask)
        self.cache.hits, self.cache.misses = old.hits, old.misses
        if old.stale.shape == self.cache.stale.shape:
            self.cache.stale[:] = old.stale
        touched = 0
        recv = None
        ch = report.changed_send
        if ch is not None and ch.any():
            recv, _ = flush_masks(ch, self.P, sg.b_max)
            # zero every changed receiver slot (device): removed
            # entries must read zero, moved entries are re-shipped by
            # the incremental refresh below
            m = jax.device_put(jnp.asarray(recv[:, :, None]),
                               self.trainer._shard)
            self._halo0 = self._flush_prog(self._halo0, m)
            self.cache.stale |= recv
            touched += int(recv.sum())
            # owner rows behind surviving changed entries: dirty, so
            # the next incremental exchange re-ships their values
            si = np.asarray(sg.send_idx)
            sel = ch & np.asarray(sg.send_mask).astype(bool)
            for p in range(self.P):
                rows = si[p][sel[p]]
                if rows.size:
                    self.freshness.mark(np.full(rows.size, p), rows)
        # ---- degree-changed + new rows: their send view changed (GCN
        # pre-scales by in_deg) or they are brand new — re-ship every
        # slot they feed ------------------------------------------------
        dirty_rows = np.zeros((self.P, self.n_max), bool)
        if report.deg_changed is not None:
            dirty_rows |= report.deg_changed
        if report.new_rows is not None:
            dirty_rows |= report.new_rows
        pp, rr = np.nonzero(dirty_rows)
        if pp.size:
            self.freshness.mark(pp, rr)
            touched += self.cache.invalidate_rows(pp, rr)
        # ---- staleness ledger -----------------------------------------
        self._feat_lag += 1
        if touched:
            self._halo_lag += 1
        self.topo_generation += 1
        return touched

    def refresh_boundary(self, ml=None) -> int:
        """Replay the send-list exchange for dirty rows only, merging
        fresh values into the resident halo cache (bit-identical to a
        full re-exchange — pinned by test). Returns slots refreshed.

        With the wire-integrity guard on (--integrity-check-every),
        a checksum mismatch on any dirty-row block discards the merge
        and rebuilds the whole halo from a full exchange — the
        recovery hammer — recording a contracted ``integrity`` event
        on `ml` when a metrics logger is supplied."""
        if not self.freshness.any:
            return 0
        n = self.cache.n_stale
        if self._wire_guard:
            new_halo, bad = self._inc_prog(
                self._feat, self._halo0, self.freshness.dirty,
                self._static)
            wb = int(bad)
            if wb:
                self.wire_bad_total += wb
                # the merged halo is suspect: rebuild from scratch
                new_halo = self.full_boundary_exchange()
                if ml is not None:
                    ml.integrity(epoch=self.topo_generation,
                                 check="wire", outcome="mismatch",
                                 target="halo", cadence=0,
                                 overhead_s=0.0, blocks=wb,
                                 detail="serving dirty-row exchange; "
                                        "halo rebuilt via full "
                                        "exchange")
            self._halo0 = new_halo
        else:
            self._halo0 = self._inc_prog(
                self._feat, self._halo0, self.freshness.dirty,
                self._static)
        self.freshness.clear()
        self.cache.mark_fresh()
        self._halo_lag = 0
        return n

    def full_boundary_exchange(self):
        """Rebuild the whole halo block from scratch (the reference the
        incremental path is pinned against; also the recovery hammer)."""
        return self._exchange_prog(self._feat, self._static)

    def refresh(self) -> None:
        """Recompute the full logits shard from the current features +
        halo cache. Served staleness collapses to the halo lag."""
        self._logits = self._refresh_prog(
            self._params, self._norm, self._feat, self._halo0,
            self._static)
        self._feat_lag = self._halo_lag

    # ---------------- query path --------------------------------------

    def query(self, node_ids, stats: Optional[ServingStats] = None
              ) -> np.ndarray:
        """Logits for global node ids, [n, n_class] f32. Pads to the
        ladder bucket; chunks above the top bucket."""
        ids = np.atleast_1d(np.asarray(node_ids, np.int64))
        if ids.size and (ids.min() < 0
                         or ids.max() >= self.num_global_nodes):
            raise ValueError("node id out of range")
        if self._logits is None:
            self.refresh()
        out = np.empty((ids.size, self.n_class), np.float32)
        top = self.ladder[-1]
        for i0 in range(0, ids.size, top):
            sl = slice(i0, min(i0 + top, ids.size))
            n = sl.stop - sl.start
            b = bucket_for(n, self.ladder)
            qp = np.full(b, -1, np.int32)
            ql = np.zeros(b, np.int32)
            qp[:n] = self._q_part[ids[sl]]
            ql[:n] = self._q_local[ids[sl]]
            out[sl] = np.asarray(
                self._query_prog(self._logits, qp, ql))[:n]
        hit = self.fully_fresh
        self.cache.record_queries(ids.size, hit)
        if stats is not None:
            stats.note_serve(ids.size, hit, self.staleness_age)
            stats.note_params(self.param_generation, self.param_staleness)
        return out

    def make_batcher(self, stats: Optional[ServingStats] = None,
                     max_delay_ms: float = 5.0,
                     clock=time.monotonic,
                     max_queue: Optional[int] = None,
                     ticket_deadline_ms: Optional[float] = None,
                     ladder=None) -> MicroBatcher:
        return MicroBatcher(
            run=lambda ids: self.query(ids, stats=stats),
            max_batch=self.ladder[-1], max_delay_ms=max_delay_ms,
            ladder_min=self.ladder[0], clock=clock,
            observer=stats.note_batch if stats is not None else None,
            max_queue=max_queue, ticket_deadline_ms=ticket_deadline_ms,
            on_shed=stats.note_shed if stats is not None else None,
            admission_ladder=ladder)
