"""RAM-bounded sequential execution of the pipelined SPMD step.

Runs the SAME staleness-1 training step as the shard_map Trainer
(trainer.py:671-792) one rank at a time on a single device, with the
collectives replaced by host-side routing. This is exact, not an
approximation, because PipeGCN-style pipelining (reference
feature_buffer.py:153-163, 219-236) makes every cross-rank input to
epoch e an output of epoch e-1:

  - layer halo features consumed at epoch e were exchanged at e-1
    (the staleness-1 carry), so rank r's epoch-e compute never needs a
    peer's epoch-e activations;
  - the boundary gradients injected at e are the probe cotangents the
    peers computed at e-1;
  - the only intra-epoch collective is psum(grads) — an associative
    reduction the host performs after the per-rank backward passes.

Peak memory is therefore ONE rank's tables + activations regardless of
P, which makes papers100M-class 64-part configs (reference
helper/utils.py:17-30; BASELINE.json multi-host grid) trainable on a
single host for validation — the role dgl's per-part files + a >=120 GB
host play for the reference (README.md:29-30).

Routing mirrors parallel/halo.py exactly:
  exchange_blocks: receiver r's distance-d halo block is owner
    (r-d) mod P's send block for distance d (_fwd_perm);
  return_blocks: owner o's distance-d bgrad block is the probe
    cotangent computed by peer (o+d) mod P at its distance-d slot
    (_bwd_perm).
tests/test_sequential.py pins loss-trajectory equality against the
shard_map Trainer on a multi-device CPU mesh.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..models.sage import ModelConfig, forward, init_norm_state, init_params
from ..obs.metrics import memory_snapshot
from ..train.losses import bce_logits_sum, cross_entropy_sum
from ..train.optim import adam_init, adam_update
from .halo import make_stale_concat
from .trainer import TrainConfig


def _ladder_caps(edge_src_by_rank, edge_dst_by_rank, P, n_max,
                 n_src_rows):
    """Shared bucket ladders + per-bucket row caps WITHOUT building any
    tables: one cheap degree-histogram pass per rank (the streamed
    analogue of build_sharded_bucket_tables's cap scan)."""
    from ..ops.bucket_spmm import degree_hist, fit_widths, row_cap

    hists = []
    for r in range(P):
        src = np.asarray(edge_src_by_rank(r))
        dst = np.asarray(edge_dst_by_rank(r))
        real = dst < n_max
        hists.append((np.bincount(dst[real], minlength=n_max),
                      np.bincount(src[real], minlength=n_src_rows)))
    # one ladder a direction, fitted to the histograms of every rank
    fw = fit_widths(degree_hist(di for di, _ in hists))
    bw = fit_widths(degree_hist(do for _, do in hists))

    def counts(deg, widths):
        w = np.asarray(widths, np.int64)
        bid = np.minimum(np.searchsorted(w, np.maximum(deg, 1)),
                         len(widths) - 1)
        real = deg > 0
        return np.bincount(bid[real], minlength=len(widths))

    fwd_caps = np.zeros(len(fw), np.int64)
    bwd_caps = np.zeros(len(bw), np.int64)
    for di, do in hists:
        fwd_caps = np.maximum(fwd_caps, counts(di, fw))
        bwd_caps = np.maximum(bwd_caps, counts(do, bw))
    return (fw, bw, [row_cap(c) for c in fwd_caps],
            [row_cap(c) for c in bwd_caps])


def _rank_bucket_tables(edge_src, edge_dst, n_max, n_src_rows, fw, bw,
                        fwd_caps, bwd_caps):
    """One rank's bucket tables padded to the shared caps — same
    layout/keys as build_sharded_bucket_tables minus the leading device
    axis, so one traced program serves every rank."""
    from ..ops.bucket_spmm import BucketPlan, pad_to_caps

    # one part a direction: the ladders and caps above are the uncut
    # tables' (bucket_spmm.Direction)
    p = BucketPlan(edge_src, edge_dst, n_max, n_src_rows,
                   fwd_widths=fw, bwd_widths=bw, parts=(1, 1))
    fwd, bwd = p.fwd.whole(), p.bwd.whole()
    fwd_mats, fwd_inv = pad_to_caps(fwd.mats, fwd.inv, fwd_caps, n_src_rows)
    bwd_mats, bwd_inv = pad_to_caps(bwd.mats, bwd.inv, bwd_caps, n_max)
    t = {"bkt_fwd_inv": fwd_inv, "bkt_bwd_inv": bwd_inv}
    t.update((f"bkt_fwd_{b:02d}", m)
             for b, m in enumerate(fwd_mats) if m.shape[1])
    t.update((f"bkt_bwd_{b:02d}", m)
             for b, m in enumerate(bwd_mats) if m.shape[1])
    return t


class SequentialRunner:
    """One-rank-at-a-time executor of the pipelined training step.

    sg: a ShardedGraph (arrays may be v3 memmaps — only rank slices are
    materialized). feat_fn/label_fn(rank) optionally synthesize the
    rank's [n_max, F] features / [n_max] labels instead of reading
    sg.feat/sg.label (papers100M-scale artifacts store topology only).
    """

    def __init__(self, sg, cfg: ModelConfig, tcfg: TrainConfig,
                 feat_fn: Optional[Callable[[int], np.ndarray]] = None,
                 label_fn: Optional[Callable[[int], np.ndarray]] = None,
                 table_cache: Optional[Dict[int, dict]] = None,
                 compact_halo: bool = False,
                 keep_carry: bool = True,
                 log: Callable[[str], None] = lambda s: None,
                 metrics=None,
                 check_finite: bool = True,
                 fault_plan=None,
                 staleness_probe_every: int = 0):
        if not tcfg.enable_pipeline:
            raise ValueError("SequentialRunner implements the pipelined "
                             "(staleness-1) step; vanilla mode has "
                             "intra-epoch halo dependencies between "
                             "ranks and needs the mesh trainer")
        if cfg.norm == "batch":
            raise ValueError("SyncBatchNorm needs intra-epoch psum of "
                             "activations; use norm='layer' or None")
        if cfg.model == "gat":
            raise ValueError("gat is not wired into SequentialRunner")
        if cfg.use_pp:
            raise ValueError("use_pp's one-shot precompute is a "
                             "cross-rank exchange; run with use_pp=False")
        self.sg = sg
        self.cfg = dataclasses.replace(cfg, sorted_edges=True)
        self.tcfg = tcfg
        self.P = sg.num_parts
        self.n_max = sg.n_max
        self.b_max = sg.b_max
        self.H = sg.halo_size
        self.n_train = float(sg.n_train_global)
        self._feat_fn = feat_fn
        self._label_fn = label_fn
        # caching every rank's tables would break the O(one-rank) RAM
        # bound this class exists for (P=64 at papers100M scale is tens
        # of GB of tables) — it is therefore strictly opt-in: pass a
        # dict (can be lru-like) only when the graph is small enough
        self._table_cache = table_cache
        self._log = log
        # optional obs.MetricsLogger: run_epoch appends one epoch
        # record per completed epoch (same schema the mesh trainer
        # emits, obs/schema.py), so full-scale sequential validation
        # runs feed the same report CLI
        self._metrics = metrics
        # resilience wiring (docs/RESILIENCE.md): a multi-hour
        # sequential epoch must not keep burning ranks after the loss
        # went non-finite — run_epoch raises DivergenceError (emitting
        # a fault record) and the caller decides rollback; the host
        # holds params/opt, so any checkpoint discipline works.
        # fault_plan (resilience.FaultPlan) supports nan-loss injection
        # for chaos-testing that path.
        self._check_finite = check_finite
        self._fault_plan = fault_plan
        # staleness probes (same contract as Trainer.fit's
        # staleness_probe_every; obs/schema.py 'staleness' records): on
        # probe epochs run_epoch compares the stale halo rows each rank
        # consumed against the fresh ones it routed — host arrays here,
        # so the drift is a plain numpy reduction over ranks
        self._probe_every = max(int(staleness_probe_every), 0)
        if self._probe_every and not keep_carry:
            raise ValueError("staleness probes need keep_carry=True "
                             "(one-shot mode has no carry to compare)")

        self._glayers = [str(i) for i in range(cfg.n_graph_layers)]
        self._widths = {k: cfg.layer_sizes[int(k)] for k in self._glayers}

        # compact_halo: replace the mesh trainer's uniform per-distance
        # pad (b_max = global max over ALL (owner, dest) pairs) with
        # per-distance caps B_d = max over owners of send_counts[:, d-1].
        # On power-law graphs (papers100M class) the uniform pad wastes
        # ~10x halo rows — locality puts huge send lists at distance 1
        # and small ones everywhere else. Exact for dropout=0 (dropped
        # pad rows are zero-feature, zero-edge); with dropout>0 the
        # [N+H, F] mask shape changes, so trajectories differ from the
        # mesh trainer by dropout noise only.
        self.compact = compact_halo
        # keep_carry=False: one-shot mode — run epoch 0 (stale buffers
        # are zeros by definition) without routing or storing the next
        # carry. The carry for ALL ranks is inherently distributed state
        # (P x layers x 2 x [H, F] — hundreds of GB at papers100M
        # scale); a single-host full-scale validation step cannot hold
        # it, and does not need to for one step.
        self.keep_carry = keep_carry
        if self.compact and self.P > 1:
            caps = [int(np.max(np.asarray(sg.send_counts)[:, dd]))
                    for dd in range(self.P - 1)]
            # round to 8 for layout friendliness but never beyond the
            # artifact's own pad (send_idx is only b_max wide)
            caps = [min(-(-c // 8) * 8, self.b_max) if c else 0
                    for c in caps]
            self._b_caps = caps
            self._b_off = np.concatenate(
                [[0], np.cumsum(caps)]).astype(np.int64)
            self.H = int(self._b_off[-1])
        else:
            self.compact = False
            self._b_caps = [self.b_max] * max(self.P - 1, 0)
            self._b_off = np.arange(self.P) * self.b_max
        n_src_rows = self.n_max + self.H
        self._ladder = _ladder_caps(
            lambda r: self._remap_src(r, np.asarray(
                sg.edge_src[r][:int(sg.edge_count[r])])),
            lambda r: np.asarray(sg.edge_dst[r][:int(sg.edge_count[r])]),
            self.P, self.n_max, n_src_rows)
        self._n_src_rows = n_src_rows
        # telemetry: host-routed halo traffic per epoch (forward rows +
        # returned cotangents for every rank) — the sequential analogue
        # of Trainer.est_halo_bytes_per_epoch
        item = jnp.dtype(self.cfg.compute_dtype).itemsize
        self._halo_bytes = 0 if self.P == 1 else int(sum(
            2 * self.P * self.H * w * item
            for w in self._widths.values()))

        rng = jax.random.PRNGKey(tcfg.seed)
        self.params = init_params(rng, self.cfg)
        self.opt = adam_init(self.params)
        self.norm = init_norm_state(self.cfg)

        cdt = self.cfg.compute_dtype
        zeros = lambda dt: {
            k: np.zeros((self.H, self._widths[k]), dt)
            for k in self._glayers}
        # per-rank receiver-side carry, exactly Trainer._init_comm
        self.comm = [
            {"halo": zeros(cdt), "bgrad": zeros(cdt),
             **({"favg": zeros(np.float32)} if tcfg.feat_corr else {}),
             **({"bavg": zeros(np.float32)} if tcfg.grad_corr else {})}
            for _ in range(self.P)
        ] if keep_carry else None
        self.last_epoch = 0
        self._jit_rank = jax.jit(self._make_rank_step())
        self._jit_adam = jax.jit(
            lambda g, o, p: adam_update(g, o, p, lr=tcfg.lr,
                                        weight_decay=tcfg.weight_decay))

    # ---------------- per-rank data ----------------------------------
    def _remap_src(self, r: int, src: np.ndarray) -> np.ndarray:
        """Map halo slots from the artifact's uniform-b_max numbering
        (n_max + (d-1)*b_max + k, partition/halo.py _localize_edges) to
        the compact per-distance layout. Identity when not compact."""
        if not self.compact:
            return src
        halo = src >= self.n_max
        slot = src[halo].astype(np.int64) - self.n_max
        dd = slot // self.b_max          # distance-1 index (d-1)
        k = slot % self.b_max
        out = src.astype(np.int64).copy()
        out[halo] = self.n_max + self._b_off[dd] + k
        return out

    def _compact_send(self, r: int):
        """Flattened send idx/mask in the compact per-distance layout
        ([H] each; rows beyond a distance's real send count masked)."""
        sg = self.sg
        idx = np.zeros(self.H, np.int32)
        mask = np.zeros(self.H, bool)
        for dd in range(self.P - 1):
            c = self._b_caps[dd]
            if not c:
                continue
            o = int(self._b_off[dd])
            idx[o:o + c] = np.asarray(sg.send_idx[r, dd, :c])
            mask[o:o + c] = np.asarray(sg.send_mask[r, dd, :c])
        return idx, mask

    def _rank_data(self, r: int) -> Dict[str, np.ndarray]:
        sg = self.sg
        e = int(sg.edge_count[r])
        src = self._remap_src(r, np.asarray(sg.edge_src[r][:e]))
        dst = np.asarray(sg.edge_dst[r][:e])
        if self._table_cache is not None and r in self._table_cache:
            tables = self._table_cache[r]
        else:
            fw, bw, fc, bc = self._ladder
            tables = _rank_bucket_tables(src, dst, self.n_max,
                                         self._n_src_rows, fw, bw, fc, bc)
            if self._table_cache is not None:
                self._table_cache[r] = tables
        feat = (self._feat_fn(r) if self._feat_fn is not None
                else np.asarray(sg.feat[r]))
        label = (self._label_fn(r) if self._label_fn is not None
                 else np.asarray(sg.label[r]))
        if self.compact:
            sidx, smask = self._compact_send(r)
        else:
            sidx = np.asarray(sg.send_idx[r]).astype(np.int32).reshape(-1)
            smask = np.asarray(sg.send_mask[r]).reshape(-1)
        d = {
            "feat": feat.astype(self.cfg.compute_dtype),
            "label": label,
            "train_mask": np.asarray(sg.train_mask[r]),
            "in_deg": np.asarray(sg.in_deg[r]),
            # flat [H] in both layouts (the uniform layout's flattened
            # [P-1, B] order IS the halo slot order)
            "send_idx": sidx,
            "send_mask": smask,
            "row_mask": (np.arange(self.n_max)
                         < int(sg.inner_count[r])).astype(np.float32),
        }
        d.update(tables)
        return d

    # ---------------- the jitted per-rank step ------------------------
    def _make_rank_step(self):
        cfg, tcfg = self.cfg, self.tcfg
        n_max, H = self.n_max, self.H
        glayers, widths = self._glayers, self._widths
        multilabel = self.sg.multilabel
        cdt = cfg.compute_dtype
        keep_carry = self.keep_carry

        def rank_step(params, norm, rng, d, stale_halo, stale_bgrad):
            """stale_halo/stale_bgrad: {layer: [H, F]} in compute dtype —
            already the corrected (EMA) buffers when corr is on; the
            host picks them, mirroring trainer.py:697-706."""
            from ..ops.bucket_spmm import make_device_bucket_spmm_fn
            from ..resilience.numerics import PHASES

            probes = {k: jnp.zeros((H, widths[k]), cdt) for k in glayers}
            sends = {}

            def comm_update(i, h):
                k = str(i)
                op = make_stale_concat(d["send_idx"], d["send_mask"],
                                       n_max)
                fbuf = op(h, stale_halo[k], stale_bgrad[k], probes_in[k])
                if keep_carry:
                    hs = jax.lax.stop_gradient(h)
                    # this epoch's send rows [H, F], routed by the host
                    # in halo slot order (exchange_blocks's pre-permute
                    # payload, flattened)
                    blk = jnp.take(hs, d["send_idx"], axis=0,
                                   mode="clip")
                    sends[k] = jnp.where(d["send_mask"][:, None], blk,
                                         0.0)
                return fbuf

            spmm_fn = make_device_bucket_spmm_fn(
                d, d["in_deg"], self._n_src_rows,
                chunk_edges=cfg.spmm_chunk, rem_dtype=cfg.rem_dtype)
            edge_dummy = jnp.zeros((8,), jnp.int32)

            def loss_fn(params, probes_arg):
                nonlocal probes_in
                probes_in = probes_arg
                # numerics tripwire: same per-phase non-finite counts
                # the mesh trainer harvests (resilience/numerics.py),
                # summed across ranks by run_epoch
                counts = {ph: jnp.zeros((), jnp.int32) for ph in PHASES}

                def nf_probe(name, x):
                    counts[name] = counts[name] + jnp.sum(
                        ~jnp.isfinite(x), dtype=jnp.int32)

                logits, new_norm = forward(
                    params, cfg, d["feat"], edge_dummy, edge_dummy,
                    d["in_deg"], n_max, training=True, rng=rng,
                    comm_update=comm_update, norm_state=norm,
                    psum=lambda x: x, row_mask=d["row_mask"],
                    spmm_fn=spmm_fn, gat_fn=None,
                    probe=nf_probe,
                )
                if multilabel:
                    loss = bce_logits_sum(logits, d["label"],
                                          d["train_mask"])
                else:
                    loss = cross_entropy_sum(logits, d["label"],
                                             d["train_mask"])
                counts["loss"] = counts["loss"] + jnp.sum(
                    ~jnp.isfinite(loss), dtype=jnp.int32)
                return loss, (new_norm, counts)

            probes_in = probes
            if keep_carry:
                (loss, (new_norm, counts)), (pgrads, probe_grads) = \
                    jax.value_and_grad(loss_fn, argnums=(0, 1),
                                       has_aux=True)(params, probes)
                return loss, pgrads, probe_grads, sends, new_norm, counts
            # one-shot mode: no next-epoch carry, so neither the probe
            # cotangents nor the send rows are fetched (XLA drops the
            # dead halo-cotangent extraction)
            (loss, (new_norm, counts)), pgrads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, probes)
            return loss, pgrads, {}, {}, new_norm, counts

        return rank_step

    # ---------------- epoch loop --------------------------------------
    def run_epoch(self, epoch: int,
                  state_path: Optional[str] = None) -> float:
        """state_path (one-shot mode only): checkpoint the grad
        accumulator + rank cursor after every rank, so a multi-hour
        full-scale epoch survives interruption — the partial sums are
        exact (host psum is associative) and a restart resumes at the
        next rank."""
        import os
        import pickle

        t_start = time.perf_counter()
        tcfg, P, H = self.tcfg, self.P, self.H
        cdt = self.cfg.compute_dtype
        if state_path is not None and self.keep_carry:
            raise ValueError("per-rank resume requires keep_carry=False "
                             "(the carry would need checkpointing too)")
        if tcfg.rng_impl != "threefry":
            base = jax.random.key(tcfg.seed + 17, impl=tcfg.rng_impl)
        else:
            base = jax.random.PRNGKey(tcfg.seed + 17)
        rng_e = jax.random.fold_in(base, epoch)

        tm = jax.tree_util.tree_map
        loss_sum = 0.0
        grad_sum = None
        start_rank = 0
        # crash-consistency: the per-rank state is only resumable
        # against the SAME topology generation it was summed over —
        # partial gradients straddling a graph delta are silently
        # wrong, so a generation mismatch restarts the epoch at rank 0
        gen = int(getattr(self, "topo_generation", 0))
        if state_path is not None and os.path.exists(state_path):
            with open(state_path, "rb") as f:
                st = pickle.load(f)
            if (st["epoch"] == epoch
                    and int(st.get("topo_generation", 0)) == gen):
                start_rank = st["next_rank"]
                loss_sum = st["loss_sum"]
                grad_sum = st["grad_sum"]
                self._log(f"resuming epoch {epoch} at rank {start_rank}")
            elif st["epoch"] == epoch:
                self._log(
                    f"discarding per-rank state: summed at "
                    f"topo_generation {st.get('topo_generation', 0)}, "
                    f"graph now at {gen} — restarting epoch {epoch} "
                    f"at rank 0")
        sends_all, probes_all = [], []
        new_norm0 = None
        nf_counts: Dict[str, int] = {}
        zero_stale = {k: np.zeros((H, self._widths[k]), cdt)
                      for k in self._glayers} if self.comm is None else None
        for r in range(start_rank, P):
            d = self._rank_data(r)
            if self.comm is None:  # one-shot: epoch-0 staleness = zeros
                stale_halo = stale_bgrad = zero_stale
            else:
                c = self.comm[r]
                stale_halo = {
                    k: (c["favg"][k].astype(cdt) if tcfg.feat_corr
                        else c["halo"][k]) for k in self._glayers}
                stale_bgrad = {
                    k: (c["bavg"][k].astype(cdt) if tcfg.grad_corr
                        else c["bgrad"][k]) for k in self._glayers}
            rng_r = jax.random.fold_in(rng_e, r)
            loss, pgrads, probe_grads, sends, new_norm, counts = \
                jax.device_get(
                    self._jit_rank(self.params, self.norm, rng_r, d,
                                   stale_halo, stale_bgrad))
            for k, v in counts.items():
                nf_counts[k] = nf_counts.get(k, 0) + int(v)
            loss_sum += float(loss)
            grad_sum = (pgrads if grad_sum is None
                        else tm(np.add, grad_sum, pgrads))
            sends_all.append(sends)
            probes_all.append(probe_grads)
            if new_norm0 is None:
                new_norm0 = new_norm
            if state_path is not None:
                with open(state_path + ".tmp", "wb") as f:
                    pickle.dump({"epoch": epoch, "next_rank": r + 1,
                                 "loss_sum": loss_sum,
                                 "grad_sum": grad_sum,
                                 "topo_generation": gen}, f)
                os.replace(state_path + ".tmp", state_path)
            self._log(f"rank {r}: loss_sum {loss_sum:.4f}")

        # ---- host-side collectives ----
        pgrads = tm(lambda g: (g / self.n_train).astype(np.float32),
                    grad_sum)
        self.params, self.opt = jax.device_get(
            self._jit_adam(pgrads, self.opt, self.params))
        if new_norm0 is not None:  # resumed-at-P restarts keep norm
            self.norm = new_norm0

        probe_due = (self._probe_every > 0
                     and epoch % self._probe_every == 0
                     and self.comm is not None)
        drift_sq = {k: 0.0 for k in self._glayers}
        fresh_sq = {k: 0.0 for k in self._glayers}
        if self.comm is not None:
            for r in range(P):
                c = self.comm[r]
                for k in self._glayers:
                    halo_next = np.zeros((H, self._widths[k]), cdt)
                    bgrad_next = np.zeros((H, self._widths[k]), cdt)
                    for dd in range(1, P):
                        sl = slice(int(self._b_off[dd - 1]),
                                   int(self._b_off[dd - 1])
                                   + self._b_caps[dd - 1])
                        # _fwd_perm: r receives owner (r-d)'s
                        # distance-d send rows (same slot range)
                        halo_next[sl] = sends_all[(r - dd) % P][k][sl]
                        # _bwd_perm: r's send rows were consumed by (r+d)
                        bgrad_next[sl] = probes_all[(r + dd) % P][k][sl]
                    if probe_due:
                        # stale = the carry consumed this epoch, fresh
                        # = what the ranks just routed; aggregate the
                        # squared norms over every rank
                        d = (halo_next.astype(np.float64)
                             - c["halo"][k].astype(np.float64))
                        drift_sq[k] += float(np.sum(d * d))
                        fresh_sq[k] += float(np.sum(
                            halo_next.astype(np.float64) ** 2))
                    c["halo"][k] = halo_next
                    c["bgrad"][k] = bgrad_next
                    m = tcfg.corr_momentum
                    if tcfg.feat_corr:
                        c["favg"][k] = (
                            m * c["favg"][k]
                            + (1 - m) * halo_next.astype(np.float32))
                    if tcfg.grad_corr:
                        c["bavg"][k] = (
                            m * c["bavg"][k]
                            + (1 - m) * bgrad_next.astype(np.float32))
        self.last_epoch = epoch + 1
        mean_loss = loss_sum / self.n_train
        # grad norm over the reduced (psum'd / n_train) gradient —
        # telemetry AND the finiteness guard below
        gnorm = float(np.sqrt(sum(
            float(np.sum(np.square(np.asarray(g, np.float64))))
            for g in jax.tree_util.tree_leaves(pgrads))))
        if self._fault_plan is not None and \
                self._fault_plan.due("nan-loss", epoch):
            self._log(f"fault-injected nan loss at epoch {epoch}")
            mean_loss = float("nan")
        if self._metrics is not None:
            # same record shape as the mesh trainer's (obs/schema.py)
            self._metrics.epoch(
                epoch=epoch,
                step_time_s=time.perf_counter() - t_start,
                loss=float(mean_loss),
                grad_norm=gnorm,
                halo_bytes=self._halo_bytes,
                staleness_age=int(1 if epoch > 0 else 0),
                memory=memory_snapshot(),
            )
        if probe_due:
            layers = {}
            max_rel = 0.0
            for k in self._glayers:
                dn = float(np.sqrt(drift_sq[k]))
                fn = float(np.sqrt(fresh_sq[k]))
                rel = dn / fn if fn > 0 else (0.0 if dn == 0.0 else 1.0)
                layers[k] = {"rel_drift": rel, "fresh_norm": fn}
                max_rel = max(max_rel, rel)
            if self._metrics is not None:
                self._metrics.staleness(epoch=epoch, layers=layers,
                                        max_rel_drift=max_rel)
            self._log(f"staleness probe epoch {epoch}: max relative "
                      f"drift {max_rel:.4f}")
        if self._check_finite and not (np.isfinite(mean_loss)
                                       and np.isfinite(gnorm)):
            from ..resilience import DivergenceError
            from ..resilience.numerics import first_nonfinite_phase

            reason = (f"non-finite loss {mean_loss!r}"
                      if not np.isfinite(mean_loss)
                      else f"non-finite grad norm {gnorm!r}")
            # tripwire provenance: the per-rank counts name the phase
            # where the non-finite value was born
            phase = first_nonfinite_phase(nf_counts)
            extra = {"phase": phase} if phase else {}
            if phase:
                reason += f" (first non-finite phase: {phase})"
            if self._metrics is not None:
                self._metrics.fault(kind="divergence", epoch=epoch,
                                    reason=reason, **extra)
            raise DivergenceError(
                f"sequential epoch {epoch}: {reason}; the caller holds "
                f"the host-side state and decides rollback")
        return mean_loss
