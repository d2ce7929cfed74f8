"""SPMD trainer: the whole training job as one jitted program per epoch.

Replaces the reference's per-rank `run()` (train.py:242-400) — N Python
processes, gloo collectives, autograd hooks, CUDA streams and a thread
pool — with a single `jit(shard_map(...))` train step over a 1-D device
mesh. Everything the reference wove through mutable global state becomes
explicit dataflow in the step's carry:

  reference                                  here
  ---------                                  ----
  ctx.buffer halo recv buffers               comm carry (halo/bgrad/EMA)
  per-param backward hooks + Reducer         lax.psum(grads)/n_train
  SyncBatchNorm dist.all_reduce              psum inside the model
  epoch-pipelined transfers (threads/tags)   staleness-1 carry swap
  torch.optim.Adam                           in-repo adam (train.optim)

Pipelined mode (--enable-pipeline): graph layer i consumes the halo
features exchanged during the *previous* epoch's step and injects the
boundary gradients received then (staleness 1, zeros at epoch 0 —
reference feature_buffer.py:153-163, 219-236); this epoch's halo blocks
and boundary grads are computed alongside and carried forward. Because
next epoch's exchange does not depend on this epoch's loss, XLA can
overlap the collectives with compute inside the step. Optional EMA
smoothing of stale features/grads (--feat-corr/--grad-corr, momentum
`corr_momentum` — reference feature_buffer.py:186-191, parser.py:44-47).
"""

from __future__ import annotations

import contextlib
import dataclasses
import os
import sys
import time
import traceback
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from ..graph.csr import Graph
from ..models.sage import ModelConfig, forward, init_norm_state, init_params
from ..obs import flight as flightrec
from ..obs.format import epoch_line, reference_eval_line, reference_train_line
from ..obs.metrics import device_info, memory_snapshot, mesh_info
from ..obs.profiler import ANCHOR_SPAN
from ..obs.trace import (BlockSpans, PhaseTimer, SpanCursor, named_phase,
                         recorder, span, trace_span)
from ..ops import tuner
from ..ops.spmm import spmm_mean
from ..partition.halo import ShardedGraph
from ..resilience import DivergenceError, PeerLost, Preempted, SentinelConfig
from ..resilience.storage import FAULTY_IO, IO_DEGRADED, IO_KINDS
from ..train.losses import bce_logits_sum, cross_entropy_sum
from ..train.metrics import calc_acc
from ..train.optim import adam_init, adam_update
from .halo import (
    exchange_blocks,
    halo_exchange,
    halo_transport_dtypes,
    make_stale_concat,
    return_blocks,
)
from .mesh import PARTS_AXIS, make_mesh

# 'auto' SpMM selection is table-driven (see _setup_spmm and
# ops/tuner.py): the kernel is resolved from a persisted measured cost
# table (tuning.json in the partition artifact) or a live micro-bench
# campaign — there are no hand-coded shape thresholds.


@dataclasses.dataclass
class TrainConfig:
    lr: float = 1e-2
    weight_decay: float = 0.0
    n_epochs: int = 100
    enable_pipeline: bool = False
    feat_corr: bool = False
    grad_corr: bool = False
    corr_momentum: float = 0.95
    log_every: int = 10
    seed: int = 0
    eval: bool = True
    # run up to this many epochs per dispatch (lax.scan inside the jitted
    # step); 1 = one program per epoch (reference-like granularity)
    fused_epochs: int = 1
    # PRNG implementation for the per-epoch dropout keys: 'threefry'
    # (jax default — counter-based, ALU-heavy per element on TPU) or
    # 'rbg' (hardware RNG-backed, much cheaper bit generation; masks
    # differ from threefry at the same seed but are equally valid
    # dropout noise). A floor-shrink lever for the dropout-RNG share of
    # the non-SpMM epoch floor (scripts/epoch_anatomy.py measures it).
    # 'unsafe_rbg' drops the fold_in/split guarantees too (fastest;
    # fine for dropout, never for init).
    rng_impl: str = "threefry"
    # reuse each dropout mask for N consecutive epochs (0/1 = fresh
    # mask every epoch): the per-epoch key becomes fold_in(base,
    # epoch // N), so N epochs share bits and the RNG share of the
    # floor divides by N. Mild regularization change — the mask cycle
    # repeats — acceptable for large N only with measurement.
    dropout_reuse: int = 0
    # halo ppermute wire dtype: 'none' (compute dtype), 'bfloat16', or
    # 'float8' (e4m3 features / e5m2 bgrads, amax-scaled per block —
    # parallel/halo.py). Pipelined mode only: the vanilla path
    # differentiates through the exchange and must stay exact.
    halo_dtype: str = "none"
    # epochs per megastep dispatch (donated-carry lax.scan + ONE host
    # metrics sync per block). 0 = inherit fused_epochs; otherwise
    # overrides it as the block size ceiling in fit().
    epoch_block: int = 0
    # issue the layer-0 halo exchange at the top of the step (before
    # loss/grad work) so its ppermute overlaps the previous epoch's
    # tail inside a fused block. Numerically identical: layer 0's
    # exchange payload is the (pre-scaled) input features, which are
    # loop-invariant. Pipelined mode, no-pp only.
    comm_prefetch: bool = False
    # ---- numerics guardrails (resilience/numerics.py) ----
    # in-graph non-finite tripwire: cheap per-phase isfinite counts
    # (halo concat / spmm / dense / norm / logits / loss / grads) ride
    # the step metrics, so a NaN's BIRTH phase is named in fault
    # records instead of just "loss is nan"
    numerics_tripwire: bool = True
    # dynamic loss scaling: 'off' | 'auto' | a positive number (static
    # scale). Non-'off' also arms in-graph overflow-skip: a non-finite
    # reduced gradient skips that epoch's parameter update (select, no
    # extra dispatch) and the host state machine backs the scale off.
    loss_scale: str = "off"
    # ---- integrity plane (resilience/integrity.py) ----
    # epochs between silent-data-corruption checks: the static-table
    # scrub, the params/carry digest verification, and the Freivalds
    # aggregation check all run at this cadence, and the pipelined
    # halo exchange gains its wire-checksum lane. 0 (default) disables
    # everything and compiles the byte-identical pre-integrity step.
    integrity_check_every: int = 0
    # Run the P-part SPMD program on ONE device: the identical
    # per-device step is wrapped in jax.vmap(axis_name='parts') instead
    # of shard_map — vmap implements psum/ppermute/axis_index
    # semantically, so staleness/convergence studies at P>1 run on a
    # single TPU chip (the environment has exactly one) at chip speed
    # with bit-matching SPMD semantics. Params/opt/norm are stored
    # stacked [P, ...] (identical across parts after the psum'd
    # update). Not for production scaling — collectives become
    # in-device data movement.
    emulate_parts: bool = False
    # ---- training-span plane (obs/trainspan.py) ----
    # always-on per-rank span emission into the metrics sink: compute /
    # halo_exchange / bgrad_return / grad_reduce / checkpoint / eval
    # spans per dispatched block plus the tracesync clock anchors.
    # Host-side Python only — zero effect on the compiled programs
    # (tests/test_trainspan.py pins zero recompiles with spans hot).
    # --no-train-traces turns it off; inert without a metrics sink.
    train_traces: bool = True


class Trainer:
    """Owns mesh, device data, jitted step/eval, and the epoch loop."""

    def __init__(
        self,
        sg: ShardedGraph,
        cfg: ModelConfig,
        tcfg: TrainConfig,
        devices=None,
    ):
        self.sg = sg
        # training arrays from ShardedGraph are CSR-ordered per device
        self.cfg = dataclasses.replace(cfg, sorted_edges=True)
        self._eval_cfg = dataclasses.replace(cfg, sorted_edges=True)
        self.tcfg = tcfg
        self.P = sg.num_parts
        self.emulated = tcfg.emulate_parts
        if self.emulated:
            # one device carries every part; the [P, ...] arrays live
            # whole on it and the parts axis is a vmap batch axis
            self.mesh = make_mesh(1, devices)
            self._shard = NamedSharding(self.mesh, PartitionSpec())
            self._repl = self._shard
        else:
            self.mesh = make_mesh(self.P, devices)
            self._shard = NamedSharding(self.mesh, PartitionSpec(PARTS_AXIS))
            self._repl = NamedSharding(self.mesh, PartitionSpec())

        # host wall clock of each set-up phase (tuner / tables / upload
        # / pp_precompute), accumulated by _timed from the spans of
        # those names; device work inside a phase is waited for, so
        # the split is honest
        self.setup_s: Dict[str, float] = {}
        recorder().install()
        self._setup_spmm()
        # with kernel tables active, the step (and the sharded
        # evaluator) aggregate through them and the raw edge list is
        # only needed for the one-shot pp precompute — at Reddit scale
        # the two int32 edge arrays are ~0.9 GB of HBM that would
        # otherwise sit resident for nothing (forward()'s edge args are
        # untraced when spmm_fn is set, so a token shape suffices)
        self._edges_trimmed = (self._bucket_tables is not None
                               or self._block_tables is not None
                               or self._gat_tables is not None)
        # bucket/block tables can also serve the pp precompute, so the
        # raw edges never reach the device at all
        pp_via_tables = (self._bucket_tables is not None
                         or self._block_tables is not None)
        need_edges = (not self._edges_trimmed) or \
            (cfg.use_pp and not pp_via_tables)
        with self._timed("upload"):
            self.data = jax.block_until_ready(
                self._put_data(skip_edges=not need_edges))
        if cfg.use_pp:
            with self._timed("pp_precompute"):
                self.data["feat"] = jax.block_until_ready(
                    self._precompute_pp())
        with span("init_state"):
            if cfg.compute_dtype != jnp.float32:
                # store input features in the compute dtype so the per-epoch
                # HBM read (and layer-0 halo exchange) is half-width; the pp
                # precompute above still ran in f32
                self.data["feat"] = self.data["feat"].astype(cfg.compute_dtype)
            if self._edges_trimmed and need_edges:
                # edges were uploaded only for the precompute above; drop
                # them now
                dummy = jnp.zeros((self.P, 8), jnp.int32)
                self.data["edge_src"] = jax.device_put(dummy, self._shard)
                self.data["edge_dst"] = jax.device_put(dummy, self._shard)

            rng = jax.random.PRNGKey(tcfg.seed)
            params = init_params(rng, self.cfg)
            if self.emulated:
                # replicated-by-construction: stacked copies stand in for
                # shard_map's replicated spec (the psum'd update keeps every
                # part's copy identical)
                stack = lambda t: jax.tree_util.tree_map(
                    lambda v: jnp.stack([v] * self.P), t)
                params, opt, norm = (stack(params), stack(adam_init(params)),
                                     stack(init_norm_state(self.cfg)))
            else:
                opt = adam_init(params)
                norm = init_norm_state(self.cfg)
            self.state = {
                "params": jax.device_put(params, self._repl),
                "opt": jax.device_put(opt, self._repl),
                "norm": jax.device_put(norm, self._repl),
                "comm": jax.device_put(self._init_comm(), self._shard),
            }
        # ---- numerics guardrails (resilience/numerics.py) ----
        from ..resilience.numerics import LossScaleConfig, LossScaler

        # host side of the dynamic loss-scale state machine; the scale
        # is passed into every dispatch as a traced scalar (value
        # changes never recompile)
        self.loss_scaler = LossScaler(
            LossScaleConfig.parse(getattr(tcfg, "loss_scale", "off")))
        # kernel fallback ladder state: an unproven kernel's first
        # dispatch is guarded (see _dispatch); successful dispatch
        # proves it. Fallbacks taken accumulate here for fit()/bench
        # to surface as contracted `fallback` records.
        self.fallbacks: list = []
        self._kernel_proven = False
        self._inject_kernel_crash = False
        with span("build_step"):
            self._step = self._build_step()
        self._eval_cache: Dict[int, Any] = {}
        self._sharded_eval_cache: Dict[int, Any] = {}
        # compiled sharded-eval programs keyed on (shape, dtype, impl) —
        # ShardedEvaluator instances come and go (one per eval graph id)
        # but their jitted forward is identical whenever the data
        # signature matches, so the program outlives the evaluator
        # (compile-count pinned in tests/test_eval.py)
        self._eval_program_cache: Dict[Any, Any] = {}

        @partial(jax.jit, static_argnames=("n",))
        def _eval_run(params, norm, feat, es, ed, deg, n):
            with named_phase("eval"):
                logits, _ = forward(
                    params, self._eval_cfg, feat, es, ed, deg, n,
                    training=False, norm_state=norm,
                    eval_pp_agg=self._eval_cfg.use_pp,
                )
            return logits

        self._eval_run = _eval_run

    @contextlib.contextmanager
    def _timed(self, phase: str):
        """A set-up span of this name, whose seconds `setup_s` adds up
        by name."""
        sp = recorder().open(phase)
        try:
            yield
        finally:
            recorder().close(sp)
            self.setup_s[phase] = self.setup_s.get(phase, 0.0) + sp.dur_s

    # ---------------- spmm kernel selection ---------------------------

    # bump when any kernel-table layout changes: stale caches must miss
    # v7: bucket and block-remainder tables slot-major [P, w, cap], cap a
    # multiple of 32 (ops/bucket_spmm.py)
    # v8: row-bucket widths fitted to the degree histogram
    # (bucket_spmm.fit_widths): tables of the x1.5 ladder must miss
    # v9: the block kernel's A stored per direction and class in the
    # order the step reads it (block_spmm._dense_tables): one
    # block-id-ordered table with its index matrices must miss
    # v10: a bucket direction whose source rows would make a gather
    # table taller than bucket_spmm.GATHER_PART_BYTES is cut by source
    # rows into parts, each its own tables under '<stem>_p<p>_...'
    # (bucket_spmm.stack_direction): uncut tables of a tall graph
    # must miss
    _TABLES_FORMAT = 10

    def _cached_tables(self, kind: str, build_fn):
        """Disk-cache derived kernel tables next to the partition
        artifact (sg.cache_dir, set by ShardedGraph.load): the O(E)
        host builds cost minutes at 100M-edge scale and depend only on
        the artifact. The cache is stamped with (_TABLES_FORMAT,
        source_edge_checksum) and validated on load — a regenerated
        artifact or a format change must rebuild, never silently load
        tables for a different graph. Corrupt/mismatched caches fall
        back to the build, and `tables_source` says why the file was
        refused. bfloat16 arrays round-trip as uint16 bit
        views (npz stores bf16 as raw void and cannot restore it);
        writes go to a temp file + atomic rename so a killed run (or a
        shared-filesystem race between hosts, halo.py save()) can never
        leave a truncated file the next run trusts."""
        with self._timed("tables"):
            tables, self.tables_source = self._load_or_build_tables(
                kind, build_fn)
        return tables

    def _load_or_build_tables(self, kind: str, build_fn):
        """(tables, "loaded from <file>" | "built in this run" |
        "built in this run (refused <file>: <reason>)")."""
        from ml_dtypes import bfloat16 as bf16

        cd = getattr(self.sg, "cache_dir", None)
        fname = os.path.join(cd, f"{kind}_tables.npz") if cd else None
        stamp = np.asarray(
            [self._TABLES_FORMAT,
             int(self.sg.source_edge_checksum) & ((1 << 64) - 1)],
            dtype=np.uint64)
        source = "built in this run"
        if fname and os.path.exists(fname):
            try:
                z = np.load(fname)
                found = z["__stamp__"] if "__stamp__" in z.files else None
                if found is not None and np.array_equal(found, stamp):
                    bf16_keys = set(z["__bf16_keys__"].tolist())
                    return {
                        k: z[k].view(bf16)
                        if k in bf16_keys else z[k]
                        for k in z.files
                        if k not in ("__bf16_keys__", "__stamp__")
                    }, f"loaded from {fname}"
                if found is None or found.shape != stamp.shape:
                    why = "no stamp"
                elif int(found[0]) != self._TABLES_FORMAT:
                    why = (f"table format {int(found[0])} != "
                           f"{self._TABLES_FORMAT}")
                else:
                    why = ("stale: source_edge_checksum mismatch "
                           "(artifact rebuilt from a different graph)")
            except Exception as exc:  # truncated/corrupt cache
                why = f"corrupt: {exc!r}"[:200]
            source += f" (refused {fname}: {why})"
        tables = build_fn()
        if fname:
            bf16_keys = [k for k, v in tables.items()
                         if v.dtype == bf16]
            tmp = f"{fname}.{os.getpid()}.tmp"
            try:
                with open(tmp, "wb") as f:
                    np.savez(
                        f,
                        __stamp__=stamp,
                        __bf16_keys__=np.asarray(bf16_keys, dtype="U64"),
                        **{k: (v.view(np.uint16) if k in bf16_keys else v)
                           for k, v in tables.items()},
                    )
                os.replace(tmp, fname)
            except OSError:  # read-only artifact dir: cache is optional
                pass
            finally:
                if os.path.exists(tmp):
                    try:
                        os.remove(tmp)
                    except OSError:
                        # genuinely-optional (storage-fault audit):
                        # orphaned temp in a cache dir, never read
                        pass
        return tables, source

    def _setup_spmm(self) -> None:
        """Resolve cfg.spmm_impl: 'bucket' builds the scatter-free
        degree-bucketed aggregation tables (ops/bucket_spmm.py), 'block'
        the hybrid dense-tile MXU kernel's (ops/block_spmm.py), 'xla'
        (default) keeps gather+segment-sum over the raw edge list.
        'auto' resolves from MEASURED cost (_resolve_auto): the
        partition artifact's persisted tuning.json when present and
        trusted, else a live micro-bench campaign (ops/tuner.py) — then
        lands on one of the three concrete impls above. No hand-coded
        shape thresholds exist on this path."""
        impl = self.cfg.spmm_impl
        self._bucket_tables = None
        self._block_tables = None
        self._block_tile = 0
        self._gat_tables = None
        self.tuning = None
        self.tables_source = None  # set by _cached_tables
        # gather slots an edge and the widths chosen, per direction, of
        # the tables in use (bucket_spmm.pad_stats) and, under the
        # block kernel, what its dense half stores
        # (block_spmm.dense_pad_stats), built or loaded
        self.tables_pad = None
        if impl not in ("xla", "auto", "bucket", "block"):
            raise ValueError(f"unknown spmm_impl: {impl}")
        if self.cfg.model == "gat":
            # per-edge attention weights run through the attention-bucket
            # kernel (ops/gat_bucket.py) — same scatter-free structure as
            # the mean path, plus per-bucket row-id tables for the
            # softmax stats. 'auto' always picks it: the raw-edge
            # segment path it replaces is the measured 19.8 s/epoch-class
            # regime (docs/PERF_NOTES.md).
            if impl in ("auto", "bucket"):
                from ..ops.gat_bucket import build_sharded_gat_tables

                self._gat_tables = self._cached_tables(
                    "gat", lambda: build_sharded_gat_tables(self.sg))
                # rem_dtype advice applies only to the bucket kernel,
                # which is what consumes it — not the raw-xla path
                if (self.cfg.rem_dtype is None
                        and float(np.mean(self.sg.edge_count)) > 2e7):
                    import warnings

                    warnings.warn(
                        "GAT at this edge count without --rem-dtype "
                        "float8: bf16 transport measured ~2x the epoch "
                        "time at Reddit scale on earlier code (record "
                        "removed); fp8 is accuracy-validated (results/"
                        "staleness_parity_gat.md)")
            return
        if impl == "auto":
            impl = self._resolve_auto()
        if impl == "bucket":
            self._use_bucket()
        elif impl == "block":
            self._use_block()

    def _use_bucket(self, dirty=None) -> None:
        from ..ops.bucket_spmm import (bucket_pad_stats,
                                       build_sharded_bucket_tables,
                                       validate_bucket_tables)

        merge = int(getattr(self.cfg, "bucket_merge", 0))
        kind = "bucket" + (f"_m{merge}" if merge else "")
        # streaming (enable_stream) keeps a per-shard BucketPlan cache
        # so a delta batch rebuilds plans only for its dirty shards
        cache = getattr(self, "_bucket_plan_cache", None)
        self._bucket_tables = self._cached_tables(
            kind, lambda: build_sharded_bucket_tables(
                self.sg, min_width=merge, plan_cache=cache,
                dirty=dirty))
        # the kernel's clip-mode gathers are sound only for
        # in-bounds tables; a rotted cache must fail HERE, loudly,
        # not clamp to wrong rows mid-epoch, and a table that lost an
        # edge must not train at all
        n_src_rows = self.sg.n_max + self.sg.halo_size
        validate_bucket_tables(
            self._bucket_tables, self.sg.n_max, n_src_rows,
            n_edges=[int(np.count_nonzero(d < self.sg.n_max))
                     for d in self.sg.edge_dst])
        self.tables_pad = bucket_pad_stats(
            self._bucket_tables, self.sg.n_max, n_src_rows)

    def _use_block(self) -> None:
        from ..ops.block_spmm import (build_sharded_block_tables,
                                      validate_dense_tables)
        from ..ops.bucket_spmm import (bucket_pad_stats, table_edges,
                                       validate_bucket_tables)

        w_hint = max(self.cfg.layer_sizes[:self.cfg.n_graph_layers])
        tile = self.cfg.block_tile
        nnz = self.cfg.block_nnz
        grp = self.cfg.block_group
        key = (f"block_{tile}_{w_hint}" + (f"_n{nnz}" if nnz else "")
               + (f"_u{grp}" if grp > 1 else ""))
        self._block_tables = self._cached_tables(
            key,
            lambda: build_sharded_block_tables(
                self.sg, tile=tile, n_feat_hint=w_hint,
                nnz_threshold=nnz, group=grp)[0])
        self._block_tile = tile
        # the tables, built or loaded: the remainder's in bounds, both
        # its directions holding the same edges, and each direction's
        # A counting every edge the remainder does not hold, once (the
        # builder counted both against the edges it was given)
        n_src_rows = self.sg.n_max + self.sg.halo_size
        validate_bucket_tables(self._block_tables, self.sg.n_max,
                               n_src_rows, stem="blkrem")
        self.tables_pad = bucket_pad_stats(
            self._block_tables, self.sg.n_max, n_src_rows, stem="blkrem")
        with self._timed("tables"):
            rem = table_edges(self._block_tables, "blkrem_fwd", n_src_rows)
            dense = validate_dense_tables(
                self._block_tables, tile,
                n_edges=[int(np.count_nonzero(d < self.sg.n_max)) - int(e)
                         for d, e in zip(self.sg.edge_dst, rem)])
        for d, stats in dense.items():
            self.tables_pad[d].update(stats)

    def _resolve_auto(self) -> str:
        """Pick the concrete kernel for spmm_impl='auto' from measured
        cost, never from shape heuristics. Trust order: (1) the
        artifact's persisted tuning.json when tuner format, source edge
        checksum AND config signature all match; (2) a live micro-bench
        campaign (ops/tuner.py) on single-process runs with cfg.tune,
        persisted back into a disk-backed artifact; (3) the tuner's
        fixed deterministic default, with a loud warning — multi-process
        runs never live-tune (per-rank timing noise would argmin
        different kernels and desync the SPMD program). The decision
        (winner + measured cost table + source) lands in self.tuning
        for fit()/bench to emit as a contracted `tuning` record."""
        import warnings

        cfg = self.cfg
        width = max(cfg.layer_sizes[:cfg.n_graph_layers])
        # what an epoch runs: under use_pp the first layer's
        # aggregation (the widest, on a wide-feature graph) is
        # precomputed once, so the candidates are timed over the widest
        # operand an in-step aggregation sees, and the estimate counts
        # those aggregations
        in_step = [self._layer_width(i)
                   for i in self._graph_layer_range()]
        step_width = max(in_step, default=width)
        sig = tuner.signature_for(
            width=width, step_width=step_width,
            block_tile=cfg.block_tile,
            bucket_merge=getattr(cfg, "bucket_merge", 0),
            chunk_edges=cfg.spmm_chunk,
            rng_impl=getattr(self.tcfg, "rng_impl", "threefry"),
            halo_dtype=getattr(self.tcfg, "halo_dtype", "none"),
            epoch_block=int(getattr(self.tcfg, "epoch_block", 0)),
            reorder=str(getattr(self.sg, "reorder", "none")),
            layout_version=int(getattr(self.sg, "layout_version", 1)))
        cd = getattr(self.sg, "cache_dir", None)
        rec, reason = None, "no artifact directory (in-memory graph)"
        if cd:
            rec, reason = tuner.load_tuning(
                cd,
                expect_checksum=getattr(self.sg,
                                        "source_edge_checksum", -1),
                signature=sig)
        source = "artifact"
        if rec is None:
            can_tune = (bool(getattr(cfg, "tune", True))
                        and jax.process_count() == 1)
            if can_tune:
                source = "live"
                with self._timed("tuner"):
                    rec = tuner.tune(
                        self.sg, width, block_tile=cfg.block_tile,
                        block_nnz=cfg.block_nnz,
                        block_group=cfg.block_group,
                        rem_dtype=cfg.rem_dtype or "auto",
                        rem_amax=cfg.rem_amax,
                        chunk_edges=cfg.spmm_chunk,
                        bucket_merge=getattr(cfg, "bucket_merge", 0),
                        rng_impl=getattr(self.tcfg, "rng_impl",
                                         "threefry"),
                        halo_dtype=getattr(self.tcfg, "halo_dtype",
                                           "none"),
                        epoch_block=int(getattr(self.tcfg,
                                                "epoch_block", 0)),
                        step_width=step_width,
                        spmm_per_epoch=max(1, len(in_step)),
                        edge_budget=int(getattr(
                            cfg, "tuner_samples",
                            tuner.DEFAULT_EDGE_BUDGET)))
                if cd:
                    try:
                        tuner.save_tuning(cd, rec)
                    except OSError as exc:
                        # routed-through-degradation (storage-fault
                        # audit): the run proceeds on the measured
                        # in-memory table, but silently losing the
                        # sidecar means every future run re-pays the
                        # micro-bench campaign — say so
                        warnings.warn(
                            f"tuning sidecar write to {cd} failed "
                            f"({exc!r}); io-degraded — the measured "
                            f"table is session-only and the next run "
                            f"will re-tune")
            else:
                source = "default"
                why = ("tuning disabled (--no-tune)"
                       if not getattr(cfg, "tune", True)
                       else "multi-process run (live tuning would "
                            "desync ranks)")
                warnings.warn(
                    f"spmm_impl='auto' with no trusted tuning table "
                    f"({reason}) and no live tune ({why}); using the "
                    f"deterministic default {tuner.DEFAULT_IMPL!r}")
                rec = {"winner": {"name": tuner.DEFAULT_IMPL,
                                  "impl": tuner.DEFAULT_IMPL,
                                  "rem_dtype": None, "rem_amax": False,
                                  "block_group": 1},
                       "costs": []}
        win = dict(rec["winner"])
        self.tuning = {
            "winner": win,
            "source": source,
            "stale_reason": None if source == "artifact" else reason,
            "costs": rec.get("costs", []),
            "emitted": False,
            # did the sample carry the shard's tiles, and how far is
            # the estimate from a traced spmm_s (null without a
            # measured table)
            **{k: rec.get(k) for k in tuner.SAMPLE_FIELDS},
        }
        # fill tuner-chosen transport/group defaults — never override
        # an explicit user pin (a pinned value restricted the grid)
        repl = {}
        if cfg.rem_dtype is None and win.get("rem_dtype"):
            repl["rem_dtype"] = win["rem_dtype"]
            repl["rem_amax"] = bool(win.get("rem_amax"))
        if win["impl"] == "block" and cfg.block_group <= 1 \
                and int(win.get("block_group", 1)) > 1:
            repl["block_group"] = int(win["block_group"])
        if repl:
            self.cfg = dataclasses.replace(self.cfg, **repl)
            self._eval_cfg = dataclasses.replace(self._eval_cfg, **repl)
        return win["impl"]

    # ---------------- data placement ----------------------------------

    @classmethod
    def prewarm_tables(cls, sg: ShardedGraph, cfg: ModelConfig) -> None:
        """Build and disk-cache the kernel tables for (sg, cfg) WITHOUT
        constructing the full trainer — no full-graph device uploads,
        no pp precompute. The O(E) host builds run ahead of time, so
        the next real run only loads npz. spmm_impl='auto'
        additionally runs the tuner's micro-bench campaign (a sample
        of whole destination tile-rows, on the current backend) and
        persists tuning.json
        into the artifact, then warms the winner's tables — this is
        the artifact-build-time tuning entry point."""
        if getattr(sg, "cache_dir", None) is None:
            raise ValueError(
                "prewarm_tables needs a disk-backed artifact "
                "(sg.cache_dir unset — load the ShardedGraph from disk "
                "or set cache_dir); the build would be discarded")
        if cfg.model == "gat":
            # the gat setup branch only builds tables for auto/bucket
            # and returns early — block would silently warm nothing
            cacheable = cfg.spmm_impl in ("auto", "bucket")
        else:
            cacheable = cfg.spmm_impl in ("auto", "bucket", "block")
        if not cacheable:
            raise ValueError(
                f"spmm_impl={cfg.spmm_impl!r} does not disk-cache "
                "tables (only auto/bucket/block — and the gat kernel — "
                "do); nothing to prewarm")
        self = cls.__new__(cls)
        self.sg = sg
        self.cfg = dataclasses.replace(cfg, sorted_edges=True)
        self._eval_cfg = self.cfg
        self.setup_s = {}
        self._setup_spmm()

    def _put_data(self, skip_edges: bool = False) -> Dict[str, jax.Array]:
        sg = self.sg
        edge_dummy = np.zeros((self.P, 8), np.int32)
        arrs = {
            "feat": sg.feat,
            "label": sg.label,
            "train_mask": sg.train_mask,
            "in_deg": sg.in_deg,
            "edge_src": edge_dummy if skip_edges
            else sg.edge_src.astype(np.int32),
            "edge_dst": edge_dummy if skip_edges
            else sg.edge_dst.astype(np.int32),
            "send_idx": sg.send_idx.astype(np.int32),
            "send_mask": sg.send_mask,
            # True for real inner rows, False for padding (BN statistics)
            "row_mask": (
                np.arange(sg.n_max)[None, :] < sg.inner_count[:, None]
            ).astype(np.float32),
        }
        if self._bucket_tables is not None:
            arrs.update(self._bucket_tables)
        if self._block_tables is not None:
            arrs.update(self._block_tables)
        if self._gat_tables is not None:
            arrs.update(self._gat_tables)
        # numpy straight to the sharding: each device receives only its
        # own [1, ...] slice. Going through jnp.asarray first would
        # commit the whole [P, ...] stack to device 0 and reshard from
        # there, a transient of every table on one chip.
        return {
            k: jax.device_put(np.asarray(v), self._shard)
            for k, v in arrs.items()
        }

    # ---------------- comm carry state --------------------------------

    def _graph_layer_range(self):
        """Graph layers that exchange halos: skip layer 0 under use_pp
        (reference feature_buffer.py:60-61, model.py:45-46)."""
        start = 1 if self.cfg.use_pp else 0
        return range(start, self.cfg.n_graph_layers)

    def _layer_width(self, i: int) -> int:
        # input width of graph layer i as seen by the exchange; under
        # use_pp the layer-0 input is the 2F concat but layer 0 never
        # exchanges, so plain layer_sizes applies to all exchanged layers
        return self.cfg.layer_sizes[i]

    def _init_comm(self):
        """Per-device stacked [P, ...] zero buffers for pipelined mode.
        Transport buffers (halo/bgrad) use the compute dtype; the EMA
        correction accumulators (favg/bavg) stay f32 so repeated small
        (1-momentum)-sized updates don't vanish in bf16."""
        if not self.tcfg.enable_pipeline:
            return {}
        H = self.sg.halo_size
        cdt = self.cfg.compute_dtype
        comm = {"halo": {}, "bgrad": {}}
        if self.tcfg.feat_corr:
            comm["favg"] = {}
        if self.tcfg.grad_corr:
            comm["bavg"] = {}
        for i in self._graph_layer_range():
            f = self._layer_width(i)
            # distinct host arrays per slot: aliased device buffers would
            # be donated twice in one Execute() and rejected
            comm["halo"][str(i)] = np.zeros((self.P, H, f), cdt)
            comm["bgrad"][str(i)] = np.zeros((self.P, H, f), cdt)
            if self.tcfg.feat_corr:
                comm["favg"][str(i)] = np.zeros((self.P, H, f), np.float32)
            if self.tcfg.grad_corr:
                comm["bavg"][str(i)] = np.zeros((self.P, H, f), np.float32)
        return comm

    # ---------------- streaming deltas (stream/patch.py) --------------

    def enable_stream(self, patcher) -> None:
        """Attach a GraphPatcher so apply_graph_deltas() can mutate the
        live training graph between epochs (docs/STREAMING.md). The
        patcher must wrap THIS trainer's sg. use_pp is refused: its
        one-shot feature precompute bakes the pre-delta topology into
        the layer-0 concat, which a patch cannot fix incrementally."""
        if self.cfg.use_pp:
            raise ValueError(
                "streaming deltas are incompatible with use_pp: the "
                "precomputed layer-0 aggregation would go stale on "
                "every topology change")
        if patcher.sg is not self.sg:
            raise ValueError(
                "patcher wraps a different ShardedGraph than this "
                "trainer's")
        self._stream = patcher
        # per-shard BucketPlan cache for dirty-shard-only rebuilds
        # (_use_bucket passes it through to build_sharded_bucket_tables)
        self._bucket_plan_cache: dict = {}
        if self._bucket_tables is not None:
            from ..ops.bucket_spmm import table_widths

            # the first delta keeps the widths the step was compiled
            # for, a ladder a part, as every later one does (a dirty
            # rebuild refits only a ladder that a row has outgrown)
            self._bucket_plan_cache.update(
                shape=(self.sg.n_max, self.sg.n_max + self.sg.halo_size),
                min_width=int(getattr(self.cfg, "bucket_merge", 0)),
                widths=tuple(table_widths(self._bucket_tables, f"bkt_{d}")
                             for d in ("fwd", "bwd")))
        # topology generation: bumped once per applied DeltaBatch, and
        # stamped into checkpoints (the journal watermark) so every
        # resume path knows which graph the params trained against
        self.topo_generation = int(getattr(self, "topo_generation", 0))

    def apply_graph_deltas(self, batch, allow_repad: bool = True):
        """Apply one DeltaBatch to the live trainer: patch the sharded
        graph in place (stream/patch.py), rebuild only the affected
        kernel tables, re-upload the data dict, and flush the pipelined
        carry rows whose halo slots changed. Compiled shapes are static
        across deltas (the step is NOT rebuilt) unless the patch
        exhausted the reserved slack and re-padded — then every shape
        grew and a recompile is the documented, loud exception.

        Returns the PatchReport (tables_rebuilt filled in)."""
        patcher = getattr(self, "_stream", None)
        if patcher is None:
            raise RuntimeError(
                "call enable_stream(patcher) before apply_graph_deltas")
        report = patcher.apply(batch, allow_repad=allow_repad)
        self.sg = patcher.sg
        rebuilt = 0
        if report.repadded:
            # padded dims grew: every table and every compiled program
            # keyed on them is invalid. Full rebuild path — identical
            # to __init__'s setup, minus use_pp (refused above).
            self._bucket_plan_cache = {}
            self._setup_spmm()
            self._edges_trimmed = (self._bucket_tables is not None
                                   or self._block_tables is not None
                                   or self._gat_tables is not None)
            rebuilt = self.P * max(
                (self._bucket_tables is not None)
                + (self._block_tables is not None)
                + (self._gat_tables is not None), 1)
            self.data = self._put_data(skip_edges=self._edges_trimmed)
            self._step = self._build_step()
            # the carry's [P, H, f] shapes changed; restart the pipeline
            # from a zero carry (one staleness-reset epoch, same as the
            # sentinel's rollback flush)
            self.state = dict(self.state)
            self.state["comm"] = jax.device_put(
                self._init_comm(), self._shard)
        else:
            dirty = report.touched_parts or None
            if self._bucket_tables is not None:
                self._use_bucket(dirty=dirty)
                rebuilt += len(dirty) if dirty else self.P
            if self._block_tables is not None:
                self._use_block()  # block plans are whole-shard; full
                rebuilt += self.P
            if self._gat_tables is not None:
                from ..ops.gat_bucket import build_sharded_gat_tables

                self._gat_tables = self._cached_tables(
                    "gat", lambda: build_sharded_gat_tables(self.sg))
                rebuilt += self.P
            tables_before = set(self.data)
            self.data = self._put_data(skip_edges=self._edges_trimmed)
            if set(self.data) != tables_before:
                # a kernel's widths were refitted (a row outgrew the
                # ladder's top width) or a bucket emptied or filled:
                # the step's in_specs name the tables one by one
                self._step = self._build_step()
            self._flush_comm_rows(report)
        if self.cfg.compute_dtype != jnp.float32:
            self.data["feat"] = self.data["feat"].astype(
                self.cfg.compute_dtype)
        # the host Graph mutated in place: id-keyed eval caches would
        # serve the pre-delta topology (program cache is shape-keyed
        # and stays — that is the zero-recompile pin)
        self._eval_cache.clear()
        self._sharded_eval_cache.clear()
        report.tables_rebuilt = rebuilt
        self.topo_generation = getattr(self, "topo_generation", 0) + 1
        return report

    # ---------------- integrity plane (resilience/integrity.py) -------

    def _rebuild_static_data(self, dirty=None) -> int:
        """Rebuild the kernel tables (dirty shards only where the
        builder supports it) from the host partition artifact and
        re-upload the static data dict — the SDC scrubber's recovery
        path. Shares the dirty-shard machinery with streaming
        (apply_graph_deltas); compiled shapes are untouched, so the
        zero-recompile pin holds. Returns per-shard rebuild count."""
        dirty = sorted(int(d) for d in dirty) if dirty else None
        rebuilt = 0
        if self._bucket_tables is not None:
            self._use_bucket(dirty=dirty)
            rebuilt += len(dirty) if dirty else self.P
        if self._block_tables is not None:
            self._use_block()  # block plans are whole-shard
            rebuilt += self.P
        if self._gat_tables is not None:
            from ..ops.gat_bucket import build_sharded_gat_tables

            self._gat_tables = self._cached_tables(
                "gat", lambda: build_sharded_gat_tables(self.sg))
            rebuilt += self.P
        # re-upload, mirroring __init__'s placement dance: edges ride
        # along only when the pp precompute (or the raw-edge kernel)
        # needs them, and are trimmed back to a token shape after
        pp_via_tables = (self._bucket_tables is not None
                         or self._block_tables is not None)
        need_edges = (not self._edges_trimmed) or \
            (self.cfg.use_pp and not pp_via_tables)
        self.data = self._put_data(skip_edges=not need_edges)
        if self.cfg.use_pp:
            self.data["feat"] = self._precompute_pp()
        if self.cfg.compute_dtype != jnp.float32:
            self.data["feat"] = self.data["feat"].astype(
                self.cfg.compute_dtype)
        if self._edges_trimmed and need_edges:
            dummy = jnp.zeros((self.P, 8), jnp.int32)
            self.data["edge_src"] = jax.device_put(dummy, self._shard)
            self.data["edge_dst"] = jax.device_put(dummy, self._shard)
        if not rebuilt:
            rebuilt = self.P  # raw-edge mode: the re-upload itself
        return rebuilt

    def _inject_bitflip(self, target: str, epoch: int, log_fn) -> bool:
        """Chaos-lane SDC injection (bitflip@E[:rN]:<target>): flip one
        bit, host-side, in the named state class on THIS rank. The
        device programs are never altered (the resilience/faults.py
        invariant) — the corruption model is state rotting while it
        sits at the boundary, exactly the window the integrity plane's
        digest scrub covers."""
        from ..resilience.integrity import flip_bit

        if jax.process_count() > 1 and target != "params":
            # fetching a SHARDED array is a cross-process collective
            # only this rank would run; multi-process drills flip the
            # replicated params (locally fetchable) instead
            log_fn(f"bitflip:{target} at epoch {epoch} skipped: "
                   f"multi-process injection supports params only")
            return False
        local_devs = [d for d in self.mesh.devices.flat
                      if d.process_index == jax.process_index()]

        def _replicate_local(arr):
            shards = [jax.device_put(arr, d) for d in local_devs]
            return jax.make_array_from_single_device_arrays(
                arr.shape, self._repl, shards)

        if target == "params":
            host_p = jax.device_get(self.state["params"])
            leaves, treedef = jax.tree_util.tree_flatten(host_p)
            # a mid-mantissa bit: the corrupt value stays finite (the
            # point of SDC — the numerics tripwire must NOT see it)
            leaves[0] = flip_bit(leaves[0], bit=11, index=epoch)
            self.state = dict(self.state)
            self.state["params"] = jax.tree_util.tree_map(
                _replicate_local,
                jax.tree_util.tree_unflatten(treedef, leaves))
            return True
        if target in ("carry", "halo"):
            comm = self.state.get("comm") or {}
            group = ("halo" if target == "halo" else
                     next((k for k in sorted(comm) if k != "halo"),
                          None))
            sub = comm.get(group) if group else None
            if not sub:
                log_fn(f"bitflip:{target} at epoch {epoch} skipped: "
                       f"pipelined carry not enabled")
                return False
            key = sorted(sub)[0]
            arr = sub[key]
            host = flip_bit(jax.device_get(arr), bit=7, index=epoch)
            comm = dict(comm)
            comm[group] = dict(sub)
            comm[group][key] = jax.device_put(np.asarray(host),
                                              arr.sharding)
            self.state = dict(self.state)
            self.state["comm"] = comm
            return True
        if target == "tables":
            cand = [k for k in sorted(self.data)
                    if k.startswith(("bkt_", "blk_", "blkrem_",
                                     "gat_"))]
            key = cand[0] if cand else "send_idx"
            arr = self.data[key]
            host = flip_bit(jax.device_get(arr), bit=3, index=epoch)
            self.data = dict(self.data)
            self.data[key] = jax.device_put(np.asarray(host),
                                            arr.sharding)
            return True
        log_fn(f"bitflip:{target} at epoch {epoch} skipped: "
               f"unknown target class")
        return False

    def _flush_comm_rows(self, report) -> None:
        """Zero the pipelined carry rows invalidated by a patch: halo
        slots whose send-list entry moved/appeared/vanished carry
        features (receiver view) and boundary grads (sender view) for
        the WRONG node — one flushed row costs one epoch of staleness-1
        correction on that row, a stale-wrong-node row corrupts it."""
        comm = self.state.get("comm")
        if not comm or report.changed_send is None:
            return
        from ..stream.patch import flush_masks

        recv, send = flush_masks(report.changed_send, self.P,
                                 self.sg.b_max)
        if not (recv.any() or send.any()):
            return
        masks = {"halo": recv, "favg": recv, "bgrad": send, "bavg": send}
        new_comm = {}
        for grp, bufs in comm.items():
            m = jax.device_put(np.asarray(masks[grp][:, :, None]),
                               self._shard)
            new_comm[grp] = {
                k: jnp.where(m, jnp.zeros((), v.dtype), v)
                for k, v in bufs.items()
            }
        self.state = dict(self.state)
        self.state["comm"] = new_comm

    # ---------------- pp precompute -----------------------------------

    def _precompute_pp(self, sg=None, data=None) -> jax.Array:
        """One-time halo exchange + mean aggregation of raw features,
        stored as concat([feat, mean_neigh]) so layer 0 needs no
        training-time communication (reference train.py:169-189).

        Defaults to the trainer's own sharded graph/data; an explicit
        (sg, data) pair computes the same concat for another graph on the
        same mesh (the sharded evaluator's use_pp input).

        Aggregates through bucket/block kernel tables when `data`
        carries them — the raw edge list then never needs to reach the
        device at all."""
        sg = sg if sg is not None else self.sg
        data = data if data is not None else self.data
        n_max = sg.n_max
        use_tables = ("bkt_fwd_inv" in data) or ("blk_fwd_inv" in data)

        def pp(d):
            d = {k: v[0] for k, v in d.items()}
            fbuf = halo_exchange(d["feat"], d["send_idx"], d["send_mask"],
                                 PARTS_AXIS, self.P)
            if use_tables:
                spmm = self.make_device_spmm_closure(
                    d, n_max=n_max, n_src_rows=n_max + sg.halo_size,
                    transport=False)
                ah = spmm(fbuf)
            else:
                ah = spmm_mean(fbuf, d["edge_src"], d["edge_dst"],
                               d["in_deg"], n_max, self.cfg.spmm_chunk,
                               self.cfg.sorted_edges)
            return jnp.concatenate([d["feat"], ah.astype(d["feat"].dtype)],
                                   axis=1)[None]

        spec = PartitionSpec(PARTS_AXIS)
        keys = ["feat", "in_deg", "send_idx", "send_mask"]
        if use_tables:
            keys += [k for k in data
                     if k.startswith(("bkt_", "blk_", "blkrem_"))]
        else:
            keys += ["edge_src", "edge_dst"]
        d_in = {k: data[k] for k in keys}
        if self.emulated:
            # single-device parts emulation: same pp body under
            # vmap(axis_name) — see _build_step
            tm = jax.tree_util.tree_map

            def vpp(d):
                return pp(tm(lambda v: v[None], d))[0]

            fn = jax.jit(jax.vmap(vpp, axis_name=PARTS_AXIS))
            return fn(d_in)
        fn = jax.jit(
            jax.shard_map(
                pp, mesh=self.mesh,
                in_specs=(jax.tree_util.tree_map(lambda _: spec, d_in),),
                out_specs=spec,
            )
        )
        return fn(d_in)

    # ---------------- the train step ----------------------------------

    def make_device_spmm_closure(self, d: Dict[str, jax.Array],
                                 n_max: Optional[int] = None,
                                 n_src_rows: Optional[int] = None,
                                 transport: bool = True):
        """Per-device mean-aggregation closure over the stripped (no
        leading device axis) table arrays in `d` — or None when `d`
        carries no kernel tables (raw-edge XLA path). The kernel kind is
        read off the table keys present, so the same builder serves the
        train step (tables matching cfg.spmm_impl) and the sharded
        evaluator (whose foreign eval graphs carry bucket tables
        regardless of the training impl). Shape overrides cover eval
        graphs sharded differently from the training graph."""
        cfg = self.cfg
        n_max = self.sg.n_max if n_max is None else n_max
        if n_src_rows is None:
            n_src_rows = n_max + self.sg.halo_size
        # transport=False: one-shot consumers (the pp precompute of RAW
        # features) must not inherit the narrowed per-epoch gather
        # transport — their cost is irrelevant and raw feature ranges
        # can exceed e4m3's +-448
        rem_dtype = cfg.rem_dtype if transport else None
        if "bkt_fwd_inv" in d:
            from ..ops.bucket_spmm import make_device_bucket_spmm_fn

            return make_device_bucket_spmm_fn(
                d, d["in_deg"], n_src_rows, chunk_edges=cfg.spmm_chunk,
                rem_dtype=rem_dtype,
                rem_amax=cfg.rem_amax and transport,
            )
        if "blk_fwd_inv" in d:
            from ..ops.block_spmm import make_device_block_spmm_fn

            return make_device_block_spmm_fn(
                d, d["in_deg"], n_max, n_src_rows, self._block_tile,
                chunk_edges=cfg.spmm_chunk, rem_dtype=rem_dtype,
                rem_amax=cfg.rem_amax and transport,
            )
        return None

    def make_device_gat_closure(self, d: Dict[str, jax.Array],
                                n_max: Optional[int] = None,
                                n_src_rows: Optional[int] = None,
                                transport: bool = True):
        """Per-device attention-aggregation closure (ops/gat_bucket.py)
        over the stripped table arrays in `d` — or None when `d`
        carries no attention-bucket tables (raw-edge GAT path).
        transport=False exempts one-shot metric-bearing consumers from
        the narrowed gather transport (same contract as
        make_device_spmm_closure)."""
        if "gat_fwd_inv" not in d:
            return None
        from ..ops.gat_bucket import make_device_gat_fn

        cfg = self.cfg
        n_max = self.sg.n_max if n_max is None else n_max
        if n_src_rows is None:
            n_src_rows = n_max + self.sg.halo_size
        return make_device_gat_fn(
            d, n_max, n_src_rows, cfg.n_heads, cfg.leaky_slope,
            chunk_edges=cfg.spmm_chunk,
            rem_dtype=cfg.rem_dtype if transport else None,
        )

    def _build_step(self):
        from ..resilience.numerics import PHASES, LossScaleConfig

        sg, cfg, tcfg, P = self.sg, self.cfg, self.tcfg, self.P
        n_max, b_max, H = sg.n_max, sg.b_max, sg.halo_size
        n_train = float(sg.n_train_global)
        multilabel = sg.multilabel
        pipeline = tcfg.enable_pipeline
        glayers = list(self._graph_layer_range())
        momentum = tcfg.corr_momentum
        # trace-time gates for the numerics guardrails: the tripwire
        # adds a handful of isfinite reductions; loss scaling adds the
        # scale multiply + the overflow-skip select. Both off -> the
        # traced program is byte-identical to the pre-guardrail step
        # (scale is a dead input).
        tripwire = bool(getattr(tcfg, "numerics_tripwire", True))
        ls_on = LossScaleConfig.parse(
            getattr(tcfg, "loss_scale", "off")).enabled
        # halo wire compression (parallel/halo.py): pipelined mode only
        # — the vanilla path differentiates through the exchange and a
        # lossy cast there would silently bias gradients
        halo_dt = getattr(tcfg, "halo_dtype", "none") or "none"
        if halo_dt != "none" and not pipeline:
            raise ValueError(
                "halo_dtype compression requires enable_pipeline: the "
                "vanilla exchange is differentiated and must stay exact")
        feat_dt, bgrad_dt = halo_transport_dtypes(halo_dt)
        # layer-0 prefetch: the layer-0 exchange payload is the
        # (pre-scaled) input features — parameter-independent — so it
        # can be issued at the very top of the step, overlapping the
        # previous epoch's tail inside a fused block. use_pp has no
        # layer-0 exchange at all.
        prefetch = (pipeline and bool(getattr(tcfg, "comm_prefetch", False))
                    and not cfg.use_pp and 0 in glayers)
        # wire-integrity checksum lane (parallel/halo.py guard=True):
        # every pipelined ring payload (halo features forward, boundary
        # grads back) ships a sender-side checksum through the same
        # permute; receiver mismatches surface as the per-epoch
        # `wire_bad` metric fit() turns into carry-flush recovery. A
        # trace-time gate like the tripwire: off (the default) compiles
        # the byte-identical pre-integrity program. Pipelined mode only
        # — the vanilla exchange is differentiated and its payloads are
        # re-verified by the desync detector instead.
        wire_guard = (pipeline and
                      int(getattr(tcfg, "integrity_check_every", 0)) > 0)

        def step(state, data, rng, scale):
            # strip the leading size-1 device axis of sharded blocks
            d = {k: v[0] for k, v in data.items()}
            comm = {
                grp: {k: v[0] for k, v in bufs.items()}
                for grp, bufs in state["comm"].items()
            }
            params, opt, norm = state["params"], state["opt"], state["norm"]
            with named_phase("dropout"):   # the epoch's key, per rank
                rank = jax.lax.axis_index(PARTS_AXIS)
                rng = jax.random.fold_in(rng, rank)
            psum = lambda x: jax.lax.psum(x, PARTS_AXIS)

            fresh_halo: Dict[str, jax.Array] = {}
            wire_bad: list = []  # per-exchange checksum-mismatch counts

            cdt = cfg.compute_dtype
            if pipeline:
                # probes must be marked device-varying: their cotangents
                # (the per-device halo grads) vary over the mesh axis
                probes = {
                    str(i): jax.lax.pcast(
                        jnp.zeros((H, self._layer_width(i)), cdt),
                        PARTS_AXIS, to="varying",
                    )
                    for i in glayers
                }

                if prefetch:
                    # issue the layer-0 ring collective before any
                    # loss/grad work: its payload is the (gcn-scaled)
                    # input features, reproduced here exactly as the
                    # forward presents them to comm_update(0, ·)
                    with jax.named_scope("halo_prefetch"):
                        h0 = d["feat"].astype(cdt)
                        if cfg.model == "gcn":
                            ds0 = jnp.sqrt(d["in_deg"].astype(jnp.float32))
                            h0 = (h0.astype(jnp.float32)
                                  / ds0[: h0.shape[0], None]).astype(cdt)
                        out = exchange_blocks(
                            h0, d["send_idx"], d["send_mask"],
                            PARTS_AXIS, P, transport_dt=feat_dt,
                            guard=wire_guard,
                        )
                        if wire_guard:
                            out, wb = out
                            wire_bad.append(wb)
                        fresh_halo["0"] = out

                def comm_update(i, h):
                    k = str(i)
                    stale_halo = (
                        comm["favg"][k].astype(cdt) if tcfg.feat_corr
                        else comm["halo"][k]
                    )
                    stale_bgrad = (
                        comm["bavg"][k].astype(cdt) if tcfg.grad_corr
                        else comm["bgrad"][k]
                    )
                    if ls_on:
                        # the carry stores UNSCALED boundary grads (the
                        # scale can change between the epoch that ships
                        # them and the one that consumes them); rescale
                        # into this epoch's scaled-cotangent frame
                        stale_bgrad = (stale_bgrad.astype(jnp.float32)
                                       * scale).astype(cdt)
                    op = make_stale_concat(d["send_idx"], d["send_mask"], n_max)
                    with named_phase("halo_concat"):
                        fbuf = op(h, stale_halo, stale_bgrad, probes_in[k])
                    # this epoch's exchange, consumed next epoch; aux
                    # only. Layer 0's was already issued at step top
                    # when prefetching (identical payload).
                    if k not in fresh_halo:
                        out = exchange_blocks(
                            jax.lax.stop_gradient(h), d["send_idx"],
                            d["send_mask"], PARTS_AXIS, P,
                            transport_dt=feat_dt, guard=wire_guard,
                        )
                        if wire_guard:
                            out, wb = out
                            wire_bad.append(wb)
                        fresh_halo[k] = out
                    return fbuf
            else:
                probes = {}

                def comm_update(i, h):
                    return halo_exchange(
                        h, d["send_idx"], d["send_mask"], PARTS_AXIS, P
                    )

            spmm_fn = self.make_device_spmm_closure(d)
            gat_fn = self.make_device_gat_closure(d)

            def loss_fn(params, probes_arg):
                nonlocal probes_in
                probes_in = probes_arg
                # numerics tripwire (resilience/numerics.py): per-phase
                # non-finite element counts, collected by the forward's
                # probe hook and returned as aux — the provenance the
                # sentinel's fault record names on a NaN trip. Seeded
                # with a device-varying zero: a phase this config never
                # probes would otherwise be an unvarying constant and
                # the psum below would trip shard_map's VMA check.
                vz = (d["row_mask"][0] * 0.0).astype(jnp.int32)
                counts = {ph: vz for ph in PHASES}

                def nf_probe(name, x):
                    with named_phase("tripwire"):
                        counts[name] = counts[name] + jnp.sum(
                            ~jnp.isfinite(x), dtype=jnp.int32)

                logits, new_norm = forward(
                    params, cfg, d["feat"], d["edge_src"], d["edge_dst"],
                    d["in_deg"], n_max, training=True, rng=rng,
                    comm_update=comm_update, norm_state=norm, psum=psum,
                    row_mask=d["row_mask"], spmm_fn=spmm_fn,
                    gat_fn=gat_fn,
                    probe=nf_probe if tripwire else None,
                )
                with named_phase("loss"):
                    if multilabel:
                        loss = bce_logits_sum(logits, d["label"],
                                              d["train_mask"])
                    else:
                        loss = cross_entropy_sum(logits, d["label"],
                                                 d["train_mask"])
                if tripwire:
                    nf_probe("loss", loss)
                # loss scaling happens HERE so every cotangent of this
                # trace (param grads AND probe/halo cotangents) carries
                # the scale; the reduction below divides it back out
                sc_loss = loss * scale if ls_on else loss
                return sc_loss, (new_norm, counts, loss)

            probes_in = probes
            (_, (new_norm, nf_counts, loss)), grads = jax.value_and_grad(
                loss_fn, argnums=(0, 1), has_aux=True
            )(params, probes)
            pgrads, probe_grads = grads

            # gradient reduction: psum of sum-loss grads / global n_train
            # (reference reducer.py:24-31 semantics, minus the threads)
            with named_phase("grad_reduce"):
                pgrads = jax.tree_util.tree_map(
                    lambda g: psum(g) / n_train, pgrads)
                if ls_on:
                    pgrads = jax.tree_util.tree_map(
                        lambda g: g / scale, pgrads)
            # global l2 norm of the reduced gradient (telemetry; the
            # grads are replicated post-psum, so this is the true
            # distributed gradient's norm, not a per-device slice's)
            with named_phase("grad_norm"):
                gnorm = jnp.sqrt(sum(
                    jnp.sum(jnp.square(g.astype(jnp.float32)))
                    for g in jax.tree_util.tree_leaves(pgrads)))
            # non-finite count over the REDUCED gradient: the tripwire's
            # 'grads' phase and (under loss scaling) the overflow flag
            # driving the in-graph step-skip
            if tripwire or ls_on:
                with named_phase("tripwire"):
                    gbad = sum(
                        jnp.sum(~jnp.isfinite(g), dtype=jnp.int32)
                        for g in jax.tree_util.tree_leaves(pgrads))
            if tripwire:
                # forward-phase counts are per-device partials; psum
                # makes them the global counts (replicated, like the
                # loss metric). The grads count is post-psum already.
                with named_phase("tripwire"):
                    nf_counts = {k: psum(v) for k, v in nf_counts.items()}
                nf_counts["grads"] = gbad
            with named_phase("adam_update"):
                new_params, new_opt = adam_update(
                    pgrads, opt, params, lr=tcfg.lr,
                    weight_decay=tcfg.weight_decay,
                )
            if ls_on:
                # overflow-skip: a non-finite reduced gradient anywhere
                # keeps params/opt at their previous values — the
                # skipped step costs one epoch, not the run. The host
                # state machine (fit() + LossScaler) sees the flag and
                # backs the scale off.
                ls_ok = gbad == 0
                sel = lambda n, o: jnp.where(ls_ok, n, o)
                new_params = jax.tree_util.tree_map(sel, new_params,
                                                    params)
                new_opt = jax.tree_util.tree_map(sel, new_opt, opt)

            new_comm = {}
            if pipeline:
                new_comm = {"halo": {}, "bgrad": {}}
                if tcfg.feat_corr:
                    new_comm["favg"] = {}
                if tcfg.grad_corr:
                    new_comm["bavg"] = {}
                for i in glayers:
                    k = str(i)
                    new_comm["halo"][k] = fresh_halo[k]
                    # ship this epoch's halo cotangents to their owners
                    bg = return_blocks(probe_grads[k], PARTS_AXIS, P,
                                       b_max, transport_dt=bgrad_dt,
                                       guard=wire_guard)
                    if wire_guard:
                        bg, wb = bg
                        wire_bad.append(wb)
                    if ls_on:
                        # probe cotangents carry this epoch's loss
                        # scale; the carry stores them UNSCALED (see
                        # comm_update's rescale on consumption)
                        bg = (bg.astype(jnp.float32) / scale).astype(
                            bg.dtype)
                    new_comm["bgrad"][k] = bg
                    if tcfg.feat_corr:
                        new_comm["favg"][k] = (
                            momentum * comm["favg"][k]
                            + (1 - momentum) * fresh_halo[k]
                        )
                    if tcfg.grad_corr:
                        new_comm["bavg"][k] = (
                            momentum * comm["bavg"][k] + (1 - momentum) * bg
                        )
                new_comm = {
                    grp: {k: v[None] for k, v in bufs.items()}
                    for grp, bufs in new_comm.items()
                }

            with named_phase("loss"):
                loss_out = psum(loss) / n_train
            new_state = {
                "params": new_params,
                "opt": new_opt,
                "norm": new_norm,
                "comm": new_comm,
            }
            m = {"loss": loss_out, "grad_norm": gnorm}
            if tripwire:
                m["numerics"] = nf_counts
            if ls_on:
                m["overflow"] = (gbad > 0).astype(jnp.int32)
            if wire_guard:
                local_bad = sum(wire_bad) if wire_bad \
                    else jnp.zeros((), jnp.int32)
                m["wire_bad"] = psum(local_bad)
            return new_state, m

        if self.emulated:
            # vmap(axis_name) in place of shard_map: identical step
            # function, parts as a batch axis on one device. The step
            # strips a leading size-1 device axis from data/comm and
            # re-adds it to new comm, so the wrapper reintroduces it
            # around the vmapped slice.
            tm = jax.tree_util.tree_map

            def vstep(state, data, rng, scale):
                st = dict(state)
                st["comm"] = tm(lambda v: v[None], state["comm"])
                d1 = tm(lambda v: v[None], data)
                ns, m = step(st, d1, rng, scale)
                ns["comm"] = tm(lambda v: v[0], ns["comm"])
                return ns, m

            vm = jax.vmap(vstep, in_axes=(0, 0, None, None), out_axes=0,
                          axis_name=PARTS_AXIS)

            def emu(state, data, rng, scale):
                ns, m = vm(state, data, rng, scale)
                # psum'd: identical across parts
                return ns, tm(lambda v: v[0], m)

            def emu_multi(state, data, rngs, scale):
                def body(st, rng):
                    return emu(st, data, rng, scale)

                return jax.lax.scan(body, state, rngs)

            self._multi_step = jax.jit(emu_multi, donate_argnums=(0,))
            return jax.jit(emu, donate_argnums=(0,))

        data_spec = jax.tree_util.tree_map(
            lambda _: PartitionSpec(PARTS_AXIS), self.data
        )
        state_spec = {
            "params": jax.tree_util.tree_map(
                lambda _: PartitionSpec(), self.state["params"]
            ),
            "opt": jax.tree_util.tree_map(
                lambda _: PartitionSpec(), self.state["opt"]
            ),
            "norm": jax.tree_util.tree_map(
                lambda _: PartitionSpec(), self.state["norm"]
            ),
            "comm": jax.tree_util.tree_map(
                lambda _: PartitionSpec(PARTS_AXIS), self.state["comm"]
            ),
        }
        # every step metric is a replicated scalar (post-psum); the
        # tripwire counts and overflow flag ride the same contract
        metric_spec = {"loss": PartitionSpec(), "grad_norm": PartitionSpec()}
        if tripwire:
            metric_spec["numerics"] = {ph: PartitionSpec()
                                       for ph in PHASES}
        if ls_on:
            metric_spec["overflow"] = PartitionSpec()
        if wire_guard:
            metric_spec["wire_bad"] = PartitionSpec()
        smapped = jax.shard_map(
            step,
            mesh=self.mesh,
            in_specs=(state_spec, data_spec, PartitionSpec(),
                      PartitionSpec()),
            out_specs=(state_spec, metric_spec),
        )

        def multi(state, data, rngs, scale):
            # k epochs in one compiled program: one dispatch, and XLA can
            # schedule epoch e+1's independent work (e.g. next halo
            # exchange) behind epoch e's tail
            def body(st, rng):
                return step(st, data, rng, scale)

            return jax.lax.scan(body, state, rngs)

        smapped_multi = jax.shard_map(
            multi,
            mesh=self.mesh,
            in_specs=(state_spec, data_spec, PartitionSpec(),
                      PartitionSpec()),
            out_specs=(state_spec, metric_spec),
        )
        self._multi_step = jax.jit(smapped_multi, donate_argnums=(0,))
        return jax.jit(smapped, donate_argnums=(0,))

    # ---------------- public API --------------------------------------

    def _epoch_rng_base(self) -> jax.Array:
        # single source of the per-run base key: train_epoch and
        # train_epochs MUST fold epochs from the same base so fused and
        # unfused runs are bit-identical
        if self.tcfg.rng_impl != "threefry":
            return jax.random.key(self.tcfg.seed + 17,
                                  impl=self.tcfg.rng_impl)
        return jax.random.PRNGKey(self.tcfg.seed + 17)

    def _epoch_rng_fold(self, epoch):
        """The value folded into the base key for `epoch` (host int or
        traced). dropout_reuse=N>1 maps N consecutive epochs onto one
        fold value, so they draw the SAME dropout masks and the RNG
        bits are generated once per N epochs after CSE inside a fused
        block — the mask-reuse floor lever. 0/1 = fresh every epoch."""
        reuse = int(getattr(self.tcfg, "dropout_reuse", 0) or 0)
        return epoch // reuse if reuse > 1 else epoch

    # ---------------- kernel fallback dispatch guard -------------------

    def _current_impl(self) -> str:
        """The aggregation kernel the step is currently built on (the
        RESOLVED impl — 'auto' never survives _setup_spmm)."""
        if self._block_tables is not None:
            return "block"
        if self._bucket_tables is not None:
            return "bucket"
        if self._gat_tables is not None:
            return "gat-bucket"
        return "xla"

    def downgrade_kernel(self, to_impl: str, reason: str) -> dict:
        """Rebuild the trainer one rung down the kernel fallback ladder
        (resilience/numerics.fallback_ladder): swap the kernel tables on
        device, restore the raw edge list if the new impl needs it, and
        rebuild the jitted step. The trainer's state (params/opt/comm)
        is untouched — the caller restores it from a host snapshot when
        the failed dispatch may have poisoned donated buffers. Returns
        the fallback record (also appended to self.fallbacks for fit()
        / bench to emit as a contracted `fallback` metrics record)."""
        frm = self._current_impl()
        self.cfg = dataclasses.replace(self.cfg, spmm_impl=to_impl)
        self._eval_cfg = dataclasses.replace(self._eval_cfg,
                                             spmm_impl=to_impl)
        self._setup_spmm()
        keep = {k: v for k, v in self.data.items()
                if not k.startswith(("bkt_", "blk_", "blkrem_", "gat_"))}
        tables_active = False
        for t in (self._bucket_tables,
                  self._block_tables, self._gat_tables):
            if t is not None:
                tables_active = True
                for k, v in t.items():
                    keep[k] = jax.device_put(np.asarray(v), self._shard)
        if not tables_active and self._edges_trimmed:
            # the raw-edge XLA path needs the real edge list the table
            # kernels let the trainer trim to a token shape
            keep["edge_src"] = jax.device_put(
                np.asarray(self.sg.edge_src, dtype=np.int32), self._shard)
            keep["edge_dst"] = jax.device_put(
                np.asarray(self.sg.edge_dst, dtype=np.int32), self._shard)
        self._edges_trimmed = tables_active
        self.data = keep
        self._step = self._build_step()
        self._kernel_proven = False
        rec = {"from_impl": frm, "to_impl": self._current_impl(),
               "epoch": int(getattr(self, "last_epoch", 0)),
               "reason": reason, "emitted": False}
        self.fallbacks.append(rec)
        # the run continues on a slower kernel and exits 0: the error
        # that forced it must be in the log whoever is (not) listening
        # to the metrics stream
        print(f"KERNEL DOWNGRADE {frm} -> {rec['to_impl']} at epoch "
              f"{rec['epoch']}; original error: {reason}",
              file=sys.stderr, flush=True)
        return rec

    def _dispatch(self, run_fn):
        """Run one step dispatch under the kernel fallback ladder: a
        compile-or-first-dispatch failure that looks like a
        kernel/backend error (numerics.is_kernel_error) downgrades the
        kernel and retries the same dispatch from a host snapshot,
        instead of killing the run (VERDICT r5: the block kernel
        hard-crashed the TPU backend at products shape with no
        fallback). Once a kernel has survived one dispatch it is
        'proven' and the guard (and its snapshot copy) costs nothing.
        Multi-process runs skip the guard: a unilateral downgrade would
        desync the SPMD program — there the crash propagates to the
        coordinated recovery paths instead."""
        from ..resilience.numerics import (KernelFallbackError,
                                           fallback_ladder,
                                           is_kernel_error)

        inject = self._inject_kernel_crash
        armed = ((not self._kernel_proven or inject)
                 and jax.process_count() == 1
                 and (inject or fallback_ladder(self._current_impl())))
        if not armed:
            # multi-process / ladder-exhausted: the injection flag must
            # not survive to poison an unrelated later dispatch
            self._inject_kernel_crash = False
            out = run_fn()
            self._kernel_proven = True
            return out
        snap = self.host_state()
        while True:
            if self._inject_kernel_crash:
                self._inject_kernel_crash = False
                err: BaseException = RuntimeError(
                    "fault-injected kernel dispatch failure "
                    "(INTERNAL: TPU backend error)")
            else:
                try:
                    out = run_fn()
                    self._kernel_proven = True
                    return out
                except Exception as exc:  # noqa: BLE001 — classified below
                    if not is_kernel_error(exc):
                        raise
                    err = exc
            # the full original error, before any record truncates it
            traceback.print_exception(err, file=sys.stderr)
            rungs = fallback_ladder(self._current_impl())
            if not rungs:
                raise KernelFallbackError(
                    f"aggregation kernel {self._current_impl()!r} failed "
                    f"with no fallback rung left: {err!r}") from err
            self.downgrade_kernel(rungs[0], repr(err)[:300])
            # the failed dispatch may have consumed the donated state
            # buffers; re-place the pre-dispatch snapshot
            self.restore_state(snap)

    def train_epoch(self, epoch: int) -> float:
        with trace_span("fit/keys"):
            rng = jax.random.fold_in(self._epoch_rng_base(),
                                     self._epoch_rng_fold(epoch))
            scale = jnp.float32(self.loss_scaler.scale)
        self.state, m = self._dispatch(
            lambda: self._step(self.state, self.data, rng, scale))
        # per-step telemetry (loss + grad norm, scalars) for fit()'s
        # metrics sink; train_epochs stores the [k]-array equivalents
        self._last_metrics = m
        loss = m["loss"]
        # last_epoch labels the buffers self.state now references (the
        # previous state was DONATED into the dispatch, so there is no
        # older state to fall back to). If the dispatch failed, these
        # buffers are poisoned and the crash handler's device_get raises
        # — it then skips the save rather than writing a wrong pair; if
        # it succeeded (even with the host interrupted during the
        # blocking float() below), state and label are consistent and a
        # resume neither skips nor repeats an epoch.
        self.last_epoch = epoch + 1
        with trace_span("fit/wait"):
            return float(loss)

    def train_epochs(self, start_epoch: int, k: int) -> np.ndarray:
        """Run epochs [start_epoch, start_epoch + k) as ONE compiled
        program (lax.scan over the step). Identical numerics to k
        train_epoch calls — same per-epoch rng fold — but a single
        dispatch, so host round-trip cost is amortized k-fold and XLA
        may overlap across epoch boundaries. Returns the k losses."""
        with trace_span("fit/keys"):
            base = self._epoch_rng_base()
            rngs = jax.vmap(
                lambda e: jax.random.fold_in(base,
                                             self._epoch_rng_fold(e)))(
                jnp.arange(start_epoch, start_epoch + k)
            )
            scale = jnp.float32(self.loss_scaler.scale)
        self.state, ms = self._dispatch(
            lambda: self._multi_step(self.state, self.data, rngs, scale))
        # ONE host sync for the whole block: pull every [k]-metric in a
        # single device_get instead of per-array transfers when fit()
        # later indexes loss/grad_norm/numerics per epoch (the megastep
        # harvest half of the dispatch-amortization lever)
        with trace_span("fit/wait"):
            ms = jax.device_get(ms)
        self._last_metrics = ms  # [k] numpy arrays; see train_epoch
        self.last_epoch = start_epoch + k  # see train_epoch
        return np.asarray(ms["loss"])

    def host_state(self) -> Dict[str, Any]:
        """Host-side copy of the full training state — the form the
        sentinel snapshots, checkpoints and resume templates use.
        Single-process: a plain device_get. Multi-process: the sharded
        comm carry spans non-addressable devices (device_get raises),
        so its global value is reassembled with an allgather — which
        makes this a COLLECTIVE there: call it at the same program
        point on every process (fit() only does so at lockstep
        dispatch boundaries)."""
        if jax.process_count() == 1:
            return jax.device_get(self.state)
        out = {k: jax.device_get(self.state[k])
               for k in ("params", "opt", "norm")}
        comm = self.state["comm"]
        if comm:
            from jax.experimental import multihost_utils

            # tiled: the global [P, ...] value of each sharded leaf (the
            # untiled form is refused for non-addressable arrays)
            out["comm"] = jax.tree_util.tree_map(
                np.asarray,
                multihost_utils.process_allgather(comm, tiled=True))
        else:
            out["comm"] = {}
        return out

    def local_partition_ids(self) -> list:
        """Global partition ids whose carry rows THIS process's devices
        own under the mesh's process-major device order. This is the
        elastic-membership redistribution mechanism in one line:
        relaunching with a different world size moves these ids, and
        restore_state re-device_puts the checkpointed FULL [P, ...]
        carry under the new shardings — partition i's rows land on
        whoever owns partition i now (resilience/elastic.py)."""
        if jax.process_count() == 1:
            return list(range(self.P))
        pid = jax.process_index()
        return [i for i, d in enumerate(self.mesh.devices.flat)
                if i < self.P and d.process_index == pid]

    def restore_state(self, host_state: Dict[str, Any]) -> None:
        """Device-place a host-side state pytree (a checkpoint load or
        a sentinel last-good snapshot) with the trainer's shardings —
        the one way to put external state back under the donated-buffer
        step. Works identically for emulated trainers (their stacked
        [P, ...] replicas ride the single-device shardings).

        The comm carry is validated to span the FULL partition count
        first: checkpoints always store all P rows (host_state's
        allgather), which is exactly what makes an elastic resume
        world-size independent — a partial carry means the caller
        sliced per-rank state (use utils.checkpoint's
        load_checkpoint_carry for that) and restoring it would
        scatter the wrong partitions onto the mesh."""
        def _check(path, a):
            shape = np.shape(a)
            if shape and shape[0] != self.P:
                raise ValueError(
                    f"comm carry leaf {jax.tree_util.keystr(path)} has "
                    f"leading dim {shape[0]}, expected the full "
                    f"partition count {self.P}: elastic restores need "
                    f"the complete [P, ...] carry (this process now "
                    f"owns partitions {self.local_partition_ids()})")
            return a

        jax.tree_util.tree_map_with_path(_check, host_state["comm"])
        self.state = {
            "params": jax.device_put(host_state["params"], self._repl),
            "opt": jax.device_put(host_state["opt"], self._repl),
            "norm": jax.device_put(host_state["norm"], self._repl),
            "comm": jax.device_put(host_state["comm"], self._shard),
        }

    def reset_comm(self) -> None:
        """Zero the pipelined comm carry: the next epoch consumes zero
        halos exactly like epoch 0, restarting the staleness-1 warmup.
        The sentinel's 'flush' action — stale boundary data produced by
        a divergent trajectory never re-enters the retried epochs."""
        self.state = dict(self.state)
        self.state["comm"] = jax.device_put(self._init_comm(), self._shard)

    def set_lr(self, lr: float) -> None:
        """Change the learning rate mid-run. The LR is a trace-time
        constant of the jitted step, so this rebuilds the step (one
        recompile per change — the sentinel's backoff path, where a
        recompile per rare trip is the right trade against threading a
        traced scalar through every healthy epoch)."""
        self.tcfg = dataclasses.replace(self.tcfg, lr=float(lr))
        self._step = self._build_step()

    def fit(
        self,
        eval_graphs: Optional[Dict[str, Tuple[Graph, str]]] = None,
        log_fn=print,
        *,
        start_epoch: int = 0,
        reference_logs: bool = False,
        result_file: Optional[str] = None,
        inductive: bool = False,
        checkpoint_dir: Optional[str] = None,
        checkpoint_every: int = 100,
        checkpoint_keep: int = 3,
        checkpoint_fallback_dir: Optional[str] = None,
        profile_dir: Optional[str] = None,
        profile_epochs: Optional[Tuple[int, int]] = None,
        staleness_probe_every: int = 0,
        measure_comm_cost: bool = False,
        sharded_eval: bool = False,
        async_eval: bool = True,
        metrics=None,
        sentinel=None,
        preemption=None,
        fault_plan=None,
        coord=None,
        stream_plan=None,
        journal=None,
    ) -> Dict[str, Any]:
        """The single epoch loop (reference train.py:327-400): periodic
        evaluation, best-val/BN-stats tracking, timing with <5-epoch
        warmup exclusion, and — for the CLI — reference-format log lines
        with measured Comm/Reduce collective costs, result files
        (train.py:33-39/54-60 formats), jax.profiler traces, and
        periodic checkpointing.

        `eval_graphs` maps split name -> (graph, mask key); must contain
        'val' (and usually 'test').

        `async_eval=True` (default) keeps evaluation off the critical
        path the way the reference's background eval thread does
        (train.py:327-328, 377-389): the eval computation is dispatched
        (with a device-side snapshot of params/BN stats) and its scalar
        is harvested at the NEXT log boundary, so the epoch loop never
        blocks on eval. Log lines/history for epoch e therefore appear
        one log period later; best-val tracking uses the snapshot, like
        the reference's deep-copied model (train.py:383).

        `sharded_eval=True` evaluates through the training mesh
        (parallel/evaluator.py) instead of one device — required when
        the full eval graph exceeds a single device's memory.

        `metrics` (an obs.MetricsLogger or None) appends structured
        JSONL telemetry: a run header (written here only if the caller
        has not already written a richer one), one record per epoch
        (step time, loss, grad norm, halo bytes, staleness age, HBM
        watermarks), one record per harvested evaluation, and a final
        run summary — the schema in obs/schema.py and
        docs/OBSERVABILITY.md. The sink never changes the log_fn
        stream: --reference-logs output stays byte-identical.

        Resilience (docs/RESILIENCE.md):

        `sentinel` (resilience.DivergenceSentinel or None) checks every
        dispatched block's loss/grad-norm; on trip, fit restores the
        last good in-memory snapshot, scales the LR down, optionally
        flushes the pipelined comm carry, and retries — bounded by the
        sentinel's max_retries, then DivergenceError. Fault/recovery
        records ride the metrics sink.

        `preemption` (resilience.PreemptionHandler or None) is polled
        at each dispatch boundary; a shutdown request checkpoints via
        the crash handler (rank-0 save) and raises Preempted, which the
        CLI maps to the resumable exit status EXIT_PREEMPTED.

        `fault_plan` (resilience.FaultPlan or None) injects
        deterministic host-side faults into the harvested metrics, the
        epoch boundary, and the checkpoint path — chaos testing only;
        the compiled device program is never altered.

        `coord` (resilience.Coordinator or None) makes every recovery
        decision above a cross-rank AGREEMENT in jax.distributed runs:
        at each dispatch boundary the ranks OR-reduce a small fault
        word (one tiny jitted psum), so a sentinel trip or preemption
        request on ANY rank executes its rollback / checkpoint+exit on
        ALL ranks in lockstep — a unilateral action would deadlock the
        next collective. The coordinator also arms the heartbeat
        watchdog (silent peers raise PeerLost instead of hanging the
        pod) and the param-digest desync detector. An inactive
        (single-process) coordinator degenerates to no-ops, so this
        path is identical to coord=None.

        `checkpoint_keep` bounds the on-disk checkpoint generations
        (keep-last-N; utils/checkpoint.py rotation).

        `checkpoint_fallback_dir` names a second directory (ideally a
        different volume) to save into when a checkpoint write into
        `checkpoint_dir` fails with OSError. With or without it, a
        failed periodic save degrades loudly instead of aborting the
        run: an ``io-degraded`` fault record is emitted, the previous
        on-disk generation stays the authoritative resume point, and
        the save is retried with FRESH state at subsequent epoch
        boundaries until one lands (``io-degraded`` recovery record).

        Profiling (docs/OBSERVABILITY.md "Profiling"):

        `profile_epochs=(A, B)` with `profile_dir` captures a
        ``jax.profiler`` device trace around the dispatched blocks of
        epochs [A, B): blocks are cut at A and at B and are otherwise
        dispatched as they would be, so the trace is of the fused
        scans the run spends its time in. After the capture stops, the
        trace (``.xplane.pb``) is folded against the compiled HLO of
        every scan length dispatched inside the window into a
        contracted ``profile`` record: MEASURED device self time per
        phase (spmm / dense / halo collectives / optimizer / ...) and
        per scope path (``spmm/bwd/gather``), busy time against the
        window, the longest idle gaps by the host span of this loop
        open in them (``fit/keys``, ``fit/harvest``, ...), and the
        measured comm/compute overlap fraction. Without
        `profile_epochs` the window is epochs start+6..start+8, and
        the same analysis runs on it. The record rides the metrics
        sink and the returned result dict ("profile").

        `stream_plan` (stream.StreamPlan or None) applies graph delta
        batches at their scheduled epoch boundaries via
        apply_graph_deltas (enable_stream must have been called).
        Fused blocks are clamped so no block straddles a scheduled
        delta, delta epochs run unfused with a forced staleness probe
        (the probe's drift IS the per-delta drift measurement), and
        each application emits a contracted ``stream`` record
        (docs/STREAMING.md).

        `staleness_probe_every=N` (pipelined mode only) measures, every
        N epochs, the per-layer relative drift between the STALE
        boundary features the step consumed and the FRESH ones it
        shipped: ``||h_stale - h_fresh|| / ||h_fresh||``. The stale
        buffers are snapshotted before the dispatch (they are donated
        into it) and compared against the post-step carry — exact, and
        the only cost is one halo-buffer copy + a small jitted norm
        program on probe epochs. Emits a ``staleness`` record per
        probe; probe epochs dispatch unfused (chunk=1)."""
        from ..utils.checkpoint import save_checkpoint

        tcfg = self.tcfg
        if metrics is not None and not metrics.header_written:
            # direct-API callers (tests, bench) get a header derived
            # from the trainer's own config; the CLI writes its richer
            # args-level header before calling fit()
            metrics.run_header(
                config={"model": dataclasses.asdict(self.cfg),
                        "train": dataclasses.asdict(self.tcfg)},
                device=device_info(), mesh=mesh_info(self.mesh),
                tables_pad=self.tables_pad)
        # ---- tuner decision (set at _setup_spmm for spmm_impl='auto'):
        # surface WHY this kernel dispatches, once per run ----
        if getattr(self, "tuning", None) is not None and \
                not self.tuning.get("emitted"):
            self.tuning["emitted"] = True
            w = self.tuning["winner"]
            log_fn(f"spmm auto-tuner: kernel={w['name']} "
                   f"(source={self.tuning['source']}"
                   + (f", {self.tuning['stale_reason']}"
                      if self.tuning.get("stale_reason") else "")
                   + ")")
            if metrics is not None:
                metrics.tuning(
                    winner=dict(w), source=self.tuning["source"],
                    stale_reason=self.tuning.get("stale_reason"),
                    costs=self.tuning.get("costs", []),
                    **{k: self.tuning.get(k)
                       for k in tuner.SAMPLE_FIELDS})
        halo_bytes = self.est_halo_bytes_per_epoch()
        # with --halo-dtype compression active, record the uncompressed
        # figure alongside so the report can print the wire ratio
        halo_unc = self.est_halo_bytes_per_epoch(compressed=False)
        halo_extra = ({"halo_bytes_uncompressed": halo_unc}
                      if halo_unc != halo_bytes else {})
        best_val, best_params, best_norm, best_epoch = 0.0, None, None, -1
        durs = []
        eval_durs = []
        history = []
        pending = None  # dispatched-but-unharvested evaluation

        def _dispatch_eval(at_epoch, at_loss, at_dur):
            handles = {}
            for split in ("val",) if (inductive or not reference_logs) \
                    else ("val", "test"):
                if split in eval_graphs:
                    g, mask = eval_graphs[split]
                    handles[split] = self.eval_dispatch(
                        g, mask, sharded=sharded_eval)
            if async_eval:
                # device-side copies: best-val harvesting needs the
                # params AS OF dispatch time (the reference deep-copies
                # the model into its eval thread, train.py:383)
                snap_p = jax.tree_util.tree_map(
                    jnp.copy, self.state["params"])
                snap_n = jax.tree_util.tree_map(jnp.copy, self.state["norm"])
            else:
                snap_p, snap_n = self.state["params"], self.state["norm"]
            return {"epoch": at_epoch, "loss": at_loss, "dur": at_dur,
                    "handles": handles, "snap_p": snap_p, "snap_n": snap_n}

        def _harvest_eval(p):
            nonlocal best_val, best_params, best_norm, best_epoch
            # plain perf_counter: an epoch boundary can harvest AND run
            # a sync eval in one iteration, so no phase key fits
            t0 = time.perf_counter()
            acc = self.eval_finish(p["handles"]["val"])
            eval_wait = time.perf_counter() - t0
            eval_durs.append(eval_wait)
            e = p["epoch"]
            eval_extra = {}
            if reference_logs:
                if inductive:
                    buf = reference_eval_line(e, acc)
                else:
                    t_acc = self.eval_finish(p["handles"]["test"])
                    buf = reference_eval_line(e, acc, t_acc)
                    eval_extra["test_acc"] = float(t_acc)
                if result_file:
                    with open(result_file, "a+") as f:
                        f.write(buf + "\n")
                log_fn(buf)
            else:
                log_fn(epoch_line(e + 1,
                                  float(np.mean(durs or [p["dur"]])),
                                  p["loss"], acc))
            if metrics is not None:
                metrics.eval_record(e, eval_wait, float(acc),
                                    **eval_extra)
            if tspan is not None:
                tspan.eval_span(e, eval_wait)
            history.append((e + 1, p["loss"], acc))
            if acc > best_val:
                best_val = acc
                best_epoch = e + 1
                # snapshot BN running stats with the params (the
                # reference deep-copies the whole model incl. buffers,
                # train.py:383)
                best_params = jax.device_get(p["snap_p"])
                best_norm = jax.device_get(p["snap_n"])
        # "bgrad" present from the start: a resumed run can hit its
        # first reference-log boundary BEFORE the one-shot measurement
        # (start_epoch + 5) and must print zeros, not KeyError
        comm_cost = {"comm": 0.0, "reduce": 0.0, "bgrad": 0.0}
        comm_measured = False
        timer = PhaseTimer()
        # ---- training-span plane (obs/trainspan.py): always-on
        # per-rank spans + tracesync clock anchors into the metrics
        # sink. Host-side only — nothing here touches a traced
        # program, so the zero-recompile pins hold with spans hot ----
        tspan = None
        if metrics is not None and getattr(tcfg, "train_traces", True):
            from ..obs.trainspan import TrainSpanPlane
            tspan = TrainSpanPlane(
                metrics, rank=jax.process_index(),
                generation=(max(coord.cfg.generation, 0)
                            if coord is not None else 0))
        profiling = False
        n_epochs = tcfg.n_epochs
        # ---- profiling window + staleness probes (obs/profiler.py) ----
        prof_window = None
        if profile_epochs is not None:
            a, b = int(profile_epochs[0]), int(profile_epochs[1])
            a, b = max(a, start_epoch), min(b, n_epochs)
            if b > a:
                prof_window = (a, b)
            else:
                log_fn(f"warning: --profile-epochs window "
                       f"{profile_epochs} is outside the run "
                       f"[{start_epoch}, {n_epochs}); no trace captured")
            if not profile_dir:
                log_fn("warning: profile_epochs set without "
                       "profile_dir; no trace captured")
                prof_window = None
        elif profile_dir and n_epochs > start_epoch:
            # no window given: a few epochs once the run is warm
            prof_window = (min(start_epoch + 6, n_epochs - 1),
                           min(start_epoch + 9, n_epochs))
        prof_started_at = None   # first epoch inside the live capture
        prof_lengths = set()     # scan lengths dispatched inside it
        prof_anchor = None       # unix time read inside the anchor span
        prof_record = None       # the parsed profile record (result)
        probe_every = max(int(staleness_probe_every), 0)
        if probe_every and not tcfg.enable_pipeline:
            log_fn("warning: staleness probes need --enable-pipeline "
                   "(vanilla exchanges are synchronous — drift is 0 by "
                   "construction); probes disabled")
            probe_every = 0

        def _start_profile():
            """Start the capture and lay its zero on the wall clock the
            stream's `span` / `tracesync` records use."""
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0  # a Python trace is a million
            opts.host_tracer_level = 2    # events; the spans stay
            jax.profiler.start_trace(profile_dir, profiler_options=opts)
            with trace_span(ANCHOR_SPAN):
                return time.time()

        def _finish_profile(window):
            """Stop + fold the live capture into a profile record."""
            jax.profiler.stop_trace()
            log_fn(f"profiler trace written to {profile_dir}")
            t0 = time.perf_counter()
            try:
                body = self._profile_analysis(profile_dir, prof_lengths)
            except Exception as exc:  # noqa: BLE001 — telemetry only
                log_fn(f"profile analysis failed: {exc!r}")
                return None
            if body is None:
                log_fn("profile analysis found no device operation in "
                       "the capture")
                return None
            body["epoch_start"], body["epoch_end"] = window
            # the trace clock's zero in unix seconds: the anchor span's
            # start was read on both clocks
            anchor_s = body.pop("anchor_s")
            body["t0_unix"] = round(prof_anchor - (anchor_s or 0.0), 6)
            body["fold_s"] = round(time.perf_counter() - t0, 3)
            log_fn(f"profile window [{window[0]}, {window[1]}): "
                   f"scans of {sorted(prof_lengths)}, busy "
                   f"{body['busy_s']:.4f}s of {body['window_s']:.4f}s, "
                   f"measured overlap "
                   f"{body['overlap_fraction']:.1%} "
                   f"(comm {body['comm_s']:.4f}s device, compute "
                   f"{body['compute_s']:.4f}s)")
            if metrics is not None:
                extras = {k: v for k, v in body.items()
                          if k not in ("phases", "comm_s", "compute_s",
                                       "overlap_fraction")}
                metrics.profile(body["phases"], body["comm_s"],
                                body["compute_s"],
                                body["overlap_fraction"], **extras)
            return body

        # megastep block size: --epoch-block overrides --fused-epochs
        # when set (same scan machinery; the separate knob lets the
        # floor-lever sweep vary block size without touching the
        # numerics-labeled fused_epochs config)
        fused = max(1, int(getattr(tcfg, "epoch_block", 0)
                           or getattr(tcfg, "fused_epochs", 1)))
        # per-epoch work (logs/eval/checkpoint/profiler) happens at these
        # period boundaries; fused blocks must not cross one
        periods = [tcfg.log_every]
        if reference_logs:
            periods.append(10)
        if checkpoint_dir:
            periods.append(checkpoint_every)

        epoch = start_epoch
        hspan = SpanCursor()
        seen_chunks = set()  # scan lengths already compiled
        # True while a dispatched-but-unfinished eval occupies the device
        # stream (its time would contaminate the next block's timing)
        eval_in_stream = False
        # ---- resilience state (docs/RESILIENCE.md) ----
        retries = 0          # consecutive sentinel rollbacks
        trip_horizon = None  # first epoch past the last trip: passing it
        #                      healthy = recovered (resets the counter)
        last_good = None     # (epoch, host snapshot) rollback target
        coord_on = coord is not None and coord.active
        # ---- integrity plane (resilience/integrity.py): SDC
        # detectors driven at every boundary (cheap dynamic digests)
        # and at --integrity-check-every cadence (table scrub +
        # Freivalds). Cadence boundaries are period boundaries: a
        # fused block must not straddle one ----
        integ = None
        integ_every = max(int(getattr(
            tcfg, "integrity_check_every", 0) or 0), 0)
        if integ_every > 0:
            from ..resilience.integrity import (
                SDC_CODES, SDC_NAMES, IntegrityPlane,
                request_quarantine)
            integ = IntegrityPlane(integ_every,
                                   rank=jax.process_index(),
                                   log=log_fn)
            periods.append(integ_every)
            integ.baseline(self)
        # a consensus-propagated peer trip (or an SDC params rollback)
        # needs the same rollback machinery whether or not the LOCAL
        # sentinel is armed
        if sentinel is not None or coord_on or integ is not None:
            last_good = (start_epoch, self.host_state())
        snap_every = max(int((sentinel.cfg if sentinel is not None
                              else SentinelConfig()).snapshot_every), 1)
        # ---- storage-fault state (resilience/storage.py) ----
        io_armed: Dict[str, int] = {}  # armed IO kind -> disarm epoch
        ckpt_pending = None  # epoch of a failed periodic save awaiting
        #                      retry; the previous generation stays the
        #                      authoritative resume point until it lands
        # ---- delta-journal state (stream/journal.py) ----
        journal_pending_since = None  # epoch of the first append the
        #                               degraded disk rejected
        last_ckpt_seq = -1  # stream seq the newest checkpoint covers
        if journal is not None and getattr(self, "_stream", None) is None:
            raise ValueError(
                "fit(journal=...) requires enable_stream(patcher): the "
                "journal records applied DeltaBatches")
        if journal is not None:
            # the CLI replays to the checkpoint watermark before fit;
            # everything journaled now is covered by that checkpoint
            last_ckpt_seq = int(self._stream.last_seq)

        def _stream_watermark():
            """Checkpoint extras pairing the state with its topology
            position (None outside streaming runs — zero npz delta)."""
            p = getattr(self, "_stream", None)
            if p is None:
                return None
            return {"__stream_seq__": np.asarray(int(p.last_seq),
                                                 np.int64),
                    "__topo_generation__": np.asarray(
                        int(getattr(self, "topo_generation", 0)),
                        np.int64)}
        if fault_plan is not None:
            # a resumed run gets the same --fault-plan; entries it
            # already lived through must not re-fire
            fault_plan.skip_before(start_epoch)
        if stream_plan is not None and journal is None:
            # LEGACY (journal-less) resume: assume the pre-start_epoch
            # deltas are already in the graph and drop them. With a
            # journal the CLI has already replayed to the checkpoint
            # watermark and called skip_journaled(); every seq past the
            # watermark stays scheduled, whatever its epoch — the WAL
            # rollback re-delivers it at the boundary it belongs to.
            stream_plan.skip_before(start_epoch)
        if coord is not None:
            coord.start()
            coord.set_checkpoint(checkpoint_dir, checkpoint_keep)
            coord.note_progress(start_epoch)
            if last_good is not None:
                coord.note_snapshot(*last_good)
            if coord_on and coord.cfg.desync_every > 0:
                # digest agreement is a collective: every rank must
                # reach it at the same epoch, so fused blocks must not
                # straddle the cadence boundary
                periods.append(coord.cfg.desync_every)
        # ---- flight recorder (obs/flight.py): host-side breadcrumbs,
        # on by default, zero effect on traced programs. The stall
        # detector is the opt-in sub-watchdog forensics thread
        # (PIPEGCN_STALL_S seconds of breadcrumb silence -> stack dump
        # WITHOUT dying); the hang@E:<ms> fault exercises it ----
        frec = flightrec.get_recorder()
        frec.crumb("fit-start", epoch=start_epoch, n_epochs=n_epochs)
        stall_det = None
        try:
            stall_s = float(os.environ.get("PIPEGCN_STALL_S", "0") or 0)
        except ValueError:
            stall_s = 0.0
        if stall_s > 0 and frec.enabled:
            stall_det = flightrec.StallDetector(frec, stall_s).start()
        # the host side of each block, from one harvest to the next,
        # rides its compute span (obs/trace.BlockSpans); the spans fit
        # records (fit/load, fit/measure_comm, fit/tail) go to the same
        # sink
        blocks = sink_prev = None
        if tspan is not None:
            sink_prev = recorder().attach(metrics, f"r{tspan.rank}",
                                          flush=False)
            blocks = BlockSpans(hspan)
        try:
            while epoch < n_epochs:
                # host spans of the loop's sections, for a profiler
                # trace's idle gaps (nothing is written without one):
                # fit/boundary, then `step` with fit/dispatch, fit/keys
                # and fit/wait inside, then fit/harvest, fit/log,
                # fit/eval, fit/checkpoint where those run
                hspan.to("fit/boundary")
                # ---- boundary faults / preemption: the one point where
                # the donated state is consistent and labeled ----
                frec.crumb("boundary", epoch=epoch)
                if coord is not None:
                    coord.note_progress(epoch)
                    # a dead peer can never complete a collective:
                    # raise PeerLost BEFORE dispatching anything
                    coord.check_peers()
                # ---- storage faults: arm/disarm the process-wide IO
                # shim at the boundary. The window closes at the next
                # checkpoint boundary (next epoch when checkpointing is
                # off) so each run exercises BOTH the degradation and
                # the recovery side of every writer's policy ----
                for kind, until in list(io_armed.items()):
                    if epoch >= until:
                        FAULTY_IO.disarm(kind)
                        del io_armed[kind]
                        log_fn(f"storage fault {kind} window closed at "
                               f"epoch {epoch}")
                if fault_plan is not None:
                    for kind in IO_KINDS:
                        arg = fault_plan.due_arg(kind, epoch)
                        if arg is None:
                            continue
                        FAULTY_IO.arm(kind, ms=arg)
                        io_armed[kind] = (epoch + checkpoint_every
                                          if checkpoint_dir else epoch + 1)
                        log_fn(f"fault-injected {kind} at epoch {epoch} "
                               f"(window closes at epoch "
                               f"{io_armed[kind]})")
                        if metrics is not None:
                            metrics.fault(kind="injected", epoch=epoch,
                                          reason=kind)
                if (ckpt_pending is not None and checkpoint_dir
                        and jax.process_count() == 1):
                    # retry the failed periodic save with FRESH state.
                    # Multi-process runs retry at the next checkpoint
                    # boundary instead: host_state() is a lockstep
                    # allgather, and only rank 0 knows a save failed
                    try:
                        save_checkpoint(checkpoint_dir,
                                        self.host_state(), epoch,
                                        keep=checkpoint_keep)
                    except OSError as io_exc:
                        log_fn(f"checkpoint retry at epoch {epoch} "
                               f"still failing ({io_exc!r})")
                    else:
                        log_fn(f"checkpoint save recovered at epoch "
                               f"{epoch} (pending since epoch "
                               f"{ckpt_pending})")
                        if metrics is not None:
                            metrics.recovery(kind=IO_DEGRADED,
                                             epoch=epoch,
                                             pending_since=ckpt_pending)
                        ckpt_pending = None
                # ---- SDC chaos + detection (resilience/integrity):
                # inject scheduled bit flips FIRST (the corruption
                # model is state rotting while parked at the
                # boundary), then run the detectors BEFORE anything
                # legitimately mutates state below (stream deltas,
                # desync chaos) — so a mismatch is attributable ----
                local_sdc_code = 0
                sdc_results: list = []
                if fault_plan is not None:
                    flip_target = fault_plan.due_str_arg(
                        "bitflip", epoch)
                    if flip_target is not None and self._inject_bitflip(
                            flip_target, epoch, log_fn):
                        log_fn(f"fault-injected bitflip:{flip_target} "
                               f"at epoch {epoch}")
                        frec.crumb("bitflip-injected", epoch=epoch,
                                   target=flip_target)
                        if metrics is not None:
                            metrics.fault(
                                kind="injected", epoch=epoch,
                                reason=f"bitflip:{flip_target}")
                if integ is not None:
                    deep = integ.due(epoch)
                    sdc_results = integ.run_checks(self, epoch,
                                                   deep=deep)
                    for res in sdc_results:
                        if res.outcome == "mismatch":
                            frec.crumb("sdc-detected", epoch=epoch,
                                       check=res.check,
                                       target=res.target)
                            log_fn(f"integrity: {res.check} mismatch "
                                   f"on {res.target} at epoch {epoch}"
                                   f" ({res.detail})")
                        # ok records only for the deep (cadence)
                        # checks: per-boundary ok digests would drown
                        # the stream
                        if metrics is not None and (
                                res.outcome == "mismatch" or deep):
                            metrics.integrity(
                                epoch=epoch, check=res.check,
                                outcome=res.outcome,
                                target=res.target,
                                cadence=integ.check_every,
                                overhead_s=round(res.overhead_s, 6),
                                detail=res.detail,
                                dirty_shards=list(res.dirty_shards))
                    bad = [r for r in sdc_results
                           if r.outcome == "mismatch"]
                    if bad:
                        local_sdc_code = SDC_CODES.get(
                            bad[0].target, 0)
                # ---- streaming deltas: the graph changes HERE, at the
                # boundary where the donated state is consistent.
                # WAL-first when a journal is attached: a batch is made
                # durable BEFORE it mutates the topology; an append the
                # degraded disk rejects queues the batch (degrade-not-
                # lose) and the apply waits for a later boundary ----
                stream_reports = []
                stream_due = [] if stream_plan is None else \
                    stream_plan.due(epoch)
                if (stream_due or journal_pending_since is not None
                        or (fault_plan is not None and
                            fault_plan.peek("graph-delta", epoch))) \
                        and pending is not None:
                    # an in-flight async eval was dispatched against the
                    # pre-patch topology; finish it before the graph (and
                    # the host-side eval context) grows under it
                    _harvest_eval(pending)
                    pending = None

                def _journal_gate(db):
                    """WAL-first: True = durable, apply now. False =
                    queued pending (or a batch ahead of it is) — do NOT
                    apply; order is preserved by the queue."""
                    nonlocal journal_pending_since
                    if journal is None:
                        return True
                    gen = (self.topo_generation + 1
                           + journal.pending_count)
                    if journal.append(db, gen):
                        if metrics is not None:
                            metrics.journal(
                                op="append", seq=int(db.seq),
                                topo_generation=gen, n_records=1,
                                lag_seqs=max(
                                    journal.last_seq() - last_ckpt_seq,
                                    0))
                        return True
                    if journal_pending_since is None:
                        journal_pending_since = epoch
                        log_fn(f"JOURNAL APPEND FAILED at epoch "
                               f"{epoch} (seq={db.seq}); io-degraded "
                               f"— delta queued, NOT applied (WAL-"
                               f"first), retrying at later boundaries")
                        if metrics is not None:
                            metrics.fault(kind=IO_DEGRADED, epoch=epoch,
                                          reason="journal append failed",
                                          component="journal")
                            metrics.journal(
                                op="degraded", seq=int(db.seq),
                                topo_generation=self.topo_generation,
                                n_records=journal.pending_count)
                    return False

                if journal is not None and journal.pending_count:
                    # the disk may have recovered: retry queued appends
                    # in order; whatever becomes durable applies now
                    drained = journal.drain_pending()
                    for db, _g in drained:
                        rep = self.apply_graph_deltas(db)
                        stream_reports.append(rep)
                        if rep.repadded:
                            seen_chunks.clear()
                    if drained and not journal.pending_count:
                        log_fn(f"journal recovered at epoch {epoch}: "
                               f"{len(drained)} queued delta(s) made "
                               f"durable and applied")
                        if metrics is not None:
                            metrics.recovery(
                                kind=IO_DEGRADED, epoch=epoch,
                                pending_since=journal_pending_since
                                if journal_pending_since is not None
                                else epoch,
                                component="journal")
                            metrics.journal(
                                op="recovered", seq=journal.last_seq(),
                                topo_generation=self.topo_generation,
                                n_records=len(drained))
                        journal_pending_since = None
                if stream_plan is not None:
                    for sb in stream_due:
                        if not _journal_gate(sb):
                            continue
                        rep = self.apply_graph_deltas(sb)
                        log_fn(
                            f"stream delta seq={rep.seq} at epoch "
                            f"{epoch}: +{rep.edges_added}/-"
                            f"{rep.edges_deleted} edges, "
                            f"+{rep.nodes_added} nodes, "
                            f"{rep.patch_ms:.1f} ms patch"
                            + (" [re-padded: recompile]"
                               if rep.repadded else ""))
                        stream_reports.append(rep)
                        if rep.repadded:
                            # the rebuilt step recompiles; keep its
                            # first blocks out of the timing stats
                            seen_chunks.clear()
                if fault_plan is not None and \
                        fault_plan.due("graph-delta", epoch):
                    # chaos lane: an unscheduled synthetic delta batch
                    # hits the live graph mid-run (scripts/chaos.sh)
                    if getattr(self, "_stream", None) is None:
                        log_fn(f"fault graph-delta at epoch {epoch} "
                               f"skipped: streaming not enabled")
                    else:
                        from ..graph.synthetic import \
                            synthetic_delta_schedule

                        # seq must clear everything applied AND
                        # everything journaled-but-queued ahead of it
                        base = self._stream.last_seq
                        if journal is not None:
                            base = max(base, journal.last_seq())
                            if journal.pending:
                                base = max(base,
                                           journal.pending[-1][0].seq)
                        fb = synthetic_delta_schedule(
                            self._stream.g, n_batches=1,
                            edges_per_batch=4, dels_per_batch=2,
                            nodes_per_batch=1, seed=epoch,
                            start_seq=base + 1)[0]
                        if metrics is not None:
                            metrics.fault(kind="injected", epoch=epoch,
                                          reason="graph-delta")
                        if _journal_gate(fb):
                            rep = self.apply_graph_deltas(fb)
                            log_fn(f"fault-injected graph delta at "
                                   f"epoch {epoch} (seq={rep.seq})")
                            stream_reports.append(rep)
                            if rep.repadded:
                                seen_chunks.clear()
                if fault_plan is not None and \
                        fault_plan.due("journal-torn", epoch):
                    # chaos lane: the newest journal segment loses its
                    # tail (interrupted append / disk corruption); the
                    # next resume must walk back to the surviving
                    # prefix and re-derive the rest from the plan
                    if journal is None:
                        log_fn(f"fault journal-torn at epoch {epoch} "
                               f"skipped: no delta journal")
                    else:
                        lost = journal.tear_newest_segment()
                        log_fn(f"fault-injected journal tear at epoch "
                               f"{epoch}: {lost} record(s) lost from "
                               f"the newest segment")
                        if metrics is not None:
                            metrics.fault(kind="injected", epoch=epoch,
                                          reason="journal-torn")
                if integ is not None and stream_reports:
                    # the deltas legitimately rebuilt tables and
                    # flushed carry rows: re-baseline, forget the
                    # now-stale dynamic digests
                    integ.baseline(self)
                    integ.drop_dynamic()
                if fault_plan is not None and fault_plan.due("crash", epoch):
                    raise RuntimeError(
                        f"fault-injected crash at epoch {epoch}")
                if fault_plan is not None and fault_plan.due("kill", epoch):
                    # hard SIGKILL: no handlers, no atexit, no
                    # checkpoint — the process vanishes like an
                    # OOM-killed rank, so the PEERS' watchdog and the
                    # elastic supervisor must do ALL the recovery
                    import os as _os
                    import signal as _signal
                    import sys as _sys

                    log_fn(f"fault-injected SIGKILL at epoch {epoch}")
                    if metrics is not None:
                        metrics.fault(kind="injected", epoch=epoch,
                                      reason="kill")
                    _sys.stdout.flush()
                    _sys.stderr.flush()
                    _os.kill(_os.getpid(), _signal.SIGKILL)
                if fault_plan is not None and \
                        fault_plan.due("kernel-crash", epoch):
                    # the next dispatch raises a simulated TPU-backend
                    # error; the _dispatch guard must absorb it via the
                    # kernel fallback ladder (resilience/numerics.py)
                    log_fn(f"fault-injected kernel crash at epoch {epoch}")
                    self._inject_kernel_crash = True
                hang_ms = (fault_plan.due_arg("hang", epoch)
                           if fault_plan is not None else None)
                if hang_ms:
                    # bounded sub-watchdog stall (hang@E[:rN]:<ms>):
                    # heartbeats keep flowing and the loop RESUMES, so
                    # only the flight recorder's stall detector — never
                    # the peers' PeerLost path — sees it
                    log_fn(f"fault-injected {hang_ms} ms stall at "
                           f"epoch {epoch}")
                    frec.crumb("stall-injected", epoch=epoch,
                               stall_ms=hang_ms)
                    time.sleep(hang_ms / 1000.0)
                    frec.crumb("stall-resumed", epoch=epoch)
                elif hang_ms is not None:
                    # simulate a wedged process: heartbeats stop too, so
                    # the PEERS' watchdogs — not this rank — must act.
                    # The open collective span is what the black-box
                    # dump's stack annotation names as the wedged phase
                    log_fn(f"fault-injected hang at epoch {epoch}")
                    frec.enter("collective", phase="fault-hang",
                               epoch=epoch)
                    if coord is not None:
                        coord.suspend_heartbeat()
                    time.sleep(3600)
                    raise RuntimeError("fault-injected hang expired")
                if fault_plan is not None and \
                        fault_plan.due("desync", epoch):
                    # silently perturb THIS rank's replicated params —
                    # the cross-rank divergence the desync detector
                    # exists to catch. Rebuilt from LOCAL single-device
                    # arrays: a device_put onto the global replicated
                    # sharding is a cross-process collective, which
                    # only this rank would run — the injection must
                    # desynchronize the STATE, not the program
                    host_p = jax.device_get(self.state["params"])
                    host_p = jax.tree_util.tree_map(
                        lambda a: (np.asarray(a)
                                   * np.asarray(1.001, np.asarray(a).dtype)),
                        host_p)
                    local_devs = [d for d in self.mesh.devices.flat
                                  if d.process_index == jax.process_index()]

                    def _replicate_local(arr):
                        shards = [jax.device_put(arr, d)
                                  for d in local_devs]
                        return jax.make_array_from_single_device_arrays(
                            arr.shape, self._repl, shards)

                    self.state = dict(self.state)
                    self.state["params"] = jax.tree_util.tree_map(
                        _replicate_local, host_p)
                    log_fn(f"fault-injected param desync at epoch {epoch}")
                    if integ is not None:
                        # the perturbation targets the DESYNC detector;
                        # forget the params digests so the integrity
                        # plane doesn't claim the other lane's fault
                        integ.drop_dynamic()
                preempt_reason = (preemption.reason
                                  if preemption is not None
                                  and preemption.requested else None)
                if fault_plan is not None and \
                        fault_plan.due("sigterm", epoch):
                    preempt_reason = preempt_reason or "fault-plan sigterm"
                preempt_extra = {}
                sdc_code = local_sdc_code
                sdc_rank = (jax.process_index()
                            if local_sdc_code else -1)
                if coord_on:
                    # boundary consensus: a shutdown request on ANY rank
                    # checkpoints + exits 75 on ALL ranks, in lockstep —
                    # one rank leaving unilaterally deadlocks the rest.
                    # The SDC code rides the same word so every rank
                    # executes the identical recovery below
                    agreed = coord.agree_boundary(
                        preempt=preempt_reason is not None,
                        sdc_code=local_sdc_code)
                    if agreed.sdc:
                        sdc_code = agreed.sdc_code
                        sdc_rank = agreed.sdc_rank
                    if agreed.preempt:
                        preempt_extra = {"agreed": True,
                                         "source_rank": agreed.preempt_rank}
                        if preempt_reason is None:
                            preempt_reason = (
                                f"peer preemption (rank "
                                f"{agreed.preempt_rank})"
                                if agreed.preempt_rank >= 0 else
                                "peer preemption (multiple ranks)")
                if preempt_reason is not None:
                    frec.crumb("preempt", epoch=epoch,
                               reason=str(preempt_reason)[:120])
                    log_fn(f"preemption requested ({preempt_reason}); "
                           f"checkpointing at epoch boundary {epoch}")
                    if metrics is not None:
                        metrics.fault(kind="preemption", epoch=epoch,
                                      reason=preempt_reason,
                                      **preempt_extra)
                    if jax.process_count() > 1 and last_good is not None:
                        # multi-process: the crash handler cannot fetch
                        # the sharded comm carry directly; materialize
                        # the boundary state HERE (every rank reaches
                        # this point — the allgather is lockstep) so
                        # the fallback save is exact, not stale
                        last_good = (epoch, self.host_state())
                        if coord is not None:
                            coord.note_snapshot(*last_good)
                    # the crash handler below does the rank-0 save
                    raise Preempted(epoch, preempt_reason)
                # ---- SDC containment & recovery: agreed above, so the
                # action below runs in lockstep on every rank ----
                if integ is not None and sdc_code:
                    sdc_target = SDC_NAMES.get(sdc_code, "params")
                    dirty = tuple(sorted({
                        int(s) for r in sdc_results
                        if r.outcome == "mismatch"
                        for s in r.dirty_shards}))
                    frec.crumb("sdc-recover", epoch=epoch,
                               target=sdc_target)
                    if metrics is not None:
                        metrics.fault(
                            kind="sdc", epoch=epoch,
                            target=sdc_target,
                            source_rank=sdc_rank,
                            strikes=integ.total_detections(),
                            agreed=coord_on)
                    # containment first: a member that keeps detecting
                    # SDC is the defective one — ask to leave the
                    # fleet (durable marker the elastic supervisor
                    # consumes at its next replan) before recovering
                    if (integ.should_quarantine()
                            and local_sdc_code
                            and coord is not None
                            and getattr(coord.cfg, "dir", "")):
                        # elastic.MEMBER_ENV: the supervisor's member
                        # id for this process (falls back to the rank
                        # outside supervised runs)
                        member = int(os.environ.get(
                            "PIPEGCN_ELASTIC_MEMBER",
                            jax.process_index()))
                        marker = request_quarantine(
                            coord.cfg.dir, member,
                            reason="recurring silent data corruption",
                            strikes=integ.total_detections(),
                            targets=sorted(integ.detections))
                        log_fn(f"integrity: recurring SDC "
                               f"({integ.total_detections()} strikes)"
                               f"; quarantine requested for member "
                               f"{member} ({marker})")
                        if metrics is not None:
                            metrics.fault(
                                kind="quarantine-request",
                                epoch=epoch, member=member,
                                strikes=integ.total_detections(),
                                targets=sorted(integ.detections))
                        if jax.process_count() > 1 \
                                and last_good is not None:
                            last_good = (epoch, self.host_state())
                            if coord is not None:
                                coord.note_snapshot(*last_good)
                        raise Preempted(
                            epoch, "recurring silent data corruption")
                    if sdc_target == "tables":
                        n_reb = self._rebuild_static_data(
                            dirty or None)
                        integ.baseline(self)
                        log_fn(f"integrity: rebuilt "
                               f"{'shards ' + str(list(dirty)) if dirty else 'all shards'}"
                               f" from the host artifact at epoch "
                               f"{epoch}")
                        if metrics is not None:
                            metrics.recovery(
                                kind="sdc", epoch=epoch,
                                target=sdc_target,
                                tables_rebuilt=n_reb,
                                dirty_shards=list(dirty))
                    elif sdc_target in ("halo", "carry"):
                        # poisoned boundary data: flush the pipelined
                        # carry (epoch-0 warmup semantics) instead of
                        # training on it for one more epoch
                        if tcfg.enable_pipeline:
                            self.reset_comm()
                        integ.drop_dynamic()
                        log_fn(f"integrity: flushed pipelined carry "
                               f"at epoch {epoch} ({sdc_target} "
                               f"corruption)")
                        if metrics is not None:
                            metrics.recovery(kind="sdc", epoch=epoch,
                                             target=sdc_target,
                                             flushed=True)
                    elif last_good is not None:  # params
                        rollback_to, good_state = last_good
                        log_fn(f"integrity: params corruption at "
                               f"epoch {epoch}; rolling back to "
                               f"epoch {rollback_to}")
                        self.restore_state(good_state)
                        self.last_epoch = rollback_to
                        if tcfg.enable_pipeline:
                            self.reset_comm()
                        integ.drop_dynamic()
                        if metrics is not None:
                            metrics.recovery(
                                kind="sdc", epoch=epoch,
                                target=sdc_target,
                                rollback_epoch=rollback_to)
                        pending = None  # in-flight eval snapshot is
                        #                 from the corrupt timeline
                        eval_in_stream = False
                        epoch = rollback_to
                        continue
                if prof_window is not None and not profiling and \
                        prof_window[0] <= epoch < prof_window[1]:
                    prof_anchor = _start_profile()
                    profiling = True
                    prof_started_at = epoch
                chunk = min(fused, n_epochs - epoch)
                for m in periods:
                    to_boundary = m - epoch % m
                    chunk = min(chunk, to_boundary)
                if stream_plan is not None:
                    # a fused block must not straddle a scheduled delta
                    nxt = stream_plan.next_epoch(epoch + 1)
                    if nxt is not None:
                        chunk = min(chunk, nxt - epoch)
                if prof_window is not None:
                    # blocks are cut at the window's two ends and are
                    # otherwise dispatched as they would be: the trace
                    # is of the programs the run spends its time in
                    for edge in prof_window:
                        if epoch < edge:
                            chunk = min(chunk, edge - epoch)
                # staleness probe: snapshot the stale halo carry BEFORE
                # the dispatch donates it (obs docs: drift is old vs
                # new carry — exchange(h[e-1]) vs exchange(h[e]))
                # delta epochs always probe: the drift across the first
                # post-patch step IS the per-delta drift measurement
                probe_due = ((probe_every > 0
                              and epoch % probe_every == 0
                              or bool(stream_reports))
                             and bool(self.state.get("comm")))
                old_halo = None
                if probe_due:
                    chunk = 1
                    old_halo = jax.tree_util.tree_map(
                        jnp.copy, self.state["comm"]["halo"])
                slow_ms = (fault_plan.due_arg("slow-rank", epoch)
                           if fault_plan is not None else None)
                if slow_ms:
                    # deterministic straggler (slow-rank@E[:rN]:<ms>):
                    # this rank arrives late at the dispatch boundary,
                    # so every peer waits on its collectives inside the
                    # compiled step. The training-span plane's aligned
                    # compute-window starts attribute the gap to this
                    # rank (obs/trainspan.py straggler attribution)
                    log_fn(f"fault-injected {slow_ms} ms straggle at "
                           f"epoch {epoch}")
                    frec.crumb("slow-rank-injected", epoch=epoch,
                               slow_ms=slow_ms)
                    time.sleep(slow_ms / 1000.0)
                hspan.to()
                timer.clear()
                # dispatch span left OPEN across the step: if the
                # program wedges inside (a dead collective), the crash
                # dump's annotation names this epoch and phase
                frec.enter("dispatch", epoch=epoch, chunk=chunk)
                # annotate=True: the host span shows up in --profile-dir
                # traces next to the named device phases
                # fit/load: written where JAX lowered, loaded or compiled
                # a program for this dispatch (a scan length's first
                # block, a step rebuilt after a graph delta)
                with timer.phase("step", annotate=True), \
                        span("fit/load", only_if_compiled=True,
                             annotate=False, epoch=epoch, epochs=chunk):
                    with trace_span("fit/dispatch"):
                        if chunk == 1:
                            loss = self.train_epoch(epoch)
                            blk_losses = np.asarray([loss])
                        else:
                            blk_losses = np.asarray(
                                self.train_epochs(epoch, chunk))
                            loss = float(blk_losses[-1])
                    with trace_span("fit/wait"):
                        jax.block_until_ready(self.state["params"])
                hspan.to("fit/harvest")
                frec.exit("dispatch", epoch=epoch)
                if tspan is not None:
                    # the block's spans: the real dispatch->harvest wall
                    # window, plus (once measure_comm landed) the comm
                    # tail ending at the harvest barrier
                    tspan.block(epoch, chunk, timer.durations()["step"],
                                **blocks.harvest())
                dur = timer.durations()["step"] / chunk
                if profiling:
                    prof_lengths.add(chunk)
                if profiling and epoch + chunk >= prof_window[1]:
                    profiling = False
                    hspan.to()
                    prof_record = _finish_profile(
                        (prof_started_at, epoch + chunk)) or prof_record
                    prof_window = None   # one capture per fit call
                # first 5 epochs after (re)start excluded from averaged
                # timings — they include jit compilation (the reference
                # excludes epochs <5 and log epochs, train.py:364). A chunk
                # length seen for the first time also compiles (one scan
                # program per distinct length) — exclude that block too. And
                # a block right after an async eval dispatch waits on the
                # eval's device time (enqueued ahead of it on the same
                # stream), so exclude it as well — the reference's Time(s)
                # likewise excludes eval (it runs on the CPU thread).
                first_of_len = chunk not in seen_chunks
                seen_chunks.add(chunk)
                if epoch >= start_epoch + 5 and not first_of_len \
                        and not eval_in_stream:
                    durs.extend([dur] * chunk)
                eval_in_stream = False
                # ---- kernel fallbacks taken during the dispatch:
                # surface them as contracted `fallback` records ----
                fb_new = False
                for fb in self.fallbacks:
                    if not fb.get("emitted"):
                        fb["emitted"] = True
                        fb_new = True
                        frec.crumb("fallback", epoch=epoch,
                                   from_impl=fb["from_impl"],
                                   to_impl=fb["to_impl"])
                        log_fn(f"kernel fallback: {fb['from_impl']} -> "
                               f"{fb['to_impl']} ({fb['reason'][:120]})")
                        if metrics is not None:
                            metrics.fallback(
                                epoch=epoch, from_impl=fb["from_impl"],
                                to_impl=fb["to_impl"],
                                reason=fb["reason"])
                        # the downgraded step recompiles; exclude its
                        # first blocks from the timing stats
                        seen_chunks.clear()
                if fb_new and integ is not None:
                    # the fallback rebuilt tables one rung down: the
                    # static baseline (and the carry it flushed) are
                    # legitimately different now
                    integ.baseline(self)
                    integ.drop_dynamic()
                # ---- halo wire checksum lane (parallel/halo.py):
                # harvested from the step metrics; a nonzero count
                # means a ppermute payload arrived with a different
                # checksum than it left with — flush the poisoned
                # carry rather than consume it next epoch ----
                if integ is not None and "wire_bad" in self._last_metrics:
                    wb_n = int(np.sum(np.asarray(
                        self._last_metrics["wire_bad"])))
                    if wb_n:
                        integ.detections["halo"] = \
                            integ.detections.get("halo", 0) + 1
                        frec.crumb("wire-bad", epoch=epoch,
                                   blocks=wb_n)
                        log_fn(f"integrity: halo wire checksum "
                               f"mismatch in {wb_n} distance block(s)"
                               f" at epoch {epoch}; flushing carry")
                        if metrics is not None:
                            metrics.integrity(
                                epoch=epoch, check="wire",
                                outcome="mismatch", target="halo",
                                cadence=integ.check_every,
                                overhead_s=0.0,
                                blocks=wb_n)
                            metrics.fault(kind="sdc", epoch=epoch,
                                          target="halo", check="wire",
                                          blocks=wb_n,
                                          agreed=False)
                        if tcfg.enable_pipeline:
                            self.reset_comm()
                        integ.drop_dynamic()
                # grad norms ride the step output ([k] arrays for fused
                # blocks) — harvested here for the metrics records AND
                # the sentinel check
                gn = np.atleast_1d(np.asarray(
                    self._last_metrics["grad_norm"], np.float64))
                frec.crumb("metrics-harvest", epoch=epoch + chunk - 1,
                           loss=float(loss), step_time_s=round(dur, 4))
                # ---- injected metric faults (host-side only: the
                # compiled device program is what production runs) ----
                if fault_plan is not None:
                    j = fault_plan.due_in("nan-loss", epoch, epoch + chunk)
                    if j is not None:
                        blk_losses = np.array(blk_losses, np.float64)
                        blk_losses[j - epoch] = np.nan
                        loss = float(blk_losses[-1])
                        log_fn(f"fault-injected nan loss at epoch {j}")
                    j = fault_plan.due_in("nan-grad", epoch, epoch + chunk)
                    if j is not None:
                        gn = np.array(gn, np.float64)
                        gn[min(j - epoch, gn.size - 1)] = np.nan
                        log_fn(f"fault-injected nan grad norm at epoch {j}")
                # ---- loss-scale state machine (resilience/numerics):
                # harvested overflow flags drive backoff / skip
                # accounting / regrowth; overflow epochs are HANDLED
                # events the sentinel must not mistake for divergence
                ovf = None
                if self.loss_scaler.cfg.enabled:
                    ovf = np.atleast_1d(np.asarray(
                        self._last_metrics.get("overflow", 0)))
                    if fault_plan is not None:
                        j = fault_plan.due_in("overflow", epoch,
                                              epoch + chunk)
                        if j is not None:
                            ovf = np.array(ovf)
                            ovf[min(j - epoch, ovf.size - 1)] = 1
                            log_fn(f"fault-injected loss-scale overflow "
                                   f"at epoch {j}")
                    for ev in self.loss_scaler.update(epoch, ovf):
                        frec.crumb("loss-scale", event=ev["kind"],
                                   epoch=ev["epoch"])
                        if ev["kind"] == "overflow":
                            log_fn(
                                f"loss-scale overflow at epoch "
                                f"{ev['epoch']}: step skipped, scale "
                                f"{ev['scale']:g}"
                                + (f" -> {ev['new_scale']:g}"
                                   if "new_scale" in ev else ""))
                        else:
                            log_fn(f"loss-scale regrown to "
                                   f"{ev['scale']:g} at epoch "
                                   f"{ev['epoch']}")
                        if metrics is not None:
                            metrics.numerics(**ev)
                if metrics is not None:
                    # one record per epoch in the block; the HBM
                    # watermark is sampled once per dispatch
                    mem = memory_snapshot()
                    for j in range(chunk):
                        e_j = epoch + j
                        metrics.epoch(
                            epoch=e_j,
                            step_time_s=dur,
                            loss=float(blk_losses[j]),
                            grad_norm=float(gn[j] if gn.size > 1
                                            else gn[0]),
                            halo_bytes=halo_bytes,
                            # pipelined mode consumes epoch e-1's
                            # boundary data (zeros at the very first
                            # epoch); vanilla exchanges synchronously
                            staleness_age=int(
                                1 if tcfg.enable_pipeline and e_j > 0
                                else 0),
                            memory=mem,
                            **halo_extra,
                        )
                # ---- staleness probe: relative drift between the
                # stale halo features this epoch consumed (snapshotted
                # above) and the fresh ones it shipped ----
                stream_drift = None
                if probe_due and old_halo is not None:
                    layers, max_rel = self._staleness_drift(
                        old_halo, self.state["comm"]["halo"])
                    stream_drift = float(max_rel)
                    if metrics is not None:
                        metrics.staleness(epoch=epoch, layers=layers,
                                          max_rel_drift=max_rel)
                    else:
                        log_fn(f"staleness probe epoch {epoch}: max "
                               f"relative drift {max_rel:.4f}")
                    old_halo = None
                # ---- contracted `stream` records for this boundary's
                # delta applications (drift measured by the forced
                # probe above; None when the pipeline is off) ----
                if stream_reports and metrics is not None:
                    for rep in stream_reports:
                        metrics.stream(
                            epoch=epoch, seq=rep.seq,
                            edges_added=rep.edges_added,
                            edges_deleted=rep.edges_deleted,
                            nodes_added=rep.nodes_added,
                            patch_ms=rep.patch_ms,
                            tables_rebuilt=rep.tables_rebuilt,
                            repadded=rep.repadded,
                            slack_remaining=rep.slack_remaining,
                            drift=stream_drift)
                # ---- divergence sentinel: check the block, roll back
                # on trip (restore last good snapshot, back the LR off,
                # flush the stale halo carry), bounded retries. With an
                # active coordinator the trip VERDICT is agreed across
                # ranks first, so the rollback below runs in lockstep
                # on the whole pod whichever rank tripped. ----
                reason = None
                trip_extra = {}
                if sentinel is not None:
                    chk_l, chk_g = blk_losses, gn
                    if ovf is not None and np.any(ovf):
                        # overflow-skipped epochs were handled by the
                        # loss scaler; mask them out of the sentinel's
                        # view (their non-finite grad norm is expected)
                        from ..resilience.numerics import \
                            sanitize_for_sentinel

                        chk_l, chk_g = sanitize_for_sentinel(
                            blk_losses, gn, ovf)
                    if chk_l is not None:
                        reason = sentinel.check(epoch, chk_l, chk_g)
                if reason is not None:
                    # ---- NaN provenance (resilience/numerics): the
                    # step's tripwire counts name the phase where the
                    # non-finite value was BORN ----
                    from ..resilience.numerics import (
                        epoch_nonfinite_counts, first_nonfinite_phase)

                    nm = self._last_metrics.get("numerics") \
                        if isinstance(self._last_metrics, dict) else None
                    if nm is not None:
                        phase = first_nonfinite_phase(nm)
                        if phase is not None:
                            bad = ~np.isfinite(np.atleast_1d(np.asarray(
                                blk_losses, np.float64)))
                            j = int(np.argmax(bad)) if bad.any() else 0
                            trip_extra["phase"] = phase
                            if metrics is not None:
                                metrics.numerics(
                                    kind="tripwire", epoch=epoch + j,
                                    phase=phase,
                                    counts=epoch_nonfinite_counts(nm, j))
                            log_fn(f"numerics tripwire: first non-finite "
                                   f"phase = {phase}")
                if coord_on:
                    desync_local = False
                    if coord.desync_due(epoch + chunk):
                        desync_local = coord.desync_check(
                            jax.device_get(self.state["params"]))
                    agreed = coord.agree_step(trip_reason=reason,
                                              desync=desync_local)
                    if agreed.desync:
                        if metrics is not None:
                            metrics.fault(
                                kind="desync", epoch=epoch + chunk - 1,
                                local_mismatch=bool(desync_local),
                                mismatched_leaves=int(
                                    coord.last_desync_mismatch),
                                # the mismatching leaf NAMES (bounded):
                                # postmortem evidence distinguishing
                                # one-tensor corruption from full
                                # divergence
                                leaves=list(coord.last_desync_leaves),
                                source_rank=agreed.desync_rank,
                                agreed=True)
                        if coord.cfg.desync_resync:
                            log_fn(f"cross-rank param desync detected "
                                   f"(source rank {agreed.desync_rank}); "
                                   f"resyncing every rank from rank 0")
                            coord.resync(self, epoch + chunk)
                            if integ is not None:
                                integ.drop_dynamic()
                            if metrics is not None:
                                metrics.recovery(kind="desync",
                                                 epoch=epoch + chunk - 1,
                                                 agreed=True)
                        else:
                            log_fn("cross-rank param desync detected; "
                                   "aborting resumably (rank 0's state "
                                   "rides the crash checkpoint)")
                            if jax.process_count() > 1 \
                                    and last_good is not None:
                                # lockstep materialization, as in the
                                # preemption branch
                                last_good = (epoch + chunk,
                                             self.host_state())
                                if coord is not None:
                                    coord.note_snapshot(*last_good)
                            raise Preempted(
                                epoch + chunk,
                                "cross-rank parameter desync")
                    if agreed.trip:
                        trip_extra = {"agreed": True,
                                      "source_rank": agreed.trip_rank}
                        if reason is None:
                            # a PEER tripped: execute the identical
                            # rollback here or the pod desynchronizes
                            reason = agreed.trip_reason()
                if reason is not None:
                    scfg = (sentinel.cfg if sentinel is not None
                            else SentinelConfig())
                    retries += 1
                    frec.crumb("sentinel-trip", epoch=epoch,
                               reason=str(reason)[:120], retry=retries)
                    rollback_to, good_state = last_good
                    new_lr = (self.tcfg.lr * scfg.lr_backoff
                              if scfg.lr_backoff < 1.0 else self.tcfg.lr)
                    log_fn(f"divergence sentinel tripped ({reason}); "
                           f"retry {retries}/{scfg.max_retries}: "
                           f"rollback to epoch {rollback_to}, "
                           f"lr -> {new_lr:g}")
                    if metrics is not None:
                        metrics.fault(
                            kind="divergence", epoch=epoch,
                            reason=reason, retry=retries,
                            rollback_epoch=rollback_to, lr=new_lr,
                            **trip_extra)
                    # restore BEFORE a possible give-up so the crash
                    # handler checkpoints the healthy state, not the
                    # divergent one
                    self.restore_state(good_state)
                    self.last_epoch = rollback_to
                    if integ is not None:
                        integ.drop_dynamic()
                    if retries > scfg.max_retries:
                        raise DivergenceError(
                            f"training diverged and "
                            f"{scfg.max_retries} recovery retries "
                            f"were exhausted: {reason}")
                    if scfg.lr_backoff < 1.0:
                        self.set_lr(new_lr)
                        # the rebuilt step recompiles once per scan
                        # length; exclude those blocks from timing
                        seen_chunks.clear()
                    if scfg.flush_on_trip and tcfg.enable_pipeline:
                        self.reset_comm()
                    trip_horizon = epoch + chunk
                    pending = None  # in-flight eval snapshot is
                    #                 from the rolled-back timeline
                    eval_in_stream = False
                    epoch = rollback_to
                    continue
                if last_good is not None:
                    if trip_horizon is not None and \
                            epoch + chunk >= trip_horizon:
                        log_fn(f"recovered past epoch {trip_horizon - 1} "
                               f"after rollback")
                        if metrics is not None:
                            metrics.recovery(kind="divergence",
                                             epoch=epoch + chunk - 1,
                                             retries=retries)
                        retries = 0
                        trip_horizon = None
                    # healthy: refresh the rollback snapshot on cadence
                    if epoch + chunk - last_good[0] >= snap_every:
                        last_good = (epoch + chunk, self.host_state())
                        if coord is not None:
                            coord.note_snapshot(*last_good)
                if integ is not None:
                    # capture params+carry digests at their production
                    # point; the NEXT boundary verifies state survived
                    # its parked window unchanged
                    integ.note_dynamic(self)
                epoch += chunk - 1  # body below sees the block's last epoch
                if measure_comm_cost and not comm_measured and \
                        epoch >= min(start_epoch + 5, n_epochs - 1):
                    # standalone collective cost, measured once post-compile
                    # (the reference reports per-epoch exposed comm/reduce
                    # waits, train.py:366-371; SPMD overlaps those inside
                    # the step, so we report the collectives' own cost)
                    with span("fit/measure_comm"):
                        comm_cost = self.measure_comm()
                    comm_measured = True
                    if tspan is not None:
                        # arm the comm tail: standalone per-epoch costs
                        # apportioned over the exchanged layers by wire
                        # bytes (the same arithmetic as
                        # est_halo_bytes_per_epoch, kept per-layer)
                        item = 4 if self.cfg.compute_dtype == jnp.float32 \
                            else 2
                        hdt = (getattr(tcfg, "halo_dtype", "none")
                               or "none") if tcfg.enable_pipeline \
                            else "none"
                        if hdt == "float8":
                            item = 1
                        elif hdt == "bfloat16":
                            item = min(item, 2)
                        tspan.set_comm(
                            comm_cost,
                            [(i, 2 * self.P * self.sg.halo_size
                              * self._layer_width(i) * item)
                             for i in self._graph_layer_range()],
                            hdt if hdt != "none" else
                            ("float32" if item == 4 else "bfloat16"))
                    if reference_logs:
                        # semantics differ from the reference: its Comm(s)
                        # is per-epoch EXPOSED wait around blocking
                        # transfers (helper/timer/comm_timer.py); SPMD
                        # overlaps those inside the jitted step, so the
                        # fields below are the collectives' standalone
                        # cost. Annotate the stream so reference-format
                        # consumers don't compare unlike quantities.
                        log_fn("# note: Comm(s)/Reduce(s) = standalone "
                               "collective cost (not exposed wait; SPMD "
                               "overlaps comm inside the step); Comm = "
                               "forward halo ring + cotangent return "
                               "ring (both modes move both)")

                hspan.to("fit/log")
                if reference_logs and (epoch + 1) % 10 == 0:
                    # reference log line format (train.py:369-371,
                    # pinned byte-exact in obs/format.py); rank is
                    # always 0 in SPMD (one controller)
                    log_fn(reference_train_line(
                        0, epoch, float(np.mean(durs or [dur])),
                        comm_cost["comm"] + comm_cost["bgrad"],
                        comm_cost["reduce"], loss))

                if (epoch + 1) % tcfg.log_every == 0:
                    do_eval = tcfg.eval and eval_graphs and "val" in eval_graphs
                    if do_eval:
                        hspan.to("fit/eval")
                        if pending is not None:
                            _harvest_eval(pending)
                            pending = None
                        p = _dispatch_eval(epoch, loss, dur)
                        if async_eval:
                            pending = p
                            eval_in_stream = True
                        else:
                            _harvest_eval(p)
                    else:
                        history.append((epoch + 1, loss, None))
                        if not reference_logs:
                            log_fn(epoch_line(
                                epoch + 1,
                                float(np.mean(durs or [dur])), loss))

                if checkpoint_dir and (epoch + 1) % checkpoint_every == 0:
                    # every process materializes (host_state is a
                    # lockstep allgather when the comm carry spans
                    # processes); only process 0 writes (reference
                    # semantics, and N-1 fewer multi-GB writes to the
                    # shared filesystem)
                    hspan.to("fit/checkpoint")
                    frec.enter("checkpoint-io", epoch=epoch + 1)
                    ck_t0 = tspan.clock() if tspan is not None else 0.0
                    host = self.host_state()
                    if jax.process_index() == 0:
                        try:
                            save_checkpoint(checkpoint_dir, host,
                                            epoch + 1,
                                            keep=checkpoint_keep,
                                            extra=_stream_watermark())
                        except OSError as io_exc:
                            # storage degradation, never an abort: the
                            # previous generation stays the
                            # authoritative resume point; retried with
                            # fresh state at later boundaries
                            was_pending = ckpt_pending
                            ckpt_pending = epoch + 1
                            log_fn(f"CHECKPOINT SAVE FAILED at epoch "
                                   f"{epoch + 1} ({io_exc!r}); "
                                   f"io-degraded — the previous "
                                   f"generation stays authoritative, "
                                   f"retrying at the next boundary")
                            if metrics is not None and was_pending is None:
                                metrics.fault(kind=IO_DEGRADED,
                                              epoch=epoch + 1,
                                              reason=repr(io_exc),
                                              component="checkpoint")
                            if checkpoint_fallback_dir:
                                try:
                                    save_checkpoint(
                                        checkpoint_fallback_dir, host,
                                        epoch + 1,
                                        keep=checkpoint_keep,
                                        extra=_stream_watermark())
                                    log_fn(
                                        f"checkpoint epoch {epoch + 1} "
                                        f"saved to fallback dir "
                                        f"{checkpoint_fallback_dir}")
                                except OSError as fb_exc:
                                    log_fn(f"fallback checkpoint dir "
                                           f"{checkpoint_fallback_dir} "
                                           f"also failed ({fb_exc!r})")
                        else:
                            if ckpt_pending is not None:
                                log_fn(f"checkpoint save recovered at "
                                       f"epoch {epoch + 1} (pending "
                                       f"since epoch {ckpt_pending})")
                                if metrics is not None:
                                    metrics.recovery(
                                        kind=IO_DEGRADED,
                                        epoch=epoch + 1,
                                        pending_since=ckpt_pending)
                                ckpt_pending = None
                            if journal is not None:
                                # the new generation covers everything
                                # applied so far: advance the durable
                                # watermark and report the replay lag a
                                # crash right now would incur
                                last_ckpt_seq = int(
                                    self._stream.last_seq)
                                if metrics is not None:
                                    metrics.journal(
                                        op="watermark",
                                        seq=last_ckpt_seq,
                                        topo_generation=int(
                                            self.topo_generation),
                                        n_records=0,
                                        lag_seqs=max(
                                            journal.last_seq()
                                            - last_ckpt_seq, 0))
                            if fault_plan is not None and \
                                    fault_plan.due("corrupt-ckpt",
                                                   epoch + 1):
                                from ..resilience.faults import \
                                    corrupt_latest_checkpoint

                                p = corrupt_latest_checkpoint(
                                    checkpoint_dir)
                                log_fn(f"fault-injected checkpoint "
                                       f"corruption: {p}")
                                if metrics is not None:
                                    metrics.fault(kind="injected",
                                                  epoch=epoch + 1,
                                                  reason="corrupt-ckpt")
                    frec.exit("checkpoint-io", epoch=epoch + 1)
                    if tspan is not None:
                        t1 = tspan.clock()
                        tspan.checkpoint_span(
                            epoch + 1, t1 - ck_t0, t_end=t1,
                            status=("error"
                                    if ckpt_pending == epoch + 1
                                    else "ok"))
                epoch += 1

        except BaseException as exc:
            # crash-resilient training (the reference's collectives
            # hang on any rank failure, SURVEY §5): best-effort save of
            # the last good state so --resume restarts from it, not
            # epoch 0. Preemption rides the same path — the boundary
            # check above raises Preempted with the state consistent.
            # last_epoch labels self.state's buffers (see train_epoch);
            # if those buffers come from a FAILED dispatch, device_get
            # below raises and the save falls back to the last host
            # snapshot when one exists — the previous periodic
            # checkpoint survives either way (saves are atomic, and the
            # generation rotation keeps the older good ones).
            if tspan is not None:
                # fault path: make the spans already emitted durable
                # before any recovery/exit handling can end the process
                try:
                    tspan.flush()
                except Exception:  # noqa: BLE001
                    pass
            converted = None
            if (coord is not None and coord.active
                    and not isinstance(exc, (Preempted, PeerLost,
                                             DivergenceError,
                                             KeyboardInterrupt))):
                # a failed collective looks like a generic runtime
                # error; ask the watchdog whether a peer actually died
                # before reporting it as a local crash
                lost = coord.await_peer_verdict()
                if lost is not None:
                    log_fn(f"dispatch failed and peer rank {lost[0]} "
                           f"stopped heartbeating ({lost[1]:.0f}s); "
                           f"reporting PeerLost instead of a crash")
                    converted = PeerLost(*lost)
            eff = converted if converted is not None else exc
            # black-box dump BEFORE the checkpoint attempts: the
            # forensics must survive even when the save path itself is
            # what's wedged. Directory preference: the configured dump
            # dir (cli/main points it at the coordination dir), else
            # the checkpoint dir, else beside the metrics stream; a
            # bare fit() with none of those skips the dump rather than
            # littering the working directory.
            try:
                done_e = int(getattr(self, "last_epoch", start_epoch))
                frec.crumb("crash", epoch=done_e,
                           error=f"{type(eff).__name__}: {eff}"[:200])
                bb_dir = frec.dump_dir or checkpoint_dir or (
                    os.path.dirname(os.fspath(metrics.path)) or "."
                    if metrics is not None
                    and getattr(metrics, "path", None) else None)
                if bb_dir:
                    flightrec.dump_blackbox(
                        "preemption" if isinstance(eff, Preempted)
                        else "fault" if isinstance(eff, PeerLost)
                        else "exception",
                        directory=bb_dir, epoch=done_e,
                        error=f"{type(eff).__name__}: {eff}"[:200])
            except Exception:  # noqa: BLE001 — never mask the fault
                pass
            if metrics is not None and isinstance(eff, PeerLost):
                try:
                    metrics.fault(kind="peer-lost",
                                  epoch=int(getattr(self, "last_epoch",
                                                    start_epoch)),
                                  peer_rank=eff.rank,
                                  silent_s=eff.silent_s)
                except Exception:  # noqa: BLE001 — still checkpoint
                    pass
            # every surviving rank saves on PeerLost (rank 0 may be the
            # dead one); otherwise rank 0 only, as before
            if checkpoint_dir and (jax.process_index() == 0
                                   or isinstance(eff, PeerLost)):
                tag = ("preemption" if isinstance(eff, Preempted)
                       else "peer-lost" if isinstance(eff, PeerLost)
                       else "crash")
                try:
                    done = int(getattr(self, "last_epoch",
                                       start_epoch))
                    save_checkpoint(checkpoint_dir,
                                    jax.device_get(self.state), done,
                                    keep=checkpoint_keep,
                                    extra=_stream_watermark())
                    log_fn(f"{tag} checkpoint saved to "
                           f"{checkpoint_dir} (epoch {done})")
                except Exception as save_exc:  # noqa: BLE001
                    if last_good is not None:
                        # poisoned buffers: the host-side snapshot is
                        # still a valid, older resume point. The live
                        # topology is never rolled back in-process, so
                        # the CURRENT watermark is the graph these
                        # params were last training against
                        try:
                            save_checkpoint(checkpoint_dir,
                                            last_good[1], last_good[0],
                                            keep=checkpoint_keep,
                                            extra=_stream_watermark())
                            log_fn(f"{tag} checkpoint fell back to the "
                                   f"epoch-{last_good[0]} snapshot "
                                   f"({save_exc!r})")
                        except Exception as snap_exc:  # noqa: BLE001
                            log_fn(f"{tag} checkpoint failed: "
                                   f"{snap_exc!r}")
                    else:
                        log_fn(f"{tag} checkpoint failed: {save_exc!r}")
            if converted is not None:
                raise converted from exc
            raise
        finally:
            # a fit-armed storage fault must never outlive fit: the
            # shim is process-wide, and later in-process work (tests,
            # a clean resume in the same interpreter) would otherwise
            # inherit a permanently "full" disk. The crash handler
            # above runs BEFORE this, still degraded — exactly like a
            # real host whose disk is full when it dies
            hspan.to()
            if blocks is not None:
                blocks.close()
                recorder().restore(sink_prev)
            for kind in list(io_armed):
                FAULTY_IO.disarm(kind)
            io_armed.clear()
            if stall_det is not None:
                stall_det.stop()
            frec.crumb("fit-end", epoch=epoch)

        if pending is not None:
            # harvest the final in-flight evaluation
            _harvest_eval(pending)
            pending = None
        if (tcfg.eval and eval_graphs and "val" in eval_graphs
                and n_epochs > start_epoch
                and n_epochs % tcfg.log_every != 0):
            # the run's final epochs lie past the last log boundary, so
            # the FINAL state was never scored (with log_every >
            # n_epochs, no eval happened at all and the summary would
            # be silently empty); always evaluate it before reporting
            _harvest_eval(_dispatch_eval(epoch - 1, loss, dur))

        if profiling:
            # run ended inside the trace window; finalize + analyze
            profiling = False
            prof_record = _finish_profile(
                (prof_started_at, epoch)) or prof_record
        if profile_dir and prof_record is None and \
                n_epochs - start_epoch <= 0:
            log_fn("warning: run too short, no profiler trace captured")

        result = {
            "best_val": best_val,
            "best_epoch": best_epoch,
            "best_params": best_params,
            "best_norm": best_norm,
            # short runs can have every block excluded (warmup /
            # first-of-scan-length); fall back to the last block's
            # per-epoch time (compile-inclusive) rather than None
            "epoch_time": float(np.mean(durs)) if durs
            else (dur if n_epochs > start_epoch else None),
            # async mode: mean EXPOSED harvest wait (the eval's device
            # time hides behind subsequent epochs); sync mode: full eval
            # wall-clock like the reference's evaluate() span
            "eval_time": float(np.mean(eval_durs)) if eval_durs else None,
            "comm_cost": comm_cost if comm_measured else None,
            "history": history,
            # the parsed profiling-window record (measured per-phase
            # device time + overlap fraction), None when no window ran
            "profile": prof_record,
        }
        if tcfg.eval and eval_graphs and "test" in eval_graphs and \
                best_params is not None:
            g, mask = eval_graphs["test"]
            result["test_acc"] = self.evaluate(g, mask, params=best_params,
                                               norm=best_norm,
                                               sharded=sharded_eval)
        if metrics is not None:
            summ: Dict[str, Any] = {
                "n_epochs": n_epochs - start_epoch,
                "epoch_time_s": result["epoch_time"],
                "best_val": float(best_val),
                "best_epoch": int(best_epoch),
                "eval_time_s": result["eval_time"],
                "comm_cost": comm_cost if comm_measured else None,
            }
            if "test_acc" in result:
                summ["test_acc"] = float(result["test_acc"])
            if 1 in seen_chunks:
                # XLA's own per-epoch FLOP count (whole-job scale) so
                # the report CLI can derive MFU. Only when the run
                # already compiled the single-epoch program — cost
                # analysis on a fused-only run would pay a whole extra
                # compile for a telemetry extra. Best-effort: some
                # backends expose no analysis.
                try:
                    ca = self.step_cost_analysis()
                    if ca.get("flops"):
                        summ["flops_per_epoch"] = \
                            float(ca["flops"]) * self.P
                except Exception:
                    pass
            metrics.summary(**summ)
        return result

    # ---------------- profiling / staleness ---------------------------

    def step_compiled_text(self, length: int = 1) -> str:
        """Optimized-HLO text of the program `fit` dispatches for a
        block of `length` epochs: the single-epoch step, or the scan of
        that length (the metadata op_name scopes are the join key
        between trace events and named phases, obs/profiler.py).
        Compiled past the persistent cache, whose entries carry the
        scope names of the checkout that wrote them
        (backend.compiled_text_uncached): one real compile."""
        from ..backend import compiled_text_uncached

        base = self._epoch_rng_base()
        scale = jnp.float32(self.loss_scaler.scale)
        if length == 1:
            lowered = self._step.lower(
                self.state, self.data, jax.random.fold_in(base, 0), scale)
        else:
            rngs = jax.vmap(lambda e: jax.random.fold_in(base, e))(
                jnp.arange(length))
            lowered = self._multi_step.lower(self.state, self.data, rngs,
                                             scale)
        return compiled_text_uncached(lowered)

    def _profile_analysis(self, profile_dir: str, lengths):
        """Fold the newest capture under `profile_dir` against the
        compiled program of every scan length dispatched inside the
        window; returns a profile-record body or None."""
        from ..obs.profiler import analyze_trace_dir

        return analyze_trace_dir(
            profile_dir,
            {k: self.step_compiled_text(k) for k in sorted(lengths)})

    def _staleness_drift(self, old_halo, new_halo):
        """Per-layer relative drift between the stale halo carry
        consumed this epoch (`old_halo`, snapshotted pre-dispatch) and
        the fresh one the step shipped (`new_halo`): the approximation
        error the staleness-1 pipeline pays. Returns ({layer:
        {rel_drift, fresh_norm}}, max_rel_drift). The norm program is
        jitted once and reused (cached by pytree structure)."""
        fn = getattr(self, "_staleness_norm_fn", None)
        if fn is None:
            @jax.jit
            def fn(old, new):
                out = {}
                for k in old:
                    d = (new[k].astype(jnp.float32)
                         - old[k].astype(jnp.float32))
                    out[k] = (jnp.sqrt(jnp.sum(d * d)),
                              jnp.sqrt(jnp.sum(jnp.square(
                                  new[k].astype(jnp.float32)))))
                return out

            self._staleness_norm_fn = fn
        norms = jax.device_get(fn(old_halo, new_halo))
        layers = {}
        max_rel = 0.0
        for k, (dn, fresh) in sorted(norms.items()):
            dn, fresh = float(dn), float(fresh)
            # degenerate all-zero fresh buffer: report 1.0 (total
            # drift) rather than an inf that breaks strict JSON readers
            rel = dn / fresh if fresh > 0 else (0.0 if dn == 0.0
                                               else 1.0)
            layers[k] = {"rel_drift": rel, "fresh_norm": fresh}
            max_rel = max(max_rel, rel)
        return layers, max_rel

    # ---------------- cost analysis -----------------------------------

    def step_cost_analysis(self) -> Dict[str, float]:
        """XLA's own cost model for ONE epoch of the train step (keys
        like 'flops' and 'bytes accessed'), for MFU / bandwidth
        reporting. Compiles the single-epoch program if it isn't already
        cached; returns {} when the backend doesn't expose an analysis."""
        rng = jax.random.fold_in(self._epoch_rng_base(), 0)
        ca = self._step.lower(self.state, self.data, rng,
                              jnp.float32(self.loss_scaler.scale)) \
            .compile().cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else None
        if not ca:
            return {}
        return {k: float(v) for k, v in ca.items()
                if isinstance(v, (int, float))}

    def est_halo_bytes_per_epoch(self, compressed: bool = True) -> int:
        """Estimated halo wire bytes per epoch: per exchanged graph
        layer, every device ships its halo block forward and the
        boundary gradients back (2x). This is the metrics records'
        `halo_bytes` field; est_ici_bytes_per_epoch adds the gradient
        all-reduce on top. `compressed=True` (default) accounts for the
        --halo-dtype wire narrowing (1 byte under float8, 2 under
        bfloat16); compressed=False gives the uncompressed figure the
        report's compression-ratio line compares against."""
        if self.P == 1:
            return 0
        item = 4 if self.cfg.compute_dtype == jnp.float32 else 2
        if compressed:
            hdt = getattr(self.tcfg, "halo_dtype", "none") or "none"
            if hdt == "float8":
                item = 1
            elif hdt == "bfloat16":
                item = min(item, 2)
        total = 0
        for i in self._graph_layer_range():
            total += 2 * self.P * self.sg.halo_size * self._layer_width(i) \
                * item
        return int(total)

    def est_ici_bytes_per_epoch(self) -> int:
        """Estimated inter-device traffic per epoch: the per-layer halo
        exchange (est_halo_bytes_per_epoch) plus the ring all-reduce of
        the grads (~2x param bytes per device)."""
        if self.P == 1:
            return 0
        n_params = sum(
            int(np.prod(p.shape))
            for p in jax.tree_util.tree_leaves(self.state["params"])
        )
        return self.est_halo_bytes_per_epoch() + int(2 * self.P * n_params * 4)

    # ---------------- comm cost measurement ---------------------------

    def measure_comm(self, repeats: int = 5) -> Dict[str, float]:
        """Standalone timing of the step's collectives: per-layer halo
        exchange ('comm', the analogue of the reference's exposed
        forward/backward transfer waits, helper/timer/comm_timer.py) and
        the gradient psum ('reduce', reference reducer timing
        train.py:359-361). In pipelined mode the real step overlaps these
        with compute, so this measures the collective cost, not exposed
        wait time."""
        if self.emulated:
            raise RuntimeError(
                "measure_comm is meaningless under emulate_parts: the "
                "collectives are in-device data movement")
        P = self.P
        spec = PartitionSpec(PARTS_AXIS)

        cdt = self.cfg.compute_dtype
        # probe through the same wire dtypes as the train step, so the
        # timed exchange moves the same bytes (incl. --halo-dtype
        # compression in pipelined mode)
        feat_dt, bgrad_dt = halo_transport_dtypes(
            getattr(self.tcfg, "halo_dtype", "none")
            if self.tcfg.enable_pipeline else "none")

        def comm_fn(feat, send_idx, send_mask):
            feat, send_idx, send_mask = feat[0], send_idx[0], send_mask[0]
            outs = []
            for i in self._graph_layer_range():
                w = self._layer_width(i)
                # probe in the compute dtype so the timed exchange moves
                # the same bytes the train step's halo transport does
                h = feat[:, :1].astype(cdt) * jnp.ones((1, w), cdt)
                blocks = exchange_blocks(h, send_idx, send_mask,
                                         PARTS_AXIS, P,
                                         transport_dt=feat_dt)
                outs.append(blocks.sum())
            return jnp.stack(outs).sum()[None] if outs else \
                jnp.zeros((1,), jnp.float32)

        comm_jit = jax.jit(jax.shard_map(
            comm_fn, mesh=self.mesh, in_specs=(spec,) * 3, out_specs=spec,
        ))

        def bgrad_fn(feat):
            # the reverse ring shipping each epoch's halo cotangents
            # back to their owners. BOTH modes move it — vanilla
            # through halo_exchange's VJP, pipelined through the comm
            # carry's explicit return_blocks — so it belongs in
            # Comm(s) for both. The EMA corrections are local
            # arithmetic — no wire traffic.
            feat = feat[0]
            outs = []
            for i in self._graph_layer_range():
                w = self._layer_width(i)
                hg = feat[:1, :1].astype(cdt) * jnp.ones(
                    ((P - 1) * self.sg.b_max, w), cdt)
                outs.append(
                    return_blocks(hg, PARTS_AXIS, P, self.sg.b_max,
                                  transport_dt=bgrad_dt).sum())
            return jnp.stack(outs).sum()[None] if outs else \
                jnp.zeros((1,), jnp.float32)

        bgrad_jit = jax.jit(jax.shard_map(
            bgrad_fn, mesh=self.mesh, in_specs=(spec,), out_specs=spec,
        ))

        def reduce_fn(params):
            return jax.tree_util.tree_map(
                lambda p: jax.lax.psum(p, PARTS_AXIS), params
            )

        reduce_jit = jax.jit(jax.shard_map(
            reduce_fn, mesh=self.mesh,
            in_specs=(jax.tree_util.tree_map(
                lambda _: PartitionSpec(), self.state["params"]),),
            out_specs=jax.tree_util.tree_map(
                lambda _: PartitionSpec(), self.state["params"]),
        ))

        d = self.data
        args = (d["feat"], d["send_idx"], d["send_mask"])
        jax.block_until_ready(comm_jit(*args))  # compile
        jax.block_until_ready(reduce_jit(self.state["params"]))

        def _med(fn, *a):
            ts = []
            for _ in range(repeats):
                t0 = time.perf_counter()
                jax.block_until_ready(fn(*a))
                ts.append(time.perf_counter() - t0)
            return float(np.median(ts))

        jax.block_until_ready(bgrad_jit(d["feat"]))  # compile
        return {
            "comm": _med(comm_jit, *args),
            "reduce": _med(reduce_jit, self.state["params"]),
            "bgrad": _med(bgrad_jit, d["feat"]),
        }

    # ---------------- evaluation --------------------------------------

    def evaluate(self, g: Graph, mask_key: str, params=None,
                 norm=None, sharded: bool = False) -> float:
        """Evaluate `g` and block for the scalar.

        sharded=False: full-graph eval on one device (reference evaluates
        the full graph on rank 0's CPU, train.py:20-61; we use the
        accelerator). sharded=True: partition-parallel eval through the
        training mesh (parallel/evaluator.py) — use for graphs too big
        for one device."""
        return self.eval_finish(
            self.eval_dispatch(g, mask_key, params, norm, sharded))

    def eval_dispatch(self, g: Graph, mask_key: str, params=None,
                      norm=None, sharded: bool = False):
        """Start an evaluation WITHOUT blocking (jax async dispatch);
        returns an opaque handle for eval_finish. The computation is
        enqueued on the devices before any subsequent train step, so
        later buffer donation cannot race it."""
        if params is None:
            params = self.state["params"]
        if norm is None:
            norm = self.state["norm"]
        if self.emulated:
            # emulate-mode params/norm are ALWAYS the stacked [P, ...]
            # replicas (state, fit snapshots); take one copy for the
            # single-device eval
            params = jax.tree_util.tree_map(lambda v: v[0], params)
            norm = jax.tree_util.tree_map(lambda v: v[0], norm)
        if sharded:
            if self.emulated:
                raise RuntimeError(
                    "sharded eval needs the real device mesh; "
                    "emulate_parts trainers evaluate full-graph")
            ev = self._get_sharded_evaluator(g)
            return ("sharded", ev, ev.counts(mask_key, params, norm))
        c = self._full_eval_cache(g)
        logits = self._eval_run(params, norm, c["feat"], c["edge_src"],
                                c["edge_dst"], c["in_deg"], c["n"])
        return ("full", c, logits, mask_key)

    def eval_finish(self, handle) -> float:
        """Resolve a dispatched evaluation to its scalar metric (blocks
        only if the device computation hasn't completed yet)."""
        if handle[0] == "sharded":
            _, ev, counts = handle
            return ev.finish(counts)
        _, c, logits, mask_key = handle
        logits = np.asarray(logits)
        m = np.asarray(c["graph"].ndata[mask_key])
        return calc_acc(logits[m], c["label"][m])

    def _get_sharded_evaluator(self, g: Graph):
        from .evaluator import ShardedEvaluator

        key = id(g)
        if key not in self._sharded_eval_cache:
            self._sharded_eval_cache[key] = (
                ShardedEvaluator.for_graph(self, g), g)
        return self._sharded_eval_cache[key][0]

    def _full_eval_cache(self, g: Graph):
        key = id(g)
        if key not in self._eval_cache:
            from ..native import stable_argsort

            n = g.num_nodes
            # CSR-sort eval edges so the sorted segment reduction applies
            order = stable_argsort(g.dst)
            self._eval_cache[key] = {
                "graph": g,  # strong ref: keeps id(g) valid while cached
                "feat": jnp.asarray(g.ndata["feat"]),
                "label": g.ndata["label"],
                "edge_src": jnp.asarray(g.src[order].astype(np.int32)),
                "edge_dst": jnp.asarray(g.dst[order].astype(np.int32)),
                "in_deg": jnp.asarray(
                    np.maximum(g.in_degrees(), 1).astype(np.float32)
                ),
                "n": n,
            }
        return self._eval_cache[key]
