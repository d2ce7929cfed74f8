"""Sharded full-graph evaluation over the training mesh.

The reference evaluates the FULL graph single-process on rank 0's host
CPU (train.py:20-61, README requires >=120 GB host RAM for papers100M);
the round-1 port evaluated on one accelerator's HBM — neither scales.
This evaluator runs the eval forward through the same shard_map layout
as training: each device computes logits for its own partition (with a
synchronous halo exchange per layer — exact, no staleness), then the
accuracy statistic is reduced with psum. No device (or host) ever holds
the full graph, so eval scales with the mesh exactly like training.

Metric reduction (train/metrics.py semantics, reference train.py:11-17):
  single-label: counts = [correct, total, 0]        -> correct/total
  multi-label:  counts = [tp, fp, fn] (pred=logits>0) -> 2tp/(2tp+fp+fn)

The counts come back as ONE tiny replicated device array, so `counts()`
is non-blocking — fit() dispatches evaluation and harvests the scalar a
log-period later, keeping eval off the critical path (the TPU analogue
of the reference's background-thread eval, train.py:327-328, 377-389).
"""

from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec

from ..graph.csr import Graph
from ..models.sage import forward
from ..obs.trace import named_phase
from .halo import halo_exchange
from .mesh import PARTS_AXIS


# bumped at TRACE time inside eval_fn: a delta of zero across repeated
# evaluator constructions proves the cached program was reused instead
# of recompiled (tests/test_eval.py pins this)
EVAL_TRACE_COUNT = 0


def _program_key(sg, dev_data, use_tables: bool, multilabel: bool):
    """Cache key for the compiled sharded-eval program: everything the
    traced computation depends on besides the trainer-fixed cfg/mesh —
    graph shapes, the data pytree signature (keys + shapes + dtypes,
    which also encodes the kernel impl via its table arrays), and the
    metric flavor."""
    return (
        sg.n_max, sg.halo_size, bool(use_tables), bool(multilabel),
        tuple(sorted((k, tuple(v.shape), str(v.dtype))
                     for k, v in dev_data.items())),
    )


def _covers_exactly(sg, g: Graph) -> bool:
    """True iff the training partitions were built from exactly graph
    `g` (the transductive case: the trainer's sharded data IS the eval
    graph, so its arrays can be reused without a rebuild). Node-ID cover
    alone is not sufficient — an eval graph can share the node set with
    different edges — so the source edge checksum must match too (old
    artifacts without one conservatively rebuild)."""
    nid = sg.global_nid[sg.global_nid >= 0]
    if nid.size != g.num_nodes:
        return False
    if not np.array_equal(np.sort(nid), np.arange(g.num_nodes)):
        return False
    if getattr(sg, "source_edge_checksum", -1) == -1:
        return False
    if int(sg.edge_count.sum()) != g.num_edges:
        return False
    from ..partition.halo import ShardedGraph

    return sg.source_edge_checksum == ShardedGraph.edge_checksum(g)


class ShardedEvaluator:
    """Evaluates one graph through a Trainer's mesh.

    Use `ShardedEvaluator.for_graph(trainer, g)`: reuses the trainer's
    device-resident arrays when `g` is the training graph (transductive),
    else partitions `g` across the same devices and uploads its shards
    (inductive val/test graphs, or any external graph).
    """

    def __init__(self, trainer, sg, data: Dict[str, jax.Array],
                 use_tables: bool = False):
        self.trainer = trainer
        self.sg = sg
        # the fixed traced input of _run (pytree structure must not
        # change between calls); lazily-added masks live in self.data
        self._dev_data = dict(data)
        self.data = dict(data)
        self._cfg = trainer.cfg  # already has sorted_edges=True
        P = trainer.P
        n_max = sg.n_max
        multilabel = sg.multilabel
        self.multilabel = multilabel

        # the compiled program is shared across evaluator instances
        # through the trainer: repeated eval of same-signature graphs
        # (convergence-study legs, serving warmup, foreign val/test
        # graphs of one shape) pays compile once, not per construction
        prog_key = _program_key(sg, self._dev_data, use_tables,
                                multilabel)
        cached = getattr(trainer, "_eval_program_cache", None)
        if cached is not None and prog_key in cached:
            self._run = cached[prog_key]
            return

        def eval_fn(params, norm, data_in, mask):
            global EVAL_TRACE_COUNT
            EVAL_TRACE_COUNT += 1
            d = {k: v[0] for k, v in data_in.items()}
            label, mask = d["label"], mask[0]

            def comm_update(i, h):
                return halo_exchange(h, d["send_idx"], d["send_mask"],
                                     PARTS_AXIS, P)

            # aggregate through kernel tables when the data carries them
            # (use_tables): the trainer's own tables for the
            # transductive covers-exactly case, or bucket tables built
            # for a foreign (inductive) eval graph — both beat the
            # raw-edge gather path. Shapes come from THIS sg, which may
            # be sharded differently from the training graph.
            # transport=False: evaluation is one-shot and metric-
            # bearing — it must not inherit the narrowed per-epoch
            # gather transport (rem_dtype), and with use_pp=False its
            # first layer aggregates RAW features
            spmm = trainer.make_device_spmm_closure(
                d, n_max=n_max, n_src_rows=n_max + sg.halo_size,
                transport=False,
            ) if use_tables else None
            # GAT aggregates through the attention-bucket closure (its
            # tables ride in the data exactly like the mean kernels')
            gat = trainer.make_device_gat_closure(
                d, n_max=n_max, n_src_rows=n_max + sg.halo_size,
                transport=False,
            ) if use_tables else None
            with named_phase("eval"):
                logits, _ = forward(
                    params, self._cfg, d["feat"], d["edge_src"],
                    d["edge_dst"], d["in_deg"], n_max,
                    training=False, halo_eval=True,
                    comm_update=comm_update,
                    norm_state=norm, spmm_fn=spmm, gat_fn=gat,
                )
            if multilabel:
                pred = logits > 0
                lab = label > 0.5
                m = mask[:, None]
                # int32 counts: exact up to 2.1e9 elements (f32 would
                # round above 2^24, well within papers100M's range)
                tp = jnp.sum(pred & lab & m, dtype=jnp.int32)
                fp = jnp.sum(pred & ~lab & m, dtype=jnp.int32)
                fn = jnp.sum(~pred & lab & m, dtype=jnp.int32)
                counts = jnp.stack([tp, fp, fn])
            else:
                correct = jnp.sum((jnp.argmax(logits, -1) == label) & mask,
                                  dtype=jnp.int32)
                total = jnp.sum(mask, dtype=jnp.int32)
                counts = jnp.stack([correct, total,
                                    jnp.zeros((), jnp.int32)])
            with named_phase("eval_metric_reduce"):
                return jax.lax.psum(counts, PARTS_AXIS)

        spec = PartitionSpec(PARTS_AXIS)
        repl = PartitionSpec()
        params_spec = jax.tree_util.tree_map(
            lambda _: repl, trainer.state["params"])
        norm_spec = jax.tree_util.tree_map(
            lambda _: repl, trainer.state["norm"])
        data_spec = jax.tree_util.tree_map(lambda _: spec, self._dev_data)
        self._run = jax.jit(jax.shard_map(
            eval_fn,
            mesh=trainer.mesh,
            in_specs=(params_spec, norm_spec, data_spec, spec),
            out_specs=repl,
        ))
        if cached is not None:
            cached[prog_key] = self._run

    # ------------------------------------------------------------------
    @staticmethod
    def for_graph(trainer, g: Graph,
                  parts: Optional[np.ndarray] = None) -> "ShardedEvaluator":
        if _covers_exactly(trainer.sg, g):
            # transductive: reuse the trainer's device arrays, kernel
            # tables included — no re-upload even when the trainer
            # trimmed the raw edge list from HBM
            return ShardedEvaluator(trainer, trainer.sg, trainer.data,
                                    use_tables=trainer._edges_trimmed)

        from ..partition.halo import ShardedGraph
        from ..partition.partitioner import partition_graph

        if parts is None:
            parts = partition_graph(g, trainer.P, method="metis",
                                    obj="vol", seed=0)
        sg = ShardedGraph.build(g, parts, n_parts=trainer.P)
        arrs = {
            "feat": sg.feat,
            "label": sg.label,
            "in_deg": sg.in_deg,
            "edge_src": sg.edge_src.astype(np.int32),
            "edge_dst": sg.edge_dst.astype(np.int32),
            "send_idx": sg.send_idx.astype(np.int32),
            "send_mask": sg.send_mask,
            "val_mask": sg.val_mask,
            "test_mask": sg.test_mask,
            "train_mask": sg.train_mask,
        }
        use_tables = False
        if trainer._edges_trimmed:
            # the training step aggregates through kernel tables, so
            # repeated evals of this foreign graph deserve the same:
            # build tables for ITS shards — the attention-bucket tables
            # for GAT (forward() ignores spmm_fn there), else the
            # general-purpose mean bucket tables
            if trainer.cfg.model == "gat":
                from ..ops.gat_bucket import build_sharded_gat_tables

                arrs.update(build_sharded_gat_tables(sg))
            else:
                from ..ops.bucket_spmm import build_sharded_bucket_tables

                arrs.update(build_sharded_bucket_tables(sg))
            use_tables = True
            # the pp precompute also aggregates through the tables, so
            # the raw edge arrays never need to reach the device
            # (mirrors Trainer._put_data skip_edges)
            dummy = np.zeros((trainer.P, 8), np.int32)
            arrs["edge_src"] = dummy
            arrs["edge_dst"] = dummy
        data = {
            k: jax.device_put(np.asarray(v), trainer._shard)
            for k, v in arrs.items()
        }
        if trainer.cfg.use_pp:
            # layer 0 consumes the precomputed [feat, mean_neigh] concat;
            # rebuild it for this graph's own edges/degrees (through the
            # kernel tables when present)
            data["feat"] = trainer._precompute_pp(sg, data)
        return ShardedEvaluator(trainer, sg, data, use_tables=use_tables)

    # ------------------------------------------------------------------
    def _mask(self, mask_key: str) -> jax.Array:
        m = self.data.get(mask_key)
        if m is None:  # trainer data carries masks under sg arrays
            m = jax.device_put(
                jnp.asarray(getattr(self.sg, mask_key)),
                self.trainer._shard)
            self.data[mask_key] = m
        return m

    def counts(self, mask_key: str, params=None, norm=None) -> jax.Array:
        """Dispatch the sharded eval; returns the [3] reduced counts as a
        device array WITHOUT blocking (jax async dispatch)."""
        t = self.trainer
        return self._run(
            params if params is not None else t.state["params"],
            norm if norm is not None else t.state["norm"],
            self._dev_data,
            self._mask(mask_key),
        )

    def finish(self, counts) -> float:
        """Turn dispatched counts into the scalar metric (blocks only if
        the computation hasn't completed yet)."""
        c = np.asarray(counts)
        if self.multilabel:
            tp, fp, fn = float(c[0]), float(c[1]), float(c[2])
            denom = 2 * tp + fp + fn
            return 2 * tp / denom if denom else 0.0
        return float(c[0]) / float(c[1]) if c[1] else 0.0

    def accuracy(self, mask_key: str, params=None, norm=None) -> float:
        return self.finish(self.counts(mask_key, params, norm))
