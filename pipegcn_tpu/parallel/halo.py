"""Device-side halo (boundary node) exchange.

The TPU-native replacement for the reference's entire comm stack —
ring-staggered gloo isend/irecv with pinned CPU staging, CUDA streams,
events and message tags (helper/feature_buffer.py:165-206) — expressed as
gather -> `lax.ppermute` -> concat inside `shard_map`. XLA differentiates
it (gather transposes to scatter-add, ppermute to the reverse ring), so
the vanilla path needs no hand-written backward; race-freedom is by
construction, and the event/stream/tag apparatus disappears.

Functions here run *inside* shard_map: array args are per-device blocks.

Ring layout (see partition.halo.ShardedGraph): at distance d, device r
sends `h[send_idx[d-1]]` to (r+d) mod P and receives the block whose rows
belong to owner (r-d) mod P; received blocks concatenate behind the inner
rows in distance order, matching the precomputed halo slot numbering.

`make_stale_concat` is the pipelined (staleness-1) variant: consuming
last epoch's halo features, injecting last epoch's boundary gradients
into this epoch's backward (reference feature_buffer.py:153-163,228-236),
and exposing this epoch's halo cotangent through a probe input so the
train step can ship it to owners for the next epoch.

Compressed transport (`--halo-dtype`): the ppermute payloads may travel
in a narrower dtype than the compute dtype — the same bf16/fp8
machinery the SpMM gather transport uses (ops/bucket_spmm.py
transport_dtypes/transport_cast) applied to the ICI wire itself. Each
distance block is cast on the sender, permuted narrow, and decoded
back to the compute dtype on the receiver; fp8 payloads ship a
per-block power-of-two inverse scale alongside (amax_transport_cast,
the PR 5 range guard), so large activations are never statically
saturated nor small cotangents flushed. Pipelined-mode only: the
exchange there sits behind stop_gradient / an explicit cotangent ship,
so the cast never lands inside a differentiated path.
"""

from __future__ import annotations

import contextlib
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ..ops.bucket_spmm import amax_transport_cast, transport_dtypes


def halo_transport_dtypes(halo_dtype: Optional[str]) -> Tuple:
    """(feature, bgrad) wire dtypes for a --halo-dtype spec, following
    the SpMM transport convention: activations e4m3, cotangents e5m2,
    bf16 for both, None = uncompressed (the compute dtype)."""
    if halo_dtype in (None, "", "none"):
        return None, None
    # reuse the rem-transport mapping ('bfloat16' | 'float8')
    return transport_dtypes(halo_dtype)


def _ensure_varying(x: jax.Array, axis_name: str) -> jax.Array:
    """Mark x device-varying over axis_name unless it already is (pcast
    rejects varying->varying)."""
    if axis_name in jax.typeof(x).vma:
        return x
    return jax.lax.pcast(x, axis_name, to="varying")


def _fwd_perm(num_parts: int, d: int):
    return [(r, (r + d) % num_parts) for r in range(num_parts)]


def _bwd_perm(num_parts: int, d: int):
    return [(r, (r - d) % num_parts) for r in range(num_parts)]


# Trace-time switch for the exposed-wait measurement ONLY
# (scripts/overlap_study.py): with identity_collectives() active, the
# ring ppermutes become identity — same shapes/dtypes/gather/concat
# structure, zero inter-device traffic. The reference's Comm(s) metric
# is the per-epoch wait its hooks EXPOSE (helper/timer/comm_timer.py,
# train.py:366-371); timing a step traced with vs without the permutes
# yields that exposed cost directly (total - hidden), which HLO def-use
# structure alone cannot. Data semantics are wrong (each device keeps
# its own boundary rows) — never use while training for real.
_IDENTITY_COLLECTIVES = False


@contextlib.contextmanager
def identity_collectives():
    global _IDENTITY_COLLECTIVES
    prev = _IDENTITY_COLLECTIVES
    _IDENTITY_COLLECTIVES = True
    try:
        yield
    finally:
        _IDENTITY_COLLECTIVES = prev


def _ring_permute(blk: jax.Array, axis_name: str, perm) -> jax.Array:
    if _IDENTITY_COLLECTIVES:
        return _ensure_varying(blk, axis_name)
    return jax.lax.ppermute(blk, axis_name, perm)


def wire_sum(x: jax.Array) -> jax.Array:
    """uint32 wraparound sum of an array's raw bits — the wire-
    integrity checksum (resilience/integrity.py uses the same
    order-independent construction for its at-rest digests; a single
    flipped bit shifts the sum by +-2^k != 0 mod 2^32, so one-flip
    detection is certain). Jittable; any dtype."""
    x = x.reshape(-1)
    if x.dtype == jnp.bool_:
        x = x.astype(jnp.uint8)
    size = jnp.dtype(x.dtype).itemsize
    if size == 1:
        u = jax.lax.bitcast_convert_type(x, jnp.uint8).astype(jnp.uint32)
    elif size == 2:
        u = jax.lax.bitcast_convert_type(x, jnp.uint16).astype(jnp.uint32)
    else:
        u = jax.lax.bitcast_convert_type(x, jnp.uint32).reshape(-1)
    if u.shape[0] == 0:
        return jnp.zeros((), jnp.uint32)
    return jnp.sum(u, dtype=jnp.uint32)


def _permute_compressed(blk: jax.Array, axis_name: str, perm,
                        transport_dt, guard: bool = False):
    """Ring-permute one distance block, optionally in a narrow wire
    dtype. fp8 payloads use the amax-clamped cast and ship the sender's
    power-of-two inverse scale through the SAME permutation, so the
    receiver decodes with its peer's scale — never its own. The result
    is always back in blk's original dtype.

    guard=True adds the wire-integrity checksum lane: the sender's
    :func:`wire_sum` of the exact permuted payload rides the SAME
    permutation (like the fp8 inverse scale), the receiver recomputes
    it on what arrived, and the return becomes ``(blk, bad)`` with
    ``bad`` an int32 0/1 mismatch flag. The lane is a trace-time
    choice: guard=False compiles the byte-identical program this
    module always built."""

    def _guarded(payload):
        s = _ring_permute(wire_sum(payload), axis_name, perm)
        rx = _ring_permute(payload, axis_name, perm)
        return rx, (wire_sum(rx) != s).astype(jnp.int32)

    if transport_dt is None:
        if not guard:
            return _ring_permute(blk, axis_name, perm)
        return _guarded(blk)
    out_dt = blk.dtype
    y, inv = amax_transport_cast(blk, transport_dt)
    bad = None
    if guard:
        y, bad = _guarded(y)
    else:
        y = _ring_permute(y, axis_name, perm)
    if inv is None:
        # bf16 wire: a straight cast round-trips through the permute
        out = y.astype(out_dt)
        return (out, bad) if guard else out
    if guard:
        inv, bad_inv = _guarded(jnp.asarray(inv, jnp.float32))
        bad = jnp.maximum(bad, bad_inv)
    else:
        inv = _ring_permute(jnp.asarray(inv, jnp.float32), axis_name,
                            perm)
    out = (y.astype(jnp.float32) * inv).astype(out_dt)
    return (out, bad) if guard else out


def exchange_blocks(
    h: jax.Array,
    send_idx: jax.Array,
    send_mask: jax.Array,
    axis_name: str,
    num_parts: int,
    transport_dt=None,
    guard: bool = False,
):
    """Gather boundary rows and ring-exchange them.

    h: [N, F] inner rows; send_idx/mask: [P-1, B]. Returns the halo block
    [(P-1)*B, F]: distance-d rows hold features owned by (r-d) mod P.
    `transport_dt` (optional) narrows the ppermute payload to that wire
    dtype (decoded back to h.dtype on arrival) — pipelined-mode halo
    compression; leave None on differentiated paths.

    guard=True (trace-time) threads the wire-integrity checksum lane
    through every distance block and returns ``(halo, bad)`` — ``bad``
    an int32 count of distance blocks whose received payload failed
    the sender's checksum (0 on a healthy wire).

    The whole gather->permute->concat runs under the "halo_exchange"
    named scope so --profile-dir traces attribute the ring collectives
    (and their backward scatters) to the phase, not anonymous fusions.
    """
    with jax.named_scope("halo_exchange"):
        blocks = []
        bad = jnp.zeros((), jnp.int32)
        for d in range(1, num_parts):
            blk = jnp.take(h, send_idx[d - 1], axis=0, mode="clip")
            blk = jnp.where(send_mask[d - 1][:, None], blk, 0.0)
            out = _permute_compressed(blk, axis_name,
                                      _fwd_perm(num_parts, d),
                                      transport_dt, guard=guard)
            if guard:
                out, b = out
                bad = bad + b
            blocks.append(out)
        if not blocks:
            # P=1: no halo, but the empty result must still be marked
            # device-varying so it types consistently as carry state
            # (e.g. in the fused-epoch scan)
            empty = _ensure_varying(
                jnp.zeros((0, h.shape[-1]), h.dtype), axis_name
            )
            if guard:
                return empty, _ensure_varying(bad, axis_name)
            return empty
        halo = jnp.concatenate(blocks, axis=0)
        return (halo, bad) if guard else halo


def halo_exchange(
    h: jax.Array,
    send_idx: jax.Array,
    send_mask: jax.Array,
    axis_name: str,
    num_parts: int,
) -> jax.Array:
    """[N, F] -> [N + (P-1)*B, F]: inner rows followed by halo rows.
    Fully differentiable (synchronous/vanilla mode,
    reference feature_buffer.py:145-152)."""
    if num_parts == 1:
        return h
    return jnp.concatenate(
        [h, exchange_blocks(h, send_idx, send_mask, axis_name, num_parts)],
        axis=0,
    )


def return_blocks(
    halo_grad: jax.Array,
    axis_name: str,
    num_parts: int,
    b_max: int,
    transport_dt=None,
    guard: bool = False,
):
    """Route halo cotangents back to their owners.

    halo_grad: [(P-1)*B, F] in distance order. The distance-d block came
    from owner (r-d); after the reverse permute, the device holds — in the
    same [(P-1)*B, F] layout — the gradients its peers computed for the
    rows listed in its own send_idx (block d-1 <- peer (r+d)).
    `transport_dt` narrows the wire payload like exchange_blocks — use
    the cotangent dtype (e5m2 under float8) for gradient range.
    guard=True returns ``(blocks, bad)`` like exchange_blocks."""
    with jax.named_scope("bgrad_return"):
        outs = []
        bad = jnp.zeros((), jnp.int32)
        for d in range(1, num_parts):
            blk = jax.lax.dynamic_slice_in_dim(
                halo_grad, (d - 1) * b_max, b_max, axis=0
            )
            out = _permute_compressed(blk, axis_name,
                                      _bwd_perm(num_parts, d),
                                      transport_dt, guard=guard)
            if guard:
                out, b = out
                bad = bad + b
            outs.append(out)
        if not outs:
            # P=1 empty case: keep the varying type (see exchange_blocks)
            empty = _ensure_varying(jnp.zeros_like(halo_grad), axis_name)
            if guard:
                return empty, _ensure_varying(bad, axis_name)
            return empty
        ret = jnp.concatenate(outs, axis=0)
        return (ret, bad) if guard else ret


def make_stale_concat(send_idx: jax.Array, send_mask: jax.Array, n_dst: int):
    """Build the staleness-1 concat op for one graph layer.

    f(h, stale_halo, stale_bgrad, probe) -> [N + H, F] buffer equal to
    concat(h, stale_halo + probe), with a custom VJP:

      d_h     = g[:N] + scatter_add(send positions, stale_bgrad)
                  (inject *last* epoch's boundary grads — reference
                   feature_buffer.py:228-236 / __update_grad :208-217)
      d_probe = g[N:]  (this epoch's halo cotangent, for the caller to
                   ship to owners; probe itself is zeros)
      d_stale_halo = d_stale_bgrad = 0  (stale values are carry state,
                   not differentiation targets)

    send_idx/mask: [P-1, B] for this device; their flattened order matches
    the [(P-1)*B] halo/bgrad row order.
    """
    flat_idx = send_idx.reshape(-1)
    flat_mask = send_mask.reshape(-1)

    @jax.custom_vjp
    def stale_concat(h, stale_halo, stale_bgrad, probe):
        return jnp.concatenate([h, stale_halo + probe], axis=0)

    def fwd(h, stale_halo, stale_bgrad, probe):
        return stale_concat(h, stale_halo, stale_bgrad, probe), (stale_bgrad,)

    def bwd(res, g):
        (stale_bgrad,) = res
        inj = jnp.where(flat_mask[:, None], stale_bgrad, 0.0)
        d_h = g[:n_dst].at[flat_idx].add(inj)
        d_probe = g[n_dst:]
        return (
            d_h,
            jnp.zeros_like(d_probe),
            jnp.zeros_like(stale_bgrad),
            d_probe,
        )

    stale_concat.defvjp(fwd, bwd)
    return stale_concat
