"""Scatter-free GAT attention aggregation on the bucket formulation.

The GAT extension previously ran only on the raw-edge segment path
(19.8 s/epoch-class at Reddit scale — three scatter passes over E
edges). This kernel carries the per-edge attention weight through the
same degree-bucket tables the mean path uses (ops/bucket_spmm.py),
removing every scatter:

  - A bucket row holds ALL in-neighbors of one destination (padded to
    the bucket width), so the edge-softmax max-shift, normalizer and
    weighted sum are plain row-wise reductions over the bucket axis —
    no segment_max/segment_sum anywhere, and no separate max pass.
  - Attention logits l_e = leaky(el[src] + er[dst]) decompose into a
    NARROW el gather ([*, H] rows of 4H bytes ride the fast row-gather
    path, docs/PERF_NOTES.md) plus a row-local er term; the expensive
    part stays the single wide message gather the mean path also pays.
  - The backward recomputes alpha in both orientations from row-wise
    stats (m, s, rho) instead of materializing an [E, H] alpha tensor
    (~GBs at Reddit scale): the dst-keyed pass produces d_er, the
    src-keyed transpose pass produces d_z and d_el, each with one wide
    gather + narrow stat gathers. Treating the max-shift m as constant
    is EXACT (the normalized output is invariant to it).

Weighted-edge analogue of the reference's `update_all` with per-edge
weights (reference module/layer.py:47-49); the GAT model family itself
is a framework extension (models/sage.py:_gat_layer defines the
semantics this kernel must reproduce bit-for-bit up to reduction
order).

Sentinel conventions (NaN-free by construction):
  z/g pad row        -> zeros       (contributes 0 to sums)
  el pad row         -> -inf        (alpha = exp(-inf - m) = 0)
  dst-stats pad row  -> m=+inf, s=1 (alpha = 0, no 0/0)
All shapes static; per-device tables pad to shared caps so one traced
program serves every device in shard_map.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .bucket_spmm import (
    DEFAULT_CHUNK_ELEMS,
    SLAB_BYTES,
    BucketPlan,
    degree_hist,
    fit_widths,
)


# ---------------------------------------------------------------------
# host-side table build


def _rows_for_buckets(inv: np.ndarray, counts: Sequence[int]
                      ) -> List[np.ndarray]:
    """Per-bucket destination ids, in bucket-position order — ONE
    argsort of inv, split by counts (inv holds offset + arange(n_b)
    per bucket and a trailing sentinel for zero-degree rows, so the
    ascending order of inv values IS the bucket concatenation order)."""
    order = np.argsort(inv, kind="stable")
    out = []
    off = 0
    for n_b in counts:
        out.append(order[off:off + n_b].astype(np.int32))
        off += n_b
    return out


def build_sharded_gat_tables(sg) -> Dict[str, np.ndarray]:
    """Stacked per-device attention-bucket tables (leading device axis).

    Same bucket structure as build_sharded_bucket_tables plus, per
    bucket, the ROW ids (which destination / source row each bucket row
    belongs to) — the attention kernel needs them to add the row-local
    logit term and to gather per-destination softmax stats in the
    transpose pass. Keys:

      gat_fwd_<b>   [P, cap_b, w_b] in-neighbor ids (sentinel R)
      gat_fwd_rows_<b> [P, cap_b]   dst ids        (sentinel n_max)
      gat_fwd_inv   [P, n_max]      cap-layout concat positions
      gat_bwd_<b>   [P, cap_b, w_b] out-neighbor (dst) ids (sentinel n_max)
      gat_bwd_rows_<b> [P, cap_b]   src ids        (sentinel R)
      gat_bwd_inv   [P, R]
    """
    P = sg.num_parts
    n_src_rows = sg.n_max + sg.halo_size

    # one ladder a direction, fitted to the degree histograms of all
    # the shards (bucket_spmm.fit_widths)
    real = [sg.edge_dst[r] < sg.n_max for r in range(P)]
    fw = fit_widths(degree_hist(
        np.bincount(sg.edge_dst[r][real[r]]) for r in range(P)))
    bw = fit_widths(degree_hist(
        np.bincount(sg.edge_src[r][real[r]]) for r in range(P)))

    # one part a direction: this kernel reads its tables whole, not
    # through bucket_aggregate
    plans = [
        BucketPlan(sg.edge_src[r], sg.edge_dst[r], sg.n_max, n_src_rows,
                   fwd_widths=fw, bwd_widths=bw, parts=(1, 1))
        for r in range(P)
    ]
    fwd = [p.fwd.whole() for p in plans]
    bwd = [p.bwd.whole() for p in plans]
    # BucketPlan's tables are slot-major [w, rows rounded up to 32]
    # (bucket_spmm, the mean kernel's layout); this kernel still reads
    # them destination-major, so the builder below takes each table's
    # transpose and the plan's own (rounded) row counts as its offsets
    fwd_n = [[m.shape[1] for m in t.mats] for t in fwd]
    bwd_n = [[m.shape[1] for m in t.mats] for t in bwd]
    fwd_caps = [max(n[b] for n in fwd_n) for b in range(len(fw))]
    bwd_caps = [max(n[b] for n in bwd_n) for b in range(len(bw))]

    def pad_mat(mat, cap, sentinel):
        if mat.shape[0] == cap:
            return mat
        return np.pad(mat, ((0, cap - mat.shape[0]), (0, 0)),
                      constant_values=sentinel)

    def pad_rows(rows, cap, sentinel):
        if rows.shape[0] == cap:
            return rows
        return np.pad(rows, (0, cap - rows.shape[0]),
                      constant_values=sentinel)

    def reoffset(inv, counts, caps):
        # vectorized bucket lookup: one searchsorted over the count
        # boundaries instead of a full-array mask per bucket
        inv = inv.astype(np.int64)
        bounds = np.zeros(len(counts) + 1, np.int64)
        np.cumsum(counts, out=bounds[1:])
        starts_new = np.zeros(len(caps), np.int64)
        np.cumsum(caps[:-1], out=starts_new[1:])
        b = np.clip(np.searchsorted(bounds, inv, side="right") - 1,
                    0, len(counts) - 1)
        out = np.where(inv >= bounds[-1], int(sum(caps)),
                       inv - bounds[b] + starts_new[b])
        return out.astype(np.int32)

    # one O(n) scan per plan/orientation (not per bucket)
    fwd_rows = [_rows_for_buckets(t.inv, t.counts) for t in fwd]
    bwd_rows = [_rows_for_buckets(t.inv, t.counts) for t in bwd]

    tables: Dict[str, np.ndarray] = {
        "gat_fwd_inv": np.stack([
            reoffset(t.inv, n, fwd_caps)
            for t, n in zip(fwd, fwd_n)]),
        "gat_bwd_inv": np.stack([
            reoffset(t.inv, n, bwd_caps)
            for t, n in zip(bwd, bwd_n)]),
    }
    for b in range(len(fw)):
        if not fwd_caps[b]:
            continue
        tables[f"gat_fwd_{b:02d}"] = np.stack(
            [pad_mat(t.mats[b].T, fwd_caps[b], n_src_rows)
             for t in fwd])
        tables[f"gat_fwd_rows_{b:02d}"] = np.stack(
            [pad_rows(r[b], fwd_caps[b], sg.n_max) for r in fwd_rows])
    for b in range(len(bw)):
        if not bwd_caps[b]:
            continue
        tables[f"gat_bwd_{b:02d}"] = np.stack(
            [pad_mat(t.mats[b].T, bwd_caps[b], sg.n_max)
             for t in bwd])
        tables[f"gat_bwd_rows_{b:02d}"] = np.stack(
            [pad_rows(r[b], bwd_caps[b], n_src_rows) for r in bwd_rows])
    return tables


# ---------------------------------------------------------------------
# device-side slab helpers


def _slab_layout(F: int, dh: int, itemsize: int) -> Tuple[int, int]:
    """(slab_elems, n_slabs) with every slab either covering WHOLE heads
    (slab = k*dh, k | H) or lying inside ONE head (slab | dh) — the
    invariant _slab_heads and the gather helpers slice by. Guaranteed by
    construction: the whole-head case shrinks k to a divisor of H, the
    sub-head case shrinks slab to a divisor of dh (worst case 1)."""
    slab = SLAB_BYTES // itemsize
    if F <= slab:
        return F, 1
    H = F // dh
    if slab >= dh:
        k = slab // dh
        while H % k:
            k -= 1
        slab = dh * k
    else:
        while dh % slab:
            slab -= 1
    return slab, F // slab


def _make_slabs(x2d: jax.Array, slab: int, n_slabs: int) -> jax.Array:
    """[R, F] -> [S, R, slab]: each slab a compact gather operand (a
    strided slice of the wide buffer does NOT ride the fast row-gather
    path — docs/PERF_NOTES.md)."""
    r = x2d.shape[0]
    return x2d.reshape(r, n_slabs, slab).swapaxes(0, 1)


def _slab_heads(j: int, slab: int, dh: int) -> Tuple[int, int, int]:
    """Static head coverage of slab j: (first_head, n_heads_covered,
    offset_within_head). Either whole heads (offset 0) or a sub-head
    range (n=1)."""
    start = j * slab
    if slab >= dh:
        return start // dh, slab // dh, 0
    return start // dh, 1, start % dh


def _gather_weighted(slabs, idx, w, slab, dh, acc_out):
    """acc_out += sum_D w * msgs, per head. slabs [S, R+1, slab];
    idx [r, D]; w [r, D, H] f32; acc_out [r, H, dh] f32 (functional:
    returns the updated value)."""
    for j in range(slabs.shape[0]):
        msgs = jnp.take(slabs[j], idx, axis=0,
                        mode="clip").astype(jnp.float32)
        h0, nh, off = _slab_heads(j, slab, dh)
        if nh >= 1 and off == 0 and slab >= dh:
            m2 = msgs.reshape(*idx.shape, nh, dh)
            part = jnp.einsum("rdh,rdhf->rhf", w[..., h0:h0 + nh], m2)
            acc_out = acc_out.at[:, h0:h0 + nh, :].add(part)
        else:
            part = jnp.einsum("rd,rdf->rf", w[..., h0], msgs)
            acc_out = acc_out.at[:, h0, off:off + slab].add(part)
    return acc_out


def _gather_contract(slabs, idx, rowvec, slab, dh):
    """c[r, D, H] = sum_f msgs * rowvec (per head). rowvec [r, H, dh]
    f32 — the row-local vector each gathered message dots against."""
    r, D = idx.shape
    H = rowvec.shape[1]
    c = jnp.zeros((r, D, H), jnp.float32)
    for j in range(slabs.shape[0]):
        msgs = jnp.take(slabs[j], idx, axis=0,
                        mode="clip").astype(jnp.float32)
        h0, nh, off = _slab_heads(j, slab, dh)
        if nh >= 1 and off == 0 and slab >= dh:
            m2 = msgs.reshape(r, D, nh, dh)
            part = jnp.einsum("rhf,rdhf->rdh", rowvec[:, h0:h0 + nh], m2)
            c = c.at[..., h0:h0 + nh].add(part)
        else:
            part = jnp.einsum("rf,rdf->rd",
                              rowvec[:, h0, off:off + slab], msgs)
            c = c.at[..., h0].add(part)
    return c


def _gather_weighted_contract(slabs, idx, w, rowvec, slab, dh, acc_out):
    """One gather pass computing BOTH sum_D w*msgs and the per-slot
    contraction c = <rowvec, msgs> (the transpose pass needs both from
    the same messages; gathering once halves its wide traffic)."""
    r, D = idx.shape
    H = rowvec.shape[1]
    c = jnp.zeros((r, D, H), jnp.float32)
    for j in range(slabs.shape[0]):
        msgs = jnp.take(slabs[j], idx, axis=0,
                        mode="clip").astype(jnp.float32)
        h0, nh, off = _slab_heads(j, slab, dh)
        if nh >= 1 and off == 0 and slab >= dh:
            m2 = msgs.reshape(r, D, nh, dh)
            acc_out = acc_out.at[:, h0:h0 + nh, :].add(
                jnp.einsum("rdh,rdhf->rhf", w[..., h0:h0 + nh], m2))
            c = c.at[..., h0:h0 + nh].add(
                jnp.einsum("rhf,rdhf->rdh", rowvec[:, h0:h0 + nh], m2))
        else:
            acc_out = acc_out.at[:, h0, off:off + slab].add(
                jnp.einsum("rd,rdf->rf", w[..., h0], msgs))
            c = c.at[..., h0].add(
                jnp.einsum("rf,rdf->rd",
                           rowvec[:, h0, off:off + slab], msgs))
    return acc_out, c


def _chunked(mat, rows, per, idx_sentinel, row_sentinel):
    """Pad a bucket to a chunk multiple and reshape for lax.scan."""
    n_b = mat.shape[0]
    per = min(per, max(n_b, 1))  # never pad a small bucket UP to the
    n_c = -(-n_b // per)         # chunk budget (that would process
    pad = n_c * per - n_b        # budget-many sentinel rows per bucket)
    if pad:
        mat = jnp.pad(mat, ((0, pad), (0, 0)),
                      constant_values=idx_sentinel)
        rows = jnp.pad(rows, (0, pad), constant_values=row_sentinel)
    return (mat.reshape(n_c, per, mat.shape[1]),
            rows.reshape(n_c, per), n_b)


def _leaky(x, slope):
    return jnp.where(x > 0, x, slope * x)


def _dleaky(x, slope):
    return jnp.where(x > 0, 1.0, slope)


# ---------------------------------------------------------------------
# the differentiable kernel


def make_device_gat_fn(
    d: Dict[str, jax.Array],
    n_dst: int,
    n_src_rows: int,
    n_heads: int,
    slope: float,
    chunk_elems: int = DEFAULT_CHUNK_ELEMS,
    chunk_edges: Optional[int] = None,
    rem_dtype: Optional[str] = None,
):
    """Bind one device's tables (leading axis stripped) into a
    differentiable closure gat(z, el, er) -> [n_dst, H, dh] f32:

        out_d = sum_{e: dst=e} softmax_d(leaky(el[src] + er[dst])) z[src]

    z [R, H, dh] (any float dtype), el [R, H] f32, er [n_dst, H] f32.
    The VJP returns (dz, del, der); everything around the aggregation
    (W matmul, a_src/a_dst products, head merge, bias) stays standard
    autodiff in the model.

    `rem_dtype` narrows the WIDE gather transports only
    (bucket_spmm.transport_dtypes): z values travel e4m3 through the
    forward and both backward contractions (the same quantized values
    everywhere, so the VJP matches the quantized forward), the
    cotangent slabs travel e5m2; attention logits, softmax stats, and
    every accumulation stay f32."""
    from .bucket_spmm import transport_cast, transport_dtypes

    fwd_dt, bwd_dt = transport_dtypes(rem_dtype)
    fwd_keys = sorted(k for k in d if k.startswith("gat_fwd_")
                      and "rows" not in k and not k.endswith("inv"))
    bwd_keys = sorted(k for k in d if k.startswith("gat_bwd_")
                      and "rows" not in k and not k.endswith("inv"))
    fwd = [(d[k], d[k.replace("gat_fwd_", "gat_fwd_rows_")])
           for k in fwd_keys]
    bwd = [(d[k], d[k.replace("gat_bwd_", "gat_bwd_rows_")])
           for k in bwd_keys]
    fwd_inv, bwd_inv = d["gat_fwd_inv"], d["gat_bwd_inv"]
    R = n_src_rows

    def rows_per_chunk(width, unit):
        budget = chunk_edges * unit if chunk_edges else chunk_elems
        return max(1, budget // max(1, width * unit))

    def fwd_pass(z, el, er):
        """One pass: narrow el gather + wide weighted z gather.
        Returns (out [n_dst,H,dh] f32 normalized, m, s [n_dst,H])."""
        H, dh = z.shape[1], z.shape[2]
        F = H * dh
        zq = transport_cast(z, fwd_dt)
        slab, n_slabs = _slab_layout(F, dh, zq.dtype.itemsize)
        z_pad = jnp.concatenate(
            [zq.reshape(R, F), jnp.zeros((1, F), zq.dtype)])
        slabs = _make_slabs(z_pad, slab, n_slabs)
        el_pad = jnp.concatenate(
            [el, jnp.full((1, H), -jnp.inf, jnp.float32)])
        er_pad = jnp.concatenate([er, jnp.zeros((1, H), jnp.float32)])

        outs, ms, ss = [], [], []
        for mat, rows in fwd:
            per = rows_per_chunk(mat.shape[1], F)
            mat_c, rows_c, n_b = _chunked(mat, rows, per, R, n_dst)

            def body(_, xs):
                idx, rr = xs
                lel = jnp.take(el_pad, idx, axis=0,
                               mode="clip")    # [r, D, H]
                l_pre = lel + jnp.take(er_pad, rr, axis=0,
                                       mode="clip")[:, None, :]
                l = _leaky(l_pre, slope)
                m = l.max(axis=1)                          # [r, H]
                m = jnp.where(jnp.isfinite(m), m, 0.0)     # all-pad rows
                w = jnp.exp(l - m[:, None, :])             # pads -> 0
                s = w.sum(axis=1)
                o = _gather_weighted(
                    slabs, idx, w, slab, dh,
                    jnp.zeros((idx.shape[0], H, dh), jnp.float32))
                return None, (o, m, s)

            _, (o, m, s) = jax.lax.scan(body, None, (mat_c, rows_c))
            outs.append(o.reshape(-1, H, dh)[:n_b])
            ms.append(m.reshape(-1, H)[:n_b])
            ss.append(s.reshape(-1, H)[:n_b])
        # sentinel row: out 0, s 1 (zero-in-degree rows emit 0, no 0/0)
        out_c = jnp.concatenate(outs + [jnp.zeros((1, H, dh),
                                                  jnp.float32)])
        m_c = jnp.concatenate(ms + [jnp.zeros((1, H), jnp.float32)])
        s_c = jnp.concatenate(ss + [jnp.ones((1, H), jnp.float32)])
        out = jnp.take(out_c, fwd_inv, axis=0, mode="clip")[:n_dst]
        m = jnp.take(m_c, fwd_inv, axis=0, mode="clip")[:n_dst]
        s = jnp.take(s_c, fwd_inv, axis=0, mode="clip")[:n_dst]
        return out / s[..., None], m, s

    @jax.custom_vjp
    def gat(z, el, er):
        return fwd_pass(z, el, er)[0]

    def gat_fwd(z, el, er):
        out, m, s = fwd_pass(z, el, er)
        return out, (z, el, er, out, m, s)

    def gat_bwd(res, g):
        z, el, er, out, m, s = res
        H, dh = z.shape[1], z.shape[2]
        F = H * dh
        g = g.astype(jnp.float32)
        rho = (g * out).sum(-1)                            # [n_dst, H]

        zq = transport_cast(z, fwd_dt)  # the SAME quantized values the
        # forward consumed — pass A's contractions then differentiate
        # the quantized forward exactly
        slab, n_slabs = _slab_layout(F, dh, zq.dtype.itemsize)
        z_pad = jnp.concatenate(
            [zq.reshape(R, F), jnp.zeros((1, F), zq.dtype)])
        z_slabs = _make_slabs(z_pad, slab, n_slabs)
        el_pad = jnp.concatenate(
            [el, jnp.full((1, H), -jnp.inf, jnp.float32)])
        er_pad = jnp.concatenate([er, jnp.zeros((1, H), jnp.float32)])

        # ---- pass A (dst-keyed): d_er ---------------------------------
        # alpha and dl recompute from (el gather, row-local m/s/rho);
        # the wide gather contracts z[src] against the row's cotangent
        ders = []
        for mat, rows in fwd:
            per = rows_per_chunk(mat.shape[1], F)
            mat_c, rows_c, n_b = _chunked(mat, rows, per, R, n_dst)

            def body_a(_, xs):
                idx, rr = xs
                lel = jnp.take(el_pad, idx, axis=0, mode="clip")
                err = jnp.take(er_pad, rr, axis=0,
                               mode="clip")      # [r, H]
                l_pre = lel + err[:, None, :]
                mr = jnp.take(m, jnp.minimum(rr, n_dst - 1), axis=0)
                sr = jnp.take(s, jnp.minimum(rr, n_dst - 1), axis=0)
                rhor = jnp.take(rho, jnp.minimum(rr, n_dst - 1), axis=0)
                alpha = jnp.exp(_leaky(l_pre, slope) - mr[:, None, :]) \
                    / sr[:, None, :]
                g_rows = jnp.take(
                    g, jnp.minimum(rr, n_dst - 1), axis=0
                ) * (rr < n_dst).astype(jnp.float32)[:, None, None]
                c = _gather_contract(z_slabs, idx, g_rows, slab, dh)
                dl = alpha * (c - rhor[:, None, :])
                return None, (dl * _dleaky(l_pre, slope)).sum(axis=1)

            _, der_b = jax.lax.scan(body_a, None, (mat_c, rows_c))
            ders.append(der_b.reshape(-1, H)[:n_b])
        der_c = jnp.concatenate(ders + [jnp.zeros((1, H), jnp.float32)])
        der = jnp.take(der_c, fwd_inv, axis=0, mode="clip")[:n_dst]

        # ---- pass B (src-keyed transpose): d_z, d_el ------------------
        # per-dst stats ride ONE narrow stacked gather; m sentinel +inf
        # zeroes pad-slot alphas
        stats = jnp.concatenate([er, m, s, rho], axis=1)   # [n_dst, 4H]
        stats_pad = jnp.concatenate([
            stats,
            jnp.concatenate([
                jnp.zeros((1, H)), jnp.full((1, H), jnp.inf),
                jnp.ones((1, H)), jnp.zeros((1, H))], axis=1
            ).astype(jnp.float32)])
        g_t = transport_cast(g, bwd_dt) if bwd_dt is not None \
            else g.astype(z.dtype)
        slab_g, n_slabs_g = _slab_layout(F, dh, g_t.dtype.itemsize)
        g_pad = jnp.concatenate(
            [g_t.reshape(n_dst, F), jnp.zeros((1, F), g_t.dtype)])
        g_slabs = _make_slabs(g_pad, slab_g, n_slabs_g)
        # rowvec z values must be the SAME quantized values the forward
        # consumed (zq), or pass B's dl = alpha*(c - rho) mixes
        # unquantized z against quantized-forward rho and biases d_el
        z_pad3 = jnp.concatenate([
            zq.astype(jnp.float32).reshape(R, H, dh),
            jnp.zeros((1, H, dh), jnp.float32)])

        dzs, dels = [], []
        for mat, rows in bwd:
            per = rows_per_chunk(mat.shape[1], F)
            mat_c, rows_c, n_b = _chunked(mat, rows, per, n_dst, R)

            def body_b(_, xs):
                idx, rr = xs
                st = jnp.take(stats_pad, idx, axis=0,
                              mode="clip")        # [r, D, 4H]
                er_g, m_g, s_g, rho_g = (
                    st[..., :H], st[..., H:2 * H],
                    st[..., 2 * H:3 * H], st[..., 3 * H:])
                el_r = jnp.take(el_pad, rr, axis=0,
                                mode="clip")        # [r, H]
                l_pre = el_r[:, None, :] + er_g
                alpha = jnp.exp(_leaky(l_pre, slope) - m_g) / s_g
                z_r = jnp.take(z_pad3, rr, axis=0,
                               mode="clip")         # [r, H, dh]
                dz_b, c = _gather_weighted_contract(
                    g_slabs, idx, alpha, z_r, slab_g, dh,
                    jnp.zeros((idx.shape[0], H, dh), jnp.float32))
                dl = alpha * (c - rho_g)
                del_b = (dl * _dleaky(l_pre, slope)).sum(axis=1)
                return None, (dz_b, del_b)

            _, (dz_b, del_b) = jax.lax.scan(body_b, None, (mat_c, rows_c))
            dzs.append(dz_b.reshape(-1, H, dh)[:n_b])
            dels.append(del_b.reshape(-1, H)[:n_b])
        dz_c = jnp.concatenate(dzs + [jnp.zeros((1, H, dh), jnp.float32)])
        del_c = jnp.concatenate(dels + [jnp.zeros((1, H), jnp.float32)])
        dz = jnp.take(dz_c, bwd_inv, axis=0, mode="clip")[:R].astype(z.dtype)
        d_el = jnp.take(del_c, bwd_inv, axis=0, mode="clip")[:R]
        return dz, d_el, der

    gat.defvjp(gat_fwd, gat_bwd)
    return gat
