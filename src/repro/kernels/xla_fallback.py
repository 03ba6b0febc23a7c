"""XLA-native layer-fused fallbacks.

Same fused schedules as the Pallas kernels (score matrix / chunk state
never materialised at full size), expressed with lax.map/lax.scan so
they compile on ANY backend — these paths back the CPU-hosted multi-pod
dry-run and non-TPU execution, and they are differentiable (the Pallas
kernels own the TPU fast path).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def _pad_axis(x, target, axis, value=0.0):
    if x.shape[axis] == target:
        return x
    pads = [(0, 0)] * x.ndim
    pads[axis] = (0, target - x.shape[axis])
    return jnp.pad(x, pads, constant_values=value)


def chunked_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, *,
    causal: bool = True,
    scale: Optional[float] = None,
    q_offset: Optional[int] = None,
    lengths: Optional[jax.Array] = None,
    block_q: int = 512,
    block_k: int = 1024,
) -> jax.Array:
    """Online-softmax attention, O(block_q * block_k) live scores.

    Outer sequential map over q blocks (rematerialised in backward),
    inner scan over kv blocks carrying (m, l, acc) — the paper's
    Fig. 5c fused schedule in pure lax.
    """
    b, hq, sq, d = q.shape
    _, hkv, skv, dv = v.shape
    group = hq // hkv
    scale = scale if scale is not None else d ** -0.5
    off = (skv - sq) if q_offset is None else q_offset

    bq = min(block_q, max(sq, 1))
    bk = min(block_k, max(skv, 1))
    sq_p = -(-sq // bq) * bq
    skv_p = -(-skv // bk) * bk
    nq, nk = sq_p // bq, skv_p // bk

    qp = _pad_axis(q, sq_p, 2).reshape(b, hq, nq, bq, d)
    kp = _pad_axis(k, skv_p, 2).reshape(b, hkv, nk, bk, d)
    vp = _pad_axis(v, skv_p, 2).reshape(b, hkv, nk, bk, dv)
    kv_valid = jnp.arange(skv_p) < skv                      # (skv_p,)
    if lengths is not None:
        kv_valid = kv_valid[None, :] & (
            jnp.arange(skv_p)[None, :] < lengths[:, None])
        kv_valid = kv_valid.reshape(b, nk, bk)
    else:
        kv_valid = jnp.broadcast_to(kv_valid.reshape(1, nk, bk),
                                    (b, nk, bk))

    def q_block(qi):
        qq = jax.lax.dynamic_index_in_dim(qp, qi, 2, keepdims=False)
        # (b, hkv, group, bq, d) — GQA without materialising repeated K/V
        qg = qq.reshape(b, hkv, group, bq, d).astype(jnp.float32)
        rows = off + qi * bq + jnp.arange(bq)               # global q pos

        def kv_step(carry, kj):
            m, l, acc = carry
            kk = jax.lax.dynamic_index_in_dim(kp, kj, 2, keepdims=False)
            vv = jax.lax.dynamic_index_in_dim(vp, kj, 2, keepdims=False)
            s = jnp.einsum("bngqd,bnkd->bngqk", qg,
                           kk.astype(jnp.float32)) * scale
            cols = kj * bk + jnp.arange(bk)
            valid = jax.lax.dynamic_index_in_dim(kv_valid, kj, 1,
                                                 keepdims=False)  # (b,bk)
            mask = valid[:, None, None, None, :]
            if causal:
                mask = mask & (cols[None, None, None, None, :]
                               <= rows[None, None, None, :, None])
            s = jnp.where(mask, s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            alpha = jnp.exp(m - m_new)
            # the where keeps rows with no valid column yet (m_new still
            # NEG_INF, so exp(s - m_new) = 1) out of the accumulators:
            # a lengths[b] = 0 row must emit zeros, not mean(v)
            p = jnp.where(mask, jnp.exp(s - m_new[..., None]), 0.0)
            l_new = l * alpha + jnp.sum(p, axis=-1)
            pv = jnp.einsum("bngqk,bnkd->bngqd", p,
                            vv.astype(jnp.float32))
            acc_new = acc * alpha[..., None] + pv
            return (m_new, l_new, acc_new), None

        init = (jnp.full((b, hkv, group, bq), NEG_INF, jnp.float32),
                jnp.zeros((b, hkv, group, bq), jnp.float32),
                jnp.zeros((b, hkv, group, bq, dv), jnp.float32))
        (m, l, acc), _ = jax.lax.scan(kv_step, init, jnp.arange(nk))
        l_safe = jnp.where(l == 0.0, 1.0, l)
        out = (acc / l_safe[..., None]).reshape(b, hq, bq, dv)
        return out.astype(q.dtype)

    # remat: backward recomputes each q block's inner scan instead of
    # storing per-step score residuals
    blocks = jax.lax.map(jax.checkpoint(q_block), jnp.arange(nq))
    o = jnp.moveaxis(blocks, 0, 2).reshape(b, hq, sq_p, dv)[:, :, :sq]
    return o


def gather_paged_kv(pool: jax.Array, block_tables: jax.Array) -> jax.Array:
    """Densify a paged KV pool with one XLA gather:
    (num_pages, Hkv, page, D) through (B, max_pages) int32 page ids ->
    (B, Hkv, max_pages*page, D).  ``kernels.ops`` densifies a pool with
    it on every impl but the reference, wherever a call does not take
    one of the two kernels that read a pool through its table."""
    b, max_pages = block_tables.shape
    _, hkv, page, d = pool.shape
    # tolerate the malformed tables this path is the downgrade for
    idx = block_tables.astype(jnp.int32)
    g = jnp.take(pool, idx, axis=0)           # (B, maxP, Hkv, page, D)
    return jnp.moveaxis(g, 2, 1).reshape(b, hkv, max_pages * page, d)


def chunked_ssd(
    x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array,
    c: jax.Array, d: Optional[jax.Array] = None, *,
    chunk: int = 128,
    h0: Optional[jax.Array] = None,
    return_final_state: bool = False,
):
    """Chunked SSD in pure lax (same math as the ssd_scan kernel), scan
    over chunks — differentiable, any backend.

    x:(B,L,H,P) dt:(B,L,H) a:(H,) b,c:(B,L,G,S)."""
    B, L, H, P = x.shape
    G, S = b.shape[2], b.shape[3]
    rep = H // G
    Lp = -(-L // chunk) * chunk
    nj = Lp // chunk
    xc = _pad_axis(x, Lp, 1).astype(jnp.float32) \
        .reshape(B, nj, chunk, H, P)
    dtc = _pad_axis(dt, Lp, 1).astype(jnp.float32) \
        .reshape(B, nj, chunk, H)
    bc = jnp.repeat(_pad_axis(b, Lp, 1).astype(jnp.float32), rep, axis=2) \
        .reshape(B, nj, chunk, H, S)
    cc = jnp.repeat(_pad_axis(c, Lp, 1).astype(jnp.float32), rep, axis=2) \
        .reshape(B, nj, chunk, H, S)
    af = a.astype(jnp.float32)
    tri = jnp.tril(jnp.ones((chunk, chunk), bool))

    def chunk_step(h, j):
        xj = jax.lax.dynamic_index_in_dim(xc, j, 1, keepdims=False)
        dj = jax.lax.dynamic_index_in_dim(dtc, j, 1, keepdims=False)
        bj = jax.lax.dynamic_index_in_dim(bc, j, 1, keepdims=False)
        cj = jax.lax.dynamic_index_in_dim(cc, j, 1, keepdims=False)
        alog = dj * af[None, None, :]                      # (B,C,H)
        cum = jnp.cumsum(alog, axis=1)                     # (B,C,H)
        total = cum[:, -1]                                 # (B,H)
        # intra-chunk: Y = ((C B^T) * L) X per head
        g = jnp.einsum("bths,buhs->bhtu", cj, bj)          # (B,H,C,C)
        rel = jnp.moveaxis(cum[:, :, None, :] - cum[:, None, :, :],
                           3, 1)                           # (B,H,t,u)
        # double-where: exp() must not see the (positive, overflowing)
        # upper triangle, or its cotangent is 0 * inf = NaN
        rel = jnp.where(tri[None, None], rel, 0.0)
        lmat = jnp.where(tri[None, None],
                         jnp.exp(rel)
                         * jnp.moveaxis(dj, 2, 1)[:, :, None, :], 0.0)
        y_intra = jnp.einsum("bhtu,buhp->bthp", g * lmat, xj)
        # inter-chunk from carried state
        dec = jnp.exp(cum)                                 # (B,C,H)
        y_inter = jnp.einsum("bths,bhps->bthp",
                             cj * dec[..., None], h)
        # state update
        w = jnp.exp(total[:, None] - cum) * dj             # (B,C,H)
        h_new = h * jnp.exp(total)[..., None, None] + jnp.einsum(
            "buhp,buhs->bhps", xj, bj * w[..., None])
        return h_new, y_intra + y_inter

    h = jnp.zeros((B, H, P, S), jnp.float32) if h0 is None \
        else h0.astype(jnp.float32)
    h, ys = jax.lax.scan(jax.checkpoint(chunk_step), h, jnp.arange(nj))
    y = jnp.moveaxis(ys, 0, 1).reshape(B, Lp, H, P)[:, :L]
    if d is not None:
        y = y + d.astype(jnp.float32)[None, None, :, None] \
            * x.astype(jnp.float32)
    y = y.astype(x.dtype)
    if return_final_state:
        return y, h
    return y


def ssd_step(x_t, dt_t, a, b_t, c_t, d, h):
    """Single-token SSD update for decode: h' = exp(a dt) h + dt x (x) b;
    y = c . h' + d x.  x_t:(B,H,P) dt_t:(B,H) b_t,c_t:(B,G,S) h:(B,H,P,S)."""
    B, H, P = x_t.shape
    G, S = b_t.shape[1], b_t.shape[2]
    rep = H // G
    bb = jnp.repeat(b_t, rep, axis=1).astype(jnp.float32)
    cc = jnp.repeat(c_t, rep, axis=1).astype(jnp.float32)
    xf = x_t.astype(jnp.float32)
    dtf = dt_t.astype(jnp.float32)
    dec = jnp.exp(a.astype(jnp.float32)[None] * dtf)       # (B,H)
    h = h * dec[..., None, None] + (xf * dtf[..., None])[..., None] \
        * bb[:, :, None, :]
    y = jnp.einsum("bhps,bhs->bhp", h, cc)
    if d is not None:
        y = y + d.astype(jnp.float32)[None, :, None] * xf
    return y.astype(x_t.dtype), h
