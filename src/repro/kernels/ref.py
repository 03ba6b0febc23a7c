"""Pure-jnp oracles for every kernel in this package.

These are the *layer-by-layer* (unfused) realisations of the paper's
attention graph: they materialise the full M x M score matrix — exactly
the schedule the paper's layer-fused execution avoids — and are used as
the numerical ground truth for the fused Pallas kernels and the XLA
chunked fallbacks.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def repeat_kv(k: jax.Array, n_rep: int) -> jax.Array:
    """(B, Hkv, S, D) -> (B, Hkv*n_rep, S, D) for GQA broadcast."""
    if n_rep == 1:
        return k
    b, h, s, d = k.shape
    return jnp.broadcast_to(k[:, :, None], (b, h, n_rep, s, d)).reshape(
        b, h * n_rep, s, d)


def attention_reference(
    q: jax.Array,                   # (B, Hq, Sq, D)
    k: jax.Array,                   # (B, Hkv, Skv, D)
    v: jax.Array,                   # (B, Hkv, Skv, Dv)
    *,
    causal: bool = False,
    scale: Optional[float] = None,
    lengths: Optional[jax.Array] = None,   # (B,) valid kv length per row
    q_offset: Optional[int] = None,        # global position of q row 0
    return_lse: bool = False,
):
    """Unfused attention: scores = QK^T fully materialised (the paper's
    layer-by-layer schedule), then row softmax, then @V.

    ``q_offset`` aligns causal masking when q is a suffix of the kv
    sequence (decode/chunked prefill); default Skv - Sq.
    """
    b, hq, sq, d = q.shape
    _, hkv, skv, _ = k.shape
    scale = scale if scale is not None else d ** -0.5
    group = hq // hkv
    k = repeat_kv(k, group)
    v = repeat_kv(v, group)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    mask = None
    if causal:
        off = (skv - sq) if q_offset is None else q_offset
        rows = off + jnp.arange(sq)[:, None]
        cols = jnp.arange(skv)[None, :]
        mask = cols <= rows                         # (Sq, Skv)
        mask = mask[None, None]
    if lengths is not None:
        lmask = jnp.arange(skv)[None, :] < lengths[:, None]   # (B, Skv)
        lmask = lmask[:, None, None, :]
        mask = lmask if mask is None else (mask & lmask)
    if mask is not None:
        s = jnp.where(mask, s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    if mask is not None:
        # a row with NO valid column has m == NEG_INF, so exp(s - m)
        # is 1, not 0 — zero it so such rows emit zeros, matching the
        # fused kernels and the chunked XLA fallback
        p = jnp.where(mask, p, 0.0)
    l = jnp.sum(p, axis=-1, keepdims=True)
    o = jnp.einsum("bhqk,bhkd->bhqd", p / jnp.maximum(l, 1e-30),
                   v.astype(jnp.float32))
    o = o.astype(q.dtype)
    if return_lse:
        lse = (m + jnp.log(jnp.maximum(l, 1e-30)))[..., 0]   # (B,Hq,Sq)
        return o, lse
    return o


def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotary embedding oracle, independent of both the Pallas kernels
    and ``models.common``: half-split pairs ``(x_i, x_{i+half})`` are
    rotated by ``positions * theta^(-i/half)`` in fp32.

    x: (..., S, D); positions: (..., S) — head axes are inserted between
    the batch and sequence dims of ``positions`` to match x's rank.
    Written against the RoFormer definition so kernel parity tests have
    a ground truth that shares no code with the implementations under
    test."""
    d = x.shape[-1]
    half = d // 2
    inv_freq = jnp.float32(theta) ** (
        -jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None] * inv_freq
    while ang.ndim < x.ndim:
        ang = jnp.expand_dims(ang, -3)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin,
                           x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def rms_norm(x: jax.Array, weight: jax.Array, eps: float) -> jax.Array:
    """RMS norm oracle over the last axis, in fp32 (Zhang & Sennrich,
    2019): ``x / sqrt(mean(x^2) + eps) * weight`` — qk-norm of each
    query head, independent of ``models.common``."""
    xf = x.astype(jnp.float32)
    out = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True)
                             + eps)
    return (out * weight.astype(jnp.float32)).astype(x.dtype)


def rope_positions(sq: int, skv: int,
                   lengths: Optional[jax.Array] = None,
                   q_offset: Optional[int] = None) -> jax.Array:
    """Rotary positions of the Sq query rows under the kernels' causal
    anchoring: with ``lengths``, rows anchor at the END of each row's
    valid prefix (row r of batch b sits at ``lengths[b] - sq + r``);
    without, at ``q_offset + r`` (default ``skv - sq``)."""
    r = jnp.arange(sq, dtype=jnp.int32)
    if lengths is not None:
        return lengths.astype(jnp.int32)[:, None] - sq + r[None, :]
    off = (skv - sq) if q_offset is None else q_offset
    return off + r


def qproj_attention_reference(
    x: jax.Array,                   # (B, Sq, E) pre-projection activations
    wq: jax.Array,                  # (E, Hq, D)
    k: jax.Array,                   # (B, Hkv, Skv, D)
    v: jax.Array,                   # (B, Hkv, Skv, D)
    *,
    rope_theta: Optional[float] = None,
    **kw,
):
    """The paper's M<N schedule, unfused oracle: materialise Q = x @ Wq in
    full (the tensor the fused kernel never stores), apply RoPE between
    the projection and the scores when ``rope_theta`` is set (the very
    op that used to force this materialisation), then attention."""
    q = jnp.einsum("bse,ehd->bhsd", x, wq.astype(x.dtype))
    if rope_theta is not None:
        pos = rope_positions(x.shape[1], k.shape[2],
                             lengths=kw.get("lengths"),
                             q_offset=kw.get("q_offset"))
        q = rope(q, pos, rope_theta)
    return attention_reference(q, k, v, **kw)


def decode_block_reference(
    x: jax.Array,                   # (B, 1, E) pre-projection activations
    wq: jax.Array,                  # (E, Hq, D)
    k: jax.Array,                   # (B, Hkv, Skv, D)
    v: jax.Array,                   # (B, Hkv, Skv, Dv)
    wo: jax.Array,                  # (Hq, Dv, E) output projection
    residual: jax.Array,            # (B, 1, E)
    lengths: jax.Array,             # (B,) valid kv prefix per row
    *,
    rope_theta: Optional[float] = None,
    scale: Optional[float] = None,
    q_norm: Optional[jax.Array] = None,     # (D,) qk-norm weight
    norm_eps: float = 1e-6,
):
    """Unfused oracle of the whole M=1 decode attention sub-block the
    megakernel folds into one launch: Q projection (+ the per-head RMS
    norm given ``q_norm``, + RoPE at position ``lengths[b] - 1``),
    masked attention over the valid prefix, output projection, residual
    add.  At M=1 the end-anchored causal triangle degenerates to the
    lengths mask itself (``cols < lengths[b]``)."""
    assert x.shape[1] == 1
    q = jnp.einsum("bse,ehd->bhsd", x, wq.astype(x.dtype))
    if q_norm is not None:
        q = rms_norm(q, q_norm, norm_eps)
    if rope_theta is not None:
        pos = rope_positions(1, k.shape[2], lengths=lengths)
        q = rope(q, pos, rope_theta)
    o = attention_reference(q, k, v, causal=False, scale=scale,
                            lengths=lengths)
    y = jnp.einsum("bhse,hed->bsd", o.astype(jnp.float32),
                   wo.astype(jnp.float32))
    return (residual.astype(jnp.float32) + y).astype(x.dtype)


def gather_pages(pool: jax.Array, block_tables: jax.Array) -> jax.Array:
    """Densify a paged KV pool: (num_pages, Hkv, page, D) gathered
    through (B, max_pages) int32 page ids into the dense
    (B, Hkv, max_pages*page, D) layout every dense-path oracle and
    kernel consumes.  Row b's j-th logical block is pool page
    ``block_tables[b, j]``; entries past a row's valid length may name
    any in-range page (canonically the allocator's null page 0) — the
    caller's ``lengths`` mask makes their content irrelevant."""
    b, max_pages = block_tables.shape
    _, hkv, page, d = pool.shape
    g = pool[block_tables]              # (B, max_pages, Hkv, page, D)
    return jnp.moveaxis(g, 2, 1).reshape(b, hkv, max_pages * page, d)


def paged_decode_block_reference(x, wq, k_pool, v_pool, wo, residual,
                                 lengths, block_tables, **kw):
    """Oracle for :func:`fused_decode_block_paged`."""
    return decode_block_reference(
        x, wq, gather_pages(k_pool, block_tables),
        gather_pages(v_pool, block_tables), wo, residual, lengths, **kw)


def softmax_reference(x: jax.Array) -> jax.Array:
    """Row-wise softmax (paper Eq. 2)."""
    m = jnp.max(x, axis=-1, keepdims=True)
    e = jnp.exp(x - m)
    return e / jnp.sum(e, axis=-1, keepdims=True)


def ssd_reference(
    x: jax.Array,                   # (B, L, H, P)   head channels
    dt: jax.Array,                  # (B, L, H)      positive step sizes
    a: jax.Array,                   # (H,)           negative decay rate
    b: jax.Array,                   # (B, L, G, S)   input projections
    c: jax.Array,                   # (B, L, G, S)   output projections
    d: Optional[jax.Array] = None,  # (H,) skip connection
    *,
    h0: Optional[jax.Array] = None,  # (B, H, P, S) initial state
    return_final_state: bool = False,
):
    """Mamba-2 SSD (state-space duality) sequential-scan oracle.

    h_t = exp(a * dt_t) * h_{t-1} + dt_t * x_t (outer) b_t
    y_t = h_t . c_t + d * x_t

    G SSM groups broadcast over H heads (H % G == 0).
    """
    B, L, H, P = x.shape
    G, S = b.shape[2], b.shape[3]
    rep = H // G
    bb = jnp.repeat(b, rep, axis=2).astype(jnp.float32)     # (B,L,H,S)
    cc = jnp.repeat(c, rep, axis=2).astype(jnp.float32)
    xf = x.astype(jnp.float32)
    dtf = dt.astype(jnp.float32)
    decay = jnp.exp(a.astype(jnp.float32)[None, :] * dtf)   # (B,L,H)

    def step(h, t):
        # h: (B, H, P, S)
        dec = decay[:, t][:, :, None, None]
        upd = (xf[:, t] * dtf[:, t][..., None])[..., None] \
            * bb[:, t][:, :, None, :]
        h = h * dec + upd
        y = jnp.einsum("bhps,bhs->bhp", h, cc[:, t])
        return h, y

    h = jnp.zeros((B, H, P, S), jnp.float32) if h0 is None \
        else h0.astype(jnp.float32)
    h, ys = jax.lax.scan(step, h, jnp.arange(L))
    y = jnp.moveaxis(ys, 0, 1)                              # (B,L,H,P)
    if d is not None:
        y = y + d.astype(jnp.float32)[None, None, :, None] * xf
    y = y.astype(x.dtype)
    if return_final_state:
        return y, h
    return y
