"""Mamba-2 SSD (state-space duality) chunked scan — Pallas TPU kernel.

Needed by the assigned mamba2-130m and jamba archs.  The paper's
attention-head fusion does not apply to attention-free layers
(DESIGN.md §Arch-applicability), but the *scheduling principle* —
fuse through the largest intermediate, keep it in local memory — does:
the (C x C) intra-chunk decay-score matrix and the running (P x S)
state live only in VMEM; HBM sees x, dt, B, C in and y out.

Chunked SSD recurrence per head (all f32 in-kernel):

  cum_t   = sum_{s<=t} a * dt_s                      (<= 0, stable)
  L[t,s]  = exp(cum_t - cum_s) * dt_s   for s <= t
  Y_intra = ((C B^T) * L) X                          (two MXU matmuls)
  Y_inter = exp(cum_t) * (C . h0)
  h'      = exp(cum_C) h0 + X^T (B * exp(cum_C - cum_t) dt_t)

Grid: (B*H, n_chunks) — chunks sequential, state in VMEM scratch.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, dt_ref, alog_ref, b_ref, c_ref, y_ref, hout_ref,
                h_scr, *, chunk: int):
    j = pl.program_id(1)
    nj = pl.num_programs(1)
    C = chunk

    @pl.when(j == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    x = x_ref[0].astype(jnp.float32)          # (C, P)
    dt = dt_ref[0].astype(jnp.float32)        # (C, 1)... stored (1, C)
    dt = dt.reshape(C, 1)
    alog = alog_ref[0].astype(jnp.float32).reshape(C, 1)   # a * dt
    bmat = b_ref[0, 0].astype(jnp.float32)    # (C, S)
    cmat = c_ref[0, 0].astype(jnp.float32)    # (C, S)

    cum = jnp.cumsum(alog, axis=0)            # (C, 1) inclusive
    total = cum[C - 1:C, :]                   # (1, 1)

    # intra-chunk: ((C B^T) * L) X
    g = jax.lax.dot_general(cmat, bmat, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (C, C)
    rel = cum - cum.reshape(1, C)             # cum_t - cum_s
    rows = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
    mask = cols <= rows
    rel = jnp.where(mask, rel, 0.0)           # keep exp() overflow-free
    l_mat = jnp.where(mask, jnp.exp(rel) * dt.reshape(1, C), 0.0)
    y_intra = jax.lax.dot_general(g * l_mat, x, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)

    # inter-chunk: exp(cum_t) * C . h0   ; h0: (P, S)
    y_inter = jnp.exp(cum) * jax.lax.dot_general(
        cmat, h_scr[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)   # (C, P)

    y_ref[0] = (y_intra + y_inter).astype(y_ref.dtype)

    # state update: h' = exp(total) h0 + X^T (B * exp(total - cum) dt)
    w = jnp.exp(total - cum) * dt             # (C, 1)
    h_scr[...] = jnp.exp(total) * h_scr[...] + jax.lax.dot_general(
        x, bmat * w, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)   # (P, S)

    @pl.when(j == nj - 1)
    def _emit():
        hout_ref[0] = h_scr[...]


def ssd_scan(x, dt, a, b, c, d=None, *, chunk: int = 128,
             interpret: bool = False, return_final_state: bool = False):
    """Chunked SSD forward.  x:(B,L,H,P) dt:(B,L,H) a:(H,)
    b,c:(B,L,G,S).  L must be padded to a chunk multiple by the caller
    (ops.ssd handles it)."""
    B, L, H, P = x.shape
    G, S = b.shape[2], b.shape[3]
    rep = H // G
    assert L % chunk == 0, "pad L to a chunk multiple"
    nj = L // chunk

    xr = jnp.moveaxis(x, 2, 1).reshape(B * H, L, P)
    dtr = jnp.moveaxis(dt, 2, 1).reshape(B * H, L)
    # per-row decay rate: row index = b*H + h  ->  head h
    a_row = a.astype(dtr.dtype)[jnp.tile(jnp.arange(H), B)]
    alog = dtr * a_row[:, None]                       # (B*H, L)
    br = jnp.moveaxis(b, 2, 1)                        # (B, G, L, S)
    cr = jnp.moveaxis(c, 2, 1)

    y, hout = pl.pallas_call(
        functools.partial(_ssd_kernel, chunk=chunk),
        grid=(B * H, nj),
        in_specs=[
            pl.BlockSpec((1, chunk, P), lambda h, j: (h, j, 0)),
            pl.BlockSpec((1, chunk), lambda h, j: (h, j)),
            pl.BlockSpec((1, chunk), lambda h, j: (h, j)),
            pl.BlockSpec((1, 1, chunk, S),
                         lambda h, j, hh=H, r=rep:
                         (h // hh, (h % hh) // r, j, 0)),
            pl.BlockSpec((1, 1, chunk, S),
                         lambda h, j, hh=H, r=rep:
                         (h // hh, (h % hh) // r, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, P), lambda h, j: (h, j, 0)),
            pl.BlockSpec((1, P, S), lambda h, j: (h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, L, P), x.dtype),
            jax.ShapeDtypeStruct((B * H, P, S), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((P, S), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(xr, dtr, alog, br, cr)

    y = jnp.moveaxis(y.reshape(B, H, L, P), 1, 2)     # (B, L, H, P)
    if d is not None:
        y = y + (d.astype(jnp.float32)[None, None, :, None]
                 * x.astype(jnp.float32)).astype(y.dtype)
    if return_final_state:
        return y, hout.reshape(B, H, P, S)
    return y
