"""The paper's M<N layer-fused schedule (Fig. 5b: fuse Q -> QK^T) on TPU.

When the query-row count is smaller than the embedding width (short
sequences / decode microbatches vs wide models), the paper fuses the Q
projection into the score computation so Q is *never stored*.  The TPU
realisation: the kernel receives the pre-projection activations ``x``
and the Q weights, computes the (block_q, d) Q tile in VMEM at the first
kv step, and keeps it resident for the whole kv loop — Q never
round-trips through HBM.  Active-memory saving vs the unfused path is
exactly the paper's A_LBL - A_LF = M.N - M^2 words (Sec. IV.C.1).

Backward reuses the fused_attention backward kernels on the recomputed
Q tile plus two small projection GEMMs (dx, dWq).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import fused_attention as fa
from repro.kernels import ref

NEG_INF = fa.NEG_INF
LANES = fa.LANES


def _qproj_fwd_kernel(x_ref, wq_ref, k_ref, v_ref, o_ref, lse_ref,
                      q_scr, acc_ref, m_ref, l_ref, *,
                      causal: bool, scale: float, q_offset: int,
                      kv_len: int, rope_theta):
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    nk = pl.num_programs(2)
    bq = x_ref.shape[1]
    bk = k_ref.shape[1]

    @pl.when(kj == 0)
    def _init():
        # the fusion: Q tile built in VMEM, never written to HBM — and,
        # with rope_theta, rotated in-register (row r sits at global
        # position q_offset + qi*bq + r), so RoPE no longer forces Q to
        # materialise between the projection and the scores
        q = jax.lax.dot_general(
            x_ref[0], wq_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        if rope_theta is not None:
            q = fa._rope_tile(q, q_offset + qi * bq, rope_theta)
        q_scr[...] = q
        fa._init_softmax_state(acc_ref, m_ref, l_ref)

    run = True
    if causal:
        run = (q_offset + (qi + 1) * bq - 1) >= (kj * bk)

    @pl.when(run)
    def _body():
        q = q_scr[...].astype(k_ref.dtype)
        k = k_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if causal:
            s = jnp.where(fa._causal_mask(bq, bk, qi, kj, q_offset),
                          s, NEG_INF)
        if kv_len % bk:
            cols = kj * bk + jax.lax.broadcasted_iota(
                jnp.int32, (bq, bk), 1)
            s = jnp.where(cols < kv_len, s, NEG_INF)
        fa._online_softmax_tile(s, None, v_ref[0], acc_ref, m_ref,
                                l_ref)

    @pl.when(kj == nk - 1)
    def _emit():
        fa._emit_softmax_out(o_ref, lse_ref, acc_ref, m_ref, l_ref)


def _qproj_fwd(x, wq, k, v, *, causal, scale, q_offset, rope_theta,
               block_q, block_k, interpret):
    b, sq, e = x.shape
    eh, hq, d = wq.shape
    assert eh == e
    _, hkv, skv, dv = v.shape
    group = hq // hkv
    bq = min(block_q, fa._round_up(sq))
    bk = min(block_k, fa._round_up(skv))
    sq_p, skv_p = fa._pad_to(sq, bq), fa._pad_to(skv, bk)
    nq, nk = sq_p // bq, skv_p // bk
    xr = fa._pad_seq(x, sq_p, axis=1)
    wqr = jnp.moveaxis(wq, 1, 0)                     # (Hq, E, D)
    kr = fa._pad_seq(k.reshape(b * hkv, skv, d), skv_p)
    vr = fa._pad_seq(v.reshape(b * hkv, skv, dv), skv_p)

    kernel = functools.partial(
        _qproj_fwd_kernel, causal=causal, scale=scale,
        q_offset=(skv - sq) if q_offset is None else q_offset,
        kv_len=skv, rope_theta=rope_theta)
    o, lse = pl.pallas_call(
        kernel,
        grid=(b * hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, e),
                         lambda h, i, j, hh=hq: (h // hh, i, 0)),
            pl.BlockSpec((1, e, d),
                         lambda h, i, j, hh=hq: (h % hh, 0, 0)),
            pl.BlockSpec((1, bk, d),
                         lambda h, i, j, hh=hq, hk=hkv, g=group:
                         ((h // hh) * hk + (h % hh) // g, j, 0)),
            pl.BlockSpec((1, bk, dv),
                         lambda h, i, j, hh=hq, hk=hkv, g=group:
                         ((h // hh) * hk + (h % hh) // g, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, dv), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec((1, bq, LANES), lambda h, i, j: (h, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * hq, sq_p, dv), x.dtype),
            jax.ShapeDtypeStruct((b * hq, sq_p, LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq, dv), jnp.float32),
            pltpu.VMEM((bq, LANES), jnp.float32),
            pltpu.VMEM((bq, LANES), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="fused_qproj_attention_fwd",
    )(xr, wqr, kr, vr)
    o = o[:, :sq].reshape(b, hq, sq, dv)
    lse = lse[:, :sq, 0].reshape(b, hq, sq)
    return o, lse


# ---------------------------------------------------------------------------
# Masked-lengths forward (KV-cached serving)
# ---------------------------------------------------------------------------

def _qproj_masked_fwd_kernel(len_ref, x_ref, wq_ref, k_ref, v_ref, o_ref,
                             q_scr, acc_ref, m_ref, l_ref, *,
                             causal: bool, scale: float, hq: int, sq: int,
                             rope_theta):
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    nk = pl.num_programs(2)
    bq = x_ref.shape[1]
    bk = k_ref.shape[1]
    length = len_ref[pl.program_id(0) // hq]

    @pl.when(kj == 0)
    def _init():
        # the fusion: Q tile built in VMEM, never written to HBM.  With
        # rope_theta the tile is rotated in-register against the scalar-
        # prefetched length: rows anchor at the END of the valid prefix,
        # so global row r sits at rotary position length - sq + r (for
        # M=1 decode that is exactly length - 1)
        q = jax.lax.dot_general(
            x_ref[0], wq_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        if rope_theta is not None:
            q = fa._rope_tile(q, length - sq + qi * bq, rope_theta)
        q_scr[...] = q
        fa._init_softmax_state(acc_ref, m_ref, l_ref)

    @pl.when(fa._masked_run(length, qi, kj, bq, bk, sq, causal))
    def _body():
        q = q_scr[...].astype(k_ref.dtype)
        k = k_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        mask = fa._masked_tile_mask(length, qi, kj, bq, bk, sq, causal)
        s = jnp.where(mask, s, NEG_INF)
        fa._online_softmax_tile(s, mask, v_ref[0], acc_ref, m_ref,
                                l_ref)

    @pl.when(kj == nk - 1)
    def _emit():
        fa._emit_softmax_out(o_ref, None, acc_ref, m_ref, l_ref)


def fused_qproj_attention_masked(x, wq, k, v, lengths, *,
                                 causal: bool = True, scale=None,
                                 rope_theta=None,
                                 block_q: int = 256, block_k: int = 512,
                                 interpret: bool = False):
    """Masked-``lengths`` Fig. 5b forward: Q = x @ Wq fused into the
    score kernel AND per-batch-row valid KV prefixes masked in-kernel
    (scalar-prefetched SMEM lengths; KV blocks wholly past
    ``lengths[b]`` skipped).  Causal rows anchor at the end of the
    valid prefix, as in :func:`fused_attention_masked`.

    ``rope_theta``: when set, the Q tile is additionally rotated
    in-register at positions ``lengths[b] - sq + r`` — rotary embedding
    folded between the fused projection and the scores, so RoPE models
    keep the Fig. 5b schedule.  Forward-only — the KV-cached serving
    path never differentiates."""
    b, sq, e = x.shape
    eh, hq, d = wq.shape
    assert eh == e
    _, hkv, skv, dv = v.shape
    scale = scale if scale is not None else d ** -0.5
    bq = min(block_q, fa._round_up(sq))
    bk = min(block_k, fa._round_up(skv))
    sq_p, skv_p = fa._pad_to(sq, bq), fa._pad_to(skv, bk)
    nq, nk = sq_p // bq, skv_p // bk
    xr = fa._pad_seq(x, sq_p, axis=1)
    wqr = jnp.moveaxis(wq, 1, 0)                     # (Hq, E, D)
    kr = fa._pad_seq(k.reshape(b * hkv, skv, d), skv_p)
    vr = fa._pad_seq(v.reshape(b * hkv, skv, dv), skv_p)
    lens = jnp.minimum(lengths.astype(jnp.int32), skv)

    kv_index = functools.partial(fa._masked_kv_index, hq=hq, hkv=hkv,
                                 bk=bk)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b * hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, e),
                         lambda h, i, j, lens_: (h // hq, i, 0)),
            pl.BlockSpec((1, e, d),
                         lambda h, i, j, lens_: (h % hq, 0, 0)),
            pl.BlockSpec((1, bk, d), kv_index),
            pl.BlockSpec((1, bk, dv), kv_index),
        ],
        out_specs=pl.BlockSpec((1, bq, dv),
                               lambda h, i, j, lens_: (h, i, 0)),
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq, dv), jnp.float32),
            pltpu.VMEM((bq, LANES), jnp.float32),
            pltpu.VMEM((bq, LANES), jnp.float32),
        ],
    )
    o = pl.pallas_call(
        functools.partial(_qproj_masked_fwd_kernel, causal=causal,
                          scale=scale, hq=hq, sq=sq,
                          rope_theta=rope_theta),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b * hq, sq_p, dv), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="fused_qproj_attention_masked",
    )(lens, xr, wqr, kr, vr)
    return o[:, :sq].reshape(b, hq, sq, dv)


# ---------------------------------------------------------------------------
# Paged forward (block-table-indirect KV-cached serving)
# ---------------------------------------------------------------------------

def _qproj_paged_fwd_kernel(len_ref, tbl_ref, x_ref, wq_ref, k_ref,
                            v_ref, o_ref, q_scr, acc_ref, m_ref, l_ref,
                            **kw):
    """Paged body == masked body: the block table only redirects the KV
    DMAs (index map); the fused Q build, in-register RoPE and masking
    all act on logical positions."""
    _qproj_masked_fwd_kernel(len_ref, x_ref, wq_ref, k_ref, v_ref,
                             o_ref, q_scr, acc_ref, m_ref, l_ref, **kw)


def fused_qproj_attention_paged(x, wq, k_pool, v_pool, lengths,
                                block_tables, *, causal: bool = True,
                                scale=None, rope_theta=None,
                                block_q: int = 256,
                                interpret: bool = False):
    """Paged-KV Fig. 5b forward: Q = x @ Wq fused into the score kernel
    over a page pool.  k_pool, v_pool: (num_pages, Hkv, page, D[v]);
    block_tables: (B, max_pages) int32 page ids; both ``lengths`` and
    the table are scalar-prefetched (``num_scalar_prefetch=2``) and
    consumed by the KV index map — see :func:`repro.kernels.
    fused_attention.fused_attention_paged` for the paging contract.
    Forward-only."""
    b, sq, e = x.shape
    eh, hq, d = wq.shape
    assert eh == e
    n_pages, hkv, page, dv = v_pool.shape
    assert k_pool.shape[:3] == (n_pages, hkv, page)
    assert page % 8 == 0, "page size must be sublane-aligned (8)"
    max_pages = block_tables.shape[1]
    scale = scale if scale is not None else d ** -0.5
    bq = min(block_q, fa._round_up(sq))
    sq_p = fa._pad_to(sq, bq)
    nq = sq_p // bq
    xr = fa._pad_seq(x, sq_p, axis=1)
    wqr = jnp.moveaxis(wq, 1, 0)                     # (Hq, E, D)
    kr = k_pool.reshape(n_pages * hkv, page, d)
    vr = v_pool.reshape(n_pages * hkv, page, dv)
    lens = jnp.minimum(lengths.astype(jnp.int32), max_pages * page)
    tbl = block_tables.astype(jnp.int32)

    kv_index = functools.partial(fa._paged_kv_index, hq=hq, hkv=hkv,
                                 page=page)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b * hq, nq, max_pages),
        in_specs=[
            pl.BlockSpec((1, bq, e),
                         lambda h, i, j, lens, tbl: (h // hq, i, 0)),
            pl.BlockSpec((1, e, d),
                         lambda h, i, j, lens, tbl: (h % hq, 0, 0)),
            pl.BlockSpec((1, page, d), kv_index),
            pl.BlockSpec((1, page, dv), kv_index),
        ],
        out_specs=pl.BlockSpec((1, bq, dv),
                               lambda h, i, j, lens, tbl: (h, i, 0)),
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq, dv), jnp.float32),
            pltpu.VMEM((bq, LANES), jnp.float32),
            pltpu.VMEM((bq, LANES), jnp.float32),
        ],
    )
    o = pl.pallas_call(
        functools.partial(_qproj_paged_fwd_kernel, causal=causal,
                          scale=scale, hq=hq, sq=sq,
                          rope_theta=rope_theta),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b * hq, sq_p, dv), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="fused_qproj_attention_paged",
    )(lens, tbl, xr, wqr, kr, vr)
    return o[:, :sq].reshape(b, hq, sq, dv)


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(4, 5, 6, 7, 8, 9, 10))
def fused_qproj_attention(x, wq, k, v, causal=True, scale=None,
                          q_offset=None, rope_theta=None, block_q=256,
                          block_k=512, interpret=False):
    """Fig. 5b schedule: Q = x @ Wq fused into QK^T — Q never stored.

    x: (B, Sq, E); wq: (E, Hq, D); k, v: (B, Hkv, Skv, D[v]).
    ``rope_theta``: rotate the in-VMEM Q tile at positions
    ``q_offset + r`` before the scores (RoPE fused in-kernel).
    """
    scale_ = scale if scale is not None else wq.shape[-1] ** -0.5
    o, _ = _qproj_fwd(x, wq, k, v, causal=causal, scale=scale_,
                      q_offset=q_offset, rope_theta=rope_theta,
                      block_q=block_q, block_k=block_k,
                      interpret=interpret)
    return o


def _fqa_fwd(x, wq, k, v, causal, scale, q_offset, rope_theta, block_q,
             block_k, interpret):
    scale_ = scale if scale is not None else wq.shape[-1] ** -0.5
    o, lse = _qproj_fwd(x, wq, k, v, causal=causal, scale=scale_,
                        q_offset=q_offset, rope_theta=rope_theta,
                        block_q=block_q, block_k=block_k,
                        interpret=interpret)
    return o, (x, wq, k, v, o, lse)


def _fqa_bwd(causal, scale, q_offset, rope_theta, block_q, block_k,
             interpret, res, g):
    x, wq, k, v, o, lse = res
    scale_ = scale if scale is not None else wq.shape[-1] ** -0.5
    # recompute the rotated Q tile (cheap GEMM + rotation) and reuse the
    # fused-attention backward on it
    q = jnp.einsum("bse,ehd->bhsd", x, wq).astype(x.dtype)
    positions = None
    if rope_theta is not None:
        off = (k.shape[2] - x.shape[1]) if q_offset is None else q_offset
        positions = off + jnp.arange(x.shape[1], dtype=jnp.int32)
        q = ref.rope(q, positions, rope_theta)
    dq, dk, dv = fa._bwd((q, k, v, o, lse), g, causal=causal, scale=scale_,
                         q_offset=q_offset, block_q=block_q,
                         block_k=block_k, interpret=interpret)
    if rope_theta is not None:
        # rotation is orthogonal: d(unrotated q) = R^T dq = R(-pos) dq
        dq = ref.rope(dq, -positions, rope_theta)
    dx = jnp.einsum("bhsd,ehd->bse", dq.astype(jnp.float32),
                    wq.astype(jnp.float32)).astype(x.dtype)
    dwq = jnp.einsum("bse,bhsd->ehd", x.astype(jnp.float32),
                     dq.astype(jnp.float32)).astype(wq.dtype)
    return dx, dwq, dk, dv


fused_qproj_attention.defvjp(_fqa_fwd, _fqa_bwd)
