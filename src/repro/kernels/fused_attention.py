"""Layer-fused attention Pallas TPU kernels — the paper's M>N schedule
(Fig. 5c: fuse QK^T -> softmax -> .V; the M x M score matrix never
leaves the core) adapted to the TPU memory hierarchy.

Paper -> TPU mapping:
  * 'rows of QK^T streamed through the SIMD core' -> online-softmax tiles
    held in VMEM between MXU calls (the VPU is the SIMD core);
  * 'one row of Q substituted by one row of the output'  -> the (block_q,
    d) fp32 accumulator in VMEM scratch, rescaled per kv block;
  * active-feature memory A_LF = 3MN -> HBM traffic is exactly Q,K,V in +
    O out (codesign.hbm_traffic_fused), vs A_LBL's extra M^2 score
    write+read.

Three kernels: forward (with logsumexp residual for training), dq
backward, dkv backward (GQA-aware: dk/dv accumulate over the query-head
group inside the sequential grid, no group-times blowup in HBM).

Grid conventions (TPU: last grid dim is sequential => VMEM scratch
carries state across it):
  forward : (B*Hq, nq, nk)         scratch: acc, m, l
  dq      : (B*Hq, nq, nk)         scratch: dq_acc
  dkv     : (B, Hkv, nk, group*nq) scratch: dk_acc, dv_acc

All block sizes default from core.codesign.recommend_attention_tiling —
the DSE engine choosing the kernel tiling is the paper's step-3 mapping
optimisation re-expressed for the MXU.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30
LANES = 128


def _causal_mask(bq: int, bk: int, qi, kj, q_offset: int):
    rows = q_offset + qi * bq + jax.lax.broadcasted_iota(
        jnp.int32, (bq, bk), 0)
    cols = kj * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    return cols <= rows


def _init_softmax_state(acc_ref, m_ref, l_ref):
    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)


def _online_softmax_tile(s, mask, v_tile, acc_ref, m_ref, l_ref):
    """One online-softmax update over a masked (bq, bk) score tile:
    rescale the running (acc, m, l) state and fold in ``p @ v``.

    ``mask`` zeroes p where set-to-NEG_INF alone is not enough: a row
    with NO valid column yet has m_new still at NEG_INF, so
    exp(s - m_new) = 1, not 0 (only the masked kernels need it; the
    unmasked kernels pass None — causal rows always see their diagonal
    first, and a later valid tile rescales any garbage away)."""
    m_prev = m_ref[:, :1]
    l_prev = l_ref[:, :1]
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)                                    # (bq, bk)
    if mask is not None:
        p = jnp.where(mask, p, 0.0)
    l_new = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
    pv = jax.lax.dot_general(
        p.astype(v_tile.dtype), v_tile, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)                   # (bq, d)
    acc_ref[...] = acc_ref[...] * alpha + pv
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
    l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)


def _emit_softmax_out(o_ref, lse_ref, acc_ref, m_ref, l_ref):
    """Normalise the accumulator into o (and lse when wanted); rows
    that never saw a valid column (l == 0) emit zeros."""
    l = l_ref[:, :1]
    l_safe = jnp.where(l == 0.0, 1.0, l)
    o_ref[0] = (acc_ref[...] / l_safe).astype(o_ref.dtype)
    if lse_ref is not None:
        # lane-broadcast (bq, LANES) rows: a (1, bq) block over a
        # (B*Hq, Sq) array is not a legal TPU block shape
        lse_ref[0] = m_ref[...] + jnp.log(l_safe)


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                acc_ref, m_ref, l_ref, *,
                causal: bool, scale: float, q_offset: int, kv_len: int):
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    nk = pl.num_programs(2)
    bq, d = q_ref.shape[1], q_ref.shape[2]
    bk = k_ref.shape[1]

    @pl.when(kj == 0)
    def _init():
        _init_softmax_state(acc_ref, m_ref, l_ref)

    # causal block skip: block fully masked iff first row < first col
    run = True
    if causal:
        run = (q_offset + (qi + 1) * bq - 1) >= (kj * bk)

    @pl.when(run)
    def _body():
        q = q_ref[0]
        k = k_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale      # (bq, bk)
        if causal:
            s = jnp.where(_causal_mask(bq, bk, qi, kj, q_offset),
                          s, NEG_INF)
        if kv_len % bk:
            # static tail mask for padded kv
            cols = kj * bk + jax.lax.broadcasted_iota(
                jnp.int32, (bq, bk), 1)
            s = jnp.where(cols < kv_len, s, NEG_INF)
        _online_softmax_tile(s, None, v_ref[0], acc_ref, m_ref, l_ref)

    @pl.when(kj == nk - 1)
    def _emit():
        _emit_softmax_out(o_ref, lse_ref, acc_ref, m_ref, l_ref)


def _fwd(q, k, v, *, causal, scale, q_offset, block_q, block_k, interpret):
    b, hq, sq, d = q.shape
    _, hkv, skv, dv = v.shape
    group = hq // hkv
    bq = min(block_q, _round_up(sq))
    bk = min(block_k, _round_up(skv))
    sq_p, skv_p = _pad_to(sq, bq), _pad_to(skv, bk)
    qr = _pad_seq(q.reshape(b * hq, sq, d), sq_p)
    kr = _pad_seq(k.reshape(b * hkv, skv, d), skv_p)
    vr = _pad_seq(v.reshape(b * hkv, skv, dv), skv_p)
    nq, nk = sq_p // bq, skv_p // bk

    kernel = functools.partial(
        _fwd_kernel, causal=causal, scale=scale,
        q_offset=(skv - sq) if q_offset is None else q_offset,
        kv_len=skv)
    o, lse = pl.pallas_call(
        kernel,
        grid=(b * hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec((1, bk, d), lambda h, i, j, g=group: (h // g, j, 0)),
            pl.BlockSpec((1, bk, dv), lambda h, i, j, g=group: (h // g, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, dv), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec((1, bq, LANES), lambda h, i, j: (h, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * hq, sq_p, dv), q.dtype),
            jax.ShapeDtypeStruct((b * hq, sq_p, LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, dv), jnp.float32),
            pltpu.VMEM((bq, LANES), jnp.float32),
            pltpu.VMEM((bq, LANES), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="fused_attention_fwd",
    )(qr, kr, vr)
    o = o[:, :sq].reshape(b, hq, sq, dv)
    lse = lse[:, :sq, 0].reshape(b, hq, sq)
    return o, lse


# ---------------------------------------------------------------------------
# Masked-lengths forward (KV-cached serving)
# ---------------------------------------------------------------------------

def _masked_run(length, qi, kj, bq: int, bk: int, sq: int, causal: bool):
    """The block-skip predicate — the perf win: KV blocks wholly past
    this row's valid prefix are never computed, so decode cost is
    proportional to the *actual* context, not the padded cache depth.
    Under causal the bound also drops blocks past the last row's
    end-of-prefix anchor."""
    run = kj * bk < length
    if causal:
        # rows anchored at the END of the valid prefix (decode/chunked
        # prefill): global row r attends cols <= length - sq + r
        run = jnp.logical_and(
            run, (length - sq + (qi + 1) * bq - 1) >= kj * bk)
    return run


def _masked_tile_mask(length, qi, kj, bq: int, bk: int, sq: int,
                      causal: bool):
    """The (bq, bk) validity mask of one score tile: cols < length[b],
    intersected with the end-anchored causal triangle."""
    cols = kj * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = cols < length
    if causal:
        rows = qi * bq + jax.lax.broadcasted_iota(
            jnp.int32, (bq, bk), 0)
        mask = jnp.logical_and(mask, cols <= length - sq + rows)
    return mask


def _masked_kv_index(h, i, j, lens, *, hq: int, hkv: int, bk: int):
    """KV block index for the masked kernels (grid dim 0 is b*hq):
    skipped iterations (blocks wholly past lengths[b]) are clamped to
    the last valid block, so they re-address an already-fetched block
    instead of issuing fresh HBM DMA — the scalar-prefetch half of the
    block-skip optimisation."""
    b = h // hq
    last = jnp.maximum((lens[b] + bk - 1) // bk - 1, 0)
    return (b * hkv + (h % hq) // (hq // hkv), jnp.minimum(j, last), 0)


def _masked_fwd_kernel(len_ref, q_ref, k_ref, v_ref, o_ref,
                       acc_ref, m_ref, l_ref, *,
                       causal: bool, scale: float, hq: int, sq: int):
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    nk = pl.num_programs(2)
    bq = q_ref.shape[1]
    bk = k_ref.shape[1]
    length = len_ref[pl.program_id(0) // hq]    # this row's valid prefix

    @pl.when(kj == 0)
    def _init():
        _init_softmax_state(acc_ref, m_ref, l_ref)

    @pl.when(_masked_run(length, qi, kj, bq, bk, sq, causal))
    def _body():
        q = q_ref[0]
        k = k_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale      # (bq, bk)
        mask = _masked_tile_mask(length, qi, kj, bq, bk, sq, causal)
        s = jnp.where(mask, s, NEG_INF)
        _online_softmax_tile(s, mask, v_ref[0], acc_ref, m_ref, l_ref)

    @pl.when(kj == nk - 1)
    def _emit():
        _emit_softmax_out(o_ref, None, acc_ref, m_ref, l_ref)


def fused_attention_masked(q, k, v, lengths, *, causal: bool = True,
                           scale=None, block_q: int = 512,
                           block_k: int = 512, interpret: bool = False):
    """Masked-``lengths`` layer-fused attention forward (the serving
    path: decode / chunked prefill over a partially-filled KV cache).

    ``lengths``: (B,) int32 valid KV prefix per batch row, scalar-
    prefetched into SMEM.  Score tiles are masked with
    ``cols < lengths[b]`` and — the perf win — KV blocks wholly past
    ``lengths[b]`` are skipped (``pl.when(kj * bk < length)`` plus a
    clamped index map), so the sequential KV grid a row pays for is
    bounded by its *actual* context, not the padded cache depth: the
    paper's input-size-adaptive schedule realised on-chip.

    Causal semantics anchor the Sq query rows at the END of the valid
    prefix: row r attends cols <= lengths[b] - Sq + r (equivalent to
    ``q_offset = lengths - Sq``, per batch row).  Rows with
    ``lengths[b] = 0`` (or no valid causal column) emit zeros.

    Forward-only: serving never differentiates; training uses
    :func:`fused_attention` (full sequences carry no lengths mask).
    """
    b, hq, sq, d = q.shape
    _, hkv, skv, dv = v.shape
    scale = scale if scale is not None else d ** -0.5
    bq = min(block_q, _round_up(sq))
    bk = min(block_k, _round_up(skv))
    sq_p, skv_p = _pad_to(sq, bq), _pad_to(skv, bk)
    qr = _pad_seq(q.reshape(b * hq, sq, d), sq_p)
    kr = _pad_seq(k.reshape(b * hkv, skv, d), skv_p)
    vr = _pad_seq(v.reshape(b * hkv, skv, dv), skv_p)
    nq, nk = sq_p // bq, skv_p // bk
    lens = jnp.minimum(lengths.astype(jnp.int32), skv)

    kv_index = functools.partial(_masked_kv_index, hq=hq, hkv=hkv,
                                 bk=bk)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b * hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda h, i, j, lens: (h, i, 0)),
            pl.BlockSpec((1, bk, d), kv_index),
            pl.BlockSpec((1, bk, dv), kv_index),
        ],
        out_specs=pl.BlockSpec((1, bq, dv),
                               lambda h, i, j, lens: (h, i, 0)),
        scratch_shapes=[
            pltpu.VMEM((bq, dv), jnp.float32),
            pltpu.VMEM((bq, LANES), jnp.float32),
            pltpu.VMEM((bq, LANES), jnp.float32),
        ],
    )
    o = pl.pallas_call(
        functools.partial(_masked_fwd_kernel, causal=causal, scale=scale,
                          hq=hq, sq=sq),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b * hq, sq_p, dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="fused_attention_masked",
    )(lens, qr, kr, vr)
    return o[:, :sq].reshape(b, hq, sq, dv)


# ---------------------------------------------------------------------------
# Backward
# ---------------------------------------------------------------------------

def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               dq_acc, *, causal, scale, q_offset, kv_len):
    qi, kj = pl.program_id(1), pl.program_id(2)
    nk = pl.num_programs(2)
    bq, d = q_ref.shape[1], q_ref.shape[2]
    bk = k_ref.shape[1]

    @pl.when(kj == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    run = True
    if causal:
        run = (q_offset + (qi + 1) * bq - 1) >= (kj * bk)

    @pl.when(run)
    def _body():
        q, k, v = q_ref[0], k_ref[0], v_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if causal:
            s = jnp.where(_causal_mask(bq, bk, qi, kj, q_offset), s, NEG_INF)
        if kv_len % bk:
            cols = kj * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(cols < kv_len, s, NEG_INF)
        p = jnp.exp(s - lse_ref[0][:, :1])                    # (bq, bk)
        dp = jax.lax.dot_general(
            do_ref[0].astype(jnp.float32), v.astype(jnp.float32),
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0][:, :1]) * scale
        dq_acc[...] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(kj == nk - 1)
    def _emit():
        dq_ref[0] = dq_acc[...].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_acc, dv_acc, *,
                causal, scale, q_offset, kv_len, nq):
    kj = pl.program_id(2)
    li = pl.program_id(3)           # sequential: group * nq steps
    nl = pl.num_programs(3)
    qi = li % nq
    bq, d = q_ref.shape[2], q_ref.shape[3]
    bk = k_ref.shape[2]

    @pl.when(li == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    run = True
    if causal:
        run = (q_offset + (qi + 1) * bq - 1) >= (kj * bk)

    @pl.when(run)
    def _body():
        q, k, v = q_ref[0, 0], k_ref[0, 0], v_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if causal:
            s = jnp.where(_causal_mask(bq, bk, qi, kj, q_offset), s, NEG_INF)
        if kv_len % bk:
            cols = kj * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(cols < kv_len, s, NEG_INF)
        p = jnp.exp(s - lse_ref[0, 0][:, :1])
        do = do_ref[0, 0].astype(jnp.float32)
        # dv += P^T dO
        dv_acc[...] += jax.lax.dot_general(
            p.astype(do_ref.dtype), do_ref[0, 0], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(
            do, v.astype(jnp.float32), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0, 0][:, :1]) * scale        # (bq, bk)
        dk_acc[...] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(li == nl - 1)
    def _emit():
        dk_ref[0, 0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)


def _bwd(res, g, *, causal, scale, q_offset, block_q, block_k, interpret):
    q, k, v, o, lse = res
    do = g
    b, hq, sq, d = q.shape
    _, hkv, skv, dv = v.shape
    group = hq // hkv
    bq = min(block_q, _round_up(sq))
    bk = min(block_k, _round_up(skv))
    sq_p, skv_p = _pad_to(sq, bq), _pad_to(skv, bk)
    nq, nk = sq_p // bq, skv_p // bk
    off = (skv - sq) if q_offset is None else q_offset

    delta = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32),
                    axis=-1)                                   # (B,Hq,Sq)
    qr = _pad_seq(q.reshape(b * hq, sq, d), sq_p)
    kr = _pad_seq(k.reshape(b * hkv, skv, d), skv_p)
    vr = _pad_seq(v.reshape(b * hkv, skv, dv), skv_p)
    dor = _pad_seq(do.reshape(b * hq, sq, dv), sq_p)
    # pad lse with +inf-ish so padded rows give p = exp(-inf) = 0; the
    # per-row residuals travel lane-broadcast, (.., Sq, LANES), like the
    # forward's lse output
    lser = _lanes(_pad_seq(lse.reshape(b * hq, sq), sq_p,
                           value=jnp.float32(1e30)))
    deltar = _lanes(_pad_seq(delta.reshape(b * hq, sq), sq_p))

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, causal=causal, scale=scale,
                          q_offset=off, kv_len=skv),
        grid=(b * hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec((1, bk, d), lambda h, i, j, g=group: (h // g, j, 0)),
            pl.BlockSpec((1, bk, dv), lambda h, i, j, g=group: (h // g, j, 0)),
            pl.BlockSpec((1, bq, dv), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec((1, bq, LANES), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec((1, bq, LANES), lambda h, i, j: (h, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda h, i, j: (h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b * hq, sq_p, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="fused_attention_bwd_dq",
    )(qr, kr, vr, dor, lser, deltar)

    q4 = _pad_seq(q.reshape(b, hq, sq, d), sq_p, axis=2)
    do4 = _pad_seq(do.reshape(b, hq, sq, dv), sq_p, axis=2)
    lse4 = _lanes(_pad_seq(lse, sq_p, axis=2, value=jnp.float32(1e30)))
    delta4 = _lanes(_pad_seq(delta, sq_p, axis=2))
    k4 = _pad_seq(k, skv_p, axis=2)
    v4 = _pad_seq(v, skv_p, axis=2)

    dk, dvg = pl.pallas_call(
        functools.partial(_dkv_kernel, causal=causal, scale=scale,
                          q_offset=off, kv_len=skv, nq=nq),
        grid=(b, hkv, nk, group * nq),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d),
                         lambda b_, h, j, l, g=group, n=nq:
                         (b_, h * g + l // n, l % n, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b_, h, j, l: (b_, h, j, 0)),
            pl.BlockSpec((1, 1, bk, dv), lambda b_, h, j, l: (b_, h, j, 0)),
            pl.BlockSpec((1, 1, bq, dv),
                         lambda b_, h, j, l, g=group, n=nq:
                         (b_, h * g + l // n, l % n, 0)),
            pl.BlockSpec((1, 1, bq, LANES),
                         lambda b_, h, j, l, g=group, n=nq:
                         (b_, h * g + l // n, l % n, 0)),
            pl.BlockSpec((1, 1, bq, LANES),
                         lambda b_, h, j, l, g=group, n=nq:
                         (b_, h * g + l // n, l % n, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bk, d), lambda b_, h, j, l: (b_, h, j, 0)),
            pl.BlockSpec((1, 1, bk, dv), lambda b_, h, j, l: (b_, h, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, hkv, skv_p, d), k.dtype),
            jax.ShapeDtypeStruct((b, hkv, skv_p, dv), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, d), jnp.float32),
            pltpu.VMEM((bk, dv), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
        name="fused_attention_bwd_dkv",
    )(q4, k4, v4, do4, lse4, delta4)

    dq = dq[:, :sq].reshape(b, hq, sq, d)
    dk = dk[:, :, :skv]
    dvg = dvg[:, :, :skv]
    return dq, dk, dvg


# ---------------------------------------------------------------------------
# custom_vjp wrapper
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(3, 4, 5, 6, 7, 8))
def fused_attention(q, k, v, causal=True, scale=None, q_offset=None,
                    block_q=512, block_k=512, interpret=False):
    """Layer-fused attention (paper Fig. 5c schedule): O(M*N) active
    memory instead of O(M^2).  q:(B,Hq,Sq,D) k,v:(B,Hkv,Skv,D[v])."""
    o, _ = _fwd(q, k, v, causal=causal,
                scale=scale if scale is not None else q.shape[-1] ** -0.5,
                q_offset=q_offset, block_q=block_q, block_k=block_k,
                interpret=interpret)
    return o


def _fa_fwd(q, k, v, causal, scale, q_offset, block_q, block_k, interpret):
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    o, lse = _fwd(q, k, v, causal=causal, scale=scale, q_offset=q_offset,
                  block_q=block_q, block_k=block_k, interpret=interpret)
    return o, (q, k, v, o, lse)


def _fa_bwd(causal, scale, q_offset, block_q, block_k, interpret, res, g):
    scale = scale if scale is not None else res[0].shape[-1] ** -0.5
    return _bwd(res, g, causal=causal, scale=scale, q_offset=q_offset,
                block_q=block_q, block_k=block_k, interpret=interpret)


fused_attention.defvjp(_fa_fwd, _fa_bwd)


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------

def _rope_tile(q, pos0, theta: float):
    """Rotate a (bq, d) Q tile in-register: row r gets rotary position
    ``pos0 + r`` (``pos0`` may be a traced scalar — e.g. the scalar-
    prefetched ``length - sq`` of the masked kernels).  Half-split
    rotation with the same frequency schedule as ``models.common.rope``
    (``exp(-i * log(theta) / half)``), computed in fp32.  Pallas TPU has
    no 1-D iota, so both the frequency index and the row index are 2-D
    ``broadcasted_iota`` planes."""
    bq, d = q.shape
    half = d // 2
    idx = jax.lax.broadcasted_iota(jnp.int32, (bq, half), 1)
    freqs = jnp.exp(idx.astype(jnp.float32)
                    * (-math.log(theta) / half))
    rows = pos0 + jax.lax.broadcasted_iota(jnp.int32, (bq, half), 0)
    ang = rows.astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1 = q[:, :half].astype(jnp.float32)
    x2 = q[:, half:].astype(jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin,
                            x2 * cos + x1 * sin], axis=-1)


def _rope_tile_at(q, pos, theta: float):
    """:func:`_rope_tile` with every row at the one rotary position
    ``pos``: the rows of a GQA group's Q tile are the query heads of a
    single token."""
    bq, d = q.shape
    half = d // 2
    idx = jax.lax.broadcasted_iota(jnp.int32, (bq, half), 1)
    freqs = jnp.exp(idx.astype(jnp.float32)
                    * (-math.log(theta) / half))
    ang = jnp.full((bq, half), pos, jnp.int32).astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1 = q[:, :half].astype(jnp.float32)
    x2 = q[:, half:].astype(jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin,
                            x2 * cos + x1 * sin], axis=-1)


def _round_up(n: int, m: int = LANES) -> int:
    return max(m, ((n + m - 1) // m) * m)


def _pad_to(n: int, block: int) -> int:
    return ((n + block - 1) // block) * block


def _lanes(x):
    """Broadcast per-row values (..., S) to (..., S, LANES): the TPU
    block layout for a per-row residual (last block dim a full lane
    width)."""
    return jnp.broadcast_to(x[..., None], x.shape + (LANES,))


def _pad_seq(x, target: int, axis: int = 1, value=None):
    n = x.shape[axis]
    if n == target:
        return x
    pads = [(0, 0)] * x.ndim
    pads[axis] = (0, target - n)
    return jnp.pad(x, pads, constant_values=0 if value is None else value)
