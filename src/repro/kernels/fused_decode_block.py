"""Decode megakernel: the whole M=1 attention sub-block in ONE Pallas
launch — Q projection (+ in-register RoPE), masked scores, online
softmax, P.V, output projection, residual add.

This pushes the paper's Fig. 5b fusion boundary outward for the decode
regime the inference surveys identify as launch-overhead- and
HBM-round-trip-bound: beyond Q (never stored), the per-head attention
output and the projected block output also never touch HBM.  The only
HBM traffic is x, Wq, K, V, Wo, residual in and the block output out —
the per-head O tile and the (B, 1, E) partial sums live in VMEM scratch
across the sequential head/KV grid.

Grid: (B, Hq, nk) with ("parallel", "arbitrary", "arbitrary") — the
head dim is sequential so the output accumulator ``y_scr`` carries
partial head contributions; per-head softmax state resets at kv step 0.
KV blocks wholly past the scalar-prefetched ``lengths[b]`` are skipped
and their DMAs clamped to the last valid block, exactly like the other
masked kernels.  At M=1 the end-anchored causal triangle degenerates to
``cols < lengths[b]``, and the rotary position is ``lengths[b] - 1``.

Forward-only: decode serving never differentiates.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import fused_attention as fa

NEG_INF = fa.NEG_INF
LANES = fa.LANES


def _decode_block_kernel(len_ref, x_ref, wq_ref, k_ref, v_ref, wo_ref,
                         res_ref, o_ref,
                         q_scr, acc_ref, m_ref, l_ref, y_scr, *,
                         scale: float, rope_theta):
    h = pl.program_id(1)
    kj = pl.program_id(2)
    nh = pl.num_programs(1)
    nk = pl.num_programs(2)
    bq = x_ref.shape[1]
    bk = k_ref.shape[1]
    length = len_ref[pl.program_id(0)]

    @pl.when(kj == 0)
    def _init():
        # fusion step 1: this head's Q row built (and rotated) in VMEM
        q = jax.lax.dot_general(
            x_ref[0], wq_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        if rope_theta is not None:
            q = fa._rope_tile(q, length - 1, rope_theta)
        q_scr[...] = q
        fa._init_softmax_state(acc_ref, m_ref, l_ref)

    @pl.when(kj * bk < length)
    def _body():
        q = q_scr[...].astype(k_ref.dtype)
        k = k_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        cols = kj * bk + jax.lax.broadcasted_iota(
            jnp.int32, (bq, bk), 1)
        mask = cols < length
        s = jnp.where(mask, s, NEG_INF)
        fa._online_softmax_tile(s, mask, v_ref[0], acc_ref, m_ref,
                                l_ref)

    @pl.when(kj == nk - 1)
    def _fold_head():
        # fusion step 2: normalise this head's O row and fold it through
        # Wo into the (bq, E) output accumulator — the per-head O never
        # leaves VMEM.  A length-0 row has l == 0 and emits zeros.
        l = l_ref[:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o = acc_ref[...] / l_safe                            # (bq, Dv)
        contrib = jax.lax.dot_general(
            o.astype(wo_ref.dtype), wo_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)              # (bq, E)

        @pl.when(h == 0)
        def _first():
            y_scr[...] = contrib

        @pl.when(h > 0)
        def _accum():
            y_scr[...] += contrib

        @pl.when(h == nh - 1)
        def _emit():
            # fusion step 3: residual add, single HBM write of the block
            o_ref[0] = (res_ref[0].astype(jnp.float32)
                        + y_scr[...]).astype(o_ref.dtype)


def _kv_index(b, h, j, lens, *, hkv: int, group: int, bk: int):
    """Clamp skipped KV blocks to the last valid one (no fresh DMA for
    blocks wholly past lengths[b]); grid dim 0 is the batch row."""
    last = jnp.maximum((lens[b] + bk - 1) // bk - 1, 0)
    return (b * hkv + h // group, jnp.minimum(j, last), 0)


def _paged_kv_index(b, h, j, lens, tbl, *, hkv: int, group: int,
                    page: int):
    """Block-table indirection for the decode megakernel (grid dim 0 is
    the batch row): the j-th logical KV page of row b is fetched from
    pool page ``tbl[b, j]``; skipped iterations clamp to the last live
    table entry (no fresh DMA), zero-length rows read ``tbl[b, 0]``."""
    last = jnp.maximum((lens[b] + page - 1) // page - 1, 0)
    return (tbl[b, jnp.minimum(j, last)] * hkv + h // group, 0, 0)


def _paged_decode_block_kernel(len_ref, tbl_ref, x_ref, wq_ref, k_ref,
                               v_ref, wo_ref, res_ref, o_ref,
                               q_scr, acc_ref, m_ref, l_ref, y_scr,
                               **kw):
    """Paged body == dense body: the table only redirects KV DMAs."""
    _decode_block_kernel(len_ref, x_ref, wq_ref, k_ref, v_ref, wo_ref,
                         res_ref, o_ref, q_scr, acc_ref, m_ref, l_ref,
                         y_scr, **kw)


def fused_decode_block_paged(x, wq, k_pool, v_pool, wo, residual,
                             lengths, block_tables, *, scale=None,
                             rope_theta=None, interpret: bool = False):
    """The decode megakernel over a paged KV pool: one Pallas launch for
    the whole M=1 attention sub-block, with KV fetched page-by-page
    through a scalar-prefetched block table.

    x, residual: (B, 1, E); wq: (E, Hq, D); k_pool, v_pool:
    (num_pages, Hkv, page, D[v]); wo: (Hq, Dv, E); lengths: (B,);
    block_tables: (B, max_pages) int32 page ids.  The KV block size IS
    the page size; ``num_scalar_prefetch=2`` hands both ``lengths`` and
    the table to the KV index map, so the indirection is free — each
    sequential kv step DMAs exactly the one pool page the table names,
    and pages past ``lengths[b]`` are skipped as in the dense masked
    kernel.  Returns (B, 1, E) = ``residual + attn_out @ Wo``.
    """
    b, sq, e = x.shape
    assert sq == 1, "fused_decode_block_paged is the M=1 decode schedule"
    eh, hq, d = wq.shape
    assert eh == e
    n_pages, hkv, page, dv = v_pool.shape
    assert k_pool.shape[:3] == (n_pages, hkv, page)
    assert page % 8 == 0, "page size must be sublane-aligned (8)"
    group = hq // hkv
    assert wo.shape == (hq, dv, e)
    max_pages = block_tables.shape[1]
    scale = scale if scale is not None else d ** -0.5
    bq = 8 if x.dtype == jnp.float32 else 16
    xr = fa._pad_seq(x, bq, axis=1)
    rr = fa._pad_seq(residual, bq, axis=1)
    wqr = jnp.moveaxis(wq, 1, 0)                     # (Hq, E, D)
    kr = k_pool.reshape(n_pages * hkv, page, d)
    vr = v_pool.reshape(n_pages * hkv, page, dv)
    lens = jnp.minimum(lengths.astype(jnp.int32), max_pages * page)
    tbl = block_tables.astype(jnp.int32)

    kv_index = functools.partial(_paged_kv_index, hkv=hkv, group=group,
                                 page=page)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, hq, max_pages),
        in_specs=[
            pl.BlockSpec((1, bq, e),
                         lambda b_, h, j, lens_, tbl_: (b_, 0, 0)),
            pl.BlockSpec((1, e, d),
                         lambda b_, h, j, lens_, tbl_: (h, 0, 0)),
            pl.BlockSpec((1, page, d), kv_index),
            pl.BlockSpec((1, page, dv), kv_index),
            pl.BlockSpec((1, dv, e),
                         lambda b_, h, j, lens_, tbl_: (h, 0, 0)),
            pl.BlockSpec((1, bq, e),
                         lambda b_, h, j, lens_, tbl_: (b_, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, e),
                               lambda b_, h, j, lens_, tbl_: (b_, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq, dv), jnp.float32),
            pltpu.VMEM((bq, LANES), jnp.float32),
            pltpu.VMEM((bq, LANES), jnp.float32),
            pltpu.VMEM((bq, e), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_paged_decode_block_kernel, scale=scale,
                          rope_theta=rope_theta),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, bq, e), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(lens, tbl, xr, wqr, kr, vr, wo, rr)
    return out[:, :1]


def fused_decode_block(x, wq, k, v, wo, residual, lengths, *,
                       scale=None, rope_theta=None, block_k: int = 512,
                       interpret: bool = False):
    """One Pallas launch for the whole decode attention sub-block.

    x, residual: (B, 1, E); wq: (E, Hq, D); k, v: (B, Hkv, Skv, D[v]);
    wo: (Hq, Dv, E) (the model's output-projection layout); lengths:
    (B,) valid KV prefix per row.  Returns (B, 1, E) =
    ``residual + attn_out @ Wo``.
    """
    b, sq, e = x.shape
    assert sq == 1, "fused_decode_block is the M=1 decode schedule"
    eh, hq, d = wq.shape
    assert eh == e
    _, hkv, skv, dv = v.shape
    group = hq // hkv
    assert wo.shape == (hq, dv, e)
    scale = scale if scale is not None else d ** -0.5
    # sublane-pad the single query row; only row 0 of the output is real
    bq = 8 if x.dtype == jnp.float32 else 16
    bk = min(block_k, fa._round_up(skv))
    skv_p = fa._pad_to(skv, bk)
    nk = skv_p // bk
    xr = fa._pad_seq(x, bq, axis=1)
    rr = fa._pad_seq(residual, bq, axis=1)
    wqr = jnp.moveaxis(wq, 1, 0)                     # (Hq, E, D)
    kr = fa._pad_seq(k.reshape(b * hkv, skv, d), skv_p)
    vr = fa._pad_seq(v.reshape(b * hkv, skv, dv), skv_p)
    lens = jnp.minimum(lengths.astype(jnp.int32), skv)

    kv_index = functools.partial(_kv_index, hkv=hkv, group=group, bk=bk)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, hq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, e), lambda b_, h, j, lens_: (b_, 0, 0)),
            pl.BlockSpec((1, e, d), lambda b_, h, j, lens_: (h, 0, 0)),
            pl.BlockSpec((1, bk, d), kv_index),
            pl.BlockSpec((1, bk, dv), kv_index),
            pl.BlockSpec((1, dv, e), lambda b_, h, j, lens_: (h, 0, 0)),
            pl.BlockSpec((1, bq, e), lambda b_, h, j, lens_: (b_, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, e),
                               lambda b_, h, j, lens_: (b_, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq, dv), jnp.float32),
            pltpu.VMEM((bq, LANES), jnp.float32),
            pltpu.VMEM((bq, LANES), jnp.float32),
            pltpu.VMEM((bq, e), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_decode_block_kernel, scale=scale,
                          rope_theta=rope_theta),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, bq, e), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(lens, xr, wqr, kr, vr, wo, rr)
    return out[:, :1]
