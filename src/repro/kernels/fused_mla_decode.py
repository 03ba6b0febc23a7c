"""Absorbed MLA decode over a paged latent cache, one Pallas launch a
layer.

Multi-head latent attention (DeepSeek-V2/V3) caches one latent vector a
token: the ``kv_lora_rank``-wide compressed KV with the shared
``qk_rope_head_dim``-wide rotary key appended (512 + 64 = 576 at
DeepSeek-V3 widths).  Decoding in absorbed form folds W_UK into the
query, so every query head scores the latent directly and the attention
is MQA: all heads over one 576-wide "KV head", whose first
``kv_lora_rank`` columns are also the value (W_UV is applied to the
output outside, with Wo).

Grid ``(B, n_blocks)``, both sequential.  A step scores one batch row's
query tile — all heads on its sublanes, so the MXU sees a full-height
tile — against a block of ``block_k // page`` pages that it gathers
from the pool itself through the scalar-prefetched block table, by
async copies double-buffered across steps (the next live step's block
is fetched while this one computes, as in the paged decode
megakernel).  Pages past ``lengths[b]`` are neither copied nor scored,
so a row pays for its own context.  Scores are ``q @ block^T`` over the
full latent width, P.V reads the block's first ``d_v`` columns, with an
online softmax across blocks; the output is the per-head latent
attention ``(B, H, d_v)``.

Forward-only: decode serving never differentiates.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import fused_attention as fa
from repro.kernels.fused_decode_block import _start, _wait, paged_blocks

NEG_INF = fa.NEG_INF
LANES = fa.LANES


def _block_copies(len_ref, tbl_ref, pool_hbm, buf, sems, b, j, slot, *,
                  ppb: int, page: int):
    """The DMAs of grid step (b, j): every page of the step's block that
    holds a token of row b, pool -> ``slot`` of the double buffer.
    Returns (live page count, copies of page i)."""
    n = jnp.clip((len_ref[b] + page - 1) // page - j * ppb, 0, ppb)

    def copies(i):
        pid = tbl_ref[b, j * ppb + i]
        return (pltpu.make_async_copy(pool_hbm.at[pid, 0], buf.at[slot, i],
                                      sems.at[slot]),)
    return n, copies


def _mla_decode_kernel(len_ref, tbl_ref, q_ref, pool_hbm, o_ref,
                       buf, sems, flight, acc_ref, m_ref, l_ref, *,
                       scale: float, ppb: int, page: int, d_v: int):
    b, j = pl.program_id(0), pl.program_id(1)
    nb, nj = pl.num_programs(0), pl.num_programs(1)
    bk = ppb * page
    length = len_ref[b]
    copies_of = functools.partial(_block_copies, len_ref, tbl_ref,
                                  pool_hbm, buf, sems, ppb=ppb, page=page)

    # flight: the last grid step whose block was prefetched, and its
    # slot.  Step ids only grow, so a stale entry never matches later.
    @pl.when((b == 0) & (j == 0))
    def _first():
        flight[0] = -1

    @pl.when(j == 0)
    def _row_init():
        fa._init_softmax_state(acc_ref, m_ref, l_ref)

    @pl.when(j * bk < length)
    def _body():
        step = b * nj + j
        slot = jnp.where(flight[0] == step, flight[1], 0)

        @pl.when(flight[0] != step)
        def _fetch_own():
            # nothing was in flight for this step: the previous live
            # step had none to prefetch (a length-0 row sat between)
            _start(*copies_of(b, j, slot))

        # prefetch the next live step's block into the other slot: the
        # row's next block, else the next row's first
        more = (j + 1) * bk < length
        nb_ = jnp.where(more, b, b + 1)
        nj_ = jnp.where(more, j + 1, 0)
        live = (nb_ < nb) & (nj_ * bk < len_ref[jnp.minimum(nb_, nb - 1)])

        @pl.when(live)
        def _prefetch():
            _start(*copies_of(nb_, nj_, 1 - slot))
            flight[0] = nb_ * nj + nj_
            flight[1] = 1 - slot

        _wait(*copies_of(b, j, slot))
        kv = buf[slot].reshape(bk, buf.shape[-1])
        s = jax.lax.dot_general(
            q_ref[0], kv, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale     # (H, bk)
        cols = j * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = cols < length
        s = jnp.where(mask, s, NEG_INF)
        # pages past the row's end were not copied: whatever the buffer
        # holds there must not reach P.V (0 * NaN is NaN)
        v = kv[:, :d_v]
        vrows = j * bk + jax.lax.broadcasted_iota(jnp.int32, v.shape, 0)
        v = jnp.where(vrows < length, v, jnp.zeros_like(v))
        fa._online_softmax_tile(s, mask, v, acc_ref, m_ref, l_ref)

    @pl.when(j == nj - 1)
    def _emit():
        # a length-0 row has l == 0 and emits zeros
        l = l_ref[:, :1]
        o_ref[0] = (acc_ref[...] / jnp.where(l == 0.0, 1.0, l)
                    ).astype(o_ref.dtype)


def fused_mla_decode_paged(q, pool, lengths, block_tables, *, d_v: int,
                           scale: float, block_k: int = 512,
                           interpret: bool = False):
    """Absorbed MLA decode attention over a paged latent pool.

    q: (B, H, Dk) — each row's absorbed query (``q_nope @ W_UK`` beside
    the rotated ``q_rope``); pool: (num_pages, 1, page, Dk) latent
    pages; lengths: (B,) valid tokens per row (the new one included);
    block_tables: (B, max_pages) int32 page ids.  ``d_v``: the latent's
    value width (its first ``d_v`` columns).  ``block_k``: the KV block
    in tokens, gathered ``block_k // page`` pages a grid step
    (:func:`~repro.kernels.fused_decode_block.paged_blocks`).  Returns
    (B, H, d_v)."""
    b, h, dk = q.shape
    n_pages, one, page, dk_pool = pool.shape
    assert one == 1 and dk_pool == dk, (pool.shape, q.shape)
    assert page % 8 == 0, "page size must be sublane-aligned (8)"
    assert d_v <= dk
    max_pages = block_tables.shape[1]
    ppb, nj = paged_blocks(block_k, page, max_pages)
    sub = 8 if q.dtype == jnp.float32 else 16
    hp = fa._pad_to(h, sub)
    qr = fa._pad_seq(q, hp, axis=1)
    lens = jnp.minimum(lengths.astype(jnp.int32), max_pages * page)
    tbl = block_tables.astype(jnp.int32)

    row = lambda b_, j, lens_, tbl_: (b_, 0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(b, nj),
        in_specs=[pl.BlockSpec((1, hp, dk), row),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, hp, d_v), row),
        scratch_shapes=[
            pltpu.VMEM((2, ppb, page, dk), pool.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SMEM((2,), jnp.int32),
            pltpu.VMEM((hp, d_v), jnp.float32),
            pltpu.VMEM((hp, LANES), jnp.float32),
            pltpu.VMEM((hp, LANES), jnp.float32),
        ])
    out = pl.pallas_call(
        functools.partial(_mla_decode_kernel, scale=scale, ppb=ppb,
                          page=page, d_v=d_v),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hp, d_v), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="fused_mla_decode_paged",
    )(lens, tbl, qr.astype(pool.dtype), pool)
    return out[:, :h]
