"""Decode megakernel: the whole M=1 attention sub-block in ONE Pallas
launch — Q projection (+ the per-head Q RMS norm of qk-norm models, +
in-register RoPE), masked scores, online softmax, P.V, output
projection, residual add.

This pushes the paper's Fig. 5b fusion boundary outward for the decode
regime the inference surveys identify as launch-overhead- and
HBM-round-trip-bound: beyond Q (never stored), the per-head attention
output and the projected block output also never touch HBM.  The only
HBM traffic is x, Wq, K, V, Wo, residual in and the block output out —
the per-head O tile and the (B, 1, E) partial sums live in VMEM scratch
across the sequential head/KV grid.

Dense grid: (B, Hq, nk) with ("parallel", "arbitrary", "arbitrary") —
the head dim is sequential so the output accumulator ``y_scr`` carries
partial head contributions; per-head softmax state resets at kv step 0.
KV blocks wholly past the scalar-prefetched ``lengths[b]`` are skipped
and their DMAs clamped to the last valid block, exactly like the other
masked kernels.  At M=1 the end-anchored causal triangle degenerates to
``cols < lengths[b]``, and the rotary position is ``lengths[b] - 1``.

Paged grid: (Hkv, B, n_blocks), all sequential.  One step scores the
whole GQA group of a KV head (its ``Hq / Hkv`` query heads on the Q
tile's sublanes) against a block of ``block_k // page`` pages, which it
gathers itself from the pool through the block table by async copies,
double-buffered across steps; pages past ``lengths[b]`` are neither
copied nor scored.  Wq and Wo blocks are indexed by the KV head alone,
so each crosses HBM once per call whatever B is: Q is projected for
every row at once when a KV head starts, and every row's group output
is folded through Wo at once when it ends, into an output accumulator
for all B rows written back once.

qk-norm (qwen3): given a ``q_norm`` weight, both kernels normalise
each query head's f32 row of the Q tile over its ``D`` lanes
(``q * rsqrt(mean(q^2) + eps) * w``) before the rotation.  The K norm
is applied before K is written to the cache, so the kernels never see
it.  Without a weight neither kernel takes the extra input and each
traces as it did before the fold.

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


def _rms_rows(q, w, eps: float):
    """qk-norm of the f32 Q tile: each row (one query head) over its D
    lanes, times the (1, D) weight.  All-zero padding rows stay zero."""
    return q * jax.lax.rsqrt(jnp.mean(q * q, axis=-1, keepdims=True)
                             + eps) * w


def _split_norm(refs, norm_eps):
    """(the Q-norm weight's ref or None, the refs after it): the weight
    is an input only when the kernel folds qk-norm."""
    return (refs[0], refs[1:]) if norm_eps is not None else (None, refs)


def _decode_block_kernel(len_ref, x_ref, wq_ref, k_ref, v_ref, wo_ref,
                         res_ref, *refs, scale: float, rope_theta,
                         norm_eps):
    qn_ref, (o_ref, q_scr, acc_ref, m_ref, l_ref, y_scr) = _split_norm(
        refs, norm_eps)
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
        if qn_ref is not None:
            q = _rms_rows(q, qn_ref[...], norm_eps)
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


def paged_blocks(block_k: int, page: int,
                 max_pages: int) -> tuple[int, int]:
    """(pages a block, blocks a row) of :func:`fused_decode_block_paged`:
    the plan's KV block in whole pages, at least one and at most the
    table's width, and enough blocks to cover the table.  The grid is
    (Hkv, B, blocks a row)."""
    ppb = max(1, min(block_k // page, max_pages))
    return ppb, -(-max_pages // ppb)


def _page_copies(len_ref, tbl_ref, k_hbm, v_hbm, k_buf, v_buf, sems,
                 h, b, j, slot, *, ppb: int, page: int):
    """The DMAs of grid step (h, b, j): K and V of every page of the
    step's block that holds a token of row b, pool -> ``slot`` of the
    double buffer.  Returns (live page count, copies of page i)."""
    n = jnp.clip((len_ref[b] + page - 1) // page - j * ppb, 0, ppb)

    def copies(i):
        pid = tbl_ref[b, j * ppb + i]
        return (pltpu.make_async_copy(k_hbm.at[pid, h], k_buf.at[slot, i],
                                      sems.at[0, slot]),
                pltpu.make_async_copy(v_hbm.at[pid, h], v_buf.at[slot, i],
                                      sems.at[1, slot]))
    return n, copies


def _start(n, copies):
    def body(i, carry):
        for cp in copies(i):
            cp.start()
        return carry
    jax.lax.fori_loop(0, n, body, 0)


def _wait(n, copies):
    def body(i, carry):
        for cp in copies(i):
            cp.wait()
        return carry
    jax.lax.fori_loop(0, n, body, 0)


def _vmem_bytes(bp: int, bq: int, e: int, group: int, d: int, dv: int,
                bk: int, w_bytes: int, kv_bytes: int) -> int:
    """Scoped VMEM for the paged megakernel: both weight blocks and the
    x / residual / output rows double-buffered by the pipeline, the KV
    double buffer, the f32 scratch and the largest f32 temporaries,
    plus 8 MiB for the compiler's own, and never under 32 MiB.  A KV
    head's Wq and Wo block at starcoder2-7b widths is 10.6 MB each, so
    this is well over the default scoped limit."""
    weights = 2 * 2 * e * group * max(d, dv) * w_bytes
    rows = 2 * 3 * bp * e * w_bytes
    kv = 2 * bk * (d + dv) * kv_bytes
    f32 = 4 * (2 * bp * group * (d + dv) + 2 * bp * e
               + bq * (d + dv + 2 * LANES + 2 * bk))
    return max(weights + rows + kv + f32 + (8 << 20), 32 << 20)


def _norm_operand(q_norm) -> list:
    """The kernel's Q-norm input: the (D,) weight as one f32 (1, D)
    block, or nothing at all when there is no norm to fold."""
    if q_norm is None:
        return []
    return [q_norm.astype(jnp.float32).reshape(1, -1)]


def _take_row(tile, r):
    """Row ``r`` (a traced index) of a 2-D f32 tile, as (1, lanes): a
    masked sublane sum, where a dynamic one-row slice would leave a
    layout that cannot be broadcast."""
    rows = jax.lax.broadcasted_iota(jnp.int32, tile.shape, 0)
    return jnp.sum(jnp.where(rows == r, tile, 0.0), axis=0, keepdims=True)


def _paged_decode_block_kernel(len_ref, tbl_ref, x_ref, wq_ref, wo_ref,
                               res_ref, *refs, scale: float, rope_theta,
                               norm_eps, group: int, ppb: int, page: int):
    qn_ref, (k_hbm, v_hbm, o_ref, k_buf, v_buf, sems, flight, q_all,
             q_scr, acc_ref, m_ref, l_ref, o_all, y_scr) = _split_norm(
        refs, norm_eps)
    h, b, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    nh, nb, nj = pl.num_programs(0), pl.num_programs(1), pl.num_programs(2)
    bq, d = q_scr.shape
    dv = acc_ref.shape[1]
    bk = ppb * page
    length = len_ref[b]
    # the aligned 8-row tile of the staging buffers that holds row b
    base = pl.multiple_of(b // 8 * 8, 8)
    copies_of = functools.partial(_page_copies, len_ref, tbl_ref, k_hbm,
                                  v_hbm, k_buf, v_buf, sems, ppb=ppb,
                                  page=page)

    # flight: the last grid step whose KV was prefetched, and its slot.
    # Step ids only grow, so a stale entry never matches a later step.
    @pl.when((h == 0) & (b == 0) & (j == 0))
    def _first():
        flight[0] = -1

    @pl.when((b == 0) & (j == 0))
    def _project():
        # fusion step 1: Q of this KV head's whole group, every row at
        # once — Wq crosses HBM once per KV head, not once per row
        for g in range(group):
            q_all[:, g * d:(g + 1) * d] = jax.lax.dot_general(
                x_ref[...], wq_ref[g], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)    # (Bp, D)

    @pl.when(j == 0)
    def _row_init():
        # row b's group tile: query head g of the group on sublane g,
        # all at the token's position lengths[b] - 1
        row = _take_row(q_all[pl.ds(base, 8), :], b - base)
        rows = jax.lax.broadcasted_iota(jnp.int32, (bq, d), 0)
        q = jnp.zeros((bq, d), jnp.float32)
        for g in range(group):
            q = jnp.where(rows == g, row[:, g * d:(g + 1) * d], q)
        if qn_ref is not None:
            q = _rms_rows(q, qn_ref[...], norm_eps)
        if rope_theta is not None:
            q = fa._rope_tile_at(q, length - 1, rope_theta)
        q_scr[...] = q
        fa._init_softmax_state(acc_ref, m_ref, l_ref)

    @pl.when(j * bk < length)
    def _body():
        step = (h * nb + b) * nj + j
        slot = jnp.where(flight[0] == step, flight[1], 0)

        @pl.when(flight[0] != step)
        def _fetch_own():
            # nothing was in flight for this step: the previous live
            # step had none to prefetch (a length-0 row sat between)
            _start(*copies_of(h, b, j, slot))

        # prefetch the next live step's block into the other slot: the
        # row's next block, else the next row's first (else the next
        # KV head's row 0)
        more = (j + 1) * bk < length
        nb_ = jnp.where(more, b, b + 1)
        nh_ = jnp.where(nb_ == nb, h + 1, h)
        nb_ = jnp.where(nb_ == nb, 0, nb_)
        nj_ = jnp.where(more, j + 1, 0)
        live = (nh_ < nh) & (nj_ * bk < len_ref[nb_])

        @pl.when(live)
        def _prefetch():
            _start(*copies_of(nh_, nb_, nj_, 1 - slot))
            flight[0] = (nh_ * nb + nb_) * nj + nj_
            flight[1] = 1 - slot

        _wait(*copies_of(h, b, j, slot))
        q = q_scr[...].astype(k_buf.dtype)
        k = k_buf[slot].reshape(bk, d)
        v = v_buf[slot].reshape(bk, dv)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        cols = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = cols < length
        s = jnp.where(mask, s, NEG_INF)
        # pages past the row's end were not copied: whatever the buffer
        # holds there must not reach P.V (0 * NaN is NaN)
        vrows = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bk, dv), 0)
        v = jnp.where(vrows < length, v, jnp.zeros_like(v))
        fa._online_softmax_tile(s, mask, v, acc_ref, m_ref, l_ref)

    @pl.when(j == nj - 1)
    def _row_out():
        # normalise the group's O rows (a length-0 row has l == 0 and
        # emits zeros) and park them on row b of the (Bp, group*Dv)
        # staging tile
        l = l_ref[:, :1]
        o = acc_ref[...] / jnp.where(l == 0.0, 1.0, l)
        eight = jax.lax.broadcasted_iota(jnp.int32, (8, dv), 0)
        for g in range(group):
            cols = pl.ds(g * dv, dv)
            o_all[pl.ds(base, 8), cols] = jnp.where(
                eight == b - base, _take_row(o, g),
                o_all[pl.ds(base, 8), cols])

    @pl.when((b == nb - 1) & (j == nj - 1))
    def _fold_group():
        # fusion step 2: every row's group output through this KV head's
        # Wo rows at once — the per-head O never leaves VMEM, and Wo
        # crosses HBM once per KV head
        contrib = jax.lax.dot_general(
            o_all[...].astype(wo_ref.dtype), wo_ref[...],
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)        # (Bp, E)

        @pl.when(h == 0)
        def _first_head():
            y_scr[...] = contrib

        @pl.when(h > 0)
        def _accum():
            y_scr[...] += contrib

        @pl.when(h == nh - 1)
        def _emit():
            # fusion step 3: residual add, single HBM write of the block
            o_ref[...] = (res_ref[...].astype(jnp.float32)
                          + y_scr[...]).astype(o_ref.dtype)


def fused_decode_block_paged(x, wq, k_pool, v_pool, wo, residual,
                             lengths, block_tables, *, scale=None,
                             rope_theta=None, q_norm=None,
                             norm_eps: float = 1e-6, block_k: int = 512,
                             interpret: bool = False):
    """The decode megakernel over a paged KV pool: one Pallas launch for
    the whole M=1 attention sub-block, with KV gathered through a
    scalar-prefetched block table.

    x, residual: (B, 1, E); wq: (E, Hq, D); k_pool, v_pool:
    (num_pages, Hkv, page, D[v]); wo: (Hq, Dv, E); lengths: (B,);
    block_tables: (B, max_pages) int32 page ids.  ``block_k``: the KV
    block in tokens (the plan's ``block_kv``); a grid step gathers a
    block of pages (:func:`paged_blocks`).  The grid has a step per (KV
    head, batch row, block), the Q tile holding the KV head's group of
    query heads, so each live page is copied once per (row, KV head)
    and Wq and Wo once per KV head.  Returns (B, 1, E) =
    ``residual + attn_out @ Wo``.
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
    sub = 8 if x.dtype == jnp.float32 else 16
    bq = fa._pad_to(group, sub)              # the group's Q rows
    bp = fa._pad_to(b, sub)                  # batch rows of x and y
    ppb, nj = paged_blocks(block_k, page, max_pages)
    xr = fa._pad_seq(x.reshape(b, e), bp, axis=0)
    rr = fa._pad_seq(residual.reshape(b, e), bp, axis=0)
    # (Hq, E, D): the layout the model's layer slice is transposed into
    # in one fused op.  A reshape to (E, Hq*D) is not free on the TPU:
    # the (Hq, D) tiles relayout, two more copies of Wq a call.
    wqr = jnp.moveaxis(wq, 1, 0)
    wor = wo.reshape(hq * dv, e)             # free: rows h*Dv.. of Wo
    lens = jnp.minimum(lengths.astype(jnp.int32), max_pages * page)
    tbl = block_tables.astype(jnp.int32)

    whole = lambda h, b_, j, lens_, tbl_: (0, 0)
    in_specs = [
        pl.BlockSpec((bp, e), whole),
        pl.BlockSpec((group, e, d), lambda h, b_, j, lens_, tbl_: (h, 0, 0)),
        pl.BlockSpec((group * dv, e), lambda h, b_, j, lens_, tbl_: (h, 0)),
        pl.BlockSpec((bp, e), whole),
    ]
    norm = _norm_operand(q_norm)
    if norm:
        in_specs.append(pl.BlockSpec((1, d), whole))
    in_specs += [pl.BlockSpec(memory_space=pl.ANY),
                 pl.BlockSpec(memory_space=pl.ANY)]
    scratch = [
        pltpu.VMEM((2, ppb, page, d), k_pool.dtype),
        pltpu.VMEM((2, ppb, page, dv), v_pool.dtype),
        pltpu.SemaphoreType.DMA((2, 2)),
        pltpu.SMEM((2,), jnp.int32),
        pltpu.VMEM((bp, group * d), jnp.float32),
        pltpu.VMEM((bq, d), jnp.float32),
        pltpu.VMEM((bq, dv), jnp.float32),
        pltpu.VMEM((bq, LANES), jnp.float32),
        pltpu.VMEM((bq, LANES), jnp.float32),
        pltpu.VMEM((bp, group * dv), jnp.float32),
        pltpu.VMEM((bp, e), jnp.float32),
    ]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(hkv, b, nj), in_specs=in_specs,
        out_specs=pl.BlockSpec((bp, e), whole), scratch_shapes=scratch)
    out = pl.pallas_call(
        functools.partial(_paged_decode_block_kernel, scale=scale,
                          rope_theta=rope_theta,
                          norm_eps=norm_eps if norm else None,
                          group=group, ppb=ppb, page=page),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((bp, e), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_vmem_bytes(bp, bq, e, group, d, dv, ppb * page,
                                         wq.dtype.itemsize,
                                         k_pool.dtype.itemsize)),
        interpret=interpret,
        name="fused_decode_block_paged",
    )(lens, tbl, xr, wqr, wor, rr, *norm, k_pool, v_pool)
    return out[:b].reshape(b, 1, e)


def fused_decode_block(x, wq, k, v, wo, residual, lengths, *,
                       scale=None, rope_theta=None, q_norm=None,
                       norm_eps: float = 1e-6, block_k: int = 512,
                       interpret: bool = False):
    """One Pallas launch for the whole decode attention sub-block.

    x, residual: (B, 1, E); wq: (E, Hq, D); k, v: (B, Hkv, Skv, D[v]);
    wo: (Hq, Dv, E) (the model's output-projection layout); lengths:
    (B,) valid KV prefix per row; ``q_norm``: (D,) qk-norm weight, as
    in :func:`fused_decode_block_paged`.  Returns (B, 1, E) =
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
    norm = _norm_operand(q_norm)
    whole = lambda b_, h, j, lens_: (0, 0)
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
        ] + [pl.BlockSpec((1, d), whole)] * len(norm),
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
                          rope_theta=rope_theta,
                          norm_eps=norm_eps if norm else None),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, bq, e), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
        name="fused_decode_block",
    )(lens, xr, wqr, kr, vr, wo, rr, *norm)
    return out[:, :1]
