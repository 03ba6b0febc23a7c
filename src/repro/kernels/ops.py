"""Public kernel ops: plan-driven dispatch wrappers.

The paper's central result is that the optimal execution schedule of an
attention head depends on its input shape (M vs N) and phase (prefill
vs KV-cached decode).  This module is where that decision meets the
runtime:

* ``attention``        — scores over given Q: the plan's
  ``fused_attention`` path (Fig. 5c Pallas kernel / chunked-XLA
  streaming fallback) or the materialising ``unfused`` reference.
* ``qproj_attention``  — Fig. 5b/fuse_all path (Q = x @ Wq folded into
  the score kernel; Q never stored).  RoPE rides along in-kernel: the
  Q tile is rotated in-register between projection and scores.
* ``decode_block``     — the M=1 decode megakernel: Q projection
  (+ qk-norm, + RoPE), masked scores, softmax, P.V, output projection
  AND the residual add in one Pallas launch
  (``kernels/fused_decode_block.py``).
* ``mla_decode``       — absorbed MLA decode over a paged latent cache:
  every query head against one latent "KV head", the block table
  scalar-prefetched (``kernels/fused_mla_decode.py``).
* ``schedule_for``     — the legacy shape-driven selector
  (core.fusion.select_schedule), kept for the paper-rule API.
* ``ssd``/``ssd_step`` — Mamba-2 SSD chunked scan / decode update.

``impl="auto"`` resolution goes through the **ExecutionPlan IR**
(``repro.lower``): the call's shapes resolve an LRU-cached plan keyed
on ``(config, phase, seq/ctx bucket)``, whose kernel path and
plan-resolved tiling (``codesign.plan_tiling``) drive the dispatch —
the DSE engine's decision, not an ad-hoc backend check.  The serving
stack passes its own ``plan`` (a ``lower.runtime.PlanDispatch``)
instead, so whole-network phase decisions reach every block's kernel
call.

A ``lengths`` mask (KV-cached decode / chunked prefill) stays on the
Pallas path: the masked scalar-prefetch kernels
(``fused_attention_masked`` / ``fused_qproj_attention_masked``) mask
score tiles in-kernel and skip KV blocks wholly past each row's valid
prefix.  A ``block_tables`` argument switches k/v to a *paged* pool
(``num_pages, Hkv, page, D``) — the serving engine's free-list-
allocated KV cache.  Two kernels read a pool through its table: the
decode megakernel (``fused_decode_block_paged``) and the MLA decode
kernel (``fused_mla_decode_paged``).  Every other paged call gathers
the pool dense once (:func:`_gather_pools`) and takes the masked
routing.  Only genuinely unsupported calls (non-float dtypes,
malformed lengths/tables) warn once *per reason* and fall back to the
chunked-XLA path, with the concrete reason recorded on the plan's
downgrade ledger so measured-vs-predicted tables never mislabel the
executed path.
"""

from __future__ import annotations

import warnings
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import codesign
from repro.core.fusion import select_schedule
from repro.core.workload import NotExpressible
from repro.kernels import ref as _ref
from repro.kernels import xla_fallback as _xla
from repro.kernels.fused_attention import fused_attention as _pallas_attn
from repro.kernels.fused_attention import (
    fused_attention_masked as _pallas_attn_masked)
from repro.kernels.fused_decode_block import (
    fused_decode_block as _pallas_decode_block)
from repro.kernels.fused_decode_block import (
    fused_decode_block_paged as _pallas_decode_block_paged)
from repro.kernels.fused_mla_decode import (
    fused_mla_decode_paged as _pallas_mla_decode_paged)
from repro.kernels.fused_qproj_attention import (
    fused_qproj_attention as _pallas_qproj_attn)
from repro.kernels.fused_qproj_attention import (
    fused_qproj_attention_masked as _pallas_qproj_attn_masked)
from repro.kernels.ssd_scan import ssd_scan as _pallas_ssd
from repro.kernels.xla_fallback import ssd_step  # re-export
from repro.lower import cache as _plan_cache
from repro.lower import runtime as _plan_rt

__all__ = ["attention", "qproj_attention", "decode_block", "mla_decode",
           "ssd",
           "ssd_step", "schedule_for", "default_impl",
           "reset_downgrade_warnings"]


def default_impl() -> str:
    return "pallas" if jax.default_backend() == "tpu" else "xla"


def schedule_for(seq_q: int, d_head: int) -> str:
    """The paper's shape rule with M = query rows, N = head width.
    'fuse_pv' (Fig. 5c) for M > N — train/prefill; 'fuse_q_qkt'
    (Fig. 5b) for M < N — decode; 'lbl' at M == N."""
    return select_schedule(seq_q, d_head)


def _blocks(sq: int, skv: int, d: int, block_q, block_k):
    if block_q is None or block_k is None:
        t = codesign.recommend_attention_tiling(sq, skv, d)
        block_q = block_q or t.block_q
        block_k = block_k or t.block_kv
    return block_q, block_k


def _auto_dispatch(entry: str, sq: int, skv: int, d: int, hq: int,
                   hkv: int, lengths_masked: bool,
                   interpret: bool) -> Optional[_plan_rt.PlanDispatch]:
    """Resolve ``impl="auto"`` through the plan cache.  Returns None
    (caller falls back to the backend default) when the shapes are not
    expressible as a DSE workload; any other failure raises."""
    try:
        plan = _plan_cache.kernel_plan(seq_q=sq, seq_kv=skv, d_head=d,
                                       n_heads=hq, n_kv_heads=hkv)
    except NotExpressible:
        return None
    return _plan_rt.dispatch(plan, backend=jax.default_backend(),
                             interpret=interpret, entry=entry,
                             lengths_masked=lengths_masked)


#: (kernel, reason) pairs already warned about — per-reason, so e.g. a
#: lengths downgrade does not suppress the first *paged*-path warning
#: (each distinct failure mode surfaces exactly once per process).
_warned_downgrade_reasons: set = set()

#: the kernels a call can miss, as :func:`_downgrade` names them
_MASKED_KERNEL = "masked-lengths Pallas kernel"
_PAGED_KERNEL = "paged-KV Pallas kernel"


def reset_downgrade_warnings() -> None:
    """Re-arm the per-reason warn-once registry of :func:`_downgrade`
    (test isolation: the registry must not leak an 'already warned'
    state between tests)."""
    _warned_downgrade_reasons.clear()


def _downgrade(plan, reason: str, *, kernel: str) -> str:
    """pallas -> xla when a call cannot take the named Pallas kernel:
    warn once per (kernel, reason) and record the concrete *reason* on
    the plan (if any) so validation tables label the measured path
    truthfully."""
    key = (kernel, reason)
    if key not in _warned_downgrade_reasons:
        warnings.warn(
            f"attention: call cannot take the {kernel} ({reason}); "
            "downgrading impl='pallas' to the chunked-XLA streaming "
            "path (recorded on the ExecutionPlan)", stacklevel=3)
        _warned_downgrade_reasons.add(key)
    if plan is not None:
        plan.plan.record_downgrade(
            f"{kernel} unavailable: {reason}", plan.path, plan.path)
    return "xla"


def _gather_pools(impl: str, block_tables, *pools) -> list:
    """Densify page pools (num_pages, Hkv, page, D) through the
    (B, max_pages) table into (B, Hkv, max_pages*page, D): the one
    paged step of every path but the two table-reading kernels.  The
    reference impl gathers with its oracle (``ref.gather_pages``), every
    other impl with the one XLA gather (``xla_fallback.gather_paged_kv``,
    which also takes the malformed tables a downgrade lands with)."""
    gather = _ref.gather_pages if impl == "reference" \
        else _xla.gather_paged_kv
    return [gather(p, block_tables) for p in pools]


def _kv_len(k, lengths, block_tables) -> int:
    """Score columns of a call: the dense cache's length, or a paged
    call's table width in tokens (a paged call must carry lengths)."""
    if block_tables is None:
        return k.shape[2]
    if lengths is None:
        raise ValueError("paged KV requires lengths")
    return block_tables.shape[1] * k.shape[2]


_MASKED_DTYPES = ("float32", "bfloat16", "float16")


def _masked_unsupported(x, lengths, causal: bool, q_offset,
                        sq: int) -> Optional[str]:
    """Reason string when the masked Pallas kernels cannot serve this
    call, else None.  The masked kernels are forward-only and cover
    the float dtypes the unmasked kernels do; anything else keeps the
    (recorded) chunked-XLA fallback.

    The masked kernels' causal anchor is the end of the valid prefix
    (``q_offset = lengths - Sq``, per batch row).  An *explicit*
    ``q_offset`` inconsistent with that anchor cannot be expressed, so
    it is checked when both values are concrete and refused with a
    recorded reason — never a silently different answer.  Abstract
    (traced) values are trusted: the model runtime constructs
    ``lengths = cache_len + Sq`` and ``q_offset = cache_len`` together.
    """
    if str(x.dtype) not in _MASKED_DTYPES:
        return f"dtype {x.dtype} outside {_MASKED_DTYPES}"
    if getattr(lengths, "ndim", 1) != 1:
        return f"lengths must be (B,), got shape {lengths.shape}"
    if not jnp.issubdtype(jnp.asarray(lengths).dtype, jnp.integer):
        return f"lengths must be integral, got {lengths.dtype}"
    if causal and q_offset is None and sq > 1:
        # ambiguous anchor: the masked kernel would use lengths - Sq
        # while the chunked fallback defaults to Skv - Sq — refuse
        # rather than give backend-dependent answers (Sq = 1 is safe:
        # the single row's limit is lengths - 1 under both)
        return ("causal multi-row lengths call without q_offset: pass "
                "q_offset = lengths - Sq (the masked kernel's anchor)")
    if causal and q_offset is not None:
        try:
            # int() raises on traced values (then the serve invariant
            # q_offset = lengths - Sq holds by construction); note
            # jax.device_get would NOT raise — it passes tracers through
            off = int(q_offset)
            lens = [int(n) for n in lengths]
        except (TypeError, jax.errors.ConcretizationTypeError,
                jax.errors.TracerArrayConversionError,
                jax.errors.TracerIntegerConversionError):
            return None
        if any(n - sq != off for n in lens):
            return (f"explicit q_offset={off} inconsistent with the "
                    f"masked kernel's causal anchor lengths - Sq "
                    f"({[n - sq for n in lens]})")
    return None


def _paged_unsupported(x, lengths, block_tables, page: int
                       ) -> Optional[str]:
    """Reason string when a table-reading kernel (the paged decode
    megakernel, the MLA decode kernel) cannot serve this call, else
    None: the masked-kernel constraints (M=1, not causal) plus the
    block-table contract — a 2-D integral (B, max_pages) table and a
    sublane-aligned page size."""
    if getattr(block_tables, "ndim", 0) != 2:
        return ("block_tables must be (B, max_pages), got shape "
                f"{getattr(block_tables, 'shape', None)}")
    if not jnp.issubdtype(jnp.asarray(block_tables).dtype, jnp.integer):
        return f"block_tables must be integral, got {block_tables.dtype}"
    if block_tables.shape[0] != lengths.shape[0]:
        return (f"block_tables rows {block_tables.shape[0]} != "
                f"lengths rows {lengths.shape[0]}")
    if page % 8:
        return f"page size {page} not sublane-aligned (8)"
    return _masked_unsupported(x, lengths, False, None, 1)


def _resolve(entry: str, impl: str, plan, sq: int, skv: int, d: int,
             hq: int, hkv: int, lengths, block_q, block_k, interpret):
    """Shared impl/tiling resolution for the attention entry points.
    Returns the (possibly auto-resolved) plan too, so the caller can
    record downgrades on it."""
    if plan is not None:
        if impl == "auto":
            impl = plan.impl
        block_q = block_q or plan.block_q
        block_k = block_k or plan.block_k
        interpret = interpret or plan.interpret
    elif impl == "auto":
        plan = _auto_dispatch(entry, sq, skv, d, hq, hkv,
                              lengths is not None, interpret)
        if plan is not None:
            impl = plan.impl
            block_q = block_q or plan.block_q
            block_k = block_k or plan.block_k
        else:
            impl = default_impl()
    block_q, block_k = _blocks(sq, skv, d, block_q, block_k)
    return impl, block_q, block_k, interpret, plan


def attention(q, k, v, *, causal: bool = True,
              scale: Optional[float] = None,
              q_offset: Optional[int] = None,
              lengths: Optional[jax.Array] = None,
              block_tables: Optional[jax.Array] = None,
              impl: str = "auto",
              block_q: Optional[int] = None,
              block_k: Optional[int] = None,
              interpret: bool = False,
              plan: Optional[_plan_rt.PlanDispatch] = None):
    """Layer-fused attention (paper Fig. 5c: QK^T -> softmax -> .V fused;
    M x M scores never materialised) or the plan's unfused reference.

    q: (B, Hq, Sq, D); k, v: (B, Hkv, Skv, D[v]); GQA via Hq % Hkv == 0.
    ``lengths``: (B,) valid kv prefix (decode / chunked prefill over a
    KV cache) — served by the masked scalar-prefetch Pallas kernel on
    the Pallas path (score tiles masked in-kernel, KV blocks wholly
    past ``lengths[b]`` skipped); the masked kernel anchors causal rows
    at the end of the valid prefix, so ``q_offset`` is implied
    (``lengths - Sq``) and ignored on that path.  Unsupported calls
    (non-float dtypes, malformed lengths) fall back to the chunked-XLA
    path with the reason warned once + recorded on the plan.
    ``plan``: a resolved ``lower.runtime.PlanDispatch``; wins over the
    auto resolution and receives downgrade records.

    ``block_tables``: (B, max_pages) int32 page ids — k and v are then
    the *page pools* (num_pages, Hkv, page, D[v]) instead of dense
    caches (``lengths`` required).  The pools are gathered dense once
    and the call takes the masked routing above: no kernel of this
    entry reads a pool through its table.
    """
    b, hq, sq, d = q.shape
    skv = _kv_len(k, lengths, block_tables)
    impl, block_q, block_k, interpret, plan = _resolve(
        "attention", impl, plan, sq, skv, d, hq, k.shape[1], lengths,
        block_q, block_k, interpret)
    if block_tables is not None:
        k, v = _gather_pools(impl, block_tables, k, v)
    if lengths is not None and impl == "pallas":
        reason = _masked_unsupported(q, lengths, causal, q_offset, sq)
        if reason is not None:
            impl = _downgrade(plan, reason, kernel=_MASKED_KERNEL)
        else:
            return _pallas_attn_masked(
                q, k, v, lengths, causal=causal, scale=scale,
                block_q=block_q, block_k=block_k, interpret=interpret)
    if impl == "pallas":
        return _pallas_attn(q, k, v, causal, scale, q_offset,
                            block_q, block_k, interpret)
    if impl == "xla":
        return _xla.chunked_attention(
            q, k, v, causal=causal, scale=scale, q_offset=q_offset,
            lengths=lengths, block_q=block_q, block_k=block_k)
    if impl == "reference":
        return _ref.attention_reference(
            q, k, v, causal=causal, scale=scale, q_offset=q_offset,
            lengths=lengths)
    raise ValueError(f"unknown impl {impl!r}")


def qproj_attention(x, wq, k, v, *, causal: bool = True,
                    scale: Optional[float] = None,
                    q_offset: Optional[int] = None,
                    lengths: Optional[jax.Array] = None,
                    block_tables: Optional[jax.Array] = None,
                    rope_theta: Optional[float] = None,
                    impl: str = "auto",
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    interpret: bool = False,
                    plan: Optional[_plan_rt.PlanDispatch] = None):
    """Layer-fused Q-projection attention (paper Fig. 5b: Q = x @ Wq fused
    into QK^T — Q never stored).  x: (B, Sq, E); wq: (E, Hq, D).
    ``lengths`` takes the masked scalar-prefetch kernel on the Pallas
    path (see :func:`attention`).  ``rope_theta`` applies rotary
    embedding to Q *between* projection and scores — in-register inside
    the Pallas kernels (row r sits at ``q_offset + r``, or
    ``lengths[b] - Sq + r`` on the masked path), on the materialised Q
    in the fallbacks.  ``block_tables``: (B, max_pages) page ids — k, v
    become pools (num_pages, Hkv, page, D[v]), gathered dense once
    before the masked routing, as in :func:`attention`."""
    b, sq, e = x.shape
    hq, d = wq.shape[1], wq.shape[-1]
    skv = _kv_len(k, lengths, block_tables)
    impl, block_q, block_k, interpret, plan = _resolve(
        "qproj_attention", impl, plan, sq, skv, d, hq, k.shape[1],
        lengths, block_q, block_k, interpret)
    if block_tables is not None:
        k, v = _gather_pools(impl, block_tables, k, v)
    if lengths is not None and impl == "pallas":
        reason = _masked_unsupported(x, lengths, causal, q_offset, sq)
        if reason is not None:
            impl = _downgrade(plan, reason, kernel=_MASKED_KERNEL)
        else:
            return _pallas_qproj_attn_masked(
                x, wq, k, v, lengths, causal=causal, scale=scale,
                rope_theta=rope_theta, block_q=block_q, block_k=block_k,
                interpret=interpret)
    if impl == "pallas":
        return _pallas_qproj_attn(x, wq, k, v, causal, scale, q_offset,
                                  rope_theta, block_q, block_k,
                                  interpret)
    q = jnp.einsum("bse,ehd->bhsd", x, wq.astype(x.dtype))
    if rope_theta is not None:
        pos = _ref.rope_positions(sq, skv, lengths=lengths,
                                  q_offset=q_offset)
        q = _ref.rope(q, pos, rope_theta)
    if impl == "xla":
        return _xla.chunked_attention(
            q, k, v, causal=causal, scale=scale, q_offset=q_offset,
            lengths=lengths, block_q=block_q, block_k=block_k)
    if impl == "reference":
        return _ref.attention_reference(
            q, k, v, causal=causal, scale=scale, q_offset=q_offset,
            lengths=lengths)
    raise ValueError(f"unknown impl {impl!r}")


def decode_block(x, wq, k, v, wo, residual, lengths, *,
                 block_tables: Optional[jax.Array] = None,
                 scale: Optional[float] = None,
                 rope_theta: Optional[float] = None,
                 q_norm: Optional[jax.Array] = None,
                 norm_eps: float = 1e-6,
                 impl: str = "auto",
                 block_k: Optional[int] = None,
                 interpret: bool = False,
                 plan: Optional[_plan_rt.PlanDispatch] = None):
    """The M=1 decode megakernel entry point: the whole attention
    sub-block — Q projection (+ the per-head RMS norm of qk-norm
    models given ``q_norm``, + RoPE at ``lengths[b] - 1``), masked
    scores over the valid prefix, online softmax, P.V, output
    projection and residual add — in ONE Pallas launch
    (``kernels/fused_decode_block.py``).

    x, residual: (B, 1, E); wq: (E, Hq, D); k, v: (B, Hkv, Skv, D[v]);
    wo: (Hq, Dv, E); lengths: (B,).  Returns (B, 1, E) =
    ``residual + attn_out @ Wo``.  Non-Pallas impls compose the same
    math from the streaming-XLA / reference pieces (identical numerics,
    more HBM round-trips).  ``block_tables``: (B, max_pages) page ids —
    k, v become pools (num_pages, Hkv, page, D[v]) and the one-launch
    kernel gathers KV through the table, ``block_k // page`` pages a
    grid step; a call it cannot serve, and every non-Pallas impl,
    gathers the pools dense first."""
    b, sq, e = x.shape
    assert sq == 1, "decode_block is the M=1 decode schedule"
    hq, d = wq.shape[1], wq.shape[-1]
    skv = _kv_len(k, lengths, block_tables)
    impl, _, block_k, interpret, plan = _resolve(
        "decode_block", impl, plan, sq, skv, d, hq, k.shape[1], lengths,
        None, block_k, interpret)
    if block_tables is not None:
        if impl == "pallas":
            reason = _paged_unsupported(x, lengths, block_tables,
                                        k.shape[2])
            if reason is None:
                return _pallas_decode_block_paged(
                    x, wq, k, v, wo, residual, lengths, block_tables,
                    scale=scale, rope_theta=rope_theta, q_norm=q_norm,
                    norm_eps=norm_eps, block_k=block_k,
                    interpret=interpret)
            impl = _downgrade(plan, reason, kernel=_PAGED_KERNEL)
        k, v = _gather_pools(impl, block_tables, k, v)
    if impl == "pallas":
        reason = _masked_unsupported(x, lengths, False, None, sq)
        if reason is not None:
            impl = _downgrade(plan, reason, kernel=_MASKED_KERNEL)
        else:
            return _pallas_decode_block(
                x, wq, k, v, wo, residual, lengths, scale=scale,
                rope_theta=rope_theta, q_norm=q_norm, norm_eps=norm_eps,
                block_k=block_k, interpret=interpret)
    if impl == "reference":
        return _ref.decode_block_reference(
            x, wq, k, v, wo, residual, lengths, rope_theta=rope_theta,
            scale=scale, q_norm=q_norm, norm_eps=norm_eps)
    if impl == "xla":
        q = jnp.einsum("bse,ehd->bhsd", x, wq.astype(x.dtype))
        if q_norm is not None:
            q = _ref.rms_norm(q, q_norm, norm_eps)
        if rope_theta is not None:
            pos = _ref.rope_positions(sq, skv, lengths=lengths)
            q = _ref.rope(q, pos, rope_theta)
        o = _xla.chunked_attention(q, k, v, causal=False, scale=scale,
                                   lengths=lengths, block_k=block_k)
        y = jnp.einsum("bhse,hed->bsd", o.astype(jnp.float32),
                       wo.astype(jnp.float32))
        return (residual.astype(jnp.float32) + y).astype(x.dtype)
    raise ValueError(f"unknown impl {impl!r}")


def mla_decode(q, pool, lengths, block_tables, *, d_v: int, scale: float,
               impl: str = "auto", block_k: Optional[int] = None,
               interpret: bool = False,
               plan: Optional[_plan_rt.PlanDispatch] = None):
    """Absorbed MLA decode attention over a paged latent pool: q (B, H,
    W), every head against the one latent "KV head" of pool (num_pages,
    1, page, W), P.V over its first ``d_v`` columns; lengths (B,),
    block_tables (B, max_pages).  Returns (B, H, d_v).  The Pallas impl
    is ``fused_mla_decode_paged``; the others gather the pool dense and
    compose the same math (``xla``: streaming, ``reference``:
    materialised scores)."""
    if plan is not None:
        impl = plan.impl if impl == "auto" else impl
        block_k = block_k or plan.block_k
        interpret = interpret or plan.interpret
    elif impl == "auto":
        impl = default_impl()
    block_k = block_k or 512
    if impl == "pallas":
        reason = _paged_unsupported(q, lengths, block_tables,
                                    pool.shape[2])
        if reason is None:
            return _pallas_mla_decode_paged(
                q, pool, lengths, block_tables, d_v=d_v, scale=scale,
                block_k=block_k, interpret=interpret)
        impl = _downgrade(plan, reason, kernel=_PAGED_KERNEL)
    lat, = _gather_pools(impl, block_tables, pool)    # (B, 1, S, W)
    q4 = q[:, :, None]
    if impl == "xla":
        o = _xla.chunked_attention(q4, lat, lat[..., :d_v], causal=False,
                                   scale=scale, lengths=lengths,
                                   block_k=block_k)
    elif impl == "reference":
        o = _ref.attention_reference(q4, lat, lat[..., :d_v], scale=scale,
                                     lengths=lengths)
    else:
        raise ValueError(f"unknown impl {impl!r}")
    return o[:, :, 0]


def ssd(x, dt, a, b, c, d=None, *, chunk: int = 128,
        impl: str = "auto",
        h0: Optional[jax.Array] = None,
        return_final_state: bool = False,
        interpret: bool = False):
    """Mamba-2 SSD chunked scan.  The Pallas kernel is forward-only (the
    serving path); training/backward uses the differentiable lax
    implementation (identical math).  SSD blocks are not expressible as
    DSE workloads yet, so ``impl="auto"`` stays the backend default."""
    if impl == "auto":
        impl = default_impl()
    if impl == "pallas" and h0 is None:
        L = x.shape[1]
        pad = (-L) % chunk
        if pad:
            x = _xla._pad_axis(x, L + pad, 1)
            dt = _xla._pad_axis(dt, L + pad, 1)
            b = _xla._pad_axis(b, L + pad, 1)
            c = _xla._pad_axis(c, L + pad, 1)
        out = _pallas_ssd(x, dt, a, b, c, d, chunk=chunk,
                          interpret=interpret,
                          return_final_state=return_final_state)
        if pad:
            if return_final_state:
                y, h = out
                return y[:, :L], h
            return out[:, :L]
        return out
    if impl in ("xla", "pallas"):
        return _xla.chunked_ssd(x, dt, a, b, c, d, chunk=chunk, h0=h0,
                                return_final_state=return_final_state)
    if impl == "reference":
        return _ref.ssd_reference(x, dt, a, b, c, d, h0=h0,
                                  return_final_state=return_final_state)
    raise ValueError(f"unknown impl {impl!r}")
