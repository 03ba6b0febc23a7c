"""Distributed layer-fused decode (§Perf optimization 'flash decoding').

Baseline decode shards the KV cache's TIME dimension over the model
axis; XLA then broadcasts every kv block to every shard (collective-
bound — see EXPERIMENTS.md §Roofline).  This module instead runs the
paper's fused schedule *per shard* and combines the shards' partial
online-softmax states — the (m, l, o) triple that the Fig. 5c schedule
streams through the SIMD core becomes the *only* cross-chip traffic:

    per shard:  o_i = sum_j exp(s_ij - m_i) v_j ;  (m_i, l_i)
    combine  :  m* = max_i m_i ;  o = sum_i exp(m_i - m*) o_i
                                      / sum_i exp(m_i - m*) l_i

Exact (not approximate): softmax is associative under this combine.
Traffic per step drops from O(cache/model_shards) broadcast to
O(B * H * D) partials — about four orders of magnitude at 32k context.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.sharding import rules as shrules

NEG_INF = -1e30


def _local_partial(q, k, v, first_col, lengths, scale):
    """Partial attention over this shard's kv columns.
    q: (B,H,S1,D) replicated; k,v: (B,Hkv,Sl,D); returns (o, m, l)."""
    b, hq, sq, d = q.shape
    hkv, sl = k.shape[1], k.shape[2]
    group = hq // hkv
    qg = q.reshape(b, hkv, group * sq, d).astype(jnp.float32)
    s = jnp.einsum("bngd,bnkd->bngk", qg, k.astype(jnp.float32)) * scale
    cols = first_col + jnp.arange(sl)
    valid = cols[None, :] < lengths[:, None]               # (B, Sl)
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    m = jnp.max(s, axis=-1)                                # (B,Hkv,G*S1)
    p = jnp.exp(s - m[..., None])
    # fully-masked shard: make its contribution exactly zero
    dead = m <= NEG_INF / 2
    p = jnp.where(dead[..., None], 0.0, p)
    m = jnp.where(dead, NEG_INF, m)
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum("bngk,bnkd->bngd", p, v.astype(jnp.float32))
    return o, m, l


def distributed_decode_attention(
    q: jax.Array,            # (B, Hq, S1, D) — S1 = 1..few
    k: jax.Array,            # (B, Hkv, S, D)  seq sharded over `axis`
    v: jax.Array,
    lengths: jax.Array,      # (B,)
    *,
    scale: Optional[float] = None,
    axis: str = "model",
    plan=None,
) -> jax.Array:
    """Exact attention over a sequence-sharded cache with partial-softmax
    combination across `axis`.  Requires an active mesh (sharding.rules
    context); falls back to the caller's path otherwise.

    ``lengths`` is per-row: with the continuous-batching engine these
    are the true per-slot write positions (``cache_len + 1``), so
    mixed-depth batches shard-combine correctly — a shard wholly past
    a row's valid prefix contributes a zeroed partial for that row.

    ``plan`` (a ``lower.runtime.PlanDispatch``): annotated, not
    consulted — the per-shard partial IS the streamed score pipeline
    (the (m, l, o) triple the Fig. 5c schedule forwards), so this path
    executes the fused schedule regardless of the plan's path; the
    plan is told so validation tables label the measured path right.
    """
    if plan is not None:
        if plan.path != "fused_attention":
            plan.plan.record_downgrade(
                "distributed decode always streams the score pipeline "
                "(partial-softmax shard combine)", plan.path,
                "fused_attention")
        plan.plan.note(
            f"distributed decode over axis {axis!r}: cross-shard "
            "traffic is the (m, l, o) partial-softmax triple only")
    mesh = shrules._current()[0]
    b, hq, sq, d = q.shape
    hkv, seq = k.shape[1], k.shape[2]
    dv = v.shape[3]
    scale = scale if scale is not None else d ** -0.5
    n_shards = dict(zip(mesh.axis_names, mesh.devices.shape))[axis]
    sl = seq // n_shards
    group = hq // hkv

    def per_shard(q, k, v, lengths):
        bl = q.shape[0]                     # local batch
        idx = jax.lax.axis_index(axis)
        o, m, l = _local_partial(q, k, v, idx * sl, lengths, scale)
        m_star = jax.lax.pmax(m, axis)
        w = jnp.exp(m - m_star)
        o = jax.lax.psum(o * w[..., None], axis)
        l = jax.lax.psum(l * w, axis)
        l = jnp.where(l == 0.0, 1.0, l)
        out = (o / l[..., None]).reshape(bl, hq, sq, dv)
        return out.astype(q.dtype)

    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    bspec = batch_axes if len(batch_axes) > 1 else \
        (batch_axes[0] if batch_axes else None)
    fn = jax.shard_map(
        per_shard, mesh=mesh,
        in_specs=(P(bspec, None, None, None),
                  P(bspec, None, axis, None),
                  P(bspec, None, axis, None),
                  P(bspec)),
        out_specs=P(bspec, None, None, None),
        check_vma=False)
    return fn(q, k, v, lengths)


def head_parallel_decode_attention(
    q: jax.Array,            # (B, Hq, S1, D)
    k: jax.Array,            # (B, Hkv, S, D) — full depth, heads sharded
    v: jax.Array,
    lengths: jax.Array,      # (B,)
    wo: jax.Array,           # (Hq, Dv, Dmodel) output projection
    *,
    scale: Optional[float] = None,
    axis: str = "model",
    plan=None,
) -> jax.Array:
    """Head-partitioned decode step: the lowered form of the DSE's
    head->core allocation (``allocation.head_partition_schedule``).
    Each mesh shard along ``axis`` owns a contiguous slice of heads,
    runs their *full-depth* attention locally, applies its slice of the
    output projection, and the shards' (B, S, Dmodel) partial outputs
    are summed with one ``psum`` — the jax analogue of the engine-side
    ``acc{h}`` chain whose replica transfers make up the predicted
    ``comm_cycles``.  Returns the combined (B, S, Dmodel) output (the
    caller adds the residual).

    Requires an active mesh whose ``axis`` size divides both Hq and
    Hkv (head groups must not straddle shards).
    """
    mesh = shrules._current()[0]
    b, hq, sq, d = q.shape
    hkv = k.shape[1]
    dv = v.shape[3]
    scale = scale if scale is not None else d ** -0.5
    n_shards = dict(zip(mesh.axis_names, mesh.devices.shape))[axis]
    if hq % n_shards or hkv % n_shards:
        raise ValueError(
            f"head-parallel decode needs heads divisible by the "
            f"{axis!r} axis: Hq={hq}, Hkv={hkv}, shards={n_shards}")
    if plan is not None:
        if plan.path != "fused_attention":
            plan.plan.record_downgrade(
                "head-parallel decode streams each shard's score "
                "pipeline (per-head partition, one output psum)",
                plan.path, "fused_attention")
        plan.plan.note(
            f"head-parallel decode over axis {axis!r}: cross-shard "
            "traffic is one (B, S, d_model) output partial per shard")

    def per_shard(q, k, v, lengths, wo):
        bl, hq_local = q.shape[0], q.shape[1]
        # full-depth local attention over this shard's heads
        o, m, l = _local_partial(q, k, v, 0, lengths, scale)
        l = jnp.where(l == 0.0, 1.0, l)
        o = (o / l[..., None]).reshape(bl, hq_local, sq, dv)
        # this shard's slice of the output projection -> (B, S, Dmodel)
        out = jnp.einsum("bhse,hed->bsd", o, wo.astype(jnp.float32))
        return jax.lax.psum(out, axis)

    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    bspec = batch_axes if len(batch_axes) > 1 else \
        (batch_axes[0] if batch_axes else None)
    fn = jax.shard_map(
        per_shard, mesh=mesh,
        in_specs=(P(bspec, axis, None, None),
                  P(bspec, axis, None, None),
                  P(bspec, axis, None, None),
                  P(bspec),
                  P(axis, None, None)),
        out_specs=P(bspec, None, None),
        check_vma=False)
    return fn(q, k, v, lengths, wo).astype(q.dtype)
