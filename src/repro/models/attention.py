"""Attention blocks: GQA (qwen3/starcoder2/phi3.5/jamba/hubert/internvl2)
and MLA (deepseek-v3), both dispatching to the paper's shape-selected
fused schedule via kernels.ops.

Schedule selection (the paper's contribution as a runtime feature):
  * train/prefill: M = seq >> N = head_dim  -> Fig. 5c fused kernel
    (ops.attention), score matrix never materialised;
  * decode:        M = 1 << N              -> Fig. 5b regime; the Q
    projection folds into the kernel (ops.qproj_attention) so Q never
    hits HBM — RoPE rides along in-register — and at M = 1 the whole
    sub-block escalates to the decode megakernel (ops.decode_block):
    projection, scores, softmax, P.V, output projection and residual
    add in one launch.  qk-norm between projection and scores is folded
    by the megakernel alone: under it ``qproj_attention`` is illegal,
    and the lowering layer records that downgrade.

The decision reaches this module two ways: ``impl="auto"`` resolves an
LRU-cached ExecutionPlan from the call shapes inside kernels/ops.py,
or the serving engine passes a ``lower.runtime.PlanDispatch`` (the
``plan`` kwarg) carrying the whole-network phase decision, plan-resolved
tiling, and the downgrade ledger.  KV-cached calls (decode / chunked
prefill) pass a ``lengths`` mask and stay on the planned Pallas path:
ops routes them to the masked scalar-prefetch kernels, whose causal
rows anchor at the end of the valid prefix — exactly this module's
``q_offset = cache_len = lengths - s`` convention.  With per-row (B,)
``cache_len`` (the continuous-batching engine's per-slot state) the
append becomes a vmapped per-row scatter, ``q_offset`` is dropped and
``lengths = cache_len + 1`` alone carries each row's causal frontier.

KV caches: GQA stores (k, v) per layer; MLA stores the *latent* cache
(c_kv + rope key), decoding in absorbed form — (B, 1, S, 640) instead
of (B, H, S, 192+128): one 576-wide latent "KV head" (padded to the
lane tile), the first 512 columns of which are also the value.  Chunks
run the masked fused_attention (d_v != d_k); paged decode runs
``ops.mla_decode``.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import ops
from repro.models import common as cm
from repro.models.common import ModelConfig, param, ones_param, rms_norm, rope
from repro.sharding import constrain


# ---------------------------------------------------------------------------
# GQA
# ---------------------------------------------------------------------------

def init_gqa(key, cfg: ModelConfig):
    d, h, hk, dh = cfg.d_model, cfg.n_heads, cfg.kv_heads, cfg.head_dim
    ks = jax.random.split(key, 6)
    p = {
        "wq": param(ks[0], (d, h, dh), ("embed", "heads", "head_dim"),
                    cfg.pdtype),
        "wk": param(ks[1], (d, hk, dh), ("embed", "kv_heads", "head_dim"),
                    cfg.pdtype),
        "wv": param(ks[2], (d, hk, dh), ("embed", "kv_heads", "head_dim"),
                    cfg.pdtype),
        "wo": param(ks[3], (h, dh, d), ("heads", "head_dim", "embed"),
                    cfg.pdtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = ones_param((dh,), ("head_dim",), cfg.pdtype)
        p["k_norm"] = ones_param((dh,), ("head_dim",), cfg.pdtype)
    return p


def _plan_kernel_args(cfg: ModelConfig, plan, interpret: bool):
    """(impl, block_q, block_k, interpret) for one attention call: the
    PlanDispatch wins when given (the plan was resolved for this
    config/phase/context and records its own downgrades); otherwise
    the config-driven defaults."""
    if plan is None:
        return (cfg.attn_impl, cfg.attn_block_q, cfg.attn_block_k,
                interpret)
    return "auto", plan.block_q, plan.block_k, \
        interpret or plan.interpret


def _per_row(cache_len) -> bool:
    """True when ``cache_len`` carries one write position per batch row
    ((B,) int32 from the continuous-batching engine) rather than a
    single scalar shared by the whole batch."""
    return getattr(cache_len, "ndim", 0) == 1


def _scatter_rows(buf, new, starts, seq_axis: int):
    """Per-row cache append: write ``new`` into ``buf`` at offset
    ``starts[b]`` along ``seq_axis`` (batch is axis 0 of both)."""
    def one(row_buf, row_new, start):
        idx = [0] * row_buf.ndim
        idx[seq_axis - 1] = start
        return jax.lax.dynamic_update_slice(row_buf, row_new, tuple(idx))
    return jax.vmap(one)(buf, new.astype(buf.dtype), starts)


def _cache_write(cache_len, b: int, s: int):
    """Normalise the two decode conventions to (starts, lengths,
    q_offset, per_row): uniform scalar ``cache_len`` keeps the scalar
    ``q_offset`` contract; per-row (B,) ``cache_len`` drops q_offset —
    at single-token steps the masked kernels anchor each row's causal
    frontier at ``lengths - s`` which IS the per-row write position."""
    if _per_row(cache_len):
        if s != 1:
            raise NotImplementedError(
                "per-row cache_len supports single-token decode steps; "
                "run multi-token (chunked) prefill per request with a "
                "scalar cache_len, then insert() the result")
        starts = cache_len.astype(jnp.int32)
        return starts, starts + s, None, True
    return (cache_len, jnp.full((b,), cache_len + s, jnp.int32),
            cache_len, False)


def gqa_forward(params, cfg: ModelConfig, x, positions, *,
                cache: Optional[dict] = None,
                cache_len: Optional[jax.Array] = None,
                block_tables: Optional[jax.Array] = None,
                interpret: bool = False,
                plan=None,
                residual: Optional[jax.Array] = None):
    """x: (B, S, D).  With cache: append k/v at cache_len, attend over
    the valid prefix (decode / chunked prefill).  ``plan``: a resolved
    ``lower.runtime.PlanDispatch`` routing this block through its
    DSE-assigned kernel path — ``plan.fuse_q`` skips the host Q
    projection (the kernel builds and RoPE-rotates the Q tile itself),
    ``plan.fuse_wo`` escalates the M=1 step to the decode megakernel.
    ``residual``: the block's skip input; when given, the returned
    output already includes it (the megakernel folds the add into the
    launch; other paths add it here), so the caller must not add it
    again.

    ``block_tables``: (B, max_pages) int32 page ids — the cache leaves
    are then page *pools* (num_pages, Hkv, page, Dh) instead of dense
    per-row buffers.  The append becomes a page-indirect scatter: row
    b's new token lands in pool page
    ``block_tables[b, cache_len[b] // page]`` at offset
    ``cache_len[b] % page``, and attention reads KV back through the
    same table: the decode megakernel on Pallas reads the pool through
    it, every other path has kernels.ops gather the pool dense first.
    Dead rows (zeroed table, cache_len 0) write into the allocator's
    reserved null page 0, whose content no live row ever reads.
    Single-token per-row decode only — prefill stays dense-side and is
    paged at ``insert()`` time by the serving engine."""
    dt = x.dtype
    b, s, _ = x.shape
    decode = cache is not None
    paged = block_tables is not None
    if paged and not decode:
        raise NotImplementedError(
            "paged KV is a decode-time storage format; prefill runs "
            "dense and is paged at insert() time")
    impl, bq, bk, interpret = _plan_kernel_args(cfg, plan, interpret)
    from repro.sharding import rules as _shrules
    dist = decode and cfg.distributed_decode and s == 1 \
        and _shrules._current()[0] is not None
    # head-parallel decode: the DSE head->core allocation lowered onto
    # the mesh's model axis (launch/mesh_lowering.py) — each shard runs
    # its heads full-depth and psums output partials.  Mutually
    # exclusive with the seq-sharded dist path; inert without a mesh.
    hp = decode and cfg.head_parallel_decode and s == 1 and not dist \
        and _shrules._current()[0] is not None
    # Q-fusion: the kernel projects (and rotates) Q from x itself, so
    # Q never exists host-side.  ``fuse_wo``: the M=1 step escalates to
    # the decode megakernel, which also folds the qk-norm of Q; the
    # qproj kernels do not, so under qk-norm Q-fusion is legal on the
    # megakernel alone — dispatch legalisation already downgrades other
    # plans; this guard refuses hand-built inconsistent ones.
    fuse_q = decode and not dist and not hp and plan is not None \
        and getattr(plan, "fuse_q", False)
    fuse_wo = fuse_q and getattr(plan, "fuse_wo", False) and s == 1 \
        and residual is not None
    fuse_q = fuse_q and (fuse_wo or not cfg.qk_norm)

    def project_kv():
        k = jnp.einsum("bsd,dhe->bhse", x, params["wk"].astype(dt))
        v = jnp.einsum("bsd,dhe->bhse", x, params["wv"].astype(dt))
        if cfg.qk_norm:
            k = rms_norm(k, params["k_norm"])
        k = rope(k, positions, cfg.rope_theta)
        return k, v

    k_new, v_new = project_kv()
    k_new = constrain(k_new, "batch", "kv_heads", "seq", "head_dim")
    v_new = constrain(v_new, "batch", "kv_heads", "seq", "head_dim")

    if not fuse_q:
        q = jnp.einsum("bsd,dhe->bhse", x, params["wq"].astype(dt))
        if cfg.qk_norm:
            q = rms_norm(q, params["q_norm"])
        q = rope(q, positions, cfg.rope_theta)
        q = constrain(q, "batch", "heads", "seq", "head_dim")

    if decode:
        starts, lengths, q_off, per_row = _cache_write(cache_len, b, s)
        if paged:
            if not per_row:
                raise NotImplementedError(
                    "paged KV requires per-row (B,) cache_len")
            if hp or dist:
                raise NotImplementedError(
                    "paged KV does not compose with the distributed "
                    "decode paths yet")
            # page-indirect append: advanced indices (page_ids, offs)
            # land row b's single new token inside its current page
            page = cache["k"].shape[2]
            page_ids = block_tables[jnp.arange(b), starts // page]
            offs = starts % page
            k_buf = cache["k"].at[page_ids, :, offs].set(
                k_new[:, :, 0, :].astype(cache["k"].dtype))
            v_buf = cache["v"].at[page_ids, :, offs].set(
                v_new[:, :, 0, :].astype(cache["v"].dtype))
        elif per_row:
            # continuous batching: each row appends at its own valid
            # length (a vmapped scatter), and the per-row lengths flow
            # straight into the masked kernels
            k_buf = _scatter_rows(cache["k"], k_new, starts, 2)
            v_buf = _scatter_rows(cache["v"], v_new, starts, 2)
        else:
            # uniform batch: one slice write at the shared position
            k_buf = jax.lax.dynamic_update_slice(
                cache["k"], k_new.astype(cache["k"].dtype),
                (0, 0, starts, 0))
            v_buf = jax.lax.dynamic_update_slice(
                cache["v"], v_new.astype(cache["v"].dtype),
                (0, 0, starts, 0))
        new_cache = {"k": k_buf, "v": v_buf}
        if hp:
            from repro.serve.distributed_decode import \
                head_parallel_decode_attention
            out = head_parallel_decode_attention(
                q, k_buf.astype(dt), v_buf.astype(dt), lengths,
                params["wo"].astype(dt), plan=plan)
            if residual is not None:
                out = residual + out
            return out, new_cache
        if dist:
            from repro.serve.distributed_decode import \
                distributed_decode_attention
            o = distributed_decode_attention(
                q, k_buf.astype(dt), v_buf.astype(dt), lengths,
                plan=plan)
        elif fuse_q:
            # in-kernel rotary position of row r is lengths - s + r =
            # cache_len + r — exactly this module's `positions`
            theta = float(cfg.rope_theta) if cfg.rope_theta else None
            wq = params["wq"].astype(dt)
            if fuse_wo:
                out = ops.decode_block(
                    x, wq, k_buf.astype(dt), v_buf.astype(dt),
                    params["wo"].astype(dt), residual, lengths,
                    block_tables=block_tables, rope_theta=theta,
                    q_norm=params.get("q_norm"), impl=impl, block_k=bk,
                    interpret=interpret, plan=plan)
                return out, new_cache
            o = ops.qproj_attention(
                x, wq, k_buf.astype(dt), v_buf.astype(dt),
                causal=cfg.causal, q_offset=q_off, lengths=lengths,
                block_tables=block_tables,
                rope_theta=theta, impl=impl, block_q=bq, block_k=bk,
                interpret=interpret, plan=plan)
        else:
            o = ops.attention(q, k_buf.astype(dt), v_buf.astype(dt),
                              causal=cfg.causal, q_offset=q_off,
                              lengths=lengths,
                              block_tables=block_tables,
                              impl=impl, block_q=bq, block_k=bk,
                              interpret=interpret, plan=plan)
    else:
        new_cache = None
        o = ops.attention(q, k_new, v_new, causal=cfg.causal,
                          impl=impl, block_q=bq, block_k=bk,
                          interpret=interpret, plan=plan)
    o = constrain(o, "batch", "heads", "seq", "head_dim")
    out = jnp.einsum("bhse,hed->bsd", o, params["wo"].astype(dt))
    if residual is not None:
        out = residual + out
    return out, new_cache


def init_gqa_cache(cfg: ModelConfig, batch: int, max_len: int,
                   dtype) -> dict:
    hk, dh = cfg.kv_heads, cfg.head_dim
    return {"k": jnp.zeros((batch, hk, max_len, dh), dtype),
            "v": jnp.zeros((batch, hk, max_len, dh), dtype)}


# ---------------------------------------------------------------------------
# MLA (deepseek-v3)
# ---------------------------------------------------------------------------

def init_mla(key, cfg: ModelConfig):
    d = cfg.d_model
    h = cfg.n_heads
    r_q, r_kv = cfg.q_lora_rank, cfg.kv_lora_rank
    d_nope, d_rope, d_v = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                           cfg.v_head_dim)
    ks = jax.random.split(key, 9)
    return {
        "wq_a": param(ks[0], (d, r_q), ("embed", "latent"), cfg.pdtype),
        "q_a_norm": ones_param((r_q,), ("latent",), cfg.pdtype),
        "wq_b": param(ks[1], (r_q, h, d_nope + d_rope),
                      ("latent", "heads", "head_dim"), cfg.pdtype),
        "wkv_a": param(ks[2], (d, r_kv + d_rope), ("embed", "latent"),
                       cfg.pdtype),
        "kv_a_norm": ones_param((r_kv,), ("latent",), cfg.pdtype),
        "wk_b": param(ks[3], (r_kv, h, d_nope),
                      ("latent", "heads", "head_dim"), cfg.pdtype),
        "wv_b": param(ks[4], (r_kv, h, d_v),
                      ("latent", "heads", "head_dim"), cfg.pdtype),
        "wo": param(ks[5], (h, d_v, d), ("heads", "head_dim", "embed"),
                    cfg.pdtype),
    }


def mla_latent_width(cfg: ModelConfig) -> int:
    """Width of a token's cached latent: the compressed KV
    (``kv_lora_rank``) and the shared rotary key
    (``qk_rope_head_dim``), zero-padded to the 128-lane tile the TPU
    stores a row in anyway (576 -> 640 at DeepSeek-V3 widths): a
    576-wide page cannot be the target of the decode kernel's DMAs."""
    return -(-(cfg.kv_lora_rank + cfg.qk_rope_head_dim) // 128) * 128


def mla_scale(cfg: ModelConfig) -> float:
    """The attention logits' scale: (qk_nope + qk_rope)^-1/2, times
    YaRN's mscale^2 when the rotary embedding is scaled."""
    scale = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
    if cfg.rope_scaling is not None:
        scale *= cfg.rope_scaling.softmax_mscale
    return scale


def _mla_q(params, cfg, x, positions, dt):
    cq = jnp.einsum("bsd,dr->bsr", x, params["wq_a"].astype(dt))
    cq = rms_norm(cq, params["q_a_norm"])
    q = jnp.einsum("bsr,rhe->bhse", cq, params["wq_b"].astype(dt))
    q_nope = q[..., :cfg.qk_nope_head_dim]
    q_rope = rope(q[..., cfg.qk_nope_head_dim:], positions, cfg.rope_theta,
                  cfg.rope_scaling)
    return q_nope, q_rope


def _mla_latent(params, cfg, x, positions, dt):
    ckv = jnp.einsum("bsd,dr->bsr", x, params["wkv_a"].astype(dt))
    c, k_rope = (ckv[..., :cfg.kv_lora_rank],
                 ckv[..., cfg.kv_lora_rank:])
    c = rms_norm(c, params["kv_a_norm"])
    k_rope = rope(k_rope[:, None], positions, cfg.rope_theta,
                  cfg.rope_scaling)[:, 0]
    return c, k_rope  # (B,S,r_kv), (B,S,d_rope)


def _pad_lanes(x, width: int):
    """Zero-pad the last axis of ``x`` to ``width``."""
    pad = width - x.shape[-1]
    if not pad:
        return x
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])


def mla_forward(params, cfg: ModelConfig, x, positions, *,
                cache: Optional[dict] = None,
                cache_len: Optional[jax.Array] = None,
                block_tables: Optional[jax.Array] = None,
                interpret: bool = False,
                plan=None,
                residual: Optional[jax.Array] = None):
    """Prefill/train: non-absorbed (per-head K/V, fused kernel, causal).
    With a cache: absorbed MQA form over the latent cache — every query
    head scores the latent (``q_nope @ W_UK`` beside ``q_rope``, d_k =
    the latent width) and P.V reads its first ``kv_lora_rank`` columns
    (W_UV applied after).  The latent cache is ``(B, 1, S, W)``, one
    shared "KV head" of width :func:`mla_latent_width`; with
    ``block_tables`` it is a page pool ``(num_pages, 1, page, W)``, each
    row's new latent appended at its own position through the table,
    and single-token decode runs the paged MLA kernel
    (``ops.mla_decode``).  ``plan`` carries the kernel args.
    ``residual`` is folded into the returned output (same contract as
    :func:`gqa_forward`)."""
    with jax.named_scope("mla"):
        out, new_cache = _mla(params, cfg, x, positions, cache, cache_len,
                              block_tables, interpret, plan)
    if residual is not None:
        out = residual + out
    return out, new_cache


def _mla(params, cfg, x, positions, cache, cache_len, block_tables,
         interpret, plan):
    dt = x.dtype
    b, s, _ = x.shape
    impl, bq, bk, interpret = _plan_kernel_args(cfg, plan, interpret)
    q_nope, q_rope = _mla_q(params, cfg, x, positions, dt)
    c, k_rope = _mla_latent(params, cfg, x, positions, dt)
    scale = mla_scale(cfg)

    if cache is None:
        if block_tables is not None:
            raise NotImplementedError(
                "paged KV is a decode-time storage format; prefill runs "
                "dense and is paged at insert() time")
        k_nope = jnp.einsum("bsr,rhe->bhse", c, params["wk_b"].astype(dt))
        v = jnp.einsum("bsr,rhe->bhse", c, params["wv_b"].astype(dt))
        q = jnp.concatenate([q_nope, q_rope], axis=-1)
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope[:, None],
                                      (b, cfg.n_heads, s,
                                       cfg.qk_rope_head_dim))], axis=-1)
        o = ops.attention(q, k, v, causal=cfg.causal, scale=scale,
                          impl=impl, block_q=bq, block_k=bk,
                          interpret=interpret, plan=plan)
        new_cache = None
    else:
        width = mla_latent_width(cfg)
        r = cfg.kv_lora_rank
        # absorbed: q' = q_nope @ W_UK -> latent space
        q_lat = jnp.einsum("bhse,rhe->bhsr", q_nope,
                           params["wk_b"].astype(dt))
        q_full = _pad_lanes(jnp.concatenate([q_lat, q_rope], axis=-1),
                            width)
        latent_new = _pad_lanes(jnp.concatenate([c, k_rope], axis=-1),
                                width)[:, None]          # (B,1,S,W)
        starts, lengths, q_off, per_row = _cache_write(cache_len, b, s)
        buf = cache["latent"]
        if block_tables is not None:
            if not per_row or s != 1:
                raise NotImplementedError(
                    "paged MLA takes single-token steps with per-row "
                    "(B,) cache_len")
            # page-indirect append: row b's new latent lands in page
            # block_tables[b, pos // page] at offset pos % page
            page = buf.shape[2]
            page_ids = block_tables[jnp.arange(b), starts // page]
            buf = buf.at[page_ids, 0, starts % page].set(
                latent_new[:, 0, 0].astype(buf.dtype))
            o_lat = ops.mla_decode(
                q_full[:, :, 0], buf, lengths, block_tables, d_v=r,
                scale=scale, impl=impl, block_k=bk, interpret=interpret,
                plan=plan)[:, :, None]                  # (B,H,1,r_kv)
        else:
            if per_row:
                buf = _scatter_rows(buf, latent_new, starts, 2)
            else:
                buf = jax.lax.dynamic_update_slice(
                    buf, latent_new.astype(buf.dtype), (0, 0, starts, 0))
            lat = buf.astype(dt)
            o_lat = ops.attention(q_full, lat, lat[..., :r],
                                  causal=cfg.causal, q_offset=q_off,
                                  scale=scale, lengths=lengths,
                                  impl=impl, block_q=bq, block_k=bk,
                                  interpret=interpret,
                                  plan=plan)            # (B,H,S,r_kv)
        new_cache = {"latent": buf}
        o = jnp.einsum("bhsr,rhe->bhse", o_lat.astype(dt),
                       params["wv_b"].astype(dt))

    return jnp.einsum("bhse,hed->bsd", o, params["wo"].astype(dt)), \
        new_cache


def init_mla_cache(cfg: ModelConfig, batch: int, max_len: int,
                   dtype) -> dict:
    """The dense latent cache: ``(batch, 1, max_len, W)``, the layout of
    one page-pool row (:func:`mla_latent_width`)."""
    return {"latent": jnp.zeros((batch, 1, max_len, mla_latent_width(cfg)),
                                dtype)}


# ---------------------------------------------------------------------------
# dispatch
# ---------------------------------------------------------------------------

def init_attention(key, cfg: ModelConfig):
    return init_mla(key, cfg) if cfg.attention == "mla" \
        else init_gqa(key, cfg)


def attention_forward(params, cfg, x, positions, **kw):
    if cfg.attention == "mla":
        return mla_forward(params, cfg, x, positions, **kw)
    return gqa_forward(params, cfg, x, positions, **kw)


def init_cache(cfg: ModelConfig, batch: int, max_len: int, dtype):
    if cfg.attention == "mla":
        return init_mla_cache(cfg, batch, max_len, dtype)
    return init_gqa_cache(cfg, batch, max_len, dtype)
