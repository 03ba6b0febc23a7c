"""The composable model: one stack covering all 10 assigned archs.

Layer i has a block kind (attn | mamba) and an FFN kind (dense | moe)
decided by ModelConfig.block_kind/ffn_kind — dense GQA (qwen3,
starcoder2), encoder-only (hubert), MoE (phi3.5, deepseek+MLA), SSM
(mamba2: no attention, no separate FFN), hybrid (jamba 1:7 + MoE/2),
VLM/audio backbones with stub frontends.

Layers are scanned over the repeating period (ModelConfig.layer_period)
so compile time and HLO size are O(period), not O(n_layers); a dense
prefix (deepseek's first 3 layers) is python-looped.  Remat policy per
period from cfg.remat.
"""

from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.models import attention as attn
from repro.models import common as cm
from repro.models import mamba as mb
from repro.models import moe as moe_mod
from repro.models.common import (ModelConfig, Param, ones_param, param,
                                 rms_norm, split_params)
from repro.sharding import constrain


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _init_layer(key, cfg: ModelConfig, i: int):
    ks = jax.random.split(key, 4)
    p: dict = {}
    if cfg.block_kind(i) == "attn":
        p["pre_norm"] = ones_param((cfg.d_model,), ("embed_act",),
                                   cfg.pdtype)
        p["attn"] = attn.init_attention(ks[0], cfg)
    else:
        p["pre_norm"] = ones_param((cfg.d_model,), ("embed_act",),
                                   cfg.pdtype)
        p["mamba"] = mb.init_mamba(ks[0], cfg)
    if cfg.block_kind(i) == "mamba" and cfg.attn_every == 0 \
            and cfg.d_ff == 0:
        return p  # pure mamba2: no separate FFN sublayer
    if cfg.ffn_kind(i) == "moe":
        p["ffn_norm"] = ones_param((cfg.d_model,), ("embed_act",),
                                   cfg.pdtype)
        p["moe"] = moe_mod.init_moe(ks[1], cfg)
    else:
        p["ffn_norm"] = ones_param((cfg.d_model,), ("embed_act",),
                                   cfg.pdtype)
        p["mlp"] = cm.init_mlp(ks[1], cfg.d_model, cfg.d_ff, cfg.mlp,
                               cfg.pdtype)
    return p


def _stack_param_trees(trees: list):
    """Stack Param trees over a new leading 'layers' axis."""
    def stack(*leaves):
        return Param(jnp.stack([l.value for l in leaves]),
                     (None,) + leaves[0].axes)
    return jax.tree.map(stack, *trees, is_leaf=cm.is_param)


def init_model(key, cfg: ModelConfig):
    ks = jax.random.split(key, cfg.n_layers + 4)
    p: dict = {}
    if cfg.vocab_size:
        p["embed"] = param(ks[0], (cfg.vocab_size, cfg.d_model),
                           ("vocab", "embed"), cfg.pdtype, scale=0.02)
    if cfg.frontend != "none":
        fdim = cfg.frontend_dim or cfg.d_model
        p["frontend_proj"] = param(ks[1], (fdim, cfg.d_model),
                                   ("embed_act", "embed"), cfg.pdtype)
    p["prefix_layers"] = [
        _init_layer(ks[2 + i], cfg, i)
        for i in range(cfg.first_dense_layers)]
    period, n_periods = cfg.layer_period, cfg.n_periods
    stacked = []
    for pos in range(period):
        per_period = [
            _init_layer(ks[2 + cfg.first_dense_layers + j * period + pos],
                        cfg, cfg.first_dense_layers + pos)
            for j in range(n_periods)]
        stacked.append(_stack_param_trees(per_period))
    p["layers"] = stacked
    p["final_norm"] = ones_param((cfg.d_model,), ("embed_act",),
                                 cfg.pdtype)
    if cfg.vocab_size and not cfg.tie_embeddings:
        p["lm_head"] = param(ks[-1], (cfg.d_model, cfg.vocab_size),
                             ("embed", "vocab"), cfg.pdtype, scale=0.02)
    return p


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _layer_forward(lp, cfg: ModelConfig, i_kind: tuple, x, positions,
                   layer_cache, cache_len, interpret, plan=None,
                   block_tables=None):
    block_kind, ffn_kind = i_kind
    aux = {}
    h = rms_norm(x, lp["pre_norm"])
    if block_kind == "attn":
        # the attention block owns its residual add (residual=x): the
        # decode megakernel folds it into the Pallas launch, every
        # other path adds it inside attention_forward
        with jax.named_scope("attention"):
            x, new_attn_cache = attn.attention_forward(
                lp["attn"], cfg, h, positions,
                cache=None if layer_cache is None
                else layer_cache.get("attn"),
                cache_len=cache_len, interpret=interpret, plan=plan,
                residual=x, block_tables=block_tables)
        new_cache = None if layer_cache is None else {"attn": new_attn_cache}
    else:
        h, new_mamba_cache = mb.mamba_forward(
            lp["mamba"], cfg, h,
            cache=None if layer_cache is None else layer_cache.get("mamba"),
            interpret=interpret)
        new_cache = None if layer_cache is None \
            else {"mamba": new_mamba_cache}
        x = x + h
    if "mlp" in lp or "moe" in lp:
        h = rms_norm(x, lp["ffn_norm"])
        with jax.named_scope("mlp"):
            if ffn_kind == "moe" and "moe" in lp:
                h, aux = moe_mod.moe_forward(lp["moe"], cfg, h)
            else:
                h = cm.mlp_forward(lp["mlp"], h, cfg.mlp)
        x = x + h
    x = constrain(x, "batch", "seq_stream", "embed_act")
    return x, new_cache, aux


def _kinds(cfg: ModelConfig, i: int) -> tuple:
    return (cfg.block_kind(i), cfg.ffn_kind(i))


def forward(params, cfg: ModelConfig, tokens=None, embeds=None, *,
            cache=None, cache_len=None, positions=None,
            interpret: bool = False, return_aux: bool = False,
            plan=None, block_tables=None):
    """tokens: (B, S) int32 and/or embeds: (B, S_f, frontend_dim)
    (stub modality frontend, prepended).  cache/cache_len: decode mode;
    ``cache_len`` is either a scalar (whole batch at one uniform
    context) or a (B,) int32 vector of per-row write positions (the
    continuous-batching engine's per-slot state).
    ``plan``: a ``lower.runtime.PlanDispatch`` routing every attention
    block through its DSE-assigned kernel path (blocks are identical,
    so one per-block record covers the scanned body — asserted at
    lowering time).
    ``block_tables``: (B, max_pages) int32 page table for paged KV
    caches; shared by all layers, so it enters the scanned body as a
    closure constant (scan-invariant), never a scanned input.
    Returns logits (+ new cache if cache given) (+ aux if asked)."""
    parts = []
    if embeds is not None:
        fp = params["frontend_proj"]
        parts.append(jnp.einsum(
            "bsf,fd->bsd", embeds.astype(cfg.cdtype),
            fp.astype(cfg.cdtype)))
    if tokens is not None:
        emb = params["embed"]
        parts.append(emb.astype(cfg.cdtype)[tokens])
    x = parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)
    b, s, _ = x.shape
    if positions is None:
        start = 0 if cache_len is None else cache_len
        if getattr(start, "ndim", 0) == 1:
            # per-row cache_len: row b's new tokens sit at start[b]..
            positions = (start.astype(jnp.int32)[:, None]
                         + jnp.arange(s, dtype=jnp.int32)[None, :])
        else:
            positions = start + jnp.arange(s, dtype=jnp.int32)[None, :]
            positions = jnp.broadcast_to(positions, (b, s))
    x = constrain(x, "batch", "seq_stream", "embed_act")

    aux_sum = {"moe_lb_loss": 0.0, "moe_z_loss": 0.0}

    def add_aux(aux):
        for k in aux_sum:
            if k in aux:
                aux_sum[k] = aux_sum[k] + aux[k]

    # dense prefix (python loop)
    new_prefix_caches = []
    for i, lp in enumerate(params["prefix_layers"]):
        lc = None if cache is None else cache["prefix"][i]
        x, nc, aux = _layer_forward(lp, cfg, _kinds(cfg, i), x, positions,
                                    lc, cache_len, interpret, plan,
                                    block_tables)
        new_prefix_caches.append(nc)
        add_aux(aux)

    # scanned body
    period = cfg.layer_period
    kinds = [_kinds(cfg, cfg.first_dense_layers + pos)
             for pos in range(period)]

    def period_fn(carry, xs):
        x = carry
        layer_params, layer_caches = xs
        new_caches = []
        aux_acc = {"moe_lb_loss": 0.0, "moe_z_loss": 0.0}
        for pos in range(period):
            lc = None if layer_caches is None else layer_caches[pos]
            x, nc, aux = _layer_forward(
                layer_params[pos], cfg, kinds[pos], x, positions, lc,
                cache_len, interpret, plan, block_tables)
            new_caches.append(nc)
            for k in aux_acc:
                if k in aux:
                    aux_acc[k] = aux_acc[k] + aux[k]
        ys = (tuple(new_caches) if layer_caches is not None else None,
              aux_acc)
        return x, ys

    if cfg.remat == "full":
        period_fn = jax.checkpoint(period_fn)
    elif cfg.remat == "dots":
        period_fn = jax.checkpoint(
            period_fn,
            policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)

    scan_caches = None if cache is None else tuple(cache["scan"])
    xs = (tuple(params["layers"]), scan_caches)
    if cfg.scan_layers:
        x, (new_scan_caches, aux_stack) = jax.lax.scan(period_fn, x, xs)
        for k in aux_sum:
            aux_sum[k] = aux_sum[k] + jnp.sum(aux_stack[k])
    else:
        # unrolled (used by the roofline cost probes: XLA cost_analysis
        # counts a while body once, so probes lower without the scan)
        per_trip = []
        for j in range(cfg.n_periods):
            xs_j = jax.tree.map(lambda a: a[j], xs)
            x, (nc, aux_j) = period_fn(x, xs_j)
            per_trip.append(nc)
            for k in aux_sum:
                aux_sum[k] = aux_sum[k] + aux_j[k]
        if cache is not None:
            new_scan_caches = jax.tree.map(
                lambda *leaves: jnp.stack(leaves), *per_trip)
        else:
            new_scan_caches = None

    x = rms_norm(x, params["final_norm"])
    with jax.named_scope("logits"):
        if "lm_head" in params:
            logits = jnp.einsum("bsd,dv->bsv", x,
                                params["lm_head"].astype(cfg.cdtype))
        elif "embed" in params:
            logits = jnp.einsum("bsd,vd->bsv", x,
                                params["embed"].astype(cfg.cdtype))
        else:
            logits = x
    logits = constrain(logits, "batch", "seq", "vocab")

    out = [logits]
    if cache is not None:
        out.append({"prefix": new_prefix_caches,
                    "scan": list(new_scan_caches)})
    if return_aux:
        out.append(aux_sum)
    return out[0] if len(out) == 1 else tuple(out)


# ---------------------------------------------------------------------------
# caches
# ---------------------------------------------------------------------------

def init_model_cache(cfg: ModelConfig, batch: int, max_len: int,
                     dtype=jnp.bfloat16):
    """Cache pytree mirroring the layer structure: python list for the
    prefix, period-stacked (n_periods leading) for the scanned body."""
    def layer_cache(i: int):
        if cfg.block_kind(i) == "attn":
            return {"attn": attn.init_cache(cfg, batch, max_len, dtype)}
        return {"mamba": mb.init_mamba_cache(cfg, batch, dtype)}

    prefix = [layer_cache(i) for i in range(cfg.first_dense_layers)]
    period, n_periods = cfg.layer_period, cfg.n_periods

    def stack_cache(pos):
        c = layer_cache(cfg.first_dense_layers + pos)
        return jax.tree.map(
            lambda a: jnp.broadcast_to(a, (n_periods,) + a.shape), c)

    return {"prefix": prefix, "scan": [stack_cache(p) for p in range(period)]}


def cache_axes(cfg: ModelConfig):
    """Logical axes of :func:`init_model_cache`'s tree (GQA caches):
    batch rows, KV heads, sequence, head width — the scanned body's
    leaves carry the leading n_periods axis."""
    for i in range(cfg.n_layers):
        if cfg.block_kind(i) != "attn" or cfg.attention != "gqa":
            raise NotImplementedError(
                "cache axes cover GQA attention caches only")
    leaf = ("batch", "kv_heads", "seq", "head_dim")
    one = {"attn": {"k": leaf, "v": leaf}}
    scanned = {"attn": {"k": (None,) + leaf, "v": (None,) + leaf}}
    return {"prefix": [one] * cfg.first_dense_layers,
            "scan": [scanned] * cfg.layer_period}


def init_params_and_axes(key, cfg: ModelConfig):
    """Convenience: init + split into (values, logical axes)."""
    return split_params(init_model(key, cfg))


def init_params(key, cfg: ModelConfig):
    """Parameter values of :func:`init_model`, built by one jitted
    program: the compiler writes each layer straight into its stack, so
    no layer is held twice (the eager path keeps every layer and the
    stack at once)."""
    return jax.jit(lambda k: split_params(init_model(k, cfg))[0])(key)


def abstract_params(cfg: ModelConfig, seed: int = 0):
    """(values SDS tree, logical-axes tree) with zero allocation."""
    captured = {}

    def f(key):
        vals, axes = split_params(init_model(key, cfg))
        captured["axes"] = axes
        return vals

    sds = jax.eval_shape(f, jax.random.PRNGKey(seed))
    return sds, captured["axes"]
