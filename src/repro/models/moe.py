"""Mixture-of-Experts FFN (phi3.5-moe 16e top-2; deepseek-v3 256e top-8
+ 1 shared; jamba 16e top-2).

The router follows the config's ``router``.  ``"sigmoid"`` is
DeepSeek-V3's (``noaux_tc``, :func:`route_sigmoid`): sigmoid scores in
float32, a correction bias that steers selection only, top-k within the
best groups, the chosen scores normalised and scaled.  Its layer holds a
share of the experts (``expert_offset``, ``experts_held``: one chip of
an expert-parallel deployment): it routes every token over all
``n_experts`` and computes its own experts' part of the result, with no
capacity and no drops (:func:`moe_forward_share`), and adds the shared
expert once.  On one chip the share runs without the exchange that
would carry tokens to and from the other chips.

``"softmax"`` dispatch is sort-based token-choice with static capacity
(production style — no (tokens, E, C) one-hot blowup):

  1. router top-k per token (softmax probs, renormalised);
  2. token copies sorted by expert id; position-in-expert from a
     searchsorted rank (static shapes);
  3. copies beyond capacity C = ceil(S*k/E * capacity_factor) dropped
     to a sentinel slot;
  4. expert GEMMs on the (E, C, d) buffer — sharded on the 'experts'
     logical axis (EP over the 'model' mesh axis; the token all-to-all
     emerges from the batch-sharded -> expert-sharded resharding);
  5. combine via the inverse permutation, weighted by router probs.

Aux losses: switch-style load-balance + router z-loss.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.models import common as cm
from repro.models.common import ModelConfig, param
from repro.sharding import constrain


def init_moe(key, cfg: ModelConfig):
    d, e, held = cfg.d_model, cfg.n_experts, cfg.held_experts
    ff = cfg.d_expert or cfg.d_ff
    ks = jax.random.split(key, 5)
    p = {
        "router": param(ks[0], (d, e), ("embed", "experts"), jnp.float32),
        "w_gate": param(ks[1], (held, d, ff),
                        ("experts", "expert_embed", "expert_mlp"),
                        cfg.pdtype),
        "w_up": param(ks[2], (held, d, ff),
                      ("experts", "expert_embed", "expert_mlp"),
                      cfg.pdtype),
        "w_down": param(ks[3], (held, ff, d),
                        ("experts", "expert_mlp", "expert_embed"),
                        cfg.pdtype),
    }
    if cfg.router == "sigmoid":
        # selection-only correction bias (a trained model's is learned)
        p["router_bias"] = cm.zeros_param((e,), ("experts",), jnp.float32)
    if cfg.n_shared_experts:
        shared_ff = ff * cfg.n_shared_experts
        p["shared"] = cm.init_mlp(ks[4], d, shared_ff, cfg.mlp, cfg.pdtype)
    return p


def _ep_mesh():
    from repro.sharding import rules as _r
    mesh = _r._current()[0]
    if mesh is not None and "model" in mesh.axis_names:
        return mesh
    return None


def _expert_compute_shard_map(cfg: ModelConfig, buf, params, dt):
    """Explicit EP (§Perf): shard_map over the model axis.

    Inside each shard: all_to_all moves the dispatch buffer's EXPERT dim
    onto the wire (each device keeps its token groups, receives every
    group's slots for ITS experts), local expert GEMMs run against
    weights that are resident (experts sharded over model, never
    gathered), and a second all_to_all routes results back.  The only
    cross-chip bytes are the token slots themselves — the lower bound
    for top-k routing.
    """
    from jax.sharding import PartitionSpec as P
    mesh = _ep_mesh()
    n_ep = dict(zip(mesh.axis_names, mesh.devices.shape))["model"]
    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    bspec = batch_axes if len(batch_axes) != 1 else batch_axes[0]
    wg, wu, wd = (params["w_gate"].astype(dt), params["w_up"].astype(dt),
                  params["w_down"].astype(dt))

    def body(buf, wg, wu, wd):
        # buf: (G_l, E, C, d) — groups sharded over (pod, data, model);
        # w*: (E/n_ep, ...) — this device's experts, resident.
        buf = jax.lax.all_to_all(buf, "model", split_axis=1,
                                 concat_axis=0, tiled=True)
        # -> (G_l * n_ep, E/n_ep, C, d): every model-peer's groups'
        #    slots for the experts this device owns
        g = jnp.einsum("gecd,edf->gecf", buf, wg)
        u = jnp.einsum("gecd,edf->gecf", buf, wu)
        h = jax.nn.silu(g.astype(jnp.float32)).astype(buf.dtype) * u
        out = jnp.einsum("gecf,efd->gecd", h, wd)
        return jax.lax.all_to_all(out, "model", split_axis=0,
                                  concat_axis=1, tiled=True)

    batch_tuple = batch_axes if isinstance(bspec, tuple) else \
        ((bspec,) if bspec else ())
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    full = n_ep
    for a in batch_tuple:
        full *= sizes[a]
    if buf.shape[0] % full == 0:
        gspec = (*batch_tuple, "model")   # groups over ALL axes
    else:
        gspec = bspec                     # fallback: model-replicated
    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(gspec, None, None, None),
                  P("model", None, None),
                  P("model", None, None),
                  P("model", None, None)),
        out_specs=P(gspec, None, None, None),
        check_vma=False)
    return fn(buf, wg, wu, wd)


def _capacity(cfg: ModelConfig, tokens_per_group: int) -> int:
    c = int(tokens_per_group * cfg.top_k / cfg.n_experts
            * cfg.capacity_factor)
    return max(8, -(-c // 8) * 8)


def route_sigmoid(logits, bias, *, top_k: int, n_groups: int,
                  topk_groups: int, scale: float):
    """DeepSeek-V3's router over (T, E) float32 logits: scores s =
    sigmoid(logits); b = s + bias chooses; a group's score is the sum of
    its two best b; the best ``topk_groups`` groups stay and the rest
    are masked to -inf; the top-``top_k`` b are selected and weighted by
    their s, normalised over the k and multiplied by ``scale``.
    Returns (weights (T, k) float32, expert ids (T, k) int32)."""
    t, e = logits.shape
    s = jax.nn.sigmoid(logits.astype(jnp.float32))
    b = s + bias.astype(jnp.float32)
    grouped = b.reshape(t, n_groups, e // n_groups)
    group_score = jax.lax.top_k(grouped, 2)[0].sum(-1)       # (T, G)
    _, keep = jax.lax.top_k(group_score, topk_groups)
    kept = jnp.zeros((t, n_groups), bool).at[
        jnp.arange(t)[:, None], keep].set(True)
    b = jnp.where(kept[:, :, None], grouped, -jnp.inf).reshape(t, e)
    _, idx = jax.lax.top_k(b, top_k)
    w = jnp.take_along_axis(s, idx, axis=-1)
    w = w / (w.sum(-1, keepdims=True) + 1e-20) * scale
    return w, idx.astype(jnp.int32)


def moe_forward_share(params, cfg: ModelConfig, x):
    """The sigmoid-routed layer over this chip's share of the experts.
    x: (B, S, D) -> (B, S, D), aux {"moe_held_pairs": the token-expert
    pairs routed to the held experts, "moe_routes": (B*S, k) int32, the
    experts each token was routed to}.

    Every held expert runs densely over every token, weighted by the
    token's routed weight for it (0 where it was not chosen): exact, no
    capacity, and at a few tokens an expert the same weight stream a
    ragged dispatch would read."""
    dt = x.dtype
    b, s, d = x.shape
    xt = x.reshape(b * s, d)
    with jax.named_scope("router"):
        # float32 scores, as published: a bf16 pass would move the
        # top-k choice near its ties
        logits = jnp.einsum("td,de->te", xt.astype(jnp.float32),
                            params["router"],
                            precision=jax.lax.Precision.HIGHEST)
        w, idx = route_sigmoid(logits, params["router_bias"],
                               top_k=cfg.top_k, n_groups=cfg.n_groups,
                               topk_groups=cfg.topk_groups,
                               scale=cfg.routed_scale)
        local = idx - cfg.expert_offset                     # (T, k)
        held = (local >= 0) & (local < cfg.held_experts)
        gate = jnp.einsum(
            "tk,tke->te", jnp.where(held, w, 0.0),
            jax.nn.one_hot(local, cfg.held_experts, dtype=jnp.float32))
    with jax.named_scope("experts"):
        g = jnp.einsum("td,edf->etf", xt, params["w_gate"].astype(dt))
        u = jnp.einsum("td,edf->etf", xt, params["w_up"].astype(dt))
        h = jax.nn.silu(g.astype(jnp.float32)).astype(dt) * u
        out = jnp.einsum("etf,efd->etd", h, params["w_down"].astype(dt))
        y = jnp.einsum("etd,te->td", out.astype(jnp.float32),
                       gate).astype(dt).reshape(b, s, d)
    if "shared" in params:
        with jax.named_scope("shared"):
            y = y + cm.mlp_forward(params["shared"], x, cfg.mlp)
    return y, {"moe_held_pairs": held.sum().astype(jnp.int32),
               "moe_routes": idx}


def moe_forward(params, cfg: ModelConfig, x):
    """x: (B, S, D) -> (B, S, D), aux dict.

    A ``"sigmoid"`` router takes :func:`moe_forward_share`.

    Baseline groups = batch rows (tokens sharded over (pod, data) only).
    With cfg.moe_group_size > 0 (§Perf), tokens regroup into
    (B*S/g, g, D) sharded over ALL mesh axes before dispatch, so the
    expert all-to-all moves 1/TP-degree as many bytes per device.
    """
    if cfg.router == "sigmoid":
        return moe_forward_share(params, cfg, x)
    if cfg.moe_local_dispatch:
        from repro.models.moe_local import _mesh, moe_forward_local
        if _mesh() is not None:
            return moe_forward_local(params, cfg, x)
    dt = x.dtype
    b_in, s_in, d = x.shape
    g = cfg.moe_group_size
    grouped = bool(g) and (b_in * s_in) % g == 0 and g < s_in * b_in
    if grouped:
        x = x.reshape(b_in * s_in // g, g, d)
        x = constrain(x, "tokens", None, "embed_act")
    b, s, _ = x.shape
    e, k = cfg.n_experts, cfg.top_k
    cap = _capacity(cfg, s)

    logits = jnp.einsum("bsd,de->bse", x.astype(jnp.float32),
                        params["router"])                    # (B,S,E) f32
    probs = jax.nn.softmax(logits, axis=-1)
    topw, topi = jax.lax.top_k(probs, k)                     # (B,S,k)
    topw = topw / jnp.maximum(topw.sum(-1, keepdims=True), 1e-9)

    def dispatch_group(xg, idg):
        """xg: (S, d); idg: (S, k) -> (E, C, d) buffer + gather info."""
        flat = idg.reshape(-1)                               # (S*k,)
        order = jnp.argsort(flat, stable=True)
        sorted_ids = flat[order]
        rank = jnp.arange(s * k) - jnp.searchsorted(
            sorted_ids, sorted_ids, side="left")
        slot = jnp.where(rank < cap, sorted_ids * cap + rank, e * cap)
        src = order // k
        buf = jnp.zeros((e * cap + 1, d), dt).at[slot].set(xg[src])
        return buf[:-1].reshape(e, cap, d), slot, order

    buf, slot, order = jax.vmap(dispatch_group)(x, topi)
    # EP resharding: tokens -> experts (model-sharded); XLA lowers this
    # constraint change to the MoE all-to-all.  In grouped mode the
    # group axis stays sharded over (pod, data) while experts take the
    # model axis the groups just vacated.  Expert-major mode (§Perf)
    # gives the expert dim EVERY axis it can take (pair with rules
    # experts=("model","data")): tokens travel to whole-expert owners
    # and expert weights/grads never cross chips.
    if cfg.moe_shard_map_ep and _ep_mesh() is not None:
        # §Perf: explicit EP dataflow — tokens all-to-all'd to the
        # expert owners, expert weights pinned local (never gathered).
        out_buf = _expert_compute_shard_map(cfg, buf, params, dt)
    else:
        if cfg.moe_expert_major_dispatch:
            buf = constrain(buf, None, "experts", None, "embed_act")
        else:
            buf = constrain(buf, "tokens_out" if grouped else "batch",
                            "experts", None, "embed_act")
        g = jnp.einsum("becd,edf->becf", buf,
                       params["w_gate"].astype(dt))
        u = jnp.einsum("becd,edf->becf", buf, params["w_up"].astype(dt))
        h = jax.nn.silu(g.astype(jnp.float32)).astype(dt) * u
        out_buf = jnp.einsum("becf,efd->becd", h,
                             params["w_down"].astype(dt))
        if cfg.moe_expert_major_dispatch:
            out_buf = constrain(out_buf, None, "experts", None,
                                "embed_act")
        else:
            out_buf = constrain(out_buf,
                                "tokens_out" if grouped else "batch",
                                "experts", None, "embed_act")

    def combine_group(ob, slot_g, order_g, wg):
        flat_out = jnp.concatenate(
            [ob.reshape(e * cap, d), jnp.zeros((1, d), dt)], axis=0)
        copies = flat_out[slot_g]                            # (S*k, d)
        inv = jnp.argsort(order_g, stable=True)
        per_tok = copies[inv].reshape(s, k, d)
        return jnp.einsum("skd,sk->sd", per_tok, wg.astype(dt))

    y = jax.vmap(combine_group)(out_buf, slot, order, topw)
    y = constrain(y, "tokens" if grouped else "batch", None, "embed_act")

    if "shared" in params:
        y = y + cm.mlp_forward(params["shared"], x, cfg.mlp)
    if grouped:
        y = y.reshape(b_in, s_in, d)

    # aux: load balance (switch-style, over all groups) + z-loss
    onehot = jax.nn.one_hot(topi, e, dtype=jnp.float32)      # (B,S,k,E)
    frac_tokens = onehot.mean(axis=(0, 1, 2)) * e
    mean_probs = probs.mean(axis=(0, 1)) * e
    lb_loss = jnp.mean(frac_tokens * mean_probs)
    z_loss = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
    return y, {"moe_lb_loss": lb_loss, "moe_z_loss": z_loss}
