"""Fully-local MoE dispatch (§Perf cell-2 follow-up, implemented).

EXPERIMENTS.md §Perf shows GSPMD replicating the scatter-built dispatch
buffer whenever its sharding must change (iterations 1/3/5).  The fix
is to build the buffer *inside* shard_map: every shard routes and
scatters its OWN tokens (local capacity), the only cross-chip traffic
is the expert all-to-all pair — the token-routing lower bound — and
the buffer never exists in a layout the partitioner must convert.

Per-shard capacity C_l = ceil(T_local * k / E * cf) is the standard
production semantics (vLLM/DeepSeek-EP): drop decisions are per-shard.
In the drop-free regime (cf large enough) the result is bit-identical
to the global moe.moe_forward — asserted by
tests/test_distributed_opts.py::test_local_dispatch_matches_global.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models import common as cm
from repro.models.common import ModelConfig
from repro.sharding import rules as shrules


def _mesh():
    mesh = shrules._current()[0]
    if mesh is not None and "model" in mesh.axis_names:
        return mesh
    return None


def moe_forward_local(params, cfg: ModelConfig, x):
    """Drop-in for moe.moe_forward when a mesh with a 'model' axis is
    active and the token count divides the device count."""
    mesh = _mesh()
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    n_dev = mesh.devices.size
    tokens = b * s
    if tokens % n_dev or e % dict(zip(mesh.axis_names,
                                      mesh.devices.shape))["model"]:
        from repro.models import moe as moe_global
        return moe_global.moe_forward(params, cfg, x)

    all_axes = tuple(mesh.axis_names)
    n_ep = dict(zip(mesh.axis_names, mesh.devices.shape))["model"]
    t_local = tokens // n_dev
    cap = max(8, -(-int(t_local * k / e * cfg.capacity_factor) // 8) * 8)
    dt = x.dtype

    def body(t_loc, router, wg, wu, wd):
        # t_loc: (T_l, d) — this shard's tokens; weights: local experts
        logits = jnp.einsum("td,de->te", t_loc.astype(jnp.float32),
                            router)
        probs = jax.nn.softmax(logits, axis=-1)
        topw, topi = jax.lax.top_k(probs, k)
        topw = topw / jnp.maximum(topw.sum(-1, keepdims=True), 1e-9)

        flat = topi.reshape(-1)
        order = jnp.argsort(flat, stable=True)
        sorted_ids = flat[order]
        rank = jnp.arange(t_local * k) - jnp.searchsorted(
            sorted_ids, sorted_ids, side="left")
        slot = jnp.where(rank < cap, sorted_ids * cap + rank, e * cap)
        src = order // k
        buf = jnp.zeros((e * cap + 1, d), dt).at[slot].set(t_loc[src])
        buf = buf[:-1].reshape(e, cap, d)

        # token-routing all-to-all: slots travel to their expert owner
        buf = jax.lax.all_to_all(buf, "model", split_axis=0,
                                 concat_axis=1, tiled=True)
        g = jnp.einsum("ecd,edf->ecf", buf, wg)
        u = jnp.einsum("ecd,edf->ecf", buf, wu)
        h = jax.nn.silu(g.astype(jnp.float32)).astype(dt) * u
        out = jnp.einsum("ecf,efd->ecd", h, wd)
        out = jax.lax.all_to_all(out, "model", split_axis=1,
                                 concat_axis=0, tiled=True)

        flat_out = jnp.concatenate(
            [out.reshape(e * cap, d), jnp.zeros((1, d), dt)], axis=0)
        copies = flat_out[slot]
        inv = jnp.argsort(order, stable=True)
        per_tok = copies[inv].reshape(t_local, k, d)
        y = jnp.einsum("tkd,tk->td", per_tok, topw.astype(dt))

        onehot = jax.nn.one_hot(topi, e, dtype=jnp.float32)
        lb = jnp.mean(onehot.mean(axis=(0, 1)) * e
                      * probs.mean(axis=0) * e)
        zl = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
        lb = jax.lax.pmean(lb, all_axes)
        zl = jax.lax.pmean(zl, all_axes)
        return y, lb, zl

    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(all_axes, None),       # tokens over every axis
                  P(None, None),           # router replicated
                  P("model", None, None),  # local experts
                  P("model", None, None),
                  P("model", None, None)),
        out_specs=(P(all_axes, None), P(), P()),
        check_vma=False)
    y, lb, zl = fn(x.reshape(tokens, d),
                   params["router"].astype(jnp.float32),
                   params["w_gate"].astype(dt),
                   params["w_up"].astype(dt),
                   params["w_down"].astype(dt))
    y = y.reshape(b, s, d)
    if "shared" in params:
        y = y + cm.mlp_forward(params["shared"], x, cfg.mlp)
    return y, {"moe_lb_loss": lb, "moe_z_loss": zl}
