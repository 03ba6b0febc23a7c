"""The plain float32 reference of the served model, and its fp8 control.

It computes the model as the repository defines it, from the sizes in a
configuration file and the weights the benchmark made (``model.py``),
importing nothing of the program:

    x = embed[tokens]
    per layer:  h = rms(x) * pre_norm
                q, k, v = h Wq, h Wk, h Wv            (GQA: kv_heads < heads)
                q, k = rms(q) * q_norm, rms(k) * k_norm   (qk-norm configs)
                q, k = rope(q), rope(k)               (half-split rotation)
                x = x + softmax(q k^T / sqrt(head_dim), causal) v Wo
                h = rms(x) * ffn_norm
                x = x + W_down gelu_tanh(h W_up)      (or silu(h W_gate) * h W_up)
    logits = (rms(x) * final_norm) lm_head

with RMSNorm ``x / sqrt(mean(x^2) + eps)``.  Every product runs in
float32 at ``Precision.HIGHEST``; each layer's bf16 weights are cast to
float32 inside the layer's program, one layer at a time, so the
reference fits beside the weights.

``lowp=True`` is the control: the same pass with every tensor the
program holds in bf16 held in fp8 instead (e4m3: four significant bits,
three stored), rounded where the program rounds: the weights, the
embedding, each product's output, each norm's output, q and k after
RoPE, the softmax weights, the residual stream after each add, and the
logits.  The range is left unlimited, as a per-tensor scale would keep
it.  It is the precision step below the configuration's bf16 that a
later change would be tempted by; the benchmark's runs never run it.
"""

from __future__ import annotations

import functools

from harness.model import Dims

QBLOCK = 512            # query rows per attention block
VBLOCK = 128            # read positions per logits block


def _hi():
    import jax
    return jax.lax.Precision.HIGHEST


def _fp8(x):
    """Round to fp8 e4m3's precision: four significant bits."""
    import jax.numpy as jnp
    m, e = jnp.frexp(x)
    return jnp.ldexp(jnp.round(m * 16.0) / 16.0, e)


def _rms(x, w, eps):
    import jax.numpy as jnp
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, pos, theta):
    """x: (..., S, D); pos: (n, S)."""
    import jax.numpy as jnp
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None, :, None] * freq
    c, s = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * c - b * s, b * c + a * s], axis=-1)


@functools.lru_cache(maxsize=None)
def _layer(d: Dims, lowp: bool):
    import jax
    import jax.numpy as jnp
    r = _fp8 if lowp else (lambda x: x)

    def mm(eq, a, b):
        return r(jnp.einsum(eq, a, r(b), precision=_hi()))

    def layer(x, lp, i):
        w = jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(
            a, i, keepdims=False).astype(jnp.float32), lp)
        n, S, E = x.shape
        H, K, Dh = d.heads, d.kv_heads, d.head_dim
        pos = jnp.broadcast_to(jnp.arange(S), (n, S))
        h = r(_rms(x, w["pre_norm"], d.eps))
        a = w["attn"]
        q = mm("nse,ehd->nhsd", h, a["wq"])
        k = mm("nse,ehd->nhsd", h, a["wk"])
        v = mm("nse,ehd->nhsd", h, a["wv"])
        if d.qk_norm:
            q = r(_rms(q, a["q_norm"], d.eps))
            k = r(_rms(k, a["k_norm"], d.eps))
        q = r(_rope(q, pos, d.rope_theta)).reshape(n, K, H // K, S, Dh)
        k = r(_rope(k, pos, d.rope_theta))

        def block(qb_start):
            qb = jax.lax.dynamic_slice_in_dim(q, qb_start, QBLOCK, axis=3)
            s = jnp.einsum("nkgqd,nksd->nkgqs", qb, k,
                           precision=_hi()) * Dh ** -0.5
            rows = qb_start + jnp.arange(QBLOCK)[:, None]
            s = jnp.where(jnp.arange(S)[None, :] <= rows, s, -jnp.inf)
            p = r(jax.nn.softmax(s, axis=-1))
            return jnp.einsum("nkgqs,nksd->nkgqd", p, v, precision=_hi())

        o = jax.lax.map(block, jnp.arange(0, S, QBLOCK))  # (nb,n,K,G,qb,D)
        o = r(jnp.moveaxis(o, 0, 3).reshape(n, H, S, Dh))
        x = r(x + mm("nhsd,hde->nse", o, a["wo"]))
        h = r(_rms(x, w["ffn_norm"], d.eps))
        m = w["mlp"]
        up = mm("nse,ef->nsf", h, m["w_up"])
        if d.mlp == "silu_glu":
            g = mm("nse,ef->nsf", h, m["w_gate"])
            act = r(r(jax.nn.sigmoid(g) * g) * up)
        else:
            act = r(0.5 * up * (1.0 + jnp.tanh(
                (2.0 / jnp.pi) ** 0.5 * (up + 0.044715 * up ** 3))))
        return r(x + mm("nsf,fe->nse", act, m["w_down"]))

    return jax.jit(layer)


def final_hidden(params, d: Dims, tokens, read_pos, lowp: bool = False):
    """The normed final hidden state at ``read_pos`` (n, R) of each of
    ``tokens`` (n, S), S a multiple of ``QBLOCK``: (n, R, E) float32."""
    import jax.numpy as jnp
    assert tokens.shape[1] % QBLOCK == 0
    r = _fp8 if lowp else (lambda x: x)
    x = r(params["embed"][tokens].astype(jnp.float32))
    lp = params["layers"][0]
    step = _layer(d, lowp)
    for i in range(d.layers):
        x = step(x, lp, i)
    x = jnp.take_along_axis(x, read_pos[..., None], axis=1)
    return r(_rms(x, params["final_norm"].astype(jnp.float32), d.eps))


@functools.lru_cache(maxsize=None)
def _readings(lowp_ctrl: bool):
    import jax
    import jax.numpy as jnp

    def run(head, h_ref, targets, h_ctrl, prog):
        """Per read position, with ``lr`` the reference's logits:
        ``gap``, its best logit less its logit of the served token;
        ``ctrl_gap``, its best less its logit of the control's first
        choice; ``rel`` and ``ctrl_rel``, the L2 distance of the program's
        logits ``prog`` and of the control's from ``lr``, over ``|lr|``;
        ``alt_gap``, its best less its logit of the served token plus one
        (a token altered where it is produced)."""
        w = head.astype(jnp.float32)
        wc = _fp8(w) if lowp_ctrl else w

        def block(args):
            hr, t, hc, pl = args
            lr = jnp.einsum("nre,ev->nrv", hr, w, precision=_hi())
            best = lr.max(-1)
            served = jnp.take_along_axis(lr, t[..., None], -1)[..., 0]
            lc = jnp.einsum("nre,ev->nrv", hc, wc, precision=_hi())
            if lowp_ctrl:
                lc = _fp8(lc)
            c = jnp.argmax(lc, -1)
            ctrl = jnp.take_along_axis(lr, c[..., None], -1)[..., 0]
            norm = jnp.linalg.norm(lr, axis=-1)
            rel = jnp.linalg.norm(pl.astype(jnp.float32) - lr, axis=-1)
            crel = jnp.linalg.norm(lc - lr, axis=-1)
            alt = jnp.take_along_axis(lr, (t[..., None] + 1) % lr.shape[-1],
                                      -1)[..., 0]
            return (best - served, best - ctrl, rel / norm, crel / norm,
                    best - alt)

        n, R, E = h_ref.shape
        nb = R // VBLOCK
        split = lambda a: jnp.moveaxis(  # noqa: E731
            a.reshape((n, nb, VBLOCK) + a.shape[2:]), 1, 0)
        outs = jax.lax.map(block, (split(h_ref), split(targets),
                                   split(h_ctrl), split(prog)))
        join = lambda a: jnp.moveaxis(a, 0, 1).reshape(n, R)  # noqa: E731
        return tuple(join(a) for a in outs)

    return jax.jit(run)


def readings(params, d: Dims, tokens, read_pos, targets, prog_logits,
             control: bool = False):
    """(gap, ctrl_gap, rel, ctrl_rel, alt_gap) arrays (n, R): see
    ``_readings``.
    The control's two are only meaningful with ``control``; without it
    the "control" is the reference itself and reads 0."""
    h_ref = final_hidden(params, d, tokens, read_pos)
    h_ctrl = final_hidden(params, d, tokens, read_pos, lowp=True) \
        if control else h_ref
    return _readings(control)(params["lm_head"], h_ref, targets, h_ctrl,
                              prog_logits)
