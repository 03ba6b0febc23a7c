"""The plain float32 reference of the served model, and its fp8 control:
what every architecture module shares, and the logits the check reads.

An architecture module (``bench/arch/<arch>.py``) computes the model's
layers as the repository defines them, from the sizes in a configuration
file and the weights it made, importing nothing of the program, with the
helpers here: RMSNorm, the half-split RoPE, the fp8 rounding and the
query and read-position blocks.  Its ``final_hidden`` gives the normed
final hidden state; ``readings`` applies the logits head ``lm_head``.
Every product runs in float32 at ``Precision.HIGHEST``.

``lowp=True`` is the control: the same pass with every tensor the
program holds in bf16 held in fp8 instead (e4m3: four significant bits,
three stored), rounded where the program rounds, and the logits too.
The range is left unlimited, as a per-tensor scale would keep it.  It is
the precision step below the configuration's bf16 that a later change
would be tempted by; the benchmark's runs never run it.
"""

from __future__ import annotations

import functools

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


def readings(arch, params, d, tokens, read_pos, targets, prog_logits,
             control: bool = False):
    """(gap, ctrl_gap, rel, ctrl_rel, alt_gap) arrays (n, R): see
    ``_readings``, with the hidden states of the architecture module
    ``arch``.
    The control's two are only meaningful with ``control``; without it
    the "control" is the reference itself and reads 0."""
    h_ref = arch.final_hidden(params, d, tokens, read_pos)
    h_ctrl = arch.final_hidden(params, d, tokens, read_pos, lowp=True) \
        if control else h_ref
    return _readings(control)(params["lm_head"], h_ref, targets, h_ctrl,
                              prog_logits)
