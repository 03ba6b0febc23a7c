"""In-kernel RoPE and the decode megakernel, interpret mode on CPU.

The RoPE-fused kernels (``fused_qproj_attention{,_masked}`` with
``rope_theta``, ``fused_decode_block``) rotate the Q tile in-register
between projection and scores — the op that used to force Q out of the
kernel and block the Q-fused decode path.  Parity here is against the
independent ``kernels.ref`` oracle (shared-code-free RoFormer
definition): random lengths, GQA group sharing, length-0 rows, lengths
not a multiple of block_k, the megakernel's folded output projection +
residual add, and the backward counter-rotation of the differentiable
qproj kernel.  Run standalone by the `lowering` CI job.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

# JAX-heavy tier: deselect with -m 'not slow' for the fast core-DSE tier
pytestmark = pytest.mark.slow

from repro.kernels import ops, ref
from repro.kernels.fused_decode_block import fused_decode_block
from repro.kernels.fused_qproj_attention import (
    fused_qproj_attention, fused_qproj_attention_masked)

KEYS = jax.random.split(jax.random.PRNGKey(23), 8)
THETA = 1e4


def _inputs(b, hq, hkv, sq, skv, d, e, dtype=jnp.float32, dv=None):
    x = jax.random.normal(KEYS[0], (b, sq, e), dtype)
    wq = jax.random.normal(KEYS[1], (e, hq, d), dtype) / np.sqrt(e)
    k = jax.random.normal(KEYS[2], (b, hkv, skv, d), dtype)
    v = jax.random.normal(KEYS[3], (b, hkv, skv, dv or d), dtype)
    return x, wq, k, v


# ---------------------------------------------------------------------------
# unmasked qproj kernel: rope at q_offset + row
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("sq,skv,q_offset", [
    (128, 128, None),            # self-attention, offset 0
    (64, 192, None),             # suffix rows, implied offset skv - sq
    (32, 192, 100),              # explicit offset
])
def test_qproj_rope_matches_oracle(sq, skv, q_offset):
    x, wq, k, v = _inputs(2, 4, 2, sq, skv, 32, 96)
    o = fused_qproj_attention(x, wq, k, v, True, None, q_offset, THETA,
                              64, 64, True)
    o_ref = ref.qproj_attention_reference(x, wq, k, v, causal=True,
                                          q_offset=q_offset,
                                          rope_theta=THETA)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref),
                               rtol=2e-5, atol=2e-5)


def test_qproj_rope_differs_from_unrotated():
    """The rotation actually happens (guards against a silently ignored
    rope_theta)."""
    x, wq, k, v = _inputs(1, 2, 2, 64, 64, 32, 64)
    o = fused_qproj_attention(x, wq, k, v, True, None, None, THETA,
                              64, 64, True)
    o_plain = fused_qproj_attention(x, wq, k, v, True, None, None, None,
                                    64, 64, True)
    assert float(jnp.abs(o - o_plain).max()) > 1e-3


def test_qproj_rope_backward_counter_rotates():
    """Gradients of the RoPE-fused kernel match autodiff through the
    oracle: the backward pass recomputes the rotated Q tile and
    counter-rotates dQ before the dx/dWq matmuls."""
    x, wq, k, v = _inputs(1, 2, 2, 64, 96, 32, 64)

    def f_kernel(x, wq, k, v):
        return (fused_qproj_attention(x, wq, k, v, True, None, None,
                                      THETA, 64, 64, True) ** 2).sum()

    def f_ref(x, wq, k, v):
        return (ref.qproj_attention_reference(
            x, wq, k, v, causal=True, rope_theta=THETA) ** 2).sum()

    g = jax.grad(f_kernel, argnums=(0, 1, 2, 3))(x, wq, k, v)
    g_ref = jax.grad(f_ref, argnums=(0, 1, 2, 3))(x, wq, k, v)
    for a, b in zip(g, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-4)


# ---------------------------------------------------------------------------
# masked qproj kernel: rope anchored at the end of each valid prefix
# ---------------------------------------------------------------------------

MASKED_SWEEP = [
    # b, hq, hkv, sq, skv, d, lengths
    (3, 4, 2, 1, 192, 32, [100, 192, 17]),    # GQA group 2, random lens
    (2, 8, 2, 1, 256, 64, [3, 250]),          # GQA group 4
    (3, 2, 2, 1, 192, 32, [0, 192, 64]),      # length-0 row
    (2, 4, 1, 1, 200, 32, [131, 77]),         # MQA, skv not block-mult
    (2, 2, 2, 4, 128, 32, [70, 128]),         # multi-row chunk
]


@pytest.mark.parametrize("b,hq,hkv,sq,skv,d,lengths", MASKED_SWEEP)
def test_masked_qproj_rope_matches_oracle(b, hq, hkv, sq, skv, d,
                                          lengths):
    """Row r of batch row b rotates at position lengths[b] - sq + r —
    the masked kernels' end-anchored convention."""
    x, wq, k, v = _inputs(b, hq, hkv, sq, skv, d, 64)
    lens = jnp.array(lengths, jnp.int32)
    o = fused_qproj_attention_masked(x, wq, k, v, lens, causal=True,
                                     rope_theta=THETA, block_q=128,
                                     block_k=64, interpret=True)
    q = jnp.einsum("bse,ehd->bhsd", x, wq)
    q = ref.rope(q, ref.rope_positions(sq, skv, lengths=lens), THETA)
    o_ref = ref.attention_reference(
        q, k, v, causal=False, lengths=lens) if sq == 1 else jnp.stack([
            ref.attention_reference(
                q[i:i + 1], k[i:i + 1], v[i:i + 1], causal=True,
                q_offset=int(lengths[i]) - sq,
                lengths=lens[i:i + 1])[0] for i in range(b)])
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# decode megakernel
# ---------------------------------------------------------------------------

MEGA_SWEEP = [
    # b, hq, hkv, skv, d, e, lengths, theta, q_norm
    (3, 4, 2, 192, 32, 64, [100, 192, 17], THETA, False),  # GQA
    (2, 8, 2, 256, 64, 128, [3, 250], THETA, False),       # group 4
    (3, 2, 2, 192, 32, 64, [0, 192, 64], THETA, False),    # length-0 row
    (2, 4, 1, 200, 32, 64, [131, 77], THETA, False),       # MQA, ragged
    (2, 4, 2, 128, 32, 64, [70, 128], None, False),        # no rope
    # qk-norm (qwen3): group 4 at D 128, a length-0 row, a row one past
    # the 64-token block edge
    (3, 8, 2, 192, 128, 128, [0, 65, 150], THETA, True),
]


def _mega_id(i, case):
    """``lengths<i>`` as pytest would name it, ``-q_norm`` on the
    qk-norm cases."""
    head = "-".join(map(str, case[:6]))
    return f"{head}-lengths{i}-{case[7]}" + ("-q_norm" if case[8] else "")


def _q_norm_weight(d, on: bool):
    """A (D,) qk-norm weight away from 1, or None."""
    if not on:
        return None
    return 1.0 + 0.3 * jax.random.normal(KEYS[6], (d,))


@pytest.mark.parametrize(
    "b,hq,hkv,skv,d,e,lengths,theta,q_norm", MEGA_SWEEP,
    ids=[_mega_id(i, c) for i, c in enumerate(MEGA_SWEEP)])
def test_decode_megakernel_matches_oracle(b, hq, hkv, skv, d, e,
                                          lengths, theta, q_norm):
    """One launch == projection + qk-norm (when given) + RoPE(lengths-1)
    + masked attention + output projection + residual, to fp32
    tolerance."""
    x, wq, k, v = _inputs(b, hq, hkv, 1, skv, d, e)
    wo = jax.random.normal(KEYS[4], (hq, d, e)) / np.sqrt(hq * d)
    res = jax.random.normal(KEYS[5], (b, 1, e))
    lens = jnp.array(lengths, jnp.int32)
    w = _q_norm_weight(d, q_norm)
    o = fused_decode_block(x, wq, k, v, wo, res, lens, rope_theta=theta,
                           q_norm=w, block_k=64, interpret=True)
    o_ref = ref.decode_block_reference(x, wq, k, v, wo, res, lens,
                                       rope_theta=theta, q_norm=w)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref),
                               rtol=2e-5, atol=2e-5)


def test_decode_megakernel_length0_emits_residual():
    """A row with no valid KV contributes zero attention: its output is
    exactly the residual passed in."""
    x, wq, k, v = _inputs(2, 2, 2, 1, 64, 32, 64)
    wo = jax.random.normal(KEYS[4], (2, 32, 64)) / 8.0
    res = jax.random.normal(KEYS[5], (2, 1, 64))
    lens = jnp.array([0, 64], jnp.int32)
    o = fused_decode_block(x, wq, k, v, wo, res, lens,
                           rope_theta=THETA, block_k=64, interpret=True)
    np.testing.assert_allclose(np.asarray(o[0]), np.asarray(res[0]),
                               rtol=1e-6, atol=1e-6)


def test_ops_decode_block_impls_agree():
    """ops.decode_block: pallas (interpret) / xla / reference compose
    the same math, with and without a qk-norm weight — and the norm
    does change the answer."""
    x, wq, k, v = _inputs(2, 4, 2, 1, 192, 32, 64)
    wo = jax.random.normal(KEYS[4], (4, 32, 64)) / np.sqrt(4 * 32)
    res = jax.random.normal(KEYS[5], (2, 1, 64))
    lens = jnp.array([100, 192], jnp.int32)
    refs = []
    for w in (None, _q_norm_weight(32, True)):
        outs = {impl: ops.decode_block(
            x, wq, k, v, wo, res, lens, rope_theta=THETA, q_norm=w,
            impl=impl, interpret=(impl == "pallas"))
            for impl in ("pallas", "xla", "reference")}
        for impl in ("pallas", "xla"):
            np.testing.assert_allclose(np.asarray(outs[impl]),
                                       np.asarray(outs["reference"]),
                                       rtol=2e-5, atol=2e-5)
        refs.append(np.asarray(outs["reference"]))
    assert np.abs(refs[0] - refs[1]).max() > 1e-2


def test_ops_qproj_rope_fallbacks_agree():
    """ops.qproj_attention(rope_theta=...) applies the same rotation on
    every impl (in-kernel on pallas, on materialised Q in fallbacks)."""
    x, wq, k, v = _inputs(2, 4, 2, 1, 192, 32, 64)
    lens = jnp.array([100, 192], jnp.int32)
    outs = {impl: ops.qproj_attention(
        x, wq, k, v, causal=True, lengths=lens, rope_theta=THETA,
        impl=impl, interpret=(impl == "pallas"))
        for impl in ("pallas", "xla", "reference")}
    for impl in ("pallas", "xla"):
        np.testing.assert_allclose(np.asarray(outs[impl]),
                                   np.asarray(outs["reference"]),
                                   rtol=2e-5, atol=2e-5)
