"""Masked-``lengths`` fused Pallas kernels (the serving path) vs the
chunked-XLA streaming fallback and the unfused oracle, interpret mode
on CPU: parity over random lengths / GQA / length-0 rows / lengths not
a multiple of block_k, plus the block-skip guarantee — KV tiles wholly
past a row's valid prefix are never computed (poisoned-NaN check)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

# JAX-heavy tier: deselect with -m 'not slow' for the fast core-DSE tier
pytestmark = pytest.mark.slow

from repro.kernels import ops, ref
from repro.kernels import xla_fallback as xla
from repro.kernels.fused_attention import fused_attention_masked
from repro.kernels.fused_qproj_attention import (
    fused_qproj_attention_masked)

KEYS = jax.random.split(jax.random.PRNGKey(11), 8)


def _qkv(b, hq, hkv, sq, skv, d, dtype=jnp.float32, dv=None):
    q = jax.random.normal(KEYS[0], (b, hq, sq, d), dtype)
    k = jax.random.normal(KEYS[1], (b, hkv, skv, d), dtype)
    v = jax.random.normal(KEYS[2], (b, hkv, skv, dv or d), dtype)
    return q, k, v


MASKED_SWEEP = [
    # b, hq, hkv, sq, skv, d, causal, lengths
    (3, 4, 2, 1, 192, 32, False, [100, 192, 17]),     # GQA group 2
    (3, 4, 2, 1, 192, 32, True, [100, 192, 17]),      # causal decode
    (2, 8, 2, 1, 256, 64, True, [3, 250]),            # GQA group 4
    (3, 2, 2, 1, 192, 32, False, [0, 192, 64]),       # length-0 row
    (2, 4, 1, 1, 200, 32, True, [131, 77]),           # MQA, ragged skv
    (2, 2, 2, 4, 128, 32, True, [70, 128]),           # multi-row chunk
]


@pytest.mark.parametrize("b,hq,hkv,sq,skv,d,causal,lengths", MASKED_SWEEP)
def test_masked_fused_matches_chunked_xla(b, hq, hkv, sq, skv, d,
                                          causal, lengths):
    """Parity with xla_fallback.chunked_attention: lengths chosen NOT
    multiples of block_k (64), incl. zero and full rows."""
    q, k, v = _qkv(b, hq, hkv, sq, skv, d)
    lens = jnp.array(lengths, jnp.int32)
    o = fused_attention_masked(q, k, v, lens, causal=causal,
                               block_q=128, block_k=64, interpret=True)
    # chunked_attention's causal anchor is a scalar q_offset; the
    # masked kernel's is per-row lengths[b] - sq — identical whenever
    # causal is off or the rows are the suffix of a uniform prefix
    if causal and len(set(lengths)) > 1:
        o_ref = jnp.stack([
            xla.chunked_attention(
                q[i:i + 1], k[i:i + 1], v[i:i + 1], causal=True,
                q_offset=int(lengths[i]) - sq,
                lengths=lens[i:i + 1], block_q=128, block_k=64)[0]
            for i in range(b)])
    else:
        q_off = (int(lengths[0]) - sq) if causal else None
        o_ref = xla.chunked_attention(q, k, v, causal=causal,
                                      q_offset=q_off, lengths=lens,
                                      block_q=128, block_k=64)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref),
                               rtol=2e-5, atol=2e-5)


def test_masked_fused_random_lengths_property():
    """Randomised lengths sweep (non-causal): masked Pallas == unfused
    oracle for every draw."""
    b, hq, hkv, sq, skv, d = 4, 4, 2, 1, 160, 32
    q, k, v = _qkv(b, hq, hkv, sq, skv, d)
    for seed in range(4):
        lens = jax.random.randint(jax.random.PRNGKey(seed), (b,), 0,
                                  skv + 1).astype(jnp.int32)
        o = fused_attention_masked(q, k, v, lens, causal=False,
                                   block_q=128, block_k=64,
                                   interpret=True)
        o_ref = ref.attention_reference(q, k, v, causal=False,
                                        lengths=lens)
        np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref),
                                   rtol=2e-5, atol=2e-5, err_msg=str(lens))


def test_masked_length_zero_row_emits_zeros_everywhere():
    """A lengths[b] = 0 row emits zeros on every impl (masked Pallas,
    chunked XLA) — softmax over an empty set is defined as 0 output."""
    q, k, v = _qkv(2, 2, 2, 1, 64, 32)
    lens = jnp.array([0, 64], jnp.int32)
    o_pl = fused_attention_masked(q, k, v, lens, causal=False,
                                  block_q=128, block_k=64,
                                  interpret=True)
    o_xla = xla.chunked_attention(q, k, v, causal=False, lengths=lens,
                                  block_q=32, block_k=32)
    assert bool(jnp.all(o_pl[0] == 0.0))
    assert bool(jnp.all(o_xla[0] == 0.0))
    np.testing.assert_allclose(np.asarray(o_pl[1]), np.asarray(o_xla[1]),
                               rtol=2e-5, atol=2e-5)


def test_masked_block_skip_never_computes_past_lengths():
    """KV tiles wholly past lengths[b] are never computed: poison k
    everywhere past each row's length and poison v in the fully-past
    tiles with NaN — a kernel that touched them would emit NaN."""
    b, hq, hkv, sq, skv, d, bk = 2, 2, 2, 1, 256, 32, 64
    q, k, v = _qkv(b, hq, hkv, sq, skv, d)
    lengths = [70, 130]                      # not multiples of bk
    lens = jnp.array(lengths, jnp.int32)
    pos = jnp.arange(skv)
    k = jnp.where(pos[None, None, :, None] >= lens[:, None, None, None],
                  jnp.nan, k)
    # v: NaN only in tiles wholly past length (a partial tile's tail
    # multiplies an exact-zero p, and IEEE 0 * NaN = NaN)
    tile_start = (pos // bk) * bk
    past_tile = tile_start[None, :] >= lens[:, None]          # (B, Skv)
    v = jnp.where(past_tile[:, None, :, None], jnp.nan, v)
    o = fused_attention_masked(q, k, v, lens, causal=False,
                               block_q=128, block_k=bk, interpret=True)
    assert not bool(jnp.any(jnp.isnan(o))), \
        "NaN in output: a KV tile past lengths was computed"
    o_ref = ref.attention_reference(
        q, jnp.nan_to_num(k), jnp.nan_to_num(v), causal=False,
        lengths=lens)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref),
                               rtol=2e-5, atol=2e-5)


def test_masked_qproj_matches_oracle():
    """Fig. 5b masked variant: Q = x @ Wq fused in AND lengths masked
    in-kernel, vs the materialising oracle."""
    ks = jax.random.split(jax.random.PRNGKey(5), 4)
    b, sq, e, hq, hkv, d, skv = 3, 1, 96, 4, 2, 32, 192
    x = jax.random.normal(ks[0], (b, sq, e)) * 0.2
    wq = jax.random.normal(ks[1], (e, hq, d)) * 0.1
    k = jax.random.normal(ks[2], (b, hkv, skv, d))
    v = jax.random.normal(ks[3], (b, hkv, skv, d))
    lens = jnp.array([100, 192, 17], jnp.int32)
    o = fused_qproj_attention_masked(x, wq, k, v, lens, causal=False,
                                     block_q=128, block_k=64,
                                     interpret=True)
    o_ref = ref.qproj_attention_reference(x, wq, k, v, causal=False,
                                          lengths=lens)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref),
                               rtol=2e-5, atol=2e-5)


def test_masked_qproj_causal_uniform_lengths():
    ks = jax.random.split(jax.random.PRNGKey(6), 4)
    b, sq, e, hq, hkv, d, skv = 2, 1, 64, 2, 2, 32, 128
    x = jax.random.normal(ks[0], (b, sq, e)) * 0.2
    wq = jax.random.normal(ks[1], (e, hq, d)) * 0.1
    k = jax.random.normal(ks[2], (b, hkv, skv, d))
    v = jax.random.normal(ks[3], (b, hkv, skv, d))
    lens = jnp.full((b,), 77, jnp.int32)
    o = fused_qproj_attention_masked(x, wq, k, v, lens, causal=True,
                                     block_q=128, block_k=64,
                                     interpret=True)
    o_ref = ref.qproj_attention_reference(x, wq, k, v, causal=True,
                                          q_offset=77 - sq, lengths=lens)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref),
                               rtol=2e-5, atol=2e-5)


# ------------------------------------------------------- ops routing

def test_ops_routes_lengths_to_masked_pallas_no_downgrade():
    """impl='pallas' + lengths now *executes* the masked kernel: no
    warning, no plan downgrade — the planned path is the executed
    path (the PR-5 acceptance criterion at the ops level)."""
    import warnings as _w
    q, k, v = _qkv(2, 2, 2, 1, 128, 32)
    lens = jnp.array([50, 128], jnp.int32)
    from repro import lower
    lower.clear_plan_cache()
    p = lower.kernel_plan(seq_q=1, seq_kv=128, d_head=32, n_heads=2,
                          n_kv_heads=2)
    d = lower.dispatch(p, backend="cpu", interpret=True,
                       lengths_masked=True)
    assert d.impl == "pallas"
    with _w.catch_warnings(record=True) as w:
        _w.simplefilter("always")
        o = ops.attention(q, k, v, causal=False, lengths=lens, plan=d,
                          interpret=True)
    assert not [x for x in w if "masked-lengths" in str(x.message)]
    # the only permitted downgrade is Q-fusion legality (entry-point),
    # never masked-lengths: the planned impl is the executed impl
    assert not [g for g in p.downgrades if "masked-lengths" in g.reason]
    o_ref = ops.attention(q, k, v, causal=False, lengths=lens,
                          impl="reference")
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref),
                               rtol=2e-5, atol=2e-5)


def test_ops_inconsistent_q_offset_downgrades_not_silently_diverges():
    """The masked kernel's causal anchor is lengths - Sq; an explicit
    concrete q_offset that disagrees cannot be expressed, so the call
    must downgrade (recorded) to the chunked-XLA path that honours it
    — never return a silently different answer."""
    q, k, v = _qkv(1, 2, 2, 4, 256, 32)
    lens = jnp.array([8], jnp.int32)
    ops.reset_downgrade_warnings()
    import warnings as _w
    with _w.catch_warnings(record=True) as w:
        _w.simplefilter("always")
        o = ops.attention(q, k, v, causal=True, lengths=lens,
                          q_offset=0, impl="pallas", interpret=True)
    assert [x for x in w if "q_offset" in str(x.message)]
    o_xla = ops.attention(q, k, v, causal=True, lengths=lens,
                          q_offset=0, impl="xla")
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_xla),
                               rtol=2e-5, atol=2e-5)
    # the consistent q_offset (= lengths - Sq) stays on the Pallas path
    ops.reset_downgrade_warnings()
    with _w.catch_warnings(record=True) as w2:
        _w.simplefilter("always")
        ops.attention(q, k, v, causal=True, lengths=lens,
                      q_offset=int(lens[0]) - 4, impl="pallas",
                      interpret=True)
    assert not w2


def test_ops_causal_multirow_lengths_without_q_offset_downgrades():
    """causal + lengths + q_offset=None + Sq > 1 is anchor-ambiguous
    (masked kernel: lengths - Sq; chunked fallback: Skv - Sq): ops
    must refuse the masked kernel (recorded) so both impls agree,
    never return backend-dependent numerics."""
    q, k, v = _qkv(1, 2, 2, 4, 64, 32)
    lens = jnp.array([8], jnp.int32)
    ops.reset_downgrade_warnings()
    import warnings as _w
    with _w.catch_warnings(record=True) as w:
        _w.simplefilter("always")
        o = ops.attention(q, k, v, causal=True, lengths=lens,
                          impl="pallas", interpret=True)
    assert [x for x in w if "q_offset" in str(x.message)]
    o_xla = ops.attention(q, k, v, causal=True, lengths=lens,
                          impl="xla")
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_xla),
                               rtol=2e-5, atol=2e-5)


def test_ops_traced_lengths_concrete_q_offset_stays_masked():
    """The serve-path shape under lax tracing: lengths traced,
    q_offset concrete — the guard must trust the invariant (not
    crash concretizing a tracer) and keep the masked Pallas path."""
    q, k, v = _qkv(1, 2, 2, 1, 128, 32)

    @jax.jit
    def f(lens):
        return ops.attention(q, k, v, causal=True, lengths=lens,
                             q_offset=99, impl="pallas",
                             interpret=True)

    o = f(jnp.array([100], jnp.int32))
    o_ref = ops.attention(q, k, v, causal=True,
                          lengths=jnp.array([100], jnp.int32),
                          q_offset=99, impl="reference")
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref),
                               rtol=2e-5, atol=2e-5)


def test_ops_unsupported_lengths_dtype_downgrades_with_reason():
    """The ledger still catches what the masked kernel can't serve —
    and records the concrete reason."""
    from repro import lower
    q, k, v = _qkv(2, 2, 2, 1, 256, 32)     # ctx 256 > 2N: fused plan
    bad_lens = jnp.array([10.0, 256.0], jnp.float32)  # non-integral
    lower.clear_plan_cache()
    p = lower.kernel_plan(seq_q=1, seq_kv=256, d_head=32, n_heads=2,
                          n_kv_heads=2)
    d = lower.dispatch(p, backend="cpu", interpret=True,
                       lengths_masked=True)
    ops.reset_downgrade_warnings()
    import warnings as _w
    with _w.catch_warnings(record=True) as w_rec:
        _w.simplefilter("always")
        ops.attention(q, k, v, causal=False, lengths=bad_lens, plan=d,
                      interpret=True)
    assert [x for x in w_rec if "masked-lengths" in str(x.message)]
    assert any("integral" in g.reason for g in p.downgrades)
