"""Compile-only checks of every attention Pallas kernel ``kernels/ops.py``
dispatches to, for a described (not attached) TPU v5e, at the published
starcoder2-7b widths in bf16 (36 query heads, 4 KV heads, d_head 128,
d_model 4608) and at the serving smoke's shapes (batch 8, page 16,
max_len 1024, prefill chunk 256).

Interpret mode never checks TPU block-shape legality or VMEM limits;
the TPU compiler does, and it is installed here even without a chip.
Nothing runs, so these tests say nothing about results or speed.

The topology is described inside a module fixture, never while a
module is imported: only one process may load the TPU library, and
under several test workers an import-time description would give the
workers different test sets.
"""

import pytest

# JAX-heavy tier: deselect with -m 'not slow' for the fast core-DSE tier
pytestmark = pytest.mark.slow

import jax
import jax.numpy as jnp

from repro.kernels import fused_attention as fa
from repro.kernels import fused_decode_block as fdb
from repro.kernels import fused_qproj_attention as fqa

BF16, F32, I32 = jnp.bfloat16, jnp.float32, jnp.int32
HQ, HKV, D, E = 36, 4, 128, 4608          # starcoder2-7b widths
THETA = 1e5
B, PAGE, MAX_LEN, CHUNK = 8, 16, 1024, 256
N_PAGES = B * MAX_LEN // PAGE + 1          # + the reserved null page


@pytest.fixture(scope="module")
def topo():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _unmasked_fwd(s):
    q, k = s((1, HQ, 2048, D)), s((1, HKV, 2048, D))
    return (lambda q, k, v: fa.fused_attention(q, k, v, True, None, None,
                                               512, 512)), (q, k, k)


def _unmasked_bwd(s):
    def loss(q, k, v):
        o = fa.fused_attention(q, k, v, True, None, None, 512, 512)
        return o.astype(F32).sum()
    q, k = s((1, HQ, 2048, D)), s((1, HKV, 2048, D))
    return jax.grad(loss, argnums=(0, 1, 2)), (q, k, k)


def _qproj_fwd(s):
    x, wq, k = s((1, 512, E)), s((E, HQ, D)), s((1, HKV, 2048, D))
    return (lambda x, wq, k, v: fqa.fused_qproj_attention(
        x, wq, k, v, True, None, None, THETA, 256, 512)), (x, wq, k, k)


def _qproj_bwd(s):
    def loss(x, wq, k, v):
        o = fqa.fused_qproj_attention(x, wq, k, v, True, None, None,
                                      THETA, 256, 512)
        return o.astype(F32).sum()
    x, wq, k = s((1, 512, E)), s((E, HQ, D)), s((1, HKV, 2048, D))
    return jax.grad(loss, argnums=(0, 1, 2, 3)), (x, wq, k, k)


def _masked(sq, bq, bk):
    def build(s):
        q = s((1, HQ, sq, D))
        k = s((1, HKV, MAX_LEN, D))
        return (lambda q, k, v, n: fa.fused_attention_masked(
            q, k, v, n, block_q=bq, block_k=bk)), (q, k, k, s((1,), I32))
    return build


def _qproj_masked(bk):
    def build(s):
        x, wq = s((1, CHUNK, E)), s((E, HQ, D))
        k = s((1, HKV, MAX_LEN, D))
        return (lambda x, wq, k, v, n: fqa.fused_qproj_attention_masked(
            x, wq, k, v, n, rope_theta=THETA, block_q=256,
            block_k=bk)), (x, wq, k, k, s((1,), I32))
    return build


def _pool(s):
    return s((N_PAGES, HKV, PAGE, D))


def _tables(s):
    return s((B, MAX_LEN // PAGE), I32)


def _paged(s):
    q, pool = s((B, HQ, 1, D)), _pool(s)
    return (lambda q, k, v, n, t: fa.fused_attention_paged(
        q, k, v, n, t)), (q, pool, pool, s((B,), I32), _tables(s))


def _qproj_paged(s):
    x, wq, pool = s((B, 1, E)), s((E, HQ, D)), _pool(s)
    return (lambda x, wq, k, v, n, t: fqa.fused_qproj_attention_paged(
        x, wq, k, v, n, t, rope_theta=THETA)), \
        (x, wq, pool, pool, s((B,), I32), _tables(s))


def _decode_block(bk):
    def build(s):
        x, wq, wo = s((B, 1, E)), s((E, HQ, D)), s((HQ, D, E))
        k = s((B, HKV, MAX_LEN, D))
        return (lambda x, wq, k, v, wo, r, n: fdb.fused_decode_block(
            x, wq, k, v, wo, r, n, rope_theta=THETA, block_k=bk)), \
            (x, wq, k, k, wo, x, s((B,), I32))
    return build


def _decode_block_paged(s):
    x, wq, wo, pool = s((B, 1, E)), s((E, HQ, D)), s((HQ, D, E)), _pool(s)
    return (lambda x, wq, k, v, wo, r, n, t: fdb.fused_decode_block_paged(
        x, wq, k, v, wo, r, n, t, rope_theta=THETA)), \
        (x, wq, pool, pool, wo, x, s((B,), I32), _tables(s))


def _decode_block_paged_at(b, max_len, bk):
    """The paged megakernel at a benchmark cell's batch and depth, with
    the plan's KV block for its deepest bucket: a VMEM overrun of the
    per-KV-head Wq and Wo blocks fails here, not on the chip."""
    def build(s):
        x, wq, wo = s((b, 1, E)), s((E, HQ, D)), s((HQ, D, E))
        pool = s((b * max_len // PAGE + 1, HKV, PAGE, D))
        return (lambda x, wq, k, v, wo, r, n, t:
                fdb.fused_decode_block_paged(x, wq, k, v, wo, r, n, t,
                                             rope_theta=THETA,
                                             block_k=bk)), \
            (x, wq, pool, pool, wo, x, s((b,), I32),
             s((b, max_len // PAGE), I32))
    return build


CASES = {
    # training / cacheless forward: the lse residual's block layout
    "fused_attention_fwd": _unmasked_fwd,
    "fused_attention_bwd": _unmasked_bwd,
    "fused_qproj_attention_fwd": _qproj_fwd,
    "fused_qproj_attention_bwd": _qproj_bwd,
    # serving prefill chunks (dense side cache) and masked decode
    "fused_attention_masked_chunk": _masked(CHUNK, 256, 256),
    "fused_attention_masked_decode": _masked(1, 128, 512),
    "fused_qproj_attention_masked_512": _qproj_masked(512),
    "fused_qproj_attention_masked_1024": _qproj_masked(1024),
    # paged decode: every rung the ladder can land on
    "fused_attention_paged": _paged,
    "fused_qproj_attention_paged": _qproj_paged,
    "fused_decode_block_512": _decode_block(512),
    "fused_decode_block_1024": _decode_block(1024),
    "fused_decode_block_paged": _decode_block_paged,
    # the benchmark's starcoder2-7b decode cells: batch-decode, completion
    "fused_decode_block_paged_b32_2048": _decode_block_paged_at(32, 2048,
                                                                1024),
    "fused_decode_block_paged_b16_4096": _decode_block_paged_at(16, 4096,
                                                                1024),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(case, one_chip):
    def s(shape, dtype=BF16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    fn, args = CASES[case](s)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


#: the paged decode kernels at starcoder2-7b widths, by the name their
#: pallas_call gives them
NAMED = {"fused_decode_block_paged": _decode_block_paged,
         "fused_attention_paged": _paged}


@pytest.mark.parametrize("name", sorted(NAMED))
def test_kernel_name_reaches_the_compiled_hlo(name, one_chip):
    def s(shape, dtype=BF16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    fn, args = NAMED[name](s)
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text and name in text
