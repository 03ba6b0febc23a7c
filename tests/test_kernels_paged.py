"""Paged-KV kernels and serving: the paged decode megakernel vs its
gather-dense oracle, the paged ``ops.attention`` / ``ops.qproj_attention``
calls (a gather, then the masked kernels) vs theirs (random
non-contiguous tables, length-0 rows, GQA, identity-table equivalence
with the masked kernel), zero-downgrade dispatch through kernels.ops, a
rung-down from the megakernel, the PageAllocator's free-list accounting,
and preempt -> resume bit-identity on the continuous-batching engine."""

import dataclasses
import warnings

import pytest

# JAX-heavy tier: deselect with -m 'not slow' for the fast core-DSE tier
pytestmark = pytest.mark.slow

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.kernels import ops, ref
from repro.kernels.fused_attention import fused_attention_masked
from repro.kernels.fused_decode_block import fused_decode_block_paged
from repro.models import init_params_and_axes
from repro.serve import (ContinuousBatchingEngine, OutOfPages,
                         PageAllocator, PagedContinuousBatchingEngine,
                         Request, RequestBatcher)
from repro.serve.engine import gather_slot_pages

KEYS = jax.random.split(jax.random.PRNGKey(23), 8)


def _pools(b, hkv, n_pages, page, d, max_pages, seed=0, shuffle=True):
    """Random pools + per-row tables over *non-contiguous* pages: the
    rows' page lists interleave across the pool (round-robin striped,
    then shuffled), never the contiguous layout a dense cache has."""
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    k_pool = jax.random.normal(k1, (n_pages, hkv, page, d), jnp.float32)
    v_pool = jax.random.normal(k2, (n_pages, hkv, page, d), jnp.float32)
    ids = np.arange(1, n_pages)            # page 0 = null, never mapped
    if shuffle:
        np.random.default_rng(seed).shuffle(ids)
    assert b * max_pages <= len(ids)
    tbl = ids[:b * max_pages].reshape(b, max_pages).astype(np.int32)
    return k_pool, v_pool, jnp.asarray(tbl)


PAGED_SWEEP = [
    # b, hq, hkv, sq, page, max_pages, d, causal, lengths
    (3, 4, 2, 1, 16, 6, 32, False, [37, 0, 96]),     # GQA + length-0
    (3, 4, 2, 1, 16, 6, 32, True, [37, 0, 96]),      # causal decode
    (2, 8, 2, 1, 8, 8, 64, True, [3, 61]),           # small pages
    (2, 4, 1, 1, 32, 4, 32, True, [100, 128]),       # MQA, full row
    (2, 2, 2, 4, 16, 8, 32, False, [70, 128]),       # multi-row chunk
]


@pytest.mark.parametrize("b,hq,hkv,sq,page,max_pages,d,causal,lengths",
                         PAGED_SWEEP)
def test_paged_attention_matches_gather_oracle(b, hq, hkv, sq, page,
                                               max_pages, d, causal,
                                               lengths):
    """Paged ops.attention on the Pallas impl (the pools gathered
    dense, then the masked kernel) == the unfused oracle over the
    gathered pools, on shuffled non-contiguous tables (lengths not page
    multiples)."""
    n_pages = b * max_pages + 1
    kp, vp, tbl = _pools(b, hkv, n_pages, page, d, max_pages)
    q = jax.random.normal(KEYS[0], (b, hq, sq, d), jnp.float32)
    lens = jnp.asarray(lengths, jnp.int32)
    kw = {}
    if causal and sq > 1:
        kw = {"q_offset": int(lengths[0]) - sq}   # multi-row contract
        lens = jnp.full((b,), lengths[0], jnp.int32)
    o = ops.attention(q, kp, vp, causal=causal, lengths=lens,
                      block_tables=tbl, impl="pallas", interpret=True,
                      **kw)
    o_ref = ref.attention_reference(
        q, ref.gather_pages(kp, tbl), ref.gather_pages(vp, tbl),
        causal=causal, lengths=lens, **kw)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref),
                               rtol=2e-5, atol=2e-5)


def test_paged_identity_table_equals_masked_dense():
    """With the identity table (row b's pages laid out contiguously),
    the paged ops.attention call reproduces the dense masked kernel
    bit-for-bit on the same logical KV — the table only changes *where*
    blocks live."""
    b, hq, hkv, page, max_pages, d = 2, 4, 2, 16, 4, 32
    skv = max_pages * page
    q = jax.random.normal(KEYS[1], (b, hq, 1, d), jnp.float32)
    k = jax.random.normal(KEYS[2], (b, hkv, skv, d), jnp.float32)
    v = jax.random.normal(KEYS[3], (b, hkv, skv, d), jnp.float32)
    lens = jnp.asarray([45, 60], jnp.int32)
    # dense rows cut into pages: pool page b*max_pages+j holds row b's
    # j-th logical block
    pool_of = lambda x: jnp.moveaxis(
        x.reshape(b, hkv, max_pages, page, d), 2, 1).reshape(
            b * max_pages, hkv, page, d)
    tbl = jnp.arange(b * max_pages, dtype=jnp.int32).reshape(b, max_pages)
    o_paged = ops.attention(q, pool_of(k), pool_of(v), causal=True,
                            lengths=lens, block_tables=tbl,
                            impl="pallas", block_q=128, block_k=page,
                            interpret=True)
    o_dense = fused_attention_masked(q, k, v, lens, causal=True,
                                     block_q=128, block_k=page,
                                     interpret=True)
    np.testing.assert_array_equal(np.asarray(o_paged),
                                  np.asarray(o_dense))


def test_paged_qproj_and_decode_block_match_oracles():
    """Paged ops.qproj_attention (gathered, then the masked fused-Q
    kernel) and the paged megakernel (in-kernel RoPE at each row's end
    anchor) == their gather-dense oracles."""
    b, hq, hkv, page, max_pages, d, e = 3, 4, 2, 16, 6, 32, 64
    n_pages = b * max_pages + 1
    kp, vp, tbl = _pools(b, hkv, n_pages, page, d, max_pages, seed=5)
    lens = jnp.asarray([37, 1, 96], jnp.int32)
    x = jax.random.normal(KEYS[4], (b, 1, e), jnp.float32)
    wq = jax.random.normal(KEYS[5], (e, hq, d), jnp.float32) * 0.1
    wo = jax.random.normal(KEYS[6], (hq, d, e), jnp.float32) * 0.1
    res = jax.random.normal(KEYS[7], (b, 1, e), jnp.float32)
    o = ops.qproj_attention(x, wq, kp, vp, causal=True, lengths=lens,
                            block_tables=tbl, rope_theta=1e4,
                            impl="pallas", interpret=True)
    o_ref = ref.qproj_attention_reference(
        x, wq, ref.gather_pages(kp, tbl), ref.gather_pages(vp, tbl),
        causal=False, lengths=lens, rope_theta=1e4)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref),
                               rtol=2e-5, atol=2e-5)
    y = fused_decode_block_paged(x, wq, kp, vp, wo, res, lens, tbl,
                                 rope_theta=1e4, interpret=True)
    y_ref = ref.paged_decode_block_reference(x, wq, kp, vp, wo, res,
                                             lens, tbl, rope_theta=1e4)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=2e-5, atol=2e-5)


MEGAKERNEL_SWEEP = [
    # dtype, hq, hkv, page, max_pages, block_k, lengths, d, q_norm
    # group 1, 3 pages a block over 7 (not a multiple): 0, 1, page - 1,
    # page, a block edge - 1 / at / + 1, the full table
    (jnp.float32, 2, 2, 8, 7, 24, [0, 1, 7, 8, 23, 24, 25, 56], 32, False),
    (jnp.bfloat16, 2, 2, 8, 7, 24, [0, 1, 7, 8, 23, 24, 25, 56], 32, False),
    # group 4, 2 pages a block over 5; two length-0 rows in a row break
    # the prefetch chain
    (jnp.float32, 8, 2, 16, 5, 32, [31, 0, 0, 33, 80, 16, 1, 32], 32, False),
    (jnp.bfloat16, 8, 2, 16, 5, 32, [31, 0, 0, 33, 80, 16, 1, 32], 32,
     False),
    # group 9 (starcoder2's 36/4) padded to 16 sublanes; a KV block
    # longer than the table is clamped to it
    (jnp.float32, 9, 1, 8, 6, 32, [48, 31, 33, 0, 9, 15], 32, False),
    (jnp.bfloat16, 18, 2, 16, 4, 1024, [64, 0, 17, 16, 63], 32, False),
    # qk-norm (qwen3's 32/8): group 4 at D 128, a length-0 row, rows one
    # past a block edge and ending on one
    (jnp.float32, 8, 2, 16, 5, 32, [33, 0, 80, 1, 64], 128, True),
    (jnp.bfloat16, 8, 2, 16, 5, 32, [33, 0, 80, 1, 64], 128, True),
]


def _megakernel_id(i, case):
    """The id pytest gives a case without ``d`` and ``q_norm``, and
    ``-q_norm`` on the qk-norm cases."""
    head = "-".join([jnp.dtype(case[0]).name, *map(str, case[1:6])])
    return f"{head}-lengths{i}" + ("-q_norm" if case[8] else "")


@pytest.mark.parametrize(
    "dtype,hq,hkv,page,max_pages,block_k,lengths,d,q_norm",
    MEGAKERNEL_SWEEP,
    ids=[_megakernel_id(i, c) for i, c in enumerate(MEGAKERNEL_SWEEP)])
def test_paged_decode_block_retiled_matches_oracle(dtype, hq, hkv, page,
                                                   max_pages, block_k,
                                                   lengths, d, q_norm):
    """The paged megakernel's (KV head, row, block of pages) grid ==
    the gather-dense oracle: scattered, non-monotone page ids, page 0
    in every slot past a row's length, rows of length 0 emitting the
    residual; with a qk-norm weight, each query head normalised before
    RoPE."""
    b, e = len(lengths), 64
    n_pages = b * max_pages + 1
    kp, vp, tbl = _pools(b, hkv, n_pages, page, d, max_pages, seed=hq)
    live = np.arange(max_pages)[None, :] < -(
        -np.asarray(lengths)[:, None] // page)
    tbl = jnp.asarray(np.where(live, np.asarray(tbl), 0), jnp.int32)
    lens = jnp.asarray(lengths, jnp.int32)
    ks = jax.random.split(jax.random.PRNGKey(hq * 100 + max_pages), 4)
    x = jax.random.normal(ks[0], (b, 1, e), jnp.float32)
    wq = jax.random.normal(ks[1], (e, hq, d), jnp.float32) * 0.2
    wo = jax.random.normal(ks[2], (hq, d, e), jnp.float32) * 0.2
    res = jax.random.normal(ks[3], (b, 1, e), jnp.float32)
    args = [a.astype(dtype) for a in (x, wq, kp, vp, wo, res)]
    w = (1.0 + 0.3 * jax.random.normal(KEYS[0], (d,))).astype(dtype) \
        if q_norm else None
    y = fused_decode_block_paged(*args[:5], args[5], lens, tbl,
                                 rope_theta=1e4, q_norm=w,
                                 block_k=block_k, interpret=True)
    assert y.dtype == dtype and y.shape == (b, 1, e)
    y_ref = ref.paged_decode_block_reference(
        *[a.astype(jnp.float32) for a in args[:6]], lens, tbl,
        rope_theta=1e4, q_norm=w)
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(y_ref), rtol=tol, atol=tol)
    dead = np.asarray(lengths) == 0
    np.testing.assert_array_equal(np.asarray(y)[dead],
                                  np.asarray(args[5])[dead])


def test_paged_dispatch_zero_downgrades_and_per_reason_warn_once():
    """ops.decode_block with block_tables stays on the paged megakernel
    (no downgrade warning); a call it *cannot* serve warns exactly once
    per distinct reason — the per-reason warn-once contract."""
    b, hq, hkv, page, max_pages, d, e = 2, 4, 2, 16, 4, 32, 64
    n_pages = b * max_pages + 1
    kp, vp, tbl = _pools(b, hkv, n_pages, page, d, max_pages, seed=9)
    x = jax.random.normal(KEYS[0], (b, 1, e), jnp.float32)
    wq = jax.random.normal(KEYS[1], (e, hq, d), jnp.float32) * 0.1
    wo = jax.random.normal(KEYS[2], (hq, d, e), jnp.float32) * 0.1
    lens = jnp.asarray([10, 50], jnp.int32)

    def call(kp, vp, lens, tbl):
        return ops.decode_block(x, wq, kp, vp, wo, x, lens,
                                block_tables=tbl, rope_theta=1e4,
                                impl="pallas", interpret=True)

    with warnings.catch_warnings():
        warnings.simplefilter("error")
        y = call(kp, vp, lens, tbl)
    np.testing.assert_allclose(
        np.asarray(y),
        np.asarray(ref.paged_decode_block_reference(
            x, wq, kp, vp, wo, x, lens, tbl, rope_theta=1e4)),
        rtol=2e-5, atol=2e-5)
    # a float table is refused -> one warning; repeating it is silent;
    # a *different* reason (misaligned page size) warns again
    bad_dtype = tbl.astype(jnp.float32)
    kp12, vp12, tbl12 = _pools(b, hkv, n_pages, 24, d, max_pages,
                               seed=9)
    kp12 = kp12[:, :, :12]                    # page = 12: not 8-aligned
    vp12 = vp12[:, :, :12]
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        call(kp, vp, lens, bad_dtype)
        call(kp, vp, lens, bad_dtype)
        call(kp12, vp12, jnp.minimum(lens, 12 * max_pages), tbl12)
    msgs = [str(x.message) for x in w]
    assert len(msgs) == 2, msgs
    assert all("paged-KV" in m for m in msgs)
    assert "masked-lengths" not in "".join(msgs)


def test_rung_down_from_paged_megakernel_matches_gather_oracle():
    """The supervisor's first rung-down from a paged megakernel
    dispatch lands on qproj_attention: through gqa_forward the pools
    are gathered dense and the masked fused-Q kernel runs.  Its output
    and cache equal the reference dispatch's, which gathers with the
    oracle, and the only ledger entry is the rung-down's own."""
    from repro import lower
    from repro.models.attention import gqa_forward, init_gqa
    from repro.models.common import ModelConfig, split_params
    cfg = ModelConfig(name="paged-rung-down", n_layers=1, d_model=128,
                      n_heads=4, n_kv_heads=2, d_ff=256, vocab_size=64,
                      param_dtype="float32", compute_dtype="float32")
    params = split_params(init_gqa(KEYS[3], cfg))[0]
    b, page, max_pages = 3, 16, 16            # 256 columns: megakernel
    kp, vp, tbl = _pools(b, cfg.kv_heads, b * max_pages + 1, page,
                         cfg.head_dim, max_pages, seed=3)
    cache_len = jnp.asarray([37, 0, 200], jnp.int32)
    x = jax.random.normal(KEYS[4], (b, 1, cfg.d_model), jnp.float32)
    plan = lower.lower(cfg, "decode", max_pages * page)
    d = lower.dispatch(plan, backend="cpu", interpret=True,
                       entry="decode_block", rope=True,
                       lengths_masked=True, paged=True)
    assert (d.path, d.impl) == (lower.DECODE_MEGAKERNEL, "pallas")
    ledger = len(plan.downgrades)
    down = lower.rung_down(d, "test")
    assert (down.path, down.impl) == (lower.QPROJ_ATTENTION, "pallas")

    def run(disp):
        return gqa_forward(params, cfg, x, cache_len[:, None],
                           cache={"k": kp, "v": vp}, cache_len=cache_len,
                           block_tables=tbl, plan=disp, residual=x)

    with warnings.catch_warnings():
        warnings.simplefilter("error")
        out, cache = run(down)
    oracle = dataclasses.replace(down, path=lower.UNFUSED,
                                 impl="reference")
    out_ref, cache_ref = run(oracle)
    np.testing.assert_allclose(np.asarray(out), np.asarray(out_ref),
                               rtol=2e-5, atol=2e-5)
    for got, want in zip(jax.tree.leaves(cache),
                         jax.tree.leaves(cache_ref)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert [g.reason for g in plan.downgrades[ledger:]] == [
        "test: rung-down decode_megakernel/pallas -> "
        "qproj_attention/pallas"]


# ---------------------------------------------------------------------------
# allocator + engine lifecycle
# ---------------------------------------------------------------------------

def test_page_allocator_accounting():
    """Free-list invariants: page 0 reserved, all-or-nothing alloc,
    release returns every page, peak_used survives release."""
    a = PageAllocator(num_pages=8, page_size=16)
    assert a.num_free == 7 and a.used_pages == 0
    ids = a.alloc("r0", 3)
    assert 0 not in ids and len(set(ids)) == 3
    assert a.used_pages == 3 and a.peak_used == 3
    assert a.ensure("r0", 3 * 16) == []            # already covered
    grown = a.ensure("r0", 3 * 16 + 1)             # crosses a boundary
    assert len(grown) == 1 and a.pages["r0"] == ids + grown
    a.alloc("r1", 3)
    with pytest.raises(OutOfPages):
        a.alloc("r2", 1)                           # 7 - 4 - 3 = 0 free
    assert a.used_pages == 7                       # failed alloc took none
    assert a.release("r0") == ids + grown
    assert a.used_pages == 3 and a.num_free == 4
    assert a.peak_used == 7                        # high-water survives
    assert a.release("missing") == []


def test_page_allocator_release_idempotent_with_note():
    """Double release is a no-op that leaves a breadcrumb: the second
    call returns [] without disturbing the free list, and the smell is
    recorded on ``notes`` for the auditor/ledger to surface."""
    a = PageAllocator(num_pages=8, page_size=16)
    ids = a.alloc("r0", 3)
    assert a.release("r0") == ids and a.notes == []
    free_before = list(a._free)
    assert a.release("r0") == []                   # idempotent no-op
    assert a._free == free_before and a.used_pages == 0
    assert len(a.notes) == 1 and "r0" in a.notes[0]
    a.release("never-leased")
    assert len(a.notes) == 2 and "never-leased" in a.notes[1]


@pytest.fixture(scope="module")
def qwen():
    cfg = configs.get_config("qwen3-8b", smoke=True)
    params, _ = init_params_and_axes(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _prompt(cfg, key, n):
    return [int(x) for x in np.asarray(jax.random.randint(
        jax.random.PRNGKey(key), (n,), 0, cfg.vocab_size))]


def test_preempt_resume_bit_identical(qwen):
    """preempt -> resume round-trips the KV bits exactly (the snapshot
    scatters into *different* pages) and the continuation emits the
    same tokens as an uninterrupted run."""
    cfg, params = qwen

    def make():
        eng = PagedContinuousBatchingEngine(
            params, cfg, batch_size=2, max_len=48, page_size=8,
            num_pages=16)
        eng.begin_prefill(0, _prompt(cfg, 40, 9))
        toks = []
        for _ in range(4):
            tokens, inserted = eng.step()
            toks += [first for _, first in inserted]
            if tokens is not None:
                toks.append(int(tokens[0]))
        return eng, toks

    eng, toks = make()
    before = jax.device_get(
        gather_slot_pages(eng.state, eng.allocator.pages[0]))
    pre = eng.preempt(0)
    assert eng.allocator.used_pages == 0 and not eng.live[0]
    eng.resume(pre, 0)
    after = jax.device_get(
        gather_slot_pages(eng.state, eng.allocator.pages[0]))
    for a, b in zip(jax.tree.leaves(before), jax.tree.leaves(after)):
        np.testing.assert_array_equal(a, b)        # bit-identical KV
    for _ in range(3):
        tokens, _ = eng.step()
        toks.append(int(tokens[0]))

    eng2, toks2 = make()                            # uninterrupted
    for _ in range(3):
        tokens, _ = eng2.step()
        toks2.append(int(tokens[0]))
    assert toks == toks2


def test_insert_writes_each_lease_to_its_table_row(qwen):
    """Admissions of prompts that take different page counts each write
    their lease to their block-table row, zero-padded, and the
    allocator counts every leased page."""
    cfg, params = qwen
    eng = PagedContinuousBatchingEngine(
        params, cfg, batch_size=2, max_len=48, page_size=8, num_pages=16)
    eng.begin_prefill(0, _prompt(cfg, 41, 5))
    eng.step()
    eng.begin_prefill(1, _prompt(cfg, 42, 30))
    eng.step()
    for slot in (0, 1):
        ids = eng.allocator.pages[slot]
        row = np.asarray(eng.state.block_tables[slot])
        assert row[:len(ids)].tolist() == ids and not row[len(ids):].any()
    assert eng.allocator.used_pages == sum(
        len(v) for v in eng.allocator.pages.values())


@pytest.mark.parametrize("n, bucket", [
    (1, 1), (2, 2), (3, 3), (5, 6), (7, 8), (9, 12), (13, 16)])
def test_insert_pads_to_a_bucket_and_writes_only_its_pages(qwen, n, bucket):
    """An insert cuts its chunks for a bucket of pages (a few shapes for
    every prompt length: its eager ops compile once a bucket) and writes
    the lease's pages alone: the padding's chunks are dropped, every
    other page keeps its bits."""
    from repro.serve.engine import (PrefillResult, _bucket,
                                    init_decode_state, insert_paged)
    assert _bucket(n) == bucket and _bucket(700) == 768
    cfg, params = qwen
    eng = PagedContinuousBatchingEngine(
        params, cfg, batch_size=1, max_len=128, page_size=8, num_pages=24)
    side = init_decode_state(cfg, 1, 128, jnp.float32).cache
    keys = iter(jax.random.split(jax.random.PRNGKey(n), 64))
    side = jax.tree.map(lambda a: jax.random.normal(next(keys), a.shape,
                                                    a.dtype), side)
    pool0 = jax.tree.map(lambda a: a + 1.0, eng.state.cache)
    state = eng.state.__class__(cache=pool0, cache_len=eng.state.cache_len,
                                last_token=eng.state.last_token,
                                block_tables=eng.state.block_tables)
    ids = [int(i) for i in np.random.default_rng(n).permutation(
        np.arange(1, 24))[:n]]
    res = PrefillResult(cache=side, length=jnp.asarray(n * 8, jnp.int32),
                        next_token=jnp.asarray(0, jnp.int32))
    new = insert_paged(state, res, 0, ids)
    assert np.asarray(new.block_tables[0]).tolist() == ids + [0] * (16 - n)
    rest = [i for i in range(24) if i not in ids]
    for old, got, dense in zip(jax.tree.leaves(pool0),
                               jax.tree.leaves(new.cache),
                               jax.tree.leaves(side)):
        old, got, dense = (np.asarray(a) for a in (old, got, dense))
        if old.ndim == 4:                       # one unscanned layer
            old, got, dense = old[None], got[None], dense[None]
        for o, g, d in zip(old, got, dense):    # each scanned layer
            h, _, dh = d.shape[1:]
            chunks = d[0, :, :n * 8].reshape(h, n, 8, dh).transpose(
                1, 0, 2, 3)
            np.testing.assert_array_equal(g[ids], chunks)
            np.testing.assert_array_equal(g[rest], o[rest])


def test_evict_vs_preempt_page_accounting(qwen):
    """Both verbs return every page to the pool; only preempt carries
    a snapshot forward.  Slot reuse after either is clean."""
    cfg, params = qwen
    eng = PagedContinuousBatchingEngine(
        params, cfg, batch_size=2, max_len=48, page_size=8,
        num_pages=12)
    eng.begin_prefill(0, _prompt(cfg, 41, 10))
    eng.begin_prefill(1, _prompt(cfg, 42, 17))
    while not all(eng.live):
        eng.step()
    held = {i: len(eng.allocator.pages[i]) for i in (0, 1)}
    assert held == {0: 2, 1: 3}
    free0 = eng.allocator.num_free
    pre = eng.preempt(0)
    assert pre.n_pages == 2 and pre.length == 10 + 1
    assert eng.allocator.num_free == free0 + 2
    assert 0 not in eng.allocator.pages
    eng.evict(1)
    assert eng.allocator.num_free == 11             # everything back
    assert not any(eng.live)
    eng.resume(pre, 1)                              # a different slot
    assert eng.live[1] and eng.row_ctx[1] == pre.length
    tokens, _ = eng.step()
    assert int(eng.state.cache_len[1]) == pre.length + 1


def test_fifo_readmission_under_page_pressure(qwen):
    """A tight pool forces the batcher to preempt the newest lease;
    the preempted request re-enters at the queue FRONT (before
    later-submitted requests) and every request still matches its
    dense-engine token chain."""
    cfg, params = qwen

    def run(paged):
        if paged:
            eng = PagedContinuousBatchingEngine(
                params, cfg, batch_size=2, max_len=48, page_size=8,
                num_pages=4)                        # 3 usable pages
        else:
            eng = ContinuousBatchingEngine(params, cfg, batch_size=2,
                                           max_len=48)
        b = RequestBatcher(batch_size=2, eos_id=-1, max_len=48)
        for uid, n in enumerate([7, 12, 5]):
            b.submit(Request(uid=uid, prompt=_prompt(cfg, 50 + uid, n),
                             max_new_tokens=6))
        events = []
        if paged:
            orig_p, orig_r = eng.preempt, eng.resume
            eng.preempt = lambda s: (events.append(
                ("preempt", b.slots[s].uid)), orig_p(s))[1]
            eng.resume = lambda pre, s: (events.append(
                ("resume", b.slots[s].uid)), orig_r(pre, s))[1]
        done = b.serve(eng, max_steps=200)
        return {r.uid: r.generated for r in done}, events

    dense, _ = run(False)
    paged, events = run(True)
    assert dense == paged
    kinds = [e[0] for e in events]
    assert "preempt" in kinds                       # pressure was real
    # every preempted uid resumed, and resumed before uid 2 (queued
    # later) finished prefill: FIFO re-admission from the queue front
    pre_uids = [u for k, u in events if k == "preempt"]
    res_uids = [u for k, u in events if k == "resume"]
    assert sorted(pre_uids) == sorted(res_uids)


def test_page_pool_exhaustion_at_budget_one(qwen):
    """A pool with ONE usable page: a one-page prompt is admitted, but
    the step that needs a second page has nothing to preempt (the lone
    request is the pool's only tenant) — the in-step ensure raises
    OutOfPages rather than corrupting state; an oversized prompt is
    never admitted at all."""
    cfg, params = qwen
    eng = PagedContinuousBatchingEngine(
        params, cfg, batch_size=1, max_len=16, page_size=8,
        num_pages=2)                                # 1 usable page
    assert not eng.can_admit_tokens(8)              # needs 2 pages
    assert eng.can_admit_tokens(5)
    eng.begin_prefill(0, _prompt(cfg, 60, 5))
    for _ in range(3):                              # ctx 5 -> 8 fits
        eng.step()
    assert eng.row_ctx[0] == 8
    with pytest.raises(OutOfPages):
        eng.step()                                  # token 9 needs page 2
