"""Paged-KV kernels and serving: block-table-indirect Pallas kernels
vs their gather-dense oracles (random non-contiguous tables, length-0
rows, GQA, identity-table equivalence with the masked kernels),
zero-downgrade dispatch through kernels.ops, the PageAllocator's
free-list accounting, and preempt -> resume bit-identity on the
continuous-batching engine."""

import warnings

import pytest

# JAX-heavy tier: deselect with -m 'not slow' for the fast core-DSE tier
pytestmark = pytest.mark.slow

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.kernels import ops, ref
from repro.kernels.fused_attention import (fused_attention_masked,
                                           fused_attention_paged)
from repro.kernels.fused_decode_block import fused_decode_block_paged
from repro.kernels.fused_qproj_attention import (
    fused_qproj_attention_paged)
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
    """fused_attention_paged == gather-dense unfused oracle over
    shuffled non-contiguous tables (lengths not page multiples)."""
    n_pages = b * max_pages + 1
    kp, vp, tbl = _pools(b, hkv, n_pages, page, d, max_pages)
    q = jax.random.normal(KEYS[0], (b, hq, sq, d), jnp.float32)
    lens = jnp.asarray(lengths, jnp.int32)
    kw = {}
    if causal and sq > 1:
        kw = {"q_offset": int(lengths[0]) - sq}   # multi-row contract
        lens = jnp.full((b,), lengths[0], jnp.int32)
    o = fused_attention_paged(q, kp, vp, lens, tbl, causal=causal,
                              interpret=True)
    o_ref = ref.paged_attention_reference(q, kp, vp, lens, tbl,
                                          causal=causal, **kw)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref),
                               rtol=2e-5, atol=2e-5)


def test_paged_identity_table_equals_masked_dense():
    """With the identity table (row b's pages laid out contiguously),
    the paged kernel reproduces the dense masked kernel bit-for-bit on
    the same logical KV — the table only changes *where* blocks live."""
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
    o_paged = fused_attention_paged(q, pool_of(k), pool_of(v), lens,
                                    tbl, causal=True, interpret=True)
    o_dense = fused_attention_masked(q, k, v, lens, causal=True,
                                     block_k=page, interpret=True)
    np.testing.assert_array_equal(np.asarray(o_paged),
                                  np.asarray(o_dense))


def test_paged_qproj_and_decode_block_match_oracles():
    """The fused-Q and megakernel paged variants (in-kernel RoPE at
    each row's end anchor) == their gather-dense oracles."""
    b, hq, hkv, page, max_pages, d, e = 3, 4, 2, 16, 6, 32, 64
    n_pages = b * max_pages + 1
    kp, vp, tbl = _pools(b, hkv, n_pages, page, d, max_pages, seed=5)
    lens = jnp.asarray([37, 1, 96], jnp.int32)
    x = jax.random.normal(KEYS[4], (b, 1, e), jnp.float32)
    wq = jax.random.normal(KEYS[5], (e, hq, d), jnp.float32) * 0.1
    wo = jax.random.normal(KEYS[6], (hq, d, e), jnp.float32) * 0.1
    res = jax.random.normal(KEYS[7], (b, 1, e), jnp.float32)
    o = fused_qproj_attention_paged(x, wq, kp, vp, lens, tbl,
                                    causal=True, rope_theta=1e4,
                                    interpret=True)
    o_ref = ref.paged_qproj_attention_reference(
        x, wq, kp, vp, lens, tbl, causal=False, rope_theta=1e4)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref),
                               rtol=2e-5, atol=2e-5)
    y = fused_decode_block_paged(x, wq, kp, vp, wo, res, lens, tbl,
                                 rope_theta=1e4, interpret=True)
    y_ref = ref.paged_decode_block_reference(x, wq, kp, vp, wo, res,
                                             lens, tbl, rope_theta=1e4)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=2e-5, atol=2e-5)


MEGAKERNEL_SWEEP = [
    # dtype, hq, hkv, page, max_pages, block_k, lengths
    # group 1, 3 pages a block over 7 (not a multiple): 0, 1, page - 1,
    # page, a block edge - 1 / at / + 1, the full table
    (jnp.float32, 2, 2, 8, 7, 24, [0, 1, 7, 8, 23, 24, 25, 56]),
    (jnp.bfloat16, 2, 2, 8, 7, 24, [0, 1, 7, 8, 23, 24, 25, 56]),
    # group 4, 2 pages a block over 5; two length-0 rows in a row break
    # the prefetch chain
    (jnp.float32, 8, 2, 16, 5, 32, [31, 0, 0, 33, 80, 16, 1, 32]),
    (jnp.bfloat16, 8, 2, 16, 5, 32, [31, 0, 0, 33, 80, 16, 1, 32]),
    # group 9 (starcoder2's 36/4) padded to 16 sublanes; a KV block
    # longer than the table is clamped to it
    (jnp.float32, 9, 1, 8, 6, 32, [48, 31, 33, 0, 9, 15]),
    (jnp.bfloat16, 18, 2, 16, 4, 1024, [64, 0, 17, 16, 63]),
]


@pytest.mark.parametrize("dtype,hq,hkv,page,max_pages,block_k,lengths",
                         MEGAKERNEL_SWEEP)
def test_paged_decode_block_retiled_matches_oracle(dtype, hq, hkv, page,
                                                   max_pages, block_k,
                                                   lengths):
    """The paged megakernel's (KV head, row, block of pages) grid ==
    the gather-dense oracle: scattered, non-monotone page ids, page 0
    in every slot past a row's length, rows of length 0 emitting the
    residual."""
    b, d, e = len(lengths), 32, 64
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
    y = fused_decode_block_paged(*args[:5], args[5], lens, tbl,
                                 rope_theta=1e4, block_k=block_k,
                                 interpret=True)
    assert y.dtype == dtype and y.shape == (b, 1, e)
    y_ref = ref.paged_decode_block_reference(
        *[a.astype(jnp.float32) for a in args[:6]], lens, tbl,
        rope_theta=1e4)
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(y_ref), rtol=tol, atol=tol)
    dead = np.asarray(lengths) == 0
    np.testing.assert_array_equal(np.asarray(y)[dead],
                                  np.asarray(args[5])[dead])


def test_paged_dispatch_zero_downgrades_and_per_reason_warn_once():
    """ops.attention with block_tables stays on the Pallas path (no
    downgrade warning); an *unsupported* paged call warns exactly once
    per distinct reason — the per-reason warn-once contract."""
    b, hq, hkv, page, max_pages, d = 2, 4, 2, 16, 4, 32
    n_pages = b * max_pages + 1
    kp, vp, tbl = _pools(b, hkv, n_pages, page, d, max_pages, seed=9)
    q = jax.random.normal(KEYS[0], (b, hq, 1, d), jnp.float32)
    lens = jnp.asarray([10, 50], jnp.int32)
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        o = ops.attention(q, kp, vp, causal=True, lengths=lens,
                          block_tables=tbl, impl="pallas",
                          interpret=True)
    np.testing.assert_allclose(
        np.asarray(o),
        np.asarray(ref.paged_attention_reference(q, kp, vp, lens, tbl,
                                                 causal=True)),
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
        ops.attention(q, kp, vp, causal=True, lengths=lens,
                      block_tables=bad_dtype, impl="pallas",
                      interpret=True)
        ops.attention(q, kp, vp, causal=True, lengths=lens,
                      block_tables=bad_dtype, impl="pallas",
                      interpret=True)
        ops.attention(q, kp12, vp12, causal=True,
                      lengths=jnp.minimum(lens, 12 * max_pages),
                      block_tables=tbl12, impl="pallas",
                      interpret=True)
    msgs = [str(x.message) for x in w]
    assert len(msgs) == 2, msgs
    assert all("paged-KV" in m for m in msgs)
    assert "masked-lengths" not in "".join(msgs)


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
