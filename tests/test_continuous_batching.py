"""Continuous-batching invariants: per-slot state, in-flight insertion,
eviction/reuse, masked-kernel parity for mixed-depth batches, admission
edge cases, and the e2e zero-lengths-downgrades acceptance check."""

import dataclasses

import pytest

# JAX-heavy tier: deselect with -m 'not slow' for the fast core-DSE tier
pytestmark = pytest.mark.slow

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs, lower
from repro.models import forward, init_params_and_axes
from repro.serve import (ContinuousBatchingEngine, Request,
                         RequestBatcher, greedy_sample,
                         make_serving_plan, prefill_request)


@pytest.fixture(scope="module")
def qwen():
    cfg = configs.get_config("qwen3-8b", smoke=True)   # N=32, 2N=64
    params, _ = init_params_and_axes(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _prompt(cfg, key, n):
    return jax.random.randint(jax.random.PRNGKey(key), (n,), 0,
                              cfg.vocab_size)


def _solo_chain(params, cfg, prompt, n_tokens):
    """The request's reference greedy chain from full forwards."""
    seq = np.asarray(prompt)[None, :]
    out = []
    for _ in range(n_tokens):
        logits = forward(params, cfg, tokens=jnp.asarray(seq))
        nxt = int(greedy_sample(logits)[0])
        out.append(nxt)
        seq = np.concatenate([seq, [[nxt]]], axis=1)
    return out


def test_insert_mid_generate_lands_in_slot_and_preserves_neighbors(qwen):
    """insert() during an active generate loop: the new request lands
    in exactly the free slot, and the rows already decoding produce
    the same tokens as if no insertion had happened."""
    cfg, params = qwen
    eng = ContinuousBatchingEngine(params, cfg, batch_size=3,
                                   max_len=48)
    pa, pb = _prompt(cfg, 1, 6), _prompt(cfg, 2, 11)
    eng.begin_prefill(0, pa)
    toks_a = []
    for _ in range(3):                       # A decodes alone
        tokens, inserted = eng.step()
        for slot, first in inserted:
            assert slot == 0
            toks_a.append(first)
        if tokens is not None:
            toks_a.append(int(tokens[0]))
    assert eng.live == [True, False, False]
    eng.begin_prefill(2, pb)                 # mid-stream, slot 2
    toks_b = []
    for _ in range(3):
        tokens, inserted = eng.step()
        for slot, first in inserted:
            assert slot == 2                 # landed in the right slot
            toks_b.append(first)
        toks_a.append(int(tokens[0]))
        if eng.live[2]:
            toks_b.append(int(tokens[2]))
    assert eng.live == [True, False, True]
    assert toks_a == _solo_chain(params, cfg, pa, len(toks_a))
    assert toks_b == _solo_chain(params, cfg, pb, len(toks_b))


def test_evicted_slot_frees_rows_for_next_request(qwen):
    """A slot evicted mid-stream is reusable immediately: the next
    request inserted into it decodes exactly its solo greedy chain —
    no state from the evicted occupant leaks through the cache rows."""
    cfg, params = qwen
    eng = ContinuousBatchingEngine(params, cfg, batch_size=2,
                                   max_len=48)
    p_old, p_new, p_other = (_prompt(cfg, 3, 13), _prompt(cfg, 4, 5),
                             _prompt(cfg, 5, 8))
    eng.begin_prefill(0, p_old)
    eng.begin_prefill(1, p_other)
    for _ in range(4):
        eng.step()
    eng.evict(0)                             # cancel the deep request
    assert eng.live == [False, True] and eng.row_ctx[0] == 0
    eng.begin_prefill(0, p_new)              # same slot, new request
    toks_new, toks_other = [], []
    for _ in range(4):
        tokens, inserted = eng.step()
        for slot, first in inserted:
            assert slot == 0
            toks_new.append(first)
        if eng.live[0] and tokens is not None:
            toks_new.append(int(tokens[0]))
        toks_other.append(int(tokens[1]))
    assert toks_new == _solo_chain(params, cfg, p_new, len(toks_new))
    # the surviving neighbour was never disturbed by evict or insert:
    # its prefill emitted token 0 and each of the 8 steps one more
    full_other = _solo_chain(params, cfg, p_other, 9)
    assert toks_other == full_other[5:9]


def test_just_inserted_and_dead_rows_masked_parity(qwen):
    """One live row among dead (length-0) rows decodes exactly its
    solo B=1 chain: the dead lanes ride along under the per-row
    lengths mask without perturbing live numerics."""
    cfg, params = qwen
    eng = ContinuousBatchingEngine(params, cfg, batch_size=4,
                                   max_len=48)
    p = _prompt(cfg, 6, 9)
    eng.begin_prefill(2, p)
    toks = []
    for _ in range(5):
        tokens, inserted = eng.step()
        for slot, first in inserted:
            toks.append(first)
        if tokens is not None:
            toks.append(int(tokens[2]))
    assert eng.live == [False, False, True, False]
    assert toks == _solo_chain(params, cfg, p, len(toks))


def test_fifo_admission_under_full_batch(qwen):
    """More requests than slots: admission is strictly FIFO as slots
    free up, every request completes, and each one's tokens match its
    solo greedy chain (slot reuse after natural completion)."""
    cfg, params = qwen
    eng = ContinuousBatchingEngine(params, cfg, batch_size=2,
                                   max_len=48)
    b = RequestBatcher(batch_size=2, eos_id=-1, max_len=48)
    lens = [5, 12, 7, 3]
    for uid, n in enumerate(lens):
        b.submit(Request(uid=uid, prompt=[int(x) for x in
                                          np.asarray(_prompt(cfg, 10 + uid,
                                                             n))],
                         max_new_tokens=3))
    done = b.serve(eng, max_steps=40)
    assert [r.uid for r in done[:2]] in ([0, 1], [1, 0])
    assert sorted(r.uid for r in done) == [0, 1, 2, 3]
    # FIFO: 2 and 3 can only start after 0 and 1 freed slots
    assert all(len(r.generated) == 3 for r in done)
    for r in done:
        assert r.generated == _solo_chain(params, cfg,
                                          jnp.asarray(r.prompt), 3)
    assert not any(eng.live) and eng.occupancy == 0.0


def test_submit_max_len_edge_admitted_with_budget_one(qwen):
    """Regression: a prompt of exactly max_len - 1 tokens with
    max_new_tokens >= 1 is admitted with its budget clamped to 1 (one
    decodable token), not rejected; max_len itself is rejected."""
    cfg, params = qwen
    b = RequestBatcher(batch_size=1, eos_id=-1, max_len=16)
    with pytest.raises(ValueError):
        b.submit(Request(uid=9, prompt=[1] * 16, max_new_tokens=4))
    edge = Request(uid=0, prompt=[int(x) for x in
                                  np.asarray(_prompt(cfg, 20, 15))],
                   max_new_tokens=4)
    b.submit(edge)
    assert edge.max_new_tokens == 1          # clamped to cache headroom
    eng = ContinuousBatchingEngine(params, cfg, batch_size=1,
                                   max_len=16)
    done = b.serve(eng, max_steps=8)
    assert len(done) == 1 and len(done[0].generated) == 1
    assert done[0].generated == _solo_chain(params, cfg,
                                            jnp.asarray(edge.prompt), 1)
    assert not any(eng.live)


@pytest.mark.parametrize("arch", ["starcoder2-7b", "qwen3-8b"])
def test_engine_e2e_mixed_depths_zero_lengths_downgrades(arch):
    """Acceptance: the continuous-batching engine path, plan-driven in
    interpret mode with rows at different depths, (a) reproduces each
    request's solo greedy chain, (b) resolves its per-step dispatch
    from the deepest LIVE row (kernel path climbs at the 2N crossover
    and the fused steps run Pallas), and (c) records ZERO
    lengths-related downgrades — the masked kernels serve every
    per-row-lengths call on the planned path."""
    cfg = configs.get_config(arch, smoke=True)
    params, _ = init_params_and_axes(jax.random.PRNGKey(0), cfg)
    crossover = 2 * cfg.head_dim             # 64 for the smoke zoo
    max_len = crossover + 32
    lower.clear_plan_cache()
    plan = make_serving_plan(cfg, max_len=max_len, interpret=True)
    assert plan is not None
    eng = ContinuousBatchingEngine(params, cfg, batch_size=2,
                                   max_len=max_len, plan=plan,
                                   prefill_chunk=32, interpret=True)
    # one row starts below the crossover and crosses it; the second is
    # admitted mid-stream at 1/8 of the deep row's context
    deep = _prompt(cfg, 30, crossover - 2)
    shallow = _prompt(cfg, 31, max(crossover // 8, 2))
    eng.begin_prefill(0, deep)
    toks_deep, toks_shallow = [], []
    for step in range(6):
        if step == 2:
            eng.begin_prefill(1, shallow)
        tokens, inserted = eng.step()
        for slot, first in inserted:
            (toks_deep if slot == 0 else toks_shallow).append(first)
        if tokens is not None:
            if eng.live[0]:
                toks_deep.append(int(tokens[0]))
            if eng.live[1]:
                toks_shallow.append(int(tokens[1]))

    # (a) per-request greedy parity at mixed depths
    assert toks_deep == _solo_chain(params, cfg, deep, len(toks_deep))
    assert toks_shallow == _solo_chain(params, cfg, shallow,
                                       len(toks_shallow))

    # (b) dispatch followed the deepest live row across the crossover,
    # to the decode megakernel (which folds qwen3's qk-norm too)
    fused = lower.DECODE_MEGAKERNEL
    decode_res = [r for r in plan.resolutions if r[0] == "decode"]
    paths = {ctx: path for (_, ctx, _, path, _) in decode_res}
    for ctx, path in paths.items():
        want = lower.UNFUSED if ctx <= crossover else fused
        assert path == want, (ctx, path)
    assert fused in paths.values()           # the deep row crossed
    fused_steps = [r for r in decode_res if r[3] == fused]
    assert fused_steps and all(r[4] == "pallas" for r in fused_steps)

    # (c) zero lengths downgrades on every decode plan the engine ran
    for (_, ctx, _, _, _) in decode_res:
        p = lower.resolve_plan(cfg, "decode", ctx,
                               n_blocks=cfg.n_layers)
        assert not p.downgrades, p.downgrades
