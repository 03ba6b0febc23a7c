"""Serving layer: batcher slot lifecycle + greedy decode correctness."""

import dataclasses

import pytest

# JAX-heavy tier: deselect with -m 'not slow' for the fast core-DSE tier
pytestmark = pytest.mark.slow

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.models import forward, init_params_and_axes
from repro.serve import Request, RequestBatcher
from repro.serve.engine import (decode_step, greedy_sample,
                                init_decode_state, prefill)


def test_batcher_slot_lifecycle():
    b = RequestBatcher(batch_size=2, eos_id=99)
    for uid in range(4):
        b.submit(Request(uid=uid, prompt=[1, 2], max_new_tokens=3))
    prefills = []

    def prefill_fn(slots, prompts):
        prefills.append(tuple(slots))

    tok = {"v": 0}

    def decode_fn():
        tok["v"] += 1
        return np.array([tok["v"], tok["v"] + 50])

    done = b.run(prefill_fn, decode_fn, max_steps=20)
    assert len(done) == 4
    assert all(len(r.generated) == 3 for r in done)
    assert prefills[0] == (0, 1)        # both slots filled at start
    assert len(prefills) >= 2           # refilled after completion


def test_batcher_submit_validates_prompts():
    """submit() fails fast on malformed requests — empty prompts,
    non-integer tokens, non-1-D shapes, zero generation budget — with
    a ValueError naming the request, instead of a shape error deep
    inside prefill.  Valid array-ish prompts are normalised to a plain
    list of ints."""
    b = RequestBatcher(batch_size=2)
    with pytest.raises(ValueError, match="empty prompt"):
        b.submit(Request(uid=0, prompt=[], max_new_tokens=3))
    with pytest.raises(ValueError, match="must be integers"):
        b.submit(Request(uid=1, prompt=[1.5, 2.0], max_new_tokens=3))
    with pytest.raises(ValueError, match="1-D"):
        b.submit(Request(uid=2, prompt=np.array([[1, 2], [3, 4]]),
                         max_new_tokens=3))
    with pytest.raises(ValueError, match="max_new_tokens"):
        b.submit(Request(uid=3, prompt=[1, 2], max_new_tokens=0))
    assert not b.queue                  # nothing malformed got queued
    b.submit(Request(uid=4, prompt=np.array([5, 6, 7]),
                     max_new_tokens=3))
    assert b.queue[0].prompt == [5, 6, 7]
    assert all(type(t) is int for t in b.queue[0].prompt)


def test_batcher_eos_terminates():
    b = RequestBatcher(batch_size=1, eos_id=7)
    b.submit(Request(uid=0, prompt=[1], max_new_tokens=100))
    b.run(lambda s, p: None, lambda: np.array([7]), max_steps=10)
    assert b.finished[0].generated == [7]


def test_batcher_eos_on_final_slot():
    """EOS landing on the *last* slot index frees it and the next
    queued request takes exactly that slot."""
    b = RequestBatcher(batch_size=3, eos_id=7)
    for uid in range(4):
        b.submit(Request(uid=uid, prompt=[1], max_new_tokens=5))
    prefills = []
    step = {"n": 0}

    def decode_fn():
        step["n"] += 1
        # step 1: EOS only on slot 2 (the final slot)
        return np.array([0, 0, 7]) if step["n"] == 1 \
            else np.array([7, 7, 7])

    b.run(lambda s, p: prefills.append(tuple(s)), decode_fn,
          max_steps=10)
    assert prefills[0] == (0, 1, 2)
    assert prefills[1] == (2,), "freed final slot must be refilled"
    assert len(b.finished) == 4
    assert b.finished[0].uid == 2       # the EOS'd final-slot request


def test_batcher_submit_after_run_started():
    """A request submitted mid-run (from inside the decode loop) is
    picked up by a later _fill_slots and completes."""
    b = RequestBatcher(batch_size=1, eos_id=9)
    b.submit(Request(uid=0, prompt=[1], max_new_tokens=2))
    late = Request(uid=1, prompt=[2], max_new_tokens=1)
    injected = {"done": False}

    def decode_fn():
        if not injected["done"]:
            injected["done"] = True
            b.submit(late)              # arrives while run() is live
        return np.array([3])

    done = b.run(lambda s, p: None, decode_fn, max_steps=10)
    assert {r.uid for r in done} == {0, 1}
    assert late.generated == [3]


def test_batcher_request_longer_than_max_len():
    """max_len guards the cache geometry: an unservable prompt is
    rejected at submit; a servable one has its generation budget
    clamped so prompt + generated never overruns the cache."""
    b = RequestBatcher(batch_size=1, eos_id=-1, max_len=8)
    with pytest.raises(ValueError, match="max_len"):
        b.submit(Request(uid=0, prompt=list(range(8))))
    ok = Request(uid=1, prompt=list(range(5)), max_new_tokens=100)
    b.submit(ok)
    assert ok.max_new_tokens == 3       # clamped to the cache headroom
    done = b.run(lambda s, p: None, lambda: np.array([1]), max_steps=10)
    assert len(done[0].generated) == 3
    assert len(done[0].prompt) + len(done[0].generated) <= 8


def test_serving_plan_step_dispatch_follows_deepest_live_row():
    """step_dispatch resolves ONE whole-batch dispatch from the
    distribution of live row contexts: the deepest live row picks the
    bucket (kernel path switches when IT crosses the 2N = 64
    crossover), and evicting the deep row drops the step back to the
    shallow rows' cheap path — dead rows never inflate the plan."""
    from repro import lower
    cfg = configs.get_config("qwen3-8b", smoke=True)   # N=32, 2N=64
    plan = lower.serving_plan(cfg, max_len=192)
    # shallow rows only: below the crossover, materialising is free
    assert plan.step_dispatch([3, 10]).path == lower.UNFUSED
    # a deep live row pulls the whole step past the crossover, up to
    # the decode megakernel (which folds qwen3's qk-norm)
    assert plan.step_dispatch([3, 100]).path == lower.DECODE_MEGAKERNEL
    # the deep row finished and was evicted: back to the cheap path
    assert plan.step_dispatch([3, 10]).path == lower.UNFUSED
    # drained batch resolves the minimal plan instead of a stale depth
    assert plan.step_dispatch([]).path == lower.UNFUSED


class _StubEngine:
    """Host-only engine double recording the serve-loop protocol."""

    def __init__(self, batch_size):
        self.batch_size = batch_size
        self.live = [False] * batch_size
        self.row_ctx = [0] * batch_size
        self._pending = {}
        self.events = []

    def begin_prefill(self, slot, prompt):
        assert not self.live[slot] and slot not in self._pending
        self._pending[slot] = len(prompt)
        self.events.append(("prefill", slot, len(prompt)))

    def step(self):
        inserted = []
        for slot, n in list(self._pending.items()):
            self.live[slot], self.row_ctx[slot] = True, n
            del self._pending[slot]
            inserted.append((slot, 100 + slot))
        if not any(self.live):
            return None, inserted
        toks = np.zeros(self.batch_size, np.int64)
        for i in range(self.batch_size):
            if self.live[i]:
                self.row_ctx[i] += 1
                toks[i] = self.row_ctx[i]
        self.events.append(("step", tuple(self.live)))
        return toks, inserted

    def evict(self, slot):
        self.live[slot], self.row_ctx[slot] = False, 0
        self.events.append(("evict", slot))


def test_batcher_serve_admission_fifo_and_eviction():
    """serve() drives the engine protocol: FIFO admission into free
    slots under the max_concurrency budget, eviction the moment a
    request finishes, and every request completes."""
    b = RequestBatcher(batch_size=3, eos_id=-1, max_concurrency=2)
    for uid in range(5):
        b.submit(Request(uid=uid, prompt=[1] * (uid + 2),
                         max_new_tokens=3))
    eng = _StubEngine(3)
    done = b.serve(eng, max_steps=40)
    assert sorted(r.uid for r in done) == list(range(5))
    assert all(len(r.generated) == 3 for r in done)
    # FIFO: prefills happen in submit order (queue fairness)
    order = [e[2] for e in eng.events if e[0] == "prefill"]
    assert order == [2, 3, 4, 5, 6]     # prompt lengths, uid order
    # admission control: never more than max_concurrency live rows
    assert all(sum(e[1]) <= 2 for e in eng.events if e[0] == "step")
    # every leased slot was evicted after finishing
    assert sum(e[0] == "evict" for e in eng.events) == 5
    assert not any(eng.live)


def test_chunked_prefill_matches_one_shot_and_switches_paths():
    """Plan-aware chunked prefill: (a) numerically equivalent to the
    one-shot prefill, (b) re-resolves the plan per chunk, so a long
    prompt crossing the context-bucket edge mid-prefill switches
    kernel path at the edge (unfused -> fused_attention past 2N)."""
    from repro import lower
    from repro.serve import chunked_prefill, make_serving_plan
    cfg = configs.get_config("qwen3-8b", smoke=True)   # N=32, 2N=64
    params, _ = init_params_and_axes(jax.random.PRNGKey(0), cfg)
    prompt = jax.random.randint(jax.random.PRNGKey(8), (1, 96), 0,
                                cfg.vocab_size)
    lower.clear_plan_cache()
    plan = make_serving_plan(cfg, max_len=128)

    s1 = init_decode_state(cfg, 1, 128, jnp.float32)
    s1 = prefill(params, cfg, prompt, s1, plan=plan)
    s2 = init_decode_state(cfg, 1, 128, jnp.float32)
    s2 = chunked_prefill(params, cfg, prompt, s2, chunk_size=16,
                         plan=plan)
    np.testing.assert_array_equal(np.asarray(s1.last_token),
                                  np.asarray(s2.last_token))
    assert int(s2.cache_len[0]) == 96

    # chunk resolutions: ctx 16 (prefill), then decode-regime chunks at
    # ctx 32..96 — the path switches exactly past the 2N = 64 edge
    chunk_res = plan.resolutions[1:]          # [0] is the one-shot
    paths = {ctx: path for (_, ctx, _, path, _) in chunk_res}
    assert paths[32] == lower.UNFUSED and paths[64] == lower.UNFUSED
    assert paths[80] == lower.FUSED_ATTENTION
    assert paths[96] == lower.FUSED_ATTENTION


def test_chunked_prefill_then_decode_consistent():
    """Decode after a chunked prefill continues the same greedy chain
    as decode after a one-shot prefill."""
    cfg = configs.get_config("qwen3-8b", smoke=True)
    from repro.serve import chunked_prefill
    params, _ = init_params_and_axes(jax.random.PRNGKey(0), cfg)
    prompt = jax.random.randint(jax.random.PRNGKey(3), (2, 20), 0,
                                cfg.vocab_size)
    s1 = init_decode_state(cfg, 2, 48, jnp.float32)
    s1 = prefill(params, cfg, prompt, s1)
    s2 = init_decode_state(cfg, 2, 48, jnp.float32)
    s2 = chunked_prefill(params, cfg, prompt, s2, chunk_size=7)
    for _ in range(3):
        s1, _ = decode_step(params, cfg, s1)
        s2, _ = decode_step(params, cfg, s2)
        np.testing.assert_array_equal(np.asarray(s1.last_token),
                                      np.asarray(s2.last_token))


def test_greedy_decode_matches_forward_argmax():
    """Three decode steps reproduce the argmax chain of full forwards."""
    cfg = configs.get_config("qwen3-8b", smoke=True)
    params, _ = init_params_and_axes(jax.random.PRNGKey(0), cfg)
    prompt = jax.random.randint(jax.random.PRNGKey(4), (2, 9), 0,
                                cfg.vocab_size)
    state = init_decode_state(cfg, 2, 32, jnp.float32)
    state = prefill(params, cfg, prompt, state)
    toks = [np.asarray(state.last_token)]
    for _ in range(2):
        state, _ = decode_step(params, cfg, state)
        toks.append(np.asarray(state.last_token))

    seq = np.asarray(prompt)
    for i in range(3):
        logits = forward(params, cfg, tokens=jnp.asarray(seq))
        nxt = np.asarray(greedy_sample(logits))
        np.testing.assert_array_equal(nxt, toks[i])
        seq = np.concatenate([seq, nxt[:, None]], axis=1)
