"""DeepSeek-V3's mechanisms at a CPU size, on seeded random weights: the
sigmoid group-limited router against a literal NumPy transcription of
the published rule, the expert share against the uncut layer, YaRN
against its formulas, the paged MLA decode kernel (interpret mode)
against a dense oracle, the serving plan's MLA dispatch, and the
engine's MLA pool and counters."""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.kernels.fused_mla_decode import fused_mla_decode_paged
from repro.models import common as cm
from repro.models import moe as moe_mod
from repro.models.common import YaRN
from repro.serve import tracing


# -- the router -------------------------------------------------------------

def published_route(logits, bias, top_k, n_group, topk_group, scale):
    """DeepSeek-V3's noaux_tc gate, line for line in NumPy (float64)."""
    t, e = logits.shape
    scores = 1.0 / (1.0 + np.exp(-logits))
    choice = scores + bias[None, :]
    group_scores = np.sort(choice.reshape(t, n_group, -1), axis=-1)[
        ..., -2:].sum(-1)
    group_idx = np.argsort(-group_scores, axis=-1, kind="stable")[
        :, :topk_group]
    group_mask = np.zeros((t, n_group))
    np.put_along_axis(group_mask, group_idx, 1, axis=1)
    score_mask = np.repeat(group_mask, e // n_group, axis=1)
    tmp = np.where(score_mask > 0, choice, -np.inf)
    idx = np.argsort(-tmp, axis=-1, kind="stable")[:, :top_k]
    w = np.take_along_axis(scores, idx, axis=1)
    w = w / (w.sum(-1, keepdims=True) + 1e-20) * scale
    return w, idx


def _route(logits, bias, **kw):
    w, idx = moe_mod.route_sigmoid(jnp.asarray(logits, jnp.float32),
                                   jnp.asarray(bias, jnp.float32), **kw)
    return np.asarray(w), np.asarray(idx)


def _as_sets(w, idx):
    return [dict(zip(i.tolist(), x.tolist())) for i, x in zip(idx, w)]


def _router_case(name):
    rng = np.random.default_rng(3)
    kw = dict(top_k=4, n_groups=4, topk_groups=2, scale=2.5)
    if name == "random":
        return rng.standard_normal((32, 16)), \
            0.1 * rng.standard_normal(16), kw
    if name == "bias_changes_selection":
        # expert 5 beats expert 6 on score, but the bias reverses them;
        # the weights stay the unbiased scores
        logits = np.full((1, 16), -4.0)
        logits[0, [0, 1, 4, 5, 6]] = [3.0, 2.9, 2.8, 2.0, 1.9]
        bias = np.zeros(16)
        bias[6] = 0.2
        return logits, bias, dict(kw, top_k=4, topk_groups=4)
    # group masking: expert 12 has the best score of all, but its group
    # (12-15) has the worst sum of two best scores and is left out
    logits = np.full((1, 16), 0.0)
    logits[0, 12] = 5.0
    logits[0, 13:16] = -6.0
    logits[0, [0, 1, 4, 5, 8, 9]] = [2.0, 2.0, 1.5, 1.5, 1.8, 1.8]
    return logits, np.zeros(16), dict(kw, topk_groups=3)


@pytest.mark.parametrize("name", ["random", "bias_changes_selection",
                                  "group_mask_excludes_best"])
def test_router_matches_the_published_rule(name):
    logits, bias, kw = _router_case(name)
    w, idx = _route(logits, bias, **kw)
    ref_w, ref_idx = published_route(logits, bias, kw["top_k"],
                                     kw["n_groups"], kw["topk_groups"],
                                     kw["scale"])
    got, want = _as_sets(w, idx), _as_sets(ref_w, ref_idx)
    for g, r in zip(got, want):
        assert set(g) == set(r)
        for k in g:
            assert g[k] == pytest.approx(r[k], rel=1e-6)
    if name == "bias_changes_selection":
        assert 6 in got[0] and 5 not in got[0]
        s = 1 / (1 + np.exp(-logits[0]))
        chosen = [0, 1, 4, 6]
        assert got[0][6] == pytest.approx(
            2.5 * s[6] / s[chosen].sum(), rel=1e-6)
    if name == "group_mask_excludes_best":
        assert 12 not in got[0]


# -- the expert share ---------------------------------------------------------

def test_expert_shares_add_up_to_the_uncut_layer():
    cfg = dataclasses.replace(
        configs.get_config("deepseek-v3-671b", smoke=True),
        expert_offset=0, experts_held=0)          # all 16 experts
    full = moe_mod.init_moe(jax.random.PRNGKey(0), cfg)
    full = jax.tree.map(lambda p: p.value, full, is_leaf=cm.is_param)
    full["router_bias"] = 0.1 * jax.random.normal(
        jax.random.PRNGKey(1), full["router_bias"].shape)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 5, cfg.d_model))
    whole, aux = moe_mod.moe_forward(full, cfg, x)
    assert int(aux["moe_held_pairs"]) == 2 * 5 * cfg.top_k
    shared = cm.mlp_forward(full["shared"], x, cfg.mlp)
    total, pairs = jnp.zeros_like(whole), 0
    for r in range(4):
        share = dataclasses.replace(cfg, expert_offset=4 * r,
                                    experts_held=4)
        p = dict(full, **{k: full[k][4 * r:4 * r + 4]
                          for k in ("w_gate", "w_up", "w_down")})
        y, aux = moe_mod.moe_forward(p, share, x)
        total, pairs = total + y - shared, pairs + int(
            aux["moe_held_pairs"])
    np.testing.assert_allclose(np.asarray(total + shared),
                               np.asarray(whole), rtol=1e-5, atol=1e-5)
    assert pairs == 2 * 5 * cfg.top_k


# -- YaRN ---------------------------------------------------------------------

DSV3_YARN = YaRN(factor=40.0, original_max_position=4096, beta_fast=32.0,
                 beta_slow=1.0, mscale=1.0, mscale_all_dim=1.0)


def test_yarn_frequencies_and_scale_follow_the_formulas():
    d, base = 64, 1e4
    assert DSV3_YARN.correction_range(d, base) == (10, 23)
    i = np.arange(d // 2, dtype=np.float64)
    extra = base ** (-2 * i / d)
    ramp = np.clip((i - 10) / 13, 0, 1)
    want = extra / 40 * ramp + extra * (1 - ramp)
    np.testing.assert_allclose(np.asarray(DSV3_YARN.inv_freq(d, base)),
                               want, rtol=1e-6)
    m = 0.1 * math.log(40) + 1
    assert m == pytest.approx(1.3689, abs=1e-4)
    assert DSV3_YARN.softmax_mscale == pytest.approx(m * m)
    assert DSV3_YARN.cos_scale == 1.0


def test_yarn_rope_rotates_by_its_frequencies_and_factor_1_is_rope():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 3, 7, 64))
    pos = jnp.broadcast_to(jnp.arange(7) * 900, (2, 7))
    one = dataclasses.replace(DSV3_YARN, factor=1.0)
    assert np.array_equal(np.asarray(cm.rope(x, pos, 1e4, one)),
                          np.asarray(cm.rope(x, pos, 1e4)))
    got = np.asarray(cm.rope(x, pos, 1e4, DSV3_YARN))
    # the angle as float32 rounds it, as the program computes it
    ang = (np.asarray(pos, np.float32)[:, None, :, None]
           * np.asarray(DSV3_YARN.inv_freq(64, 1e4), np.float32))
    a, b = np.asarray(x)[..., :32], np.asarray(x)[..., 32:]
    want = np.concatenate([a * np.cos(ang) - b * np.sin(ang),
                           b * np.cos(ang) + a * np.sin(ang)], -1)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


# -- the paged MLA decode kernel ---------------------------------------------

@pytest.mark.parametrize("block_k", [8, 32, 40])
def test_paged_mla_kernel_matches_a_dense_oracle(block_k):
    """Ragged lengths (one row empty), blocks of 1, 4 and 5 pages, and a
    permuted page table."""
    b, h, w, dv, page, max_pages = 3, 8, 128, 96, 8, 12
    rng = np.random.default_rng(0)
    n_pages = b * max_pages + 1
    pool = jnp.asarray(rng.standard_normal((n_pages, 1, page, w)),
                       jnp.float32)
    q = jnp.asarray(rng.standard_normal((b, h, w)), jnp.float32)
    lengths = jnp.asarray([37, 0, 96], jnp.int32)
    table = jnp.asarray(rng.permutation(np.arange(1, n_pages))[
        :b * max_pages].reshape(b, max_pages), jnp.int32)
    got = fused_mla_decode_paged(q, pool, lengths, table, d_v=dv,
                                 scale=0.1, block_k=block_k,
                                 interpret=True)
    dense = jnp.take(pool, table, axis=0)[:, :, 0].reshape(
        b, max_pages * page, w)
    s = jnp.einsum("bhw,bsw->bhs", q, dense) * 0.1
    live = jnp.arange(max_pages * page)[None, None] < lengths[:, None, None]
    p = jnp.where(live, jax.nn.softmax(jnp.where(live, s, -jnp.inf), -1),
                  0.0)
    want = jnp.einsum("bhs,bsv->bhv", jnp.nan_to_num(p), dense[..., :dv])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


# -- the serving plan and the engine ----------------------------------------

@pytest.fixture(scope="module")
def smoke():
    cfg = configs.get_config("deepseek-v3-671b", smoke=True)
    from repro.models import init_params_and_axes
    params, _ = init_params_and_axes(jax.random.PRNGKey(0), cfg)
    return cfg, params


def test_serving_plan_runs_mla_on_two_kernels(smoke):
    from repro.lower import MLA_DECODE, FUSED_ATTENTION, rung_down
    from repro.serve import make_serving_plan
    cfg, _ = smoke
    sp = make_serving_plan(cfg, 256, interpret=True, paged=True,
                           page_size=16)
    for ctx in (3, 100, 255):
        d = sp.step_dispatch([ctx])
        assert (d.path, d.impl, d.paged) == (MLA_DECODE, "pallas", True)
    for pos, rows in ((0, 32), (64, 32), (200, 16)):
        d = sp.chunk_dispatch(pos + rows, rows)
        assert (d.path, d.impl, d.paged) == (FUSED_ATTENTION, "pallas",
                                             False)
    assert sp.crossover_ctx == 2 * (cfg.kv_lora_rank
                                    + cfg.qk_rope_head_dim)
    assert not sp.downgrades
    down = rung_down(sp.step_dispatch([5]))
    assert (down.path, down.impl) == (MLA_DECODE, "xla")
    assert rung_down(down) is None


def test_paged_engine_serves_mla_through_the_kernel(smoke):
    """Prefill on the side, paged decode on the Pallas kernel (interpret
    mode), greedy tokens equal to the dense engine's, and the counters:
    the latent pool's bytes, the kernel's grid, the held pairs."""
    from repro.serve import (ContinuousBatchingEngine,
                             PagedContinuousBatchingEngine,
                             make_serving_plan)
    cfg, params = smoke
    max_len, page = 64, 16
    prompts = [np.arange(5, 5 + n) % cfg.vocab_size for n in (9, 23)]
    tracing.reset()
    runs = {}
    for paged in (False, True):
        sp = make_serving_plan(cfg, max_len, interpret=True, paged=paged,
                               page_size=page if paged else None)
        kw = dict(batch_size=2, max_len=max_len, plan=sp,
                  dtype=jnp.float32, prefill_chunk=16, interpret=True)
        eng = PagedContinuousBatchingEngine(
            params, cfg, page_size=page, num_pages=9, **kw) if paged \
            else ContinuousBatchingEngine(params, cfg, **kw)
        for slot, p in enumerate(prompts):
            eng.begin_prefill(slot, p)
        toks = []
        for _ in range(5):
            t, _ = eng.step()
            if t is not None:
                toks.append(np.asarray(t).tolist())
        runs[paged] = toks
    assert runs[True] == runs[False]
    c = tracing.counters()
    w = -(-(cfg.kv_lora_rank + cfg.qk_rope_head_dim) // 128) * 128
    assert c["engine.latent_pool_bytes"] == cfg.n_layers * 9 * page * w * 4
    assert c["engine.decode_grid_steps"] > 0
    assert 0 < c["engine.decode_kv_blocks"] <= c["engine.decode_grid_steps"]
    assert c["engine.moe_held_pairs"] > 0
    assert eng.last_dispatch.path == "mla_decode"


def test_mla_preempt_resume_continues_bit_for_bit(smoke):
    """A preempted MLA row's latent pages come back bit for bit in other
    pages, and its continuation's tokens and logits equal an
    uninterrupted run's."""
    from repro.models.attention import mla_latent_width
    from repro.serve import PagedContinuousBatchingEngine
    from repro.serve.engine import gather_slot_pages
    cfg, params = smoke
    prompt = (np.arange(11) * 7 + 3) % cfg.vocab_size

    def make():
        eng = PagedContinuousBatchingEngine(
            params, cfg, batch_size=2, max_len=48, page_size=8,
            num_pages=16, dtype=jnp.float32)
        eng.begin_prefill(0, prompt)
        for _ in range(4):
            eng.step()
        return eng

    def cont(eng):
        out = []
        for _ in range(3):
            tokens, _ = eng.step()
            out.append((int(tokens[0]), np.asarray(eng.last_logits[0])))
        return out

    eng = make()
    old = list(eng.allocator.pages[0])
    before = jax.device_get(gather_slot_pages(eng.state, old))
    pre = eng.preempt(0)
    eng.begin_prefill(1, prompt[:9])        # takes pages while 0 is out
    eng.step()
    eng.resume(pre, 0)
    assert eng.allocator.pages[0] != old
    after = jax.device_get(
        gather_slot_pages(eng.state, eng.allocator.pages[0]))
    assert all(a.shape[-1] == mla_latent_width(cfg)
               for a in jax.tree.leaves(before))
    for a, b in zip(jax.tree.leaves(before), jax.tree.leaves(after)):
        np.testing.assert_array_equal(a, b)
    got, want = cont(eng), cont(make())
    assert [t for t, _ in got] == [t for t, _ in want]
    for (_, a), (_, b) in zip(got, want):
        np.testing.assert_array_equal(a, b)


class _Device:
    """A device that reports a memory limit and what is resident."""

    def __init__(self, limit, in_use):
        self.stats = {"bytes_limit": limit, "bytes_in_use": in_use}

    def memory_stats(self):
        return self.stats


@pytest.mark.parametrize("spare_pools, in_use", [
    (2.5, 0), (2.5, 10 ** 9), (1.0, 0), (1.0, 10 ** 9)])
def test_pool_pages_from_static_sizes(smoke, spare_pools, in_use):
    """The pool is full where two fit beside the weights, else what fits
    twice in all but the reserve; what else is resident moves neither."""
    from repro.serve.engine import POOL_RESERVE, pool_leaves, pool_pages
    cfg, params = smoke
    batch, max_len, page = 16, 256, 8
    full = batch * max_len // page + 1
    weights = sum(a.nbytes for a in jax.tree.leaves(params))
    per_page = cfg.n_layers * sum(
        a.nbytes for a in jax.tree.leaves(
            pool_leaves(cfg, 1, page, jnp.bfloat16)))
    limit = int(weights + spare_pools * full * per_page)
    got = pool_pages(cfg, batch, max_len, page, jnp.bfloat16, params,
                     _Device(limit, in_use))
    if spare_pools >= 2:
        assert got == full
    else:
        assert 2 < got == int((limit * (1 - POOL_RESERVE) - weights)
                              // (2 * per_page)) < full
    assert pool_pages(cfg, batch, max_len, page, jnp.bfloat16, params,
                      _Device(limit, 0)) == got
