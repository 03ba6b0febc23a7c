"""Lowering subsystem: ExecutionPlan IR, plan cache, and the
schedule-aware serve path.

Fast (pure-Python) tier: IR construction, bucketing (crossover-aligned
edges), dispatch legalisation, downgrade ledger, cache identity.

Slow (JAX) tier — also run standalone by the required `lowering` CI
job in Pallas interpret mode on CPU: for two zoo configs the
DSE-chosen prefill and decode PhasePlans are lowered and executed via
``serve_step``; the outputs match the reference path bit-for-bit in
ranking (greedy tokens) and numerically (logits), and the decode plan
switches kernel path exactly when the KV context crosses the
analytical crossover ``alpha_kv = min(1, 2N/C)`` (C = 2N).
"""

import dataclasses
import sys
import warnings
from pathlib import Path
from types import SimpleNamespace

import pytest

from repro import lower
from repro.core import analytical

REPO = Path(__file__).resolve().parent.parent

ZOO = ("qwen3-8b", "starcoder2-7b")


@dataclasses.dataclass(frozen=True)
class ToyConfig:
    """Hashable ModelConfig stand-in (plan-cache keys must hash)."""

    name: str = "toy"
    d_model: int = 128
    n_heads: int = 4
    kv_heads: int = 2
    head_dim: int = 32
    d_ff: int = 256
    mlp: str = "silu_glu"
    rope_theta: float = 1e6
    qk_norm: bool = False
    n_layers: int = 2


def toy_cfg(**kw):
    return ToyConfig(**kw)


# ---------------------------------------------------------------------------
# fast tier: the IR itself (no JAX)
# ---------------------------------------------------------------------------

def test_kernel_path_mapping():
    from repro.lower.plan import kernel_path_for
    assert kernel_path_for(False, False) == lower.UNFUSED
    assert kernel_path_for(True, False) == lower.UNFUSED
    assert kernel_path_for(False, True) == lower.FUSED_ATTENTION
    assert kernel_path_for(True, True) == lower.QPROJ_ATTENTION
    assert kernel_path_for(True, True, fuse_block=True) == \
        lower.DECODE_MEGAKERNEL


def test_bucket_edges_pin_the_decode_crossover():
    """The first decode bucket edge must sit exactly at C = 2N so the
    runtime re-resolves (and can switch path) where alpha_kv crosses 1."""
    n = 32
    assert lower.bucket_for("decode", 1, n) == 2 * n
    assert lower.bucket_for("decode", 2 * n, n) == 2 * n
    assert lower.bucket_for("decode", 2 * n + 1, n) == 4 * n
    assert lower.bucket_for("prefill", 200, n) == 256
    # every decode bucket is decision-homogeneous: alpha_kv == 1
    # throughout the first bucket, < 1 throughout every later one
    assert analytical.alpha_kv(1, 2 * n, n) == 1.0
    assert analytical.alpha_kv(1, 2 * n + 1, n) < 1.0


def test_lowered_blocks_are_homogeneous_and_per_block():
    plan = lower.lower(toy_cfg(), "decode", 256, n_blocks=3)
    assert plan.n_blocks == 3 and len(plan.blocks) == 3
    assert {b.kernel_path for b in plan.blocks} == {plan.kernel_path}
    assert [b.block_index for b in plan.blocks] == [0, 1, 2]
    assert plan.crossover_ctx == 64
    # M=1 decode past the crossover escalates all the way: the whole
    # attention sub-block (projection + RoPE .. residual) one launch
    assert plan.kernel_path == lower.DECODE_MEGAKERNEL
    assert plan.block(0).streamed == (("Q", "QKT"), ("QKT", "SM"),
                                      ("SM", "AV"), ("AV", "PROJ"),
                                      ("PROJ", "OUT"))
    assert plan.block(0).materialized == ()
    # the qproj rung is still lowerable as a counterfactual override
    qp = lower.lower(toy_cfg(), "decode", 256, fuse_block=False)
    assert qp.kernel_path == lower.QPROJ_ATTENTION
    assert qp.block(0).streamed == (("Q", "QKT"), ("QKT", "SM"),
                                    ("SM", "AV"))


def test_decode_path_flips_at_crossover_in_the_ir():
    cfg = toy_cfg()
    below = lower.lower(cfg, "decode", 64)    # C = 2N: alpha_kv = 1
    above = lower.lower(cfg, "decode", 65)
    assert below.kernel_path == lower.UNFUSED
    # the DSE still streams Q below the crossover (free gain); no
    # standalone runtime kernel realises it, which kernel_path_for
    # folds into UNFUSED while the IR keeps the flag visible
    assert below.block(0).fuse_q and not below.block(0).fuse_scores
    assert below.block(0).materialized == ("QKT", "SM")
    assert above.kernel_path == lower.DECODE_MEGAKERNEL
    assert above.alpha < 1.0 == below.alpha
    # multi-row decode (chunked prefill) stays on the qproj rung:
    # the megakernel is the M=1 schedule
    rows = lower.lower(cfg, "decode", 65, decode_tokens=4)
    assert rows.kernel_path == lower.QPROJ_ATTENTION


def test_prefill_path_follows_m_vs_n():
    cfg = toy_cfg()
    assert lower.lower(cfg, "prefill", 128).kernel_path == \
        lower.FUSED_ATTENTION                 # M > N
    assert lower.lower(cfg, "prefill", 32).kernel_path == \
        lower.UNFUSED                         # M == N: Eq. 6, no gain


def test_plan_resolved_tiling():
    plan = lower.lower(toy_cfg(), "prefill", 512)
    t = plan.tiling
    assert t.block_q % 128 == 0 and t.block_kv % 128 == 0
    assert t.fits


def test_dispatch_legalises_qproj_and_records():
    plan = lower.lower(toy_cfg(qk_norm=True), "decode", 256)
    assert plan.kernel_path == lower.DECODE_MEGAKERNEL
    d = lower.dispatch(plan, backend="cpu", entry="qproj_attention",
                       rope=True, qk_norm=True, lengths_masked=False)
    assert d.path == lower.FUSED_ATTENTION and d.impl == "xla"
    assert len(plan.downgrades) == 1
    # qk-norm is what keeps a qproj_attention entry off Q-fusion (its
    # kernels have no norm); RoPE is fused in-kernel and must never
    # appear as a downgrade reason
    assert "qk-norm" in plan.downgrades[0].reason
    assert "RoPE" not in plan.downgrades[0].reason
    # dedup: same deviation again only bumps the count
    lower.dispatch(plan, backend="cpu", entry="qproj_attention",
                   rope=True, qk_norm=True)
    assert len(plan.downgrades) == 1 and plan.downgrades[0].count == 2
    assert "downgrade" in plan.describe()


def test_dispatch_rope_is_a_note_not_a_downgrade():
    """RoPE between projection and scores no longer blocks Q-fusion:
    the fused kernels rotate the Q tile in-register, so a RoPE-only
    plan keeps its planned path with an empty ledger."""
    plan = lower.lower(toy_cfg(), "decode", 256)
    d = lower.dispatch(plan, backend="tpu", entry="decode_block",
                       rope=True)
    assert d.path == lower.DECODE_MEGAKERNEL and d.impl == "pallas"
    assert d.fuse_q and d.fuse_wo
    assert not plan.downgrades
    assert any("RoPE fused in-kernel" in n for n in plan.notes)


def test_dispatch_megakernel_ladder():
    """Each missing capability steps the megakernel down exactly one
    rung: a call site without Wo/residual -> qproj_attention; qk-norm
    on top -> fused_attention."""
    plan = lower.lower(toy_cfg(), "decode", 256)
    d = lower.dispatch(plan, backend="tpu", entry="qproj_attention",
                       rope=True)
    assert d.path == lower.QPROJ_ATTENTION
    assert d.fuse_q and not d.fuse_wo
    assert plan.downgrades[-1].to_path == lower.QPROJ_ATTENTION
    assert "Wo/residual" in plan.downgrades[-1].reason
    d2 = lower.dispatch(plan, backend="tpu", entry="attention")
    assert d2.path == lower.FUSED_ATTENTION and not d2.fuse_q


def test_dispatch_qk_norm_folds_into_the_megakernel():
    """qk-norm blocks nothing on a ``decode_block`` entry: the
    megakernel normalises the Q tile itself, so the plan keeps its rung
    with an empty ledger; the same plan from a ``qproj_attention``
    entry still falls past Q-fusion to fused_attention for qk-norm."""
    plan = lower.lower(toy_cfg(qk_norm=True), "decode", 256)
    d = lower.dispatch(plan, backend="tpu", entry="decode_block",
                       rope=True, qk_norm=True, lengths_masked=True,
                       paged=True)
    assert d.path == lower.DECODE_MEGAKERNEL and d.impl == "pallas"
    assert d.fuse_q and d.fuse_wo
    assert not plan.downgrades
    d2 = lower.dispatch(plan, backend="tpu", entry="qproj_attention",
                        qk_norm=True)
    assert d2.path == lower.FUSED_ATTENTION and not d2.fuse_q
    assert "qk-norm" in plan.downgrades[-1].reason


def test_dispatch_masked_lengths_stays_pallas():
    """Masked decode is legal Pallas (the scalar-prefetch masked
    kernels): fused paths keep their planned impl, the plan gets a
    note, and the downgrade ledger stays empty."""
    plan = lower.lower(toy_cfg(), "decode", 256)
    d = lower.dispatch(plan, backend="tpu", entry="decode_block",
                       lengths_masked=True)
    assert d.path == lower.DECODE_MEGAKERNEL and d.impl == "pallas"
    assert not plan.downgrades
    assert any("masked-lengths" in n for n in plan.notes)


def test_dispatch_paged_note_only_on_the_megakernel():
    """Of the Pallas paths only the decode megakernel reads a page pool
    through its table: a paged ``decode_megakernel`` dispatch carries
    the block-table note, a paged ``qproj_attention`` one (a prefill
    chunk's, whose pool ``kernels.ops`` gathers dense) does not, and
    neither records a downgrade."""
    mega = lower.lower(toy_cfg(), "decode", 256)
    d = lower.dispatch(mega, backend="tpu", entry="decode_block",
                       rope=True, lengths_masked=True, paged=True)
    assert (d.path, d.impl, d.paged) == (lower.DECODE_MEGAKERNEL,
                                         "pallas", True)
    qproj = lower.lower(toy_cfg(), "decode", 256, decode_tokens=8)
    d2 = lower.dispatch(qproj, backend="tpu", entry="qproj_attention",
                        rope=True, lengths_masked=True, paged=True)
    assert (d2.path, d2.impl, d2.paged) == (lower.QPROJ_ATTENTION,
                                            "pallas", True)
    assert any("block table" in n for n in mega.notes)
    assert not any("block table" in n for n in qproj.notes)
    assert not mega.downgrades and not qproj.downgrades


def test_impl_for_backend_matrix():
    assert lower.impl_for(lower.UNFUSED, "tpu") == "reference"
    assert lower.impl_for(lower.FUSED_ATTENTION, "tpu") == "pallas"
    assert lower.impl_for(lower.FUSED_ATTENTION, "cpu") == "xla"
    assert lower.impl_for(lower.FUSED_ATTENTION, "cpu",
                          interpret=True) == "pallas"


def test_plan_cache_identity_per_bucket():
    cfg = toy_cfg()
    lower.clear_plan_cache()
    a = lower.resolve_plan(cfg, "decode", 100)
    b = lower.resolve_plan(cfg, "decode", 128)   # same bucket (64,128]
    c = lower.resolve_plan(cfg, "decode", 129)   # next bucket
    assert a is b and a is not c
    assert a.bucket == 128 and c.bucket == 256
    info = lower.plan_cache_info()
    assert info.hits >= 1 and info.misses >= 2


def test_kernel_plan_prefill_shares_bucket_entries():
    """Shape-only prefill resolution must not fragment the cache: all
    seq_q in one bucket share one entry (decode_tokens is normalised
    out of the prefill key)."""
    lower.clear_plan_cache()
    a = lower.kernel_plan(seq_q=100, seq_kv=100, d_head=32,
                          n_heads=4, n_kv_heads=2)
    b = lower.kernel_plan(seq_q=120, seq_kv=120, d_head=32,
                          n_heads=4, n_kv_heads=2)
    assert a is b and a.phase == "prefill" and a.bucket == 128


def test_serving_plan_unsupported_config_is_none():
    # an SSM block is not lowerable; MLA is, as its absorbed head
    # (tests/test_mla_moe.py)
    ssm = SimpleNamespace(name="ssm-ish", d_model=128, n_heads=4,
                          kv_heads=4, head_dim=32, d_ff=256,
                          attention="gqa", rope_theta=1e6,
                          qk_norm=False, n_layers=2,
                          block_kind=lambda i: "ssm")
    assert lower.serving_plan(ssm, max_len=64) is None
    assert lower.serving_plan(toy_cfg(), max_len=64) is not None


def test_predict_matches_engine_closed_form_regime():
    """The lowered decode plan's predicted peak is context-independent
    (A_LF = 2MN per head) while the forced-LBL counterfactual grows
    with C — the alpha_kv statement, via the ExecutionPlan API."""
    cfg = toy_cfg()
    fused_small = lower.lower(cfg, "decode", 256)
    fused_large = lower.lower(cfg, "decode", 1024)
    lbl_small = lower.lower(cfg, "decode", 256, fuse_q=False,
                            fuse_scores=False)
    lbl_large = lower.lower(cfg, "decode", 1024, fuse_q=False,
                            fuse_scores=False)
    assert fused_small.predicted_peak_words == \
        fused_large.predicted_peak_words
    assert lbl_large.predicted_peak_words > lbl_small.predicted_peak_words


# ---------------------------------------------------------------------------
# slow tier: plans executed by the runtime (JAX; Pallas interpret on CPU)
# ---------------------------------------------------------------------------

try:
    import jax
    import jax.numpy as jnp
    import numpy as np
    HAVE_JAX = True
except ImportError:                  # the fast IR tests above still run
    HAVE_JAX = False

needs_jax = pytest.mark.skipif(not HAVE_JAX, reason="needs jax")


@needs_jax
@pytest.mark.slow
def test_ops_auto_resolves_through_plan_cache():
    from repro.kernels import ops
    lower.clear_plan_cache()
    q = jax.random.normal(jax.random.PRNGKey(0), (1, 4, 256, 32))
    k = jax.random.normal(jax.random.PRNGKey(1), (1, 2, 256, 32))
    v = jax.random.normal(jax.random.PRNGKey(2), (1, 2, 256, 32))
    o = ops.attention(q, k, v, causal=True, impl="auto")
    o_ref = ops.attention(q, k, v, causal=True, impl="reference")
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref),
                               rtol=2e-5, atol=2e-5)
    assert lower.plan_cache_info().misses >= 1


@needs_jax
@pytest.mark.slow
def test_ops_lengths_pallas_runs_masked_kernel_without_warning():
    """impl='pallas' + lengths executes the masked scalar-prefetch
    kernel (no silent downgrade, no warning) and matches the
    materialising reference."""
    from repro.kernels import ops
    q = jax.random.normal(jax.random.PRNGKey(0), (2, 2, 1, 32))
    k = jax.random.normal(jax.random.PRNGKey(1), (2, 2, 16, 32))
    v = jax.random.normal(jax.random.PRNGKey(2), (2, 2, 16, 32))
    lengths = jnp.array([8, 16], jnp.int32)
    ops.reset_downgrade_warnings()
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        o = ops.attention(q, k, v, causal=False, lengths=lengths,
                          impl="pallas", interpret=True)
    assert not [x for x in w if "masked-lengths" in str(x.message)], \
        "masked lengths must not downgrade off the Pallas path"
    o_ref = ops.attention(q, k, v, causal=False, lengths=lengths,
                          impl="reference")
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref),
                               rtol=2e-5, atol=2e-5)


@needs_jax
@pytest.mark.slow
def test_ops_lengths_downgrade_warns_once_with_reason():
    """The remaining ledger path: calls the masked kernel cannot serve
    (here: non-integral lengths) warn exactly once and record the
    concrete reason."""
    from repro.kernels import ops
    q = jax.random.normal(jax.random.PRNGKey(0), (2, 2, 1, 32))
    k = jax.random.normal(jax.random.PRNGKey(1), (2, 2, 16, 32))
    v = jax.random.normal(jax.random.PRNGKey(2), (2, 2, 16, 32))
    bad = jnp.array([8.0, 16.0], jnp.float32)
    ops.reset_downgrade_warnings()
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        o = ops.attention(q, k, v, causal=False, lengths=bad,
                          impl="pallas")
        ops.attention(q, k, v, causal=False, lengths=bad, impl="pallas")
    msgs = [x for x in w if "masked-lengths" in str(x.message)]
    assert len(msgs) == 1, "downgrade must warn exactly once"
    assert "integral" in str(msgs[0].message)
    o_ref = ops.attention(q, k, v, causal=False,
                          lengths=bad.astype(jnp.int32),
                          impl="reference")
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref),
                               rtol=2e-5, atol=2e-5)


@needs_jax
@pytest.mark.slow
@pytest.mark.parametrize("arch", ZOO)
def test_lowered_prefill_plan_executes_in_pallas_interpret(arch):
    """The DSE-chosen prefill plan, dispatched for interpret mode,
    really runs the Pallas kernel and matches the reference."""
    from repro import configs
    from repro.kernels import ops
    cfg = configs.get_config(arch, smoke=True)
    plan = lower.resolve_plan(cfg, "prefill", 128)
    assert plan.kernel_path == lower.FUSED_ATTENTION   # M=128 > N=32
    d = lower.dispatch(plan, backend="cpu", interpret=True)
    assert d.impl == "pallas" and d.interpret
    hq, hkv, dh = cfg.n_heads, cfg.kv_heads, cfg.head_dim
    q = jax.random.normal(jax.random.PRNGKey(0), (1, hq, 128, dh))
    k = jax.random.normal(jax.random.PRNGKey(1), (1, hkv, 128, dh))
    v = jax.random.normal(jax.random.PRNGKey(2), (1, hkv, 128, dh))
    o = ops.attention(q, k, v, causal=True, plan=d)
    o_ref = ops.attention(q, k, v, causal=True, impl="reference")
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref),
                               rtol=2e-5, atol=2e-5)


@needs_jax
@pytest.mark.slow
@pytest.mark.parametrize("arch", ZOO)
def test_serve_plan_end_to_end_equivalence_and_crossover(arch):
    """Acceptance: lower the DSE prefill + decode PhasePlans, execute
    via serve_step in interpret mode, assert (a) numerical equivalence
    with the reference path and (b) the decode plan switches kernel
    path when the KV context crosses alpha_kv's C = 2N."""
    from repro import configs
    from repro.models import init_params_and_axes
    from repro.serve import (init_decode_state, make_serving_plan,
                             prefill, serve_step)
    cfg = configs.get_config(arch, smoke=True)
    n = cfg.head_dim
    crossover = 2 * n
    prompt_len, steps = crossover - 3, 6
    max_len = crossover * 2
    params, _ = init_params_and_axes(jax.random.PRNGKey(0), cfg)
    prompt = jax.random.randint(jax.random.PRNGKey(4),
                                (2, prompt_len), 0, cfg.vocab_size)

    lower.clear_plan_cache()
    plan = make_serving_plan(cfg, max_len=max_len, interpret=True)
    assert plan is not None and plan.crossover_ctx == crossover

    # reference: the materialising path end to end, no plan
    ref_cfg = dataclasses.replace(cfg, attn_impl="reference")
    s_ref = init_decode_state(ref_cfg, 2, max_len, jnp.float32)
    s_ref = prefill(params, ref_cfg, prompt, s_ref)
    ref_toks = [np.asarray(s_ref.last_token)]
    for _ in range(steps):
        s_ref = serve_step(params, ref_cfg, s_ref)
        ref_toks.append(np.asarray(s_ref.last_token))

    # plan-driven, interpret mode
    s = init_decode_state(cfg, 2, None, jnp.float32, plan=plan)
    s = prefill(params, cfg, prompt, s, plan=plan, interpret=True)
    toks = [np.asarray(s.last_token)]
    for _ in range(steps):
        s = serve_step(params, cfg, s, plan=plan, interpret=True)
        toks.append(np.asarray(s.last_token))

    # (a) numerical equivalence: same greedy trajectory
    for a, b in zip(ref_toks, toks):
        np.testing.assert_array_equal(a, b)

    # (b) the kernel path switched exactly at the crossover, and above
    # it both archs climb to the decode megakernel: RoPE is fused
    # in-kernel, and so is qwen3's qk-norm
    fused = lower.DECODE_MEGAKERNEL
    decode_res = [r for r in plan.resolutions if r[0] == "decode"]
    assert len(decode_res) == steps
    paths = {ctx: path for (_, ctx, _, path, _) in decode_res}
    for ctx, path in paths.items():
        want = lower.UNFUSED if ctx <= crossover else fused
        assert path == want, (ctx, path)
    assert lower.UNFUSED in paths.values()
    assert fused in paths.values()

    # acceptance: the fused decode steps really executed Pallas (the
    # masked scalar-prefetch kernels / the megakernel) — ZERO lengths
    # downgrades; the resolved kernel path is the path that ran
    fused_steps = [r for r in decode_res if r[3] == fused]
    assert fused_steps and all(r[4] == "pallas" for r in fused_steps)
    above = lower.resolve_plan(cfg, "decode", crossover + 1,
                               n_blocks=cfg.n_layers)
    # RoPE-bearing configs, qk-norm or not, on the Q-fused megakernel
    # path with an EMPTY downgrade ledger
    assert not above.downgrades, above.downgrades
    assert any("RoPE fused in-kernel" in n for n in above.notes)
    assert not any("masked-lengths" in g.reason
                   for g in above.downgrades), above.downgrades
    assert any("masked-lengths" in n for n in above.notes)


@needs_jax
@pytest.mark.slow
def test_decode_logits_equivalence_across_paths():
    """Logits (not just argmax) agree between the plan-driven and
    reference decode paths on both sides of the crossover."""
    from repro import configs
    from repro.models import init_params_and_axes
    from repro.serve import (decode_step, init_decode_state,
                             make_serving_plan, prefill)
    cfg = configs.get_config("qwen3-8b", smoke=True)
    params, _ = init_params_and_axes(jax.random.PRNGKey(0), cfg)
    prompt = jax.random.randint(jax.random.PRNGKey(4), (1, 61), 0,
                                cfg.vocab_size)
    ref_cfg = dataclasses.replace(cfg, attn_impl="reference")
    lower.clear_plan_cache()
    plan = make_serving_plan(cfg, max_len=96)

    s = init_decode_state(cfg, 1, 96, jnp.float32)
    s = prefill(params, cfg, prompt, s, plan=plan)
    s_ref = init_decode_state(ref_cfg, 1, 96, jnp.float32)
    s_ref = prefill(params, ref_cfg, prompt, s_ref)
    for _ in range(5):                 # ctx 62..66 crosses 64
        s, logits = decode_step(params, cfg, s, plan=plan)
        s_ref, logits_ref = decode_step(params, ref_cfg, s_ref)
        np.testing.assert_allclose(np.asarray(logits),
                                   np.asarray(logits_ref),
                                   rtol=1e-4, atol=1e-4)


@needs_jax
@pytest.mark.slow
def test_validate_costmodel_emits_ranking_table():
    """The measured-vs-predicted harness runs on the interpret backend
    and emits ranking + scaling agreement rows (acceptance)."""
    sys.path.insert(0, str(REPO / "tools"))
    try:
        import validate_costmodel as vc
    finally:
        sys.path.pop(0)
    rows = vc.validate(("qwen3-8b",), smoke=True, backend="interpret",
                       prefill_seqs=(64, 256), decode_ctxs=(48, 192),
                       repeats=3)   # best-of-3: timing must be stable
                                    # enough for the scaling assertion
    runs = [r for r in rows if r["kind"] == "run"]
    rankings = [r for r in rows if r["kind"] == "ranking"]
    scalings = [r for r in rows if r["kind"] == "scaling"]
    assert runs and rankings and scalings
    for r in runs:
        assert r["predicted_cycles"] > 0 and r["measured_us"] > 0
        assert r["path"] in lower.KERNEL_PATHS
    for r in rankings:
        assert 0.0 <= r["rank_agreement"] <= 1.0
    # shape scaling: the predicted-faster (smaller) shape is measured
    # faster — robust for prefill, whose work grows quadratically
    # (decode at M=1 is dispatch-overhead-bound at these toy depths,
    # so its scaling rows are emitted but not asserted)
    for r in scalings:
        assert r["pairs"] >= 1
        if r["phase"] == "prefill":
            assert r["rank_agreement"] == 1.0, r
    # interpret mode really took the Pallas kernel on fused paths
    assert any(r["impl"] == "pallas" for r in runs)
