"""The ``mla_moe`` architecture (DeepSeek-V3) through the harness on the
CPU: a tiny configuration of the cell's file, with its router, expert
share and YaRN, served by the paged engine and checked against
``bench/arch/mla_moe.py``'s reference; the fp8 control fails the same
limits; the ``moe`` scope's ops are named from the program's own
executable."""

import json
import re

import pytest

from conftest import ROOT, make_tiny_root
from harness import cell, check
from harness.manifest import Manifest, load_module

READER = load_module(ROOT / "bench" / "metrics" / "moe.decode_ms.py")

SEED = 2 ** 33 + 777            # seeds past 32 bits, as benchmark runs use
#: the window (s): long enough that a CPU shared with other test workers
#: still serves the check's 8 tokens
WINDOW = 3.0
#: deepseek-v3.json at a CPU size: 16 experts routed in 4 groups (the
#: best 2 kept), top-4, 4 held; YaRN over a 32-position window, so the
#: cell's contexts run past it
TINY = {
    "num_hidden_layers": 3, "first_k_dense_replace": 1, "hidden_size": 64,
    "intermediate_size": 128, "moe_intermediate_size": 32,
    "num_attention_heads": 4, "num_key_value_heads": 4, "q_lora_rank": 32,
    "kv_lora_rank": 48, "qk_nope_head_dim": 16, "qk_rope_head_dim": 16,
    "v_head_dim": 16, "n_group": 4, "topk_group": 2,
    "num_experts_per_tok": 4, "n_routed_experts": 4, "vocab_size": 256,
    "torch_dtype": "float32",
    "published": {"num_hidden_layers": 61, "first_k_dense_replace": 3,
                  "n_routed_experts": 16, "num_nextn_predict_layers": 1},
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 4,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 32,
                     "type": "yarn"},
}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = make_tiny_root(tmp_path_factory.mktemp("dsv3"), loop="closed")
    bench = root / "bench"
    cfg = json.loads((bench / "configs" / "deepseek-v3.json").read_text())
    cfg.update(TINY, name="dsv3-tiny")
    (bench / "configs" / "dsv3-tiny.json").write_text(json.dumps(cfg))
    man = json.loads((root / "BENCHMARK.json").read_text())
    man["configs"].append({"name": "dsv3-tiny", "source": "tests",
                           "file": "bench/configs/dsv3-tiny.json",
                           "reduced": [], "why": "CPU test size"})
    man["workloads"].append({"name": "dsv3-tiny.mix", "config": "dsv3-tiny",
                             "traffic": "tiny-mix", "chips": 1,
                             "why": "CPU test cell"})
    man["per_layer"] = [m for m in man["per_layer"]
                        if m["name"] not in ("mla_decode_roofline",
                                             "moe.decode_ms")] + [
        {"name": "mla_decode_roofline", "unit": "%", "better": "higher",
         "source": "device_trace", "layer": "kernels",
         "moves": "tokens_per_s", "workloads": ["dsv3-tiny.mix"]},
        {"name": "moe.decode_ms", "unit": "ms", "better": "lower",
         "source": "device_trace", "layer": "model step",
         "moves": "tokens_per_s", "workloads": ["dsv3-tiny.mix"]}]
    (root / "BENCHMARK.json").write_text(json.dumps(man))
    return root


def test_cell_runs_and_matches_the_reference(root, capsys):
    out = cell.run(Manifest(root), "dsv3-tiny.mix", SEED, WINDOW, True, 0.0,
                   compile_cache=False)
    assert out["correct"] is True, out["check"]
    assert out["check"]["tokens_checked"]["value"] >= 8
    # the CPU trace has no device ops: the scope's time is 0, the MLA
    # kernel's roofline unread
    assert out["metrics"]["moe.decode_ms"]["value"] == 0.0
    assert "mla_decode_roofline" not in out["metrics"]
    printed = capsys.readouterr().out
    # the program kept its routing at every decoded read position (all
    # but each checked request's first, which its prefill predicts), and
    # (float32 here) every choice it made was the reference's own or
    # within a tie of it: none refused
    line = re.search(r"reference: of (\d+) read positions, (\d+) took the "
                     r"program's choice of experts at a tie within \S+ and "
                     r"(\d+) had a choice further off; the program kept its "
                     r"routing at (\d+) of them", printed)
    assert line, printed
    read, took, refused, kept = map(int, line.groups())
    n_checked = int(re.search(r"check: (\d+) requests", printed).group(1))
    assert kept == read - n_checked > 0 and refused == 0
    assert "plan.resolutions in the window" in printed
    assert "'mla_decode'" in printed


def test_control_fails_and_scopes_name_the_executables_ops(root):
    import jax.numpy as jnp
    from repro.serve import tracing
    ses = cell.Session(Manifest(root), "dsv3-tiny.mix", SEED,
                       compile_cache=False)
    s = ses.serve(ses.plan(SEED), WINDOW)
    eng = ses.engine
    decode = [f for k, f in eng._launches.items() if k[0] == "decode"]
    assert decode
    text = decode[0].lower(eng.params, eng.state,
                           jnp.asarray(eng.live)).compile().as_text()
    real = READER.scope_ops([text], "moe")
    assert real and real <= READER.scope_ops(
        tracing.compiled_texts("decode"), "moe")
    assert READER.scope_ops([text], "mla")
    reqs, logits = s["driver"].reqs, eng.logits
    ses.free_engine()
    lim = ses.mix["check"]
    limits = {"logit_gap": float(lim["logit_gap_limit"]),
              "logit_rel": float(lim["logit_rel_limit"]),
              "tokens_checked": int(lim["tokens_checked_min"])}
    read = ses.check(reqs, logits, control=True)
    ok, _ = check.verdict(read, limits)
    assert ok, read
    # the fp8 control, read at the same positions, fails a limit
    assert (read["control_gap"] > limits["logit_gap"]
            or read["control_rel"] > limits["logit_rel"]), read


HLO = """HloModule m

%fused_computation (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  ROOT %tanh.0 = f32[8]{0} tanh(%p), metadata={op_name="jit(f)/moe/router/tanh"}
}

%fused_computation.1 (p.1: f32[8]) -> f32[8] {
  %p.1 = f32[8]{0} parameter(0)
  %neg.0 = f32[8]{0} negate(%p.1), metadata={op_name="jit(f)/moe/experts/neg"}
  ROOT %exp.0 = f32[8]{0} exponential(%neg.0), metadata={op_name="jit(f)/mla/exp"}
}

%fused_computation.2 (p.2: f32[8]) -> (f32[8], f32[8]) {
  %p.2 = f32[8]{0} parameter(0)
  %abs.0 = f32[8]{0} abs(%p.2), metadata={op_name="jit(f)/mla/abs"}
  ROOT %tuple.0 = (f32[8]{0}, f32[8]{0}) tuple(%abs.0, %p.2)
}

ENTRY %main.2 (x.1: f32[8]) -> f32[8] {
  %x.1 = f32[8]{0} parameter(0), metadata={op_name="x"}
  %dot.1 = f32[8]{0} multiply(%x.1, %x.1), metadata={op_name="jit(f)/mla/mul"}
  %exp_fusion = f32[8]{0} fusion(%dot.1), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(f)/moe/experts/neg"}
  %multi = (f32[8]{0}, f32[8]{0}) fusion(%exp_fusion), kind=kLoop, calls=%fused_computation.2, metadata={op_name="jit(f)/moe/shared/abs"}
  ROOT %tanh_fusion = f32[8]{0} fusion(%dot.1), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(f)/add"}
}
"""


@pytest.mark.parametrize("scope, ops", [
    # a fusion is where its root is: tanh_fusion's root is the router's
    ("moe", {"tanh.0", "tanh_fusion", "neg.0", "multi"}),
    ("router", {"tanh.0", "tanh_fusion"}),
    # exp_fusion holds a moe instruction, but its root is mla's; the
    # multi-output fusion's tuple root has no path, so its own counts
    ("mla", {"dot.1", "exp.0", "exp_fusion", "abs.0"}),
    ("shared", {"multi"}),
])
def test_scope_ops_put_a_fusion_where_its_root_is(scope, ops):
    assert READER.scope_ops([HLO], scope) == ops


ARCH = load_module(ROOT / "bench" / "arch" / "mla_moe.py")
#: biased scores (bias 0) of the 16 experts in 4 groups: groups 0 and 1
#: kept (1.70, 1.55 against 0.9, 0.5), experts 0, 4, 1, 5 chosen, with
#: a wide margin to the next
SCORES = [0.90, 0.80, 0.10, 0.10, 0.85, 0.70, 0.10, 0.10,
          0.50, 0.40, 0.10, 0.10, 0.30, 0.20, 0.10, 0.10]


@pytest.mark.parametrize("change, forced, taken", [
    # expert 6 a hair under expert 5: the swap is within rounding
    ({6: 0.698}, [0, 4, 1, 6], True),
    # group 2 a hair under group 1: its two best in place of group 1's
    ({8: 0.78, 9: 0.766}, [0, 8, 1, 9], True),
    # the same choices with the scores as they are: too far off
    ({}, [0, 4, 1, 6], False),
    ({}, [0, 8, 1, 9], False),
    # a duplicate is no choice of four experts
    ({6: 0.698}, [0, 4, 1, 1], False),
])
def test_reference_takes_a_choice_only_within_a_tie(change, forced, taken):
    import jax.numpy as jnp
    import numpy as np
    cfg = json.loads((ROOT / "bench" / "configs"
                      / "deepseek-v3.json").read_text())
    d = ARCH.Dims.from_config({**cfg, **TINY})
    sc = np.array(SCORES, np.float32)
    for e, v in change.items():
        sc[e] = v
    logits = jnp.log(sc / (1 - sc))[None]
    bias = jnp.zeros((d.routed,), jnp.float32)
    want = jnp.asarray([forced], jnp.int32)
    w0, ids0, _ = ARCH.route(d, logits, bias)
    for has in (False, True):
        w, ids, info = ARCH.route(d, logits, bias, want,
                                  jnp.asarray([has]))
        took, refused = (bool(v) for v in np.asarray(info[0, 2:]))
        if has and taken:
            assert (took, refused) == (True, False)
            assert list(np.asarray(ids[0])) == forced
            np.testing.assert_allclose(
                w[0], sc[forced] / sc[forced].sum() * d.routed_scale,
                rtol=1e-6)
        else:
            # not given, or refused: the rule's own choice and weights
            assert (took, refused) == (False, has)
            np.testing.assert_array_equal(ids, ids0)
            np.testing.assert_array_equal(w, w0)
