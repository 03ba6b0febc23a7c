"""The architecture seam (bench/arch/<arch>.py, found by a configuration
file's "arch" key): the GQA module makes the same weights and the same
reference hidden states, bit for bit, as the harness made before they
moved there; and an architecture added as a file, with a configuration
that names it, runs through the harness with no edit to the harness."""

import hashlib
import json
import pathlib
import shutil

import numpy as np
import pytest

from conftest import ROOT, make_tiny_root
from harness import check, reference
from harness.cell import Session
from harness.manifest import Manifest
from harness.recorder import Span

DATA = json.loads((pathlib.Path(__file__).parent / "data"
                   / "hashes_before_arch_move.json").read_text())
SEED = 2 ** 33 + 12345          # the driver's seeds exceed 32 bits
#: each configuration at a size the CPU runs in seconds: its file's
#: activation, qk-norm, RoPE base, eps and bf16 kept, the sizes cut
SMOKE = {
    "starcoder2-7b": {"num_hidden_layers": 2, "hidden_size": 96,
                      "intermediate_size": 192, "num_attention_heads": 6,
                      "num_key_value_heads": 2, "head_dim": 16,
                      "vocab_size": 256},
    "qwen3-8b": {"num_hidden_layers": 2, "hidden_size": 64,
                 "intermediate_size": 128, "num_attention_heads": 4,
                 "num_key_value_heads": 2, "head_dim": 16,
                 "vocab_size": 256},
}


def params_digest(params) -> str:
    import jax
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        a = np.asarray(leaf)
        h.update(f"{jax.tree_util.keystr(path)} {a.dtype} {a.shape}"
                 .encode())
        h.update(a.tobytes())
    return h.hexdigest()


def array_digest(a) -> str:
    a = np.asarray(a)
    return hashlib.sha256(f"{a.dtype} {a.shape}".encode()
                          + a.tobytes()).hexdigest()


def inputs(vocab: int):
    """Two sequences of one query block and eight read positions."""
    import jax.numpy as jnp
    S = reference.QBLOCK
    toks = np.random.default_rng(0).integers(0, vocab, (2, S))
    pos = np.random.default_rng(1).integers(0, S, (2, 8))
    return jnp.asarray(toks, jnp.int32), jnp.asarray(pos, jnp.int32)


@pytest.mark.parametrize("name", sorted(SMOKE))
def test_gqa_weights_and_reference_are_bit_identical(name):
    man = Manifest(ROOT)
    cfg = dict(man.config(name), **SMOKE[name])
    arch = man.arch(cfg["arch"])
    d = arch.dims(cfg)
    params = arch.make_params(d, SEED)
    assert params_digest(params) == DATA["params"][name]
    h = arch.final_hidden(params, d, *inputs(d.vocab))
    assert array_digest(h) == DATA["hidden"][name]


def test_added_architecture_needs_files_and_entries_only(tmp_path):
    root = make_tiny_root(tmp_path)
    bench = root / "bench"
    shutil.copy(bench / "arch" / "gqa.py", bench / "arch" / "gqa_twin.py")
    cfg = json.loads((bench / "configs" / "tiny.json").read_text())
    cfg.update(name="twin", arch="gqa_twin")
    (bench / "configs" / "twin.json").write_text(json.dumps(cfg))
    man_path = root / "BENCHMARK.json"
    man = json.loads(man_path.read_text())
    man["configs"].append({"name": "twin", "source": "tests",
                           "file": "bench/configs/twin.json",
                           "reduced": [], "why": "CPU test size"})
    man["workloads"].append({"name": "twin.mix", "config": "twin",
                             "traffic": "tiny-mix", "chips": 1,
                             "why": "CPU test cell"})
    man_path.write_text(json.dumps(man))
    for p in (ROOT / "bench" / "harness").glob("*.py"):
        assert (bench / "harness" / p.name).read_bytes() == p.read_bytes()

    m = Manifest(root)
    arch = m.arch(m.config("twin")["arch"])
    assert pathlib.Path(arch.__file__).name == "gqa_twin.py"
    d = arch.dims(m.config("twin"))
    params = arch.make_params(d, 3)
    h = np.asarray(arch.final_hidden(params, d, *inputs(d.vocab)))
    assert h.shape == (2, 8, d.d_model) and np.isfinite(h).all()
    span = Span("decode", 0, 1, "decode_megakernel", rows=2,
                contexts=(3, 5))
    assert arch.step_flops(d, span) > 0
    assert arch.layer_calls(d, "decode_megakernel") == d.layers

    # the harness drives a cell of the added architecture end to end
    ses = Session(m, "twin.mix", 3, compile_cache=False)
    assert ses.arch is arch
    s = ses.serve(ses.plan(3), 1.0)
    reqs, logits = s["driver"].reqs, ses.engine.logits
    ses.free_engine()
    read = ses.check(reqs, logits)
    lim = ses.mix["check"]
    ok, _ = check.verdict(read, {
        "logit_gap": float(lim["logit_gap_limit"]),
        "logit_rel": float(lim["logit_rel_limit"]),
        "tokens_checked": int(lim["tokens_checked_min"])})
    assert ok, read
