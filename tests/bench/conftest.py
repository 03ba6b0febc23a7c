"""Shared helpers of the benchmark's tests: the harness on ``sys.path``
and a temporary copy of the benchmark with a tiny configuration and
cell that run on the CPU in seconds."""

from __future__ import annotations

import json
import pathlib
import shutil
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "bench"))
sys.path.insert(0, str(ROOT / "src"))

TINY_CONFIG = {
    "name": "tiny", "model_type": "starcoder2", "arch": "gqa",
    "hidden_size": 64,
    "intermediate_size": 128, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "num_hidden_layers": 2,
    "vocab_size": 256, "rope_theta": 10000.0,
    "hidden_act": "gelu_pytorch_tanh", "rms_norm_eps": 1e-6,
    "tie_word_embeddings": False, "torch_dtype": "float32",
}

TINY_MIX = {
    "loop": "open", "rate": 40.0,
    "prompt": {"dist": "lognormal", "median": 40, "sigma": 0.5,
               "min": 16, "max": 80},
    "output": {"dist": "uniform", "min": 4, "max": 12},
    "grid": 16, "pool": 200, "pool_seed": 7, "fill_rows": 3,
    "fill_group": 2,
    "engine": {"batch": 4, "max_len": 128, "prefill_chunk": 32,
               "page_size": 16},
    "check": {"sample_tokens": 32, "max_sequences": 2,
              "tokens_checked_min": 8, "logit_gap_limit": 1e-3,
              "logit_rel_limit": 1e-4},
}


def make_tiny_root(dst: pathlib.Path, *, mlp: str = "gelu_pytorch_tanh",
                   qk_norm: bool = False, loop: str = "open") -> pathlib.Path:
    """A copy of the benchmark under ``dst`` with one tiny configuration
    (``tiny``) and one cell (``tiny.mix``) added as files and entries."""
    shutil.copytree(ROOT / "bench", dst / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    man = json.loads((ROOT / "BENCHMARK.json").read_text())
    cfg = dict(TINY_CONFIG, hidden_act=mlp, qk_norm=qk_norm)
    (dst / "bench" / "configs" / "tiny.json").write_text(json.dumps(cfg))
    mix = dict(TINY_MIX, loop=loop)
    if loop == "closed":
        mix.update(clients=4, fill_rows=4)
    (dst / "bench" / "traffic" / "tiny-mix.json").write_text(
        json.dumps(mix))
    peaks = json.loads((dst / "bench" / "peaks.json").read_text())
    peaks["devices"]["cpu"] = {"bf16_flops": 1e12,
                               "hbm_bytes_per_s": 1e11}
    (dst / "bench" / "peaks.json").write_text(json.dumps(peaks))
    man["configs"].append({"name": "tiny", "source": "tests",
                           "file": "bench/configs/tiny.json",
                           "reduced": [], "why": "CPU test size"})
    man["workloads"].append({"name": "tiny.mix", "config": "tiny",
                             "traffic": "tiny-mix", "chips": 1,
                             "why": "CPU test cell"})
    for m in man["end_to_end"] + man["per_layer"]:
        if "workloads" in m:
            m["workloads"].append("tiny.mix")
    (dst / "BENCHMARK.json").write_text(json.dumps(man, indent=1))
    return dst


@pytest.fixture
def tiny_root(tmp_path):
    return make_tiny_root(tmp_path)
