"""Each kernel's FLOP and byte count (bench/kernels/) against a hand
computation, and the model FLOPs behind mfu.* (bench/harness/counts.py,
with the GQA architecture's bench/arch/gqa.py)."""

import pathlib

import pytest

from harness import counts
from harness.manifest import load_module
from harness.recorder import Span

BENCH = pathlib.Path(__file__).parents[2] / "bench"
KERNELS = BENCH / "kernels"
GQA = load_module(BENCH / "arch" / "gqa.py")

# small numbers so the hand computation is easy to follow
D = GQA.Dims(layers=2, d_model=8, heads=4, kv_heads=2, head_dim=2,
             d_ff=16, vocab=10, mlp="gelu", qk_norm=False, rope_theta=1e4,
             eps=1e-6, dtype="bfloat16")


def kernel(name):
    return load_module(KERNELS / f"{name}.py")


def test_decode_megakernel():
    k = kernel("fused_decode_block_paged")
    assert (k.PHASE, k.PATH) == ("decode", "decode_megakernel")
    s = Span("decode", 0, 1, "decode_megakernel", rows=2, contexts=(3, 5))
    f, b = k.cost(D, s)
    # per row: Q 2*8*4*2 = 128, scores+PV 4*4*2*c, O 2*4*2*8 = 128
    assert f == 2 * (128 + 128) + 4 * 4 * 2 * (3 + 5)
    # Wq, Wo 8*4*2 each; K,V 2*2*2 per token; x, residual, out 8 a row
    assert b == 2 * (64 + 64 + 2 * 2 * 2 * 8 + 3 * 2 * 8)


def test_paged_attention_decode():
    k = kernel("fused_attention_paged")
    assert (k.PHASE, k.PATH) == ("decode", "fused_attention")
    s = Span("decode", 0, 1, "fused_attention", rows=2, contexts=(3, 5))
    f, b = k.cost(D, s)
    assert f == 4 * 4 * 2 * 8
    assert b == 2 * (2 * 2 * 2 * 8 + 2 * 2 * 4 * 2)


def test_masked_attention_prefill_chunk():
    k = kernel("fused_attention_masked")
    assert (k.PHASE, k.PATH) == ("prefill", "fused_attention")
    s = Span("prefill", 0, 1, "fused_attention", rows=3, offset=4)
    f, b = k.cost(D, s)
    # causal columns: rows attend 5, 6 and 7 columns
    assert counts.causal_cols(3, 4) == 5 + 6 + 7
    assert f == 4 * 4 * 2 * 18
    assert b == 2 * (2 * 2 * 2 * 7 + 2 * 3 * 4 * 2)


def test_qproj_attention_prefill_chunk():
    k = kernel("fused_qproj_attention_masked")
    assert (k.PHASE, k.PATH) == ("prefill", "qproj_attention")
    s = Span("prefill", 0, 1, "qproj_attention", rows=3, offset=4)
    f, b = k.cost(D, s)
    assert f == 2 * 3 * 8 * 4 * 2 + 4 * 4 * 2 * 18
    assert b == 2 * (3 * 8 + 8 * 4 * 2 + 2 * 2 * 2 * 7 + 3 * 4 * 2)


def test_model_flops():
    # a layer: attention 8*8 + 2*8*4 + 8*8 = 192, gelu MLP 2*8*16 = 256
    assert D.layer_params == 448
    dec = Span("decode", 0, 1, "x", rows=2, contexts=(3, 5))
    assert GQA.step_flops(D, dec) == (
        2 * (2 * 2 * 448 + 2 * 8 * 10) + 4 * 2 * 4 * 2 * 8)
    pre = Span("prefill", 0, 1, "x", rows=3, offset=4)
    assert GQA.step_flops(D, pre) == (
        3 * 2 * 2 * 448 + 2 * 8 * 10 + 4 * 2 * 4 * 2 * 18)


class _Run:
    def __init__(self, spans, events=None):
        self.spans, self.events, self.dims = spans, events, D
        self.arch = GQA
        self.peaks = {"bf16_flops": 1e3, "hbm_bytes_per_s": 1e3}
        self.kernels = [kernel(p.stem) for p in sorted(KERNELS.glob("*.py"))]
        self.notes = []


def test_mfu_over_host_time():
    spans = [Span("decode", 0.0, 2.0, "x", rows=1, contexts=(4,))]
    want = GQA.step_flops(D, spans[0]) / 2.0 / 1e3 * 100
    assert counts.mfu(_Run(spans), "decode") == pytest.approx(want)
    assert counts.mfu(_Run(spans), "prefill") is None


def test_roofline_least_time_over_kernel_time():
    k = kernel("fused_decode_block_paged")
    span = Span("decode", 0, 1, "decode_megakernel", rows=1, contexts=(4,))
    f, b = k.cost(D, span)
    least = D.layers * max(f / 1e3, b / 1e3)
    ev = {"host": [["window", 0, 10**9], ["decode_step", 0, 10**9]],
          "device": [["checkpoint.1 custom-call:tpu_custom_call", 0, 10**8],
                     ["checkpoint.1 custom-call:tpu_custom_call", 2 * 10**8,
                      10**8],
                     ["fusion.2 fusion", 4 * 10**8, 10**8]]}
    got = counts.roofline(_Run([span], ev), "decode")
    assert got == pytest.approx(100 * least / 0.2)
    # no trace, or no kernel for the path: nothing to read
    assert counts.roofline(_Run([span]), "decode") is None
    assert counts.roofline(_Run([span], ev), "prefill") is None
