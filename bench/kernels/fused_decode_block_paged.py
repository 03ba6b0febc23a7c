"""The paged decode megakernel (``kernels/fused_decode_block.py``):
Q projection, RoPE, masked attention over the row's pages, output
projection and residual add of one layer, for every row of a decode
step.  What the step needs at least, per layer call:

* FLOPs, per live row of context c: 2*E*Hq*D (Q) + 4*Hq*D*c (scores
  and P.V) + 2*Hq*D*E (output projection);
* bytes: Wq and Wo once, each live row's K and V once, and its input,
  residual and output rows (bf16)."""

#: the kernel's ops in the trace: the profiler names a Pallas kernel by
#: its scope (``checkpoint.N custom-call:tpu_custom_call``), not by the
#: kernel; it is the only one its launch runs, and the launch's host span
#: (decode_step or prefill_chunk) says which phase it served.
EVENT = r" custom-call:tpu_custom_call$"
PHASE = "decode"
PATH = "decode_megakernel"


def cost(d, span):
    E, H, K, D = d.d_model, d.heads, d.kv_heads, d.head_dim
    c = sum(span.contexts)
    flops = span.rows * 4 * E * H * D + 4 * H * D * c
    byts = 2 * (2 * E * H * D + 2 * K * D * c + 3 * span.rows * E)
    return flops, byts
