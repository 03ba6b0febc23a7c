"""Masked Q-projection attention (``fused_qproj_attention_masked`` in
``kernels/fused_qproj_attention.py``) as a later prefill chunk calls it:
the chunk's R rows of x and Wq in, Q built and rotated in the kernel,
causal attention after P cached tokens.  Per layer call, at least:

* FLOPs: 2*R*E*Hq*D (Q) + 4*Hq*D per causal column, R*P + R*(R+1)/2;
* bytes: x and Wq once, K and V of the P+R columns once, O out
  (bf16)."""

from harness.counts import causal_cols

#: the kernel's ops in the trace: the profiler names a Pallas kernel by
#: its scope (``checkpoint.N custom-call:tpu_custom_call``), not by the
#: kernel; it is the only one its launch runs, and the launch's host span
#: (decode_step or prefill_chunk) says which phase it served.
EVENT = r" custom-call:tpu_custom_call$"
PHASE = "prefill"
PATH = "qproj_attention"


def cost(d, span):
    E, H, K, D = d.d_model, d.heads, d.kv_heads, d.head_dim
    R, P = span.rows, span.offset
    flops = 2 * R * E * H * D + 4 * H * D * causal_cols(R, P)
    byts = 2 * (R * E + E * H * D + 2 * K * D * (P + R) + R * H * D)
    return flops, byts
