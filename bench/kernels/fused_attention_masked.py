"""Masked attention (``fused_attention_masked`` in
``kernels/fused_attention.py``) as a prefill chunk calls it: R query rows
after P cached tokens of the request's side cache, causal.  Per layer
call, at least:

* FLOPs: 4*Hq*D per causal score column, R*P + R*(R+1)/2 columns;
* bytes: K and V of the P+R columns once, Q in and O out (bf16)."""

from harness.counts import causal_cols

#: the kernel's ops in the trace: the profiler names a Pallas kernel by
#: its scope (``checkpoint.N custom-call:tpu_custom_call``), not by the
#: kernel; it is the only one its launch runs, and the launch's host span
#: (decode_step or prefill_chunk) says which phase it served.
EVENT = r" custom-call:tpu_custom_call$"
PHASE = "prefill"
PATH = "fused_attention"


def cost(d, span):
    H, K, D = d.heads, d.kv_heads, d.head_dim
    R, P = span.rows, span.offset
    flops = 4 * H * D * causal_cols(R, P)
    byts = 2 * (2 * K * D * (P + R) + 2 * R * H * D)
    return flops, byts
