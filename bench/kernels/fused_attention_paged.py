"""Paged attention (``fused_attention_paged`` in
``kernels/fused_attention.py``) as a decode step calls it, one query row
per batch row, Q projected outside.  Per layer call, at least:

* FLOPs: 4*Hq*D*c for each live row of context c (scores and P.V);
* bytes: each live row's K and V once, its Q row in and O row out
  (bf16)."""

#: the kernel's ops in the trace: the profiler names a Pallas kernel by
#: its scope (``checkpoint.N custom-call:tpu_custom_call``), not by the
#: kernel; it is the only one its launch runs, and the launch's host span
#: (decode_step or prefill_chunk) says which phase it served.
EVENT = r" custom-call:tpu_custom_call$"
PHASE = "decode"
PATH = "fused_attention"


def cost(d, span):
    H, K, D = d.heads, d.kv_heads, d.head_dim
    c = sum(span.contexts)
    flops = 4 * H * D * c
    byts = 2 * (2 * K * D * c + 2 * span.rows * H * D)
    return flops, byts
