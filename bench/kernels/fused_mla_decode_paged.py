"""Absorbed MLA decode over the paged latent cache
(``kernels/fused_mla_decode.py``): every query head against one latent
"KV head", Q absorbed and W_UV, Wo applied outside.  What a decode step
needs at least, per layer call:

* FLOPs, per live row of context c: 2*H*L*c (scores over the latent,
  L = kv_lora + qk_rope) + 2*H*kv_lora*c (P.V over its first kv_lora
  columns);
* bytes: each live row's latent once (2*L*c, bf16), its absorbed query
  (H x L) in and its (H x kv_lora) output out."""

#: the kernel's ops in the trace: the profiler names a Pallas kernel by
#: its scope (``checkpoint.N custom-call:tpu_custom_call``), not by the
#: kernel; it is the only one its launch runs, and the launch's host span
#: (decode_step or prefill_chunk) says which phase it served.
EVENT = r" custom-call:tpu_custom_call$"
PHASE = "decode"
PATH = "mla_decode"


def cost(d, span):
    H, L, r = d.heads, d.kv_lora + d.qk_rope, d.kv_lora
    c = sum(span.contexts)
    flops = 2 * H * (L + r) * c
    byts = 2 * (L * c + span.rows * H * (L + r))
    return flops, byts
