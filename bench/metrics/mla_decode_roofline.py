"""Kernels, decode: the paged MLA decode kernel's least time (the larger
of FLOPs over peak and bytes over HBM bandwidth, per call, from
bench/kernels/fused_mla_decode_paged.py) summed over the window's decode
steps, over its device time in the trace's decode steps.  None where no
decode step ran the ``mla_decode`` path."""

from harness import counts


def read(run):
    if not any(s.kind == "decode" and s.path == "mla_decode"
               for s in run.spans):
        return None
    return counts.roofline(run, "decode")
