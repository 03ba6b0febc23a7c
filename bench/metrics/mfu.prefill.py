"""Model step, prefill: model FLOPs of every prefill chunk launched in
the window (2 x layer parameters x chunk rows, causal attention, one row
of logits) over the chunks' host-clock time, as a share of the chip's
bf16 peak.  The traced run waits for each chunk, so its span covers it."""

from harness import counts


def read(run):
    return counts.mfu(run, "prefill")
