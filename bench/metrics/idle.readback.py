"""Model step: the share (%) of the traced window in which the chip was
idle while the engine read a decode step's logits and tokens back to
the host (``engine.decode.readback``, harness/program_spans.py)."""

from harness import program_spans


def read(run):
    return program_spans.share(run, "readback")
