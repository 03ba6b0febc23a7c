"""Model step: the share (%) of the traced window in which the chip was
idle while an engine span other than the decode readback (admission's
side cache, prefill and decode launches, page-table growth, insert,
evict, preempt, resume) was the innermost span on the host
(harness/program_spans.py)."""

from harness import program_spans


def read(run):
    return program_spans.share(run, "engine")
