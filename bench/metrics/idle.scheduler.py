"""Scheduler: the share (%) of the traced window in which the chip was
idle while the program's scheduler (``sched.step``, ``sched.admit``, in
their own time) was the innermost span on the host
(harness/program_spans.py)."""

from harness import program_spans


def read(run):
    return program_spans.share(run, "scheduler")
