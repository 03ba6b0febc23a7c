"""Model FLOP utilisation and kernel roofline shares, from recorded spans.

A decode span carries the contexts its live rows attended; a prefill
span its rows and the tokens already in the side cache.  The counts are
the model's useful work: live rows only, one row of logits per prefill
chunk (the one the program returns), causal attention over each row's
own columns.  The model's FLOPs a step and its layer calls through each
kernel come from the cell's architecture module (``run.arch``).
"""

from __future__ import annotations

from typing import Optional

from harness import trace


def causal_cols(rows: int, offset: int) -> int:
    """Score columns of ``rows`` causal query rows after ``offset``
    cached tokens: row r attends offset + r + 1 of them."""
    return rows * offset + rows * (rows + 1) // 2


def mfu(run, kind: str) -> Optional[float]:
    """Model FLOPs of the ``kind`` spans over their host-clock time, as a
    share (%) of the chip's peak."""
    spans = [s for s in run.spans if s.kind == kind]
    secs = sum(s.t1 - s.t0 for s in spans)
    if not spans or secs <= 0:
        return None
    flops = sum(run.arch.step_flops(run.dims, s) for s in spans)
    return 100.0 * flops / secs / run.peaks["bf16_flops"]


def roofline(run, phase: str) -> Optional[float]:
    """Sum of the least times of the ``phase`` attention kernel calls
    over the sum of those kernels' device times in the trace (%).  The
    least time of a call is the larger of its FLOPs over the peak and
    its bytes over the HBM bandwidth, from the kernel's count module at
    the call's shapes and live lengths."""
    if run.events is None:
        return None
    by_path = {k.PATH: k for k in run.kernels if k.PHASE == phase}
    least, calls, used = 0.0, 0, {}
    for s in run.spans:
        k = by_path.get(s.path) if s.kind == phase else None
        if k is None:
            continue
        f, b = k.cost(run.dims, s)
        n = run.arch.layer_calls(run.dims, k.PATH)
        least += n * max(f / run.peaks["bf16_flops"],
                         b / run.peaks["hbm_bytes_per_s"])
        calls += n
        used[k.EVENT] = k
    if not used:
        return None
    dev_ns, n = 0, 0
    within = {"decode": "decode_step", "prefill": "prefill_chunk"}[phase]
    for pattern in used:
        t, c = trace.kernel_ns(run.events, pattern, within)
        dev_ns, n = dev_ns + t, n + c
    run.notes.append(f"{phase} attention kernels: {calls} calls recorded, "
                     f"{n} trace events, {dev_ns / 1e9:.6f} s device time")
    if dev_ns <= 0:
        return None
    return 100.0 * least / (dev_ns / 1e9)
