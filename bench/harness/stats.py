"""End-to-end metric arithmetic over a window's timeline.

Every number is taken over all requests or all gaps of the window, never
from medians of pieces.  A percentile is the nearest-rank one: the
smallest sample with at least p% of the samples at or below it.
"""

from __future__ import annotations

import math
from typing import Optional


def percentile(values, p: float) -> Optional[float]:
    xs = sorted(values)
    if not xs:
        return None
    k = max(1, math.ceil(p / 100.0 * len(xs)))
    return xs[k - 1]


def tokens_in_window(tl) -> int:
    return sum(1 for ts in tl.tokens.values() for t in ts
               if tl.t0 <= t <= tl.t1)


def due_in_window(tl) -> list:
    return [u for u, d in tl.due.items() if tl.t0 <= d <= tl.t1]


def ttfts(tl) -> list:
    """Due time to first token, for every request due in the window; one
    still without a token at the window's end counts with its wait so
    far (so a backlog raises the tail)."""
    out = []
    for u in due_in_window(tl):
        ts = tl.tokens.get(u) or []
        first = ts[0] if ts and ts[0] <= tl.t1 else tl.t1
        out.append(first - tl.due[u])
    return out


def scheduler_time(tl, spans) -> list:
    """For every scheduler step of the window, its time outside the
    engine's launch spans (``spans``: prefill chunks and decode steps):
    admission, page accounting, eviction and the eager insert ops."""
    out = []
    inside = sorted((s.t0, s.t1) for s in spans)
    k = 0
    for t0, t1 in tl.step_spans:
        busy = 0.0
        while k < len(inside) and inside[k][1] <= t1:
            a, b = inside[k]
            if a >= t0:
                busy += b - a
            k += 1
        out.append(t1 - t0 - busy)
    return out


def gaps(tl) -> list:
    """Every gap between consecutive tokens of one request, both tokens
    inside the window."""
    out = []
    for ts in tl.tokens.values():
        inside = [t for t in ts if tl.t0 <= t <= tl.t1]
        out.extend(b - a for a, b in zip(inside, inside[1:]))
    return out


def lateness(tl) -> list:
    """How late the generator submitted each request due in the window."""
    return [tl.submitted[u] - tl.due[u] for u in due_in_window(tl)
            if u in tl.submitted]
