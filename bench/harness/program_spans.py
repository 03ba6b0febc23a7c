"""Device-idle time of the traced window, put down to program layers.

The program keeps its own spans (``repro.serve.tracing``: the
scheduler's ``sched.*``, the engine's ``engine.*``, Python's collections
as ``host.gc``) in memory, stamped with ``time.time_ns()``.  The trace's
plain events (``harness/trace.py``) are offsets from the profile's start,
whose own stamp they do not keep, so the offset is found here: each
``serve`` call the harness times as ``scheduler`` runs at most one
program ``sched.step``, a few microseconds in, and the smallest such lag
over the matched pairs gives it.  The program's ``engine.decode`` spans,
which open a few microseconds inside the harness's ``decode_step`` spans,
then check it (the clock note).

Every idle nanosecond of the window (the complement of
``trace.busy_intervals``) goes to the innermost span open at that
moment, among the program's spans and the harness's own host spans
(the latest started), and through it to a group: ``sched.*`` →
scheduler; ``engine.decode.readback`` → readback; any other
``engine.*`` → engine; ``host.gc`` → gc; a harness span (``scheduler``,
``generator``, ``decode_step``, ``prefill_chunk``) → bench; no span →
none.  The six groups add up to the window's idle time.

A program without the recorder gives no spans, and every reader here
returns None.
"""

from __future__ import annotations

import bisect
import collections
import heapq
from typing import Optional

from harness import trace

GROUPS = ("scheduler", "engine", "readback", "gc", "bench", "none")

#: a harness ``scheduler`` span and the program ``sched.step`` nearest
#: it match when their starts lie within this of the offset (steps are
#: 0.1-1 s apart)
MATCH_NS = 1_000_000


def group_of(name: str) -> str:
    """The group of a program span's idle time."""
    if name.startswith("sched."):
        return "scheduler"
    if name == "engine.decode.readback":
        return "readback"
    if name.startswith("engine."):
        return "engine"
    if name == "host.gc":
        return "gc"
    return "none"


def program_spans() -> Optional[list]:
    """The program recorder's spans, or None without a recorder."""
    try:
        from repro.serve import tracing
    except ImportError:
        return None
    return tracing.spans()


def offset_ns(ev: dict, spans: list) -> Optional[tuple]:
    """``(offset, pairs)``: program ``time_ns`` less ``offset`` is trace
    time, from the ``pairs`` harness ``scheduler`` spans matched to a
    program ``sched.step``; None where fewer than half match."""
    hs = sorted(s for n, s, _ in ev["host"] if n == "scheduler")
    ps = sorted(s.start_ns for s in spans if s.name == "sched.step")
    if not hs or not ps:
        return None

    def lags(c):
        out = []
        for h in hs:
            i = bisect.bisect_left(ps, h + c)
            p = min((ps[j] for j in (i - 1, i) if 0 <= j < len(ps)),
                    key=lambda p: abs(p - h - c))
            if abs(p - h - c) <= MATCH_NS:
                out.append(p - h)
        return out

    best: list = []
    for h in hs[:3]:
        for p in ps:
            got = lags(p - h)
            if len(got) > len(best):
                best = got
    if len(best) < max(1, len(hs) // 2):
        return None
    return min(best), len(best)


def _innermost(cands: list) -> list:
    """``[(t0, t1, i)]``: the time ``cands`` (``(start, end, ...)``)
    cover, cut where the innermost open span (the latest started, the
    shorter of two started together) changes; ``i`` indexes it."""
    order = sorted(range(len(cands)), key=lambda i: cands[i][0])
    bounds = sorted({c[0] for c in cands} | {c[1] for c in cands})
    heap: list = []
    out: list = []
    k = 0
    for a, b in zip(bounds, bounds[1:]):
        while k < len(order) and cands[order[k]][0] <= a:
            i = order[k]
            heapq.heappush(heap, (-cands[i][0], cands[i][1] - cands[i][0],
                                  i))
            k += 1
        while heap and cands[heap[0][2]][1] <= a:
            heapq.heappop(heap)
        if heap:
            i = heap[0][2]
            if out and out[-1][2] == i and out[-1][1] == a:
                out[-1] = (out[-1][0], b, i)
            else:
                out.append((a, b, i))
    return out


def idle_intervals(ev: dict) -> list:
    """The window's idle intervals: the complement of the busy ones."""
    w0, w1 = trace.window_of(ev)
    out, prev = [], w0
    for s, e in trace.busy_intervals(ev):
        if s > prev:
            out.append((prev, s))
        prev = max(prev, e)
    if w1 > prev:
        out.append((prev, w1))
    return out


def attribute(ev: dict, spans: list, offset: int) -> dict:
    """The idle split of the window (see the module doc): ``groups`` and
    ``by_name`` in ns, the ten longest ``gaps`` each named by the
    innermost program span over its midpoint, the window's program span
    ``counts`` by name, and ``clock_ms``: the largest distance from a
    program ``engine.decode`` start to the nearest harness
    ``decode_step`` start (None without one)."""
    w0, w1 = trace.window_of(ev)
    prog = [(s.start_ns - offset, s.end_ns - offset, s.name,
             group_of(s.name)) for s in spans]
    prog = [c for c in prog if c[1] > w0 and c[0] < w1]
    cands = prog + [(s, s + d, n, "bench") for n, s, d in ev["host"]
                    if n != "window" and s + d > w0 and s < w1]
    idle = idle_intervals(ev)
    groups = dict.fromkeys(GROUPS, 0)
    by_name: dict = collections.Counter()
    segs = _innermost(cands)
    i = j = 0
    while i < len(idle) and j < len(segs):
        a = max(idle[i][0], segs[j][0])
        b = min(idle[i][1], segs[j][1])
        if b > a:
            c = cands[segs[j][2]]
            groups[c[3]] += b - a
            by_name[c[2]] += b - a
        if idle[i][1] <= segs[j][1]:
            i += 1
        else:
            j += 1
    groups["none"] = sum(e - s for s, e in idle) - sum(
        v for k, v in groups.items() if k != "none")
    gaps = []
    for s, e in sorted(idle, key=lambda g: g[0] - g[1])[:10]:
        mid = (s + e) // 2
        inner = [c for c in prog if c[0] <= mid < c[1]]
        name = max(inner, key=lambda c: (c[0], c[0] - c[1]))[2] \
            if inner else "none"
        gaps.append([name, (e - s) / 1e9])
    steps = sorted(s for n, s, _ in ev["host"] if n == "decode_step")
    dist = []
    for a, _, name, _ in prog:
        if name == "engine.decode" and a >= w0 and steps:
            k = bisect.bisect_left(steps, a)
            dist.append(min(abs(a - steps[i]) for i in (k - 1, k)
                            if 0 <= i < len(steps)))
    return {"groups": groups, "by_name": dict(by_name), "gaps": gaps,
            "counts": dict(collections.Counter(
                c[2] for c in prog if c[0] >= w0)),
            "clock_ms": max(dist) / 1e6 if dist else None,
            "decode_spans": len(dist)}


def split(run) -> Optional[dict]:
    """The traced run's idle split (computed once, its notes appended
    once), or None: no trace, no program recorder, or no offset."""
    if "_program_idle" in run.__dict__:
        return run.__dict__["_program_idle"]
    out = None
    spans = program_spans() if run.events is not None else None
    found = offset_ns(run.events, spans) if spans else None
    if found is not None:
        offset, pairs = found
        out = attribute(run.events, spans, offset)
        _notes(run, out, offset, pairs, spans)
    elif spans is not None:
        run.notes.append("program spans: no offset found between the "
                         "program's sched.step and the trace's scheduler "
                         "spans; the idle split is not read")
    run.__dict__["_program_idle"] = out
    return out


def _notes(run, out: dict, offset: int, pairs: int, spans: list) -> None:
    window = trace.window_ns(run.events)
    g = out["groups"]
    idle = sum(g.values())
    run.notes.append(
        "idle split, % of the window: " + ", ".join(
            f"{k} {100.0 * v / window:.4f}" for k, v in g.items())
        + f"; idle {100.0 * idle / window:.4f}; none is "
        f"{100.0 * g['none'] / idle if idle else 0.0:.2f} % of the idle time")
    run.notes.append("idle by innermost span, s: " + str(sorted(
        ((k, v / 1e9) for k, v in out["by_name"].items()),
        key=lambda kv: -kv[1])))
    run.notes.append(f"idle gaps, longest 10, by innermost program span "
                     f"(name, s): {out['gaps']}")
    run.notes.append(f"program spans in the window: "
                     f"{sorted(out['counts'].items())}")
    oldest = min((s.start_ns for s in spans), default=None)
    full = oldest is not None and oldest - offset > trace.window_of(
        run.events)[0]
    run.notes.append(
        f"clock: profile start at time_ns {offset} (from {pairs} "
        f"scheduler/sched.step pairs); largest distance from a program "
        f"engine.decode start to its harness decode_step start "
        f"{out['clock_ms']} ms over {out['decode_spans']} spans"
        + ("; the ring lost the window's first spans" if full else ""))


def share(run, group: str) -> Optional[float]:
    """``group``'s idle time as a share (%) of the traced window."""
    out = split(run)
    if out is None:
        return None
    return 100.0 * out["groups"][group] / trace.window_ns(run.events)
