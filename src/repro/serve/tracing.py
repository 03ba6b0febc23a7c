"""A process-wide flight recorder for the serving engine and scheduler.

``span(name, **attrs)`` times a block of host code twice over: it opens a
``jax.profiler.TraceAnnotation`` of the same name (so the span shows in
xprof and TensorBoard whenever a profile is captured) and, when the block
ends, appends one :class:`Span` to a bounded in-memory ring.  Spans are
stamped with ``time.time_ns()``, the clock the profiler stamps its own
host events with (a profile's events are offsets from its
``profile_start_time``, itself a ``time.time_ns()`` reading), so the
ring's spans, the device ops and any other annotation of the same
profile can be laid on one timeline.

``count(name, n)`` keeps cumulative counters.  A ``gc.callbacks`` hook
records every Python collection as a ``host.gc`` span.

The spans the engine and scheduler open (``docs/serving.md``, "Spans and
counters"): ``sched.step``, ``sched.admit``, ``engine.begin_prefill``,
``engine.prefill``, ``engine.insert``, ``engine.evict``,
``engine.preempt``, ``engine.resume``, ``engine.decode`` with its
children ``engine.decode.prepare``, ``.launch`` and ``.readback``.

``enabled = False`` turns every span and counter into a no-op (tests,
and measuring what the recorder costs).
"""

from __future__ import annotations

import collections
import contextlib
import gc
import itertools
import threading
import time
from typing import NamedTuple, Optional

from jax.profiler import TraceAnnotation

#: the one switch: off, ``span`` and ``count`` record nothing
enabled = True

#: ring size: far more spans than one measured window opens
CAPACITY = 65_536


class Span(NamedTuple):
    name: str
    start_ns: int                 # time.time_ns() at entry
    end_ns: int
    parent: Optional[int]         # id of the enclosing span, same thread
    attrs: dict
    id: int


_ring: collections.deque = collections.deque(maxlen=CAPACITY)
_counters: dict = {}
_ids = itertools.count(1)
_local = threading.local()


def _stack() -> list:
    stack = getattr(_local, "stack", None)
    if stack is None:
        stack = _local.stack = []
    return stack


@contextlib.contextmanager
def span(name: str, **attrs):
    """Record the block as span ``name``.  Yields the span's ``attrs``
    dict: keys set inside the block are kept on the recorded span (the
    profiler's annotation carries only those given at entry)."""
    if not enabled:
        yield attrs
        return
    stack = _stack()
    sid = next(_ids)
    parent = stack[-1] if stack else None
    stack.append(sid)
    # the stamps sit right inside the annotation's own, with nothing
    # that allocates (and so may collect) between them
    ann = TraceAnnotation(name, **attrs)
    ann.__enter__()
    start = time.time_ns()
    try:
        yield attrs
    finally:
        end = time.time_ns()
        ann.__exit__(None, None, None)
        stack.pop()
        _ring.append(Span(name, start, end, parent, attrs, sid))


def count(name: str, n: int = 1) -> None:
    """Add ``n`` to the cumulative counter ``name``."""
    if enabled:
        _counters[name] = _counters.get(name, 0) + n


def counter(name: str) -> int:
    return _counters.get(name, 0)


def counters() -> dict:
    return dict(_counters)


def spans() -> list:
    """The ring's spans, oldest first (by end)."""
    return list(_ring)


def reset() -> None:
    """Empty the ring and zero every counter."""
    _ring.clear()
    _counters.clear()


def self_times(recorded) -> dict:
    """Total self time (ns) by span name: each span's duration less the
    time its children (spans whose ``parent`` is its id) cover."""
    child_ns: dict = {}
    for s in recorded:
        if s.parent is not None:
            child_ns[s.parent] = child_ns.get(s.parent, 0) \
                + s.end_ns - s.start_ns
    out: dict = {}
    for s in recorded:
        out[s.name] = out.get(s.name, 0) + s.end_ns - s.start_ns \
            - child_ns.get(s.id, 0)
    return out


def summary() -> str:
    """One line: every counter, then each span name's total self time."""
    cs = " ".join(f"{k}={v}" for k, v in sorted(_counters.items()))
    st = " ".join(f"{k}={v / 1e6:.3f}ms"
                  for k, v in sorted(self_times(_ring).items()))
    return f"counters: {cs or '-'} | self time: {st or '-'}"


def _on_gc(phase: str, info: dict) -> None:
    """``gc.callbacks`` hook: a ``host.gc`` span around each collection."""
    if phase == "start":
        if not enabled:
            _local.gc = None
            return
        stack = _stack()
        sid = next(_ids)
        ann = TraceAnnotation("host.gc", generation=info["generation"])
        ann.__enter__()
        _local.gc = (ann, sid, stack[-1] if stack else None,
                     info["generation"], time.time_ns())
        stack.append(sid)
        return
    open_ = getattr(_local, "gc", None)
    if open_ is None:
        return
    _local.gc = None
    ann, sid, parent, generation, start = open_
    end = time.time_ns()
    ann.__exit__(None, None, None)
    _stack().pop()
    _ring.append(Span("host.gc", start, end, parent,
                      {"generation": generation}, sid))


gc.callbacks.append(_on_gc)
