"""The benchmark's engine subclass and what it records.

``Recorder`` is an engine mixin, put in front of the program's paged
engine class by subclassing (the program's objects are never patched).
It keeps, on the host clock:

* the time each request's slot was leased (``begin_prefill``): the end
  of its queue wait;
* one span per prefill-chunk launch (with its offset and rows) and per
  decode step (with the live rows' contexts and the dispatched path):
  what ``mfu.*`` and the kernel rooflines count;
* ``jax.profiler.TraceAnnotation`` host spans ``prefill_chunk`` and
  ``decode_step`` around the same calls, so a device trace can say what
  the host was doing in each idle gap;
* each request's decode logits as the engine handed them to the host
  (``logits[uid]``, one row a decoded token after the first), which the
  check compares with the reference's.

With ``block_chunks`` set (the traced run), each prefill chunk is waited
for, so its span covers its device time.  ``alter`` is a test hook: a
function ``(slot, token) -> token`` applied to every decoded token as
the engine hands it out.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Callable, Optional

import numpy as np


@dataclasses.dataclass
class Span:
    kind: str                   # "prefill" | "decode"
    t0: float
    t1: float
    path: Optional[str]         # dispatched kernel path (None: no plan)
    rows: int = 1               # prefill: chunk rows; decode: live rows
    offset: int = 0             # prefill: tokens already in the cache
    contexts: tuple = ()        # decode: each live row's context after


class Recorder:
    """Mixin for ``PagedContinuousBatchingEngine`` (see module doc)."""

    clock: Callable[[], float] = time.perf_counter
    recording = False
    block_chunks = False
    alter: Optional[Callable[[int, int], int]] = None

    def setup_recorder(self, uid_of_prompt: dict) -> None:
        self.uid_of_prompt = uid_of_prompt
        self.leased: dict = {}          # uid -> lease time
        self.spans: list = []
        self.slot_uid: dict = {}
        self.logits: dict = {}          # uid -> [decode logits row, ...]

    def begin_prefill(self, slot, prompt):
        super().begin_prefill(slot, prompt)
        uid = self.uid_of_prompt[tuple(prompt)]
        self.slot_uid[slot] = uid
        if self.recording:
            self.leased[uid] = self.clock()

    def _launch(self, kind, dispatch):
        fn = super()._launch(kind, dispatch)
        if kind != "prefill":
            return fn
        import jax
        from jax.profiler import TraceAnnotation
        path = None if dispatch is None else dispatch.path

        def chunk(params, tokens, cache, pos):
            t0 = self.clock()
            with TraceAnnotation("prefill_chunk"):
                out = fn(params, tokens, cache, pos)
                if self.block_chunks:
                    jax.block_until_ready(out)
            if self.recording:
                self.spans.append(Span("prefill", t0, self.clock(), path,
                                       rows=int(tokens.shape[1]),
                                       offset=int(pos)))
            return out
        return chunk

    def decode_once(self):
        from jax.profiler import TraceAnnotation
        t0 = self.clock()
        with TraceAnnotation("decode_step"):
            toks = super().decode_once()
        if toks is not None:
            for i, live in enumerate(self.live):
                if live:
                    self.logits.setdefault(self.slot_uid[i], []).append(
                        np.array(self.last_logits[i]))
        if toks is not None and self.recording:
            d = self.last_dispatch
            ctx = tuple(c for c, live in zip(self.row_ctx, self.live) if live)
            self.spans.append(Span("decode", t0, self.clock(),
                                   None if d is None else d.path,
                                   rows=len(ctx), contexts=ctx))
        if toks is not None and self.alter is not None:
            toks = toks.copy()
            for i, live in enumerate(self.live):
                if live:
                    toks[i] = self.alter(i, int(toks[i]))
        return toks


def engine_class(base=None):
    """``Recorder`` in front of the program's paged engine (or ``base``)."""
    if base is None:
        from repro.serve import PagedContinuousBatchingEngine as base

    class BenchEngine(Recorder, base):
        pass

    return BenchEngine
