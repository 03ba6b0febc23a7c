"""Drive the program's scheduler with the generated traffic.

The entry every window drives is ``RequestBatcher.serve(engine,
max_steps=1)``: the program's own admission, prefill, decode and evict
loop, one scheduler step a call.  Between calls the driver submits every
request that has fallen due (open loop) or whose client's last request
finished (closed loop).  With no row live and nothing pending it sleeps
until the next request is due.  A decoded token is on the host when
``serve`` returns (the engine reads the logits back), so the clock after
each call stamps every token the call produced.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Optional

from harness.traffic import Plan, Req


@dataclasses.dataclass
class Timeline:
    """What the window produced, on the host clock."""
    t0: float
    t1: float
    due: dict                   # uid -> due time (window requests)
    submitted: dict             # uid -> submit time
    leased: dict                # uid -> lease time
    tokens: dict                # uid -> [time of each generated token]
    failed: list                # uids refused at submit
    steps: int
    #: (start, end) of each scheduler step (``serve`` call) in the window
    step_spans: list = dataclasses.field(default_factory=list)

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0


class Driver:
    def __init__(self, engine, plan: Plan, max_len: int,
                 clock=time.perf_counter):
        from repro.serve import Request, RequestBatcher
        self.Request = Request
        self.engine = engine
        self.plan = plan
        self.clock = clock
        self.batcher = RequestBatcher(engine.batch_size, max_len=max_len)
        self.reqs: dict = {}            # uid -> program Request
        self.inflight: dict = {}        # uid -> tokens seen so far
        self.tokens: dict = {}
        self.submitted: dict = {}
        self.due: dict = {}
        self.failed: list = []
        self.client_of: dict = {}       # closed loop: uid -> client
        self.next_stream = 0
        self.steps = 0
        self.step_spans: list = []
        #: one (time, requests waiting, live rows) per step
        self.queue_len: list = []
        engine.setup_recorder({tuple(r.prompt): r.uid
                               for r in plan.fill + plan.stream})

    # -- submission ----------------------------------------------------------

    def _submit(self, r: Req, now: float) -> None:
        req = self.Request(uid=r.uid, prompt=list(r.prompt),
                           max_new_tokens=r.max_new)
        self.submitted[r.uid] = now
        try:
            self.batcher.submit(req)
        except ValueError:
            self.failed.append(r.uid)
            return
        self.reqs[r.uid] = req
        self.inflight[r.uid] = 0
        self.tokens[r.uid] = []

    def _next_stream(self) -> Optional[Req]:
        if self.next_stream >= len(self.plan.stream):
            raise RuntimeError("traffic pool exhausted: raise the mix's "
                               "'pool'")
        r = self.plan.stream[self.next_stream]
        self.next_stream += 1
        return r

    # -- one scheduler step ----------------------------------------------------

    def step(self) -> list:
        """One ``serve`` call; stamps new tokens; returns finished uids."""
        from jax.profiler import TraceAnnotation
        t0 = self.clock()
        with TraceAnnotation("scheduler"):
            self.batcher.serve(self.engine, max_steps=1)
        now = self.clock()
        self.steps += 1
        if self.engine.recording:
            self.step_spans.append((t0, now))
        self.queue_len.append((now, len(self.batcher.queue),
                               sum(self.engine.live)))
        done = []
        for uid, seen in list(self.inflight.items()):
            req = self.reqs[uid]
            n = len(req.generated)
            if n > seen:
                self.tokens[uid].extend([now] * (n - seen))
                self.inflight[uid] = n
            if req.done:
                del self.inflight[uid]
                done.append(uid)
        return done

    @property
    def busy(self) -> bool:
        return self.batcher.active or bool(self.engine._pending)

    # -- phases ----------------------------------------------------------------

    def fill(self, group: int) -> None:
        """Put the steady-state rows in, ``group`` prefills at a time."""
        fill = self.plan.fill
        for k in range(0, len(fill), group):
            batch = fill[k:k + group]
            now = self.clock()
            for j, r in enumerate(batch):
                self._submit(r, now)
                self.client_of[r.uid] = k + j
            while any(r.uid in self.inflight and self.inflight[r.uid] == 0
                      for r in batch):
                for uid in self.step():
                    self._client_next(uid)

    def _client_next(self, uid: int) -> None:
        """Closed loop: the client whose request ``uid`` finished sends
        its next one now."""
        if self.plan.loop != "closed":
            return
        now = self.clock()
        r = self._next_stream()
        self.due[r.uid] = now
        self.client_of[r.uid] = self.client_of.get(uid)
        self._submit(r, now)

    def window(self, seconds: float, t0: float) -> Timeline:
        """Offer the stream for ``seconds`` from ``t0`` (a step started
        before the end runs to its end, and the window with it)."""
        from jax.profiler import TraceAnnotation
        self.engine.recording = True
        end = t0 + seconds
        open_loop = self.plan.loop == "open"
        now = self.clock()
        while now < end:
            with TraceAnnotation("generator"):
                if open_loop:
                    while (self.next_stream < len(self.plan.stream)
                           and t0 + self.plan.stream[self.next_stream].due
                           <= now):
                        r = self._next_stream()
                        self.due[r.uid] = t0 + r.due
                        self._submit(r, now)
                    if not self.busy:
                        if self.next_stream >= len(self.plan.stream):
                            raise RuntimeError(
                                "traffic pool exhausted: raise the mix's "
                                "'pool'")
                        nxt = t0 + self.plan.stream[self.next_stream].due
                        time.sleep(max(0.0, min(nxt, end) - now))
                        now = self.clock()
                        continue
            for uid in self.step():
                self._client_next(uid)
            now = self.clock()
        self.engine.recording = False
        return Timeline(t0=t0, t1=now, due=dict(self.due),
                        submitted=dict(self.submitted),
                        leased=dict(self.engine.leased),
                        tokens={u: list(v) for u, v in self.tokens.items()},
                        failed=list(self.failed), steps=self.steps,
                        step_spans=list(self.step_spans))
