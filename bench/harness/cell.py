"""One run of one cell: set-up, the measured window, the check, the line.

Set-up makes the weights from the seed, builds the engine as
``launch/serve.make_engine`` does (paged, with the plan), warms every
program the window can dispatch, and fills the rows the load holds in
steady state.  The window then drives the program's scheduler with the
cell's traffic; nothing compiles in it (the count is printed).  Once it
has closed, the device's peak memory is read, the engine's state is
freed, and the served tokens are checked against the plain reference.
"""

from __future__ import annotations

import collections
import dataclasses
import gc
import logging
import sys
import time
from typing import Callable, Optional

from harness import check, stats, trace, traffic
from harness.driver import Driver
from harness.manifest import Manifest
from harness.recorder import engine_class


@dataclasses.dataclass
class RunData:
    """What metric readers see."""
    timeline: object
    spans: list
    events: Optional[dict]
    arch: object                # the cell's bench/arch/<arch>.py
    dims: object                # its dims(cfg)
    peaks: dict
    kernels: list
    setup_s: float
    peak_bytes: int
    notes: list


def say(*a) -> None:
    print(*a, flush=True)


def _key(d) -> tuple:
    return (d.path, d.impl, d.block_q, d.block_k, d.interpret, d.paged)


def warm(engine, plan: traffic.Plan, lengths: list, min_ctx: int,
         done: set) -> int:
    """Run once every program the window can dispatch and ``done`` does
    not hold yet: each decode dispatch key over every deepest-row context
    from ``min_ctx`` up, each prefill chunk (dispatch key, rows) of the
    prompt ``lengths`` the window can admit, and the eager insert/evict
    ops at each page count those prompts take.  Results are dropped: the
    engine's state is untouched.  Returns how many ran."""
    import jax
    import jax.numpy as jnp
    from repro.serve.engine import (PrefillResult, evict_paged,
                                    init_decode_state, insert_paged)
    sp, B, max_len = engine.plan, engine.batch_size, engine.max_len
    todo = {}
    for ctx in range(min_ctx, max_len):
        d = sp.step_dispatch([ctx])
        todo.setdefault(("decode", _key(d)), d)
    chunk = engine.prefill_chunk
    for total in lengths:
        for pos in range(0, total, chunk):
            rows = min(chunk, total - pos)
            d = sp.chunk_dispatch(pos + rows, rows)
            todo.setdefault(("prefill", _key(d), rows), (d, pos))
    for n in {engine.allocator.pages_for(t + 1) for t in lengths}:
        todo.setdefault(("insert", n), n)
    todo.setdefault(("evict",), None)
    todo = {k: v for k, v in todo.items() if k not in done}
    side = None
    # decode programs first: they load before any side cache takes the
    # memory a near-full chip has left for them
    for k, v in sorted(todo.items(), key=lambda kv: kv[0][0] != "decode"):
        if k[0] != "decode" and side is None:
            side = init_decode_state(engine.cfg, 1, max_len,
                                     engine.dtype).cache
        if k[0] == "decode":
            out = engine._launch("decode", v)(
                engine.params, engine.state, jnp.zeros((B,), bool))
        elif k[0] == "prefill":
            d, pos = v
            out = engine._launch("prefill", d)(
                engine.params, jnp.zeros((1, k[2]), jnp.int32), side,
                jnp.int32(pos))
        elif k[0] == "insert":
            res = PrefillResult(cache=side, length=jnp.asarray(v, jnp.int32),
                                next_token=jnp.asarray(0, jnp.int32))
            out = insert_paged(engine.state, res, 0, list(range(1, v + 1)))
        else:
            out = (evict_paged(engine.state, 0),
                   jax.lax.dynamic_update_slice(
                       engine.state.block_tables,
                       jnp.asarray([[1]], jnp.int32), (0, 0)))
        jax.block_until_ready(out)
        # drop it before the next launch: a decode step's output is a
        # whole pool, and two beside the weights do not fit qwen3-8b
        del out
        done.add(k)
    return len(todo)


def window_lengths(plan: traffic.Plan, seconds: float) -> list:
    """The prompt lengths the window can admit: open loop, every request
    due inside it; closed loop, the next two per client (a request here
    outlasts a window, so a client sends at most one more)."""
    if plan.loop == "open":
        reqs = [r for r in plan.stream if r.due <= seconds]
    else:
        reqs = plan.stream[:2 * plan.clients]
    return sorted({len(r.prompt) for r in reqs})


def _ledger(plan) -> dict:
    """The downgrade ledger as {(plan, from, to, reason): count}."""
    return {(id(p), d.from_path, d.to_path, d.reason): d.count
            for p in plan.plans.values() for d in p.downgrades}


class _Compiles(logging.Handler):
    """Collects what ``jax_log_compiles`` reports inside the window."""

    def __init__(self):
        super().__init__()
        self.names: list = []

    def emit(self, record):
        msg = record.getMessage()
        if msg.startswith("Compiling "):
            self.names.append(msg.split(" with ")[0][len("Compiling "):])


class Session:
    """One process's set-up of a cell: weights from the seed, the engine
    as ``launch/serve.make_engine`` builds it (paged, with the plan), and
    every program the cell's traffic can dispatch warmed."""

    def __init__(self, man: Manifest, cell_name: str, seed: int, *,
                 compile_cache: bool = True, engine_base=None,
                 alter: Optional[Callable] = None, mix: Optional[dict] = None):
        import jax
        from repro.launch.compilation import enable_compile_cache
        from repro.launch.serve import make_engine
        self.man, self.name, self.seed = man, cell_name, seed
        self.cell = man.cell(cell_name)
        cfg_json = man.config(self.cell["config"])
        self.mix = mix or man.traffic(self.cell["traffic"])
        eng_kw = self.mix["engine"]
        self.max_len = int(eng_kw["max_len"])
        self.arch = man.arch(cfg_json["arch"])
        self.dims = self.arch.dims(cfg_json)
        self.devs = jax.devices()
        self.dev = self.devs[0]
        self.peaks = man.peaks(self.dev.device_kind)
        if compile_cache:
            say(f"compile cache: {enable_compile_cache()}")
            jax.config.update("jax_persistent_cache_min_compile_time_secs",
                              0)
        say(f"cell {cell_name}: config {self.cell['config']} "
            f"({self.arch.describe(self.dims)}), traffic "
            f"{self.cell['traffic']} ({self.mix['loop']} loop), engine "
            f"{eng_kw}, seed {seed}")
        t = time.perf_counter()
        self.params = self.arch.make_params(self.dims, seed)
        jax.block_until_ready(self.params)
        self.t_weights = time.perf_counter() - t
        self.engine = make_engine(
            self.params, self.arch.program_config(cfg_json),
            batch=int(eng_kw["batch"]), max_len=self.max_len,
            prefill_chunk=int(eng_kw["prefill_chunk"]), paged=True,
            page_size=int(eng_kw["page_size"]),
            engine_cls=engine_class(engine_base))
        self.engine.alter = alter
        self.warmed: set = set()

    def plan(self, seed: int, mix: Optional[dict] = None) -> traffic.Plan:
        return traffic.make_plan(mix or self.mix, seed, self.dims.vocab,
                                 self.max_len)

    def reset(self) -> None:
        """Empty every row and pending prefill (between sweep rates)."""
        eng = self.engine
        for slot in range(eng.batch_size):
            if eng.live[slot]:
                eng.evict(slot)
        for slot in list(eng._pending):
            eng.allocator.release(slot)
            del eng._pending[slot]

    def serve(self, plan: traffic.Plan, seconds: float,
              traced: bool = False) -> dict:
        """Fill the steady-state rows, then the measured window.  Returns
        the driver, the timeline, the compiles in the window (count and
        names), the trace events (traced) and the fill's time."""
        import jax
        from repro.launch.compilation import count_compiles
        driver = Driver(self.engine, plan, self.max_len)
        t = time.perf_counter()
        n_warm = warm(self.engine, plan, window_lengths(plan, seconds),
                      int(self.mix["prompt"]["min"]), self.warmed)
        t_warm = time.perf_counter() - t
        t = time.perf_counter()
        driver.fill(int(self.mix["fill_group"]))
        t_fill = time.perf_counter() - t
        fill_steps = driver.steps
        n_res = len(self.engine.plan.resolutions)
        ledger = _ledger(self.engine.plan)
        self.engine.block_chunks = traced
        # name what compiles in the window, without JAX's own log lines
        handler = _Compiles()
        jlog = logging.getLogger("jax")
        saved = jlog.handlers[:], jlog.propagate
        jlog.handlers, jlog.propagate = [handler], False
        jax.config.update("jax_log_compiles", True)
        events = None
        t_window = time.time()
        try:
            with count_compiles() as compiles:
                if traced:
                    with trace.capture() as events:
                        tl = driver.window(seconds, time.perf_counter())
                else:
                    tl = driver.window(seconds, time.perf_counter())
        finally:
            jax.config.update("jax_log_compiles", False)
            jlog.handlers, jlog.propagate = saved
        return {"driver": driver, "timeline": tl, "compiles": compiles[0],
                "compile_names": handler.names, "events": events,
                "t_fill": t_fill, "fill_steps": fill_steps,
                "n_warm": n_warm, "t_warm": t_warm,
                "t_window": t_window, "resolutions_from": n_res,
                "ledger_before": ledger}

    def report(self, s: dict) -> None:
        """The counts of a window, on lines of their own."""
        eng, tl, driver = self.engine, s["timeline"], s["driver"]
        due = stats.due_in_window(tl)
        leased = [u for u in due if u in tl.leased]
        finished = [u for u in driver.reqs if driver.reqs[u].done
                    and tl.tokens[u] and tl.t0 <= tl.tokens[u][-1] <= tl.t1]
        late = stats.lateness(tl)
        say(f"window {tl.seconds:.3f}s, {tl.steps - s['fill_steps']} "
            f"scheduler steps; compiles in the window: {s['compiles']} "
            f"{s['compile_names'][:10]}")
        say(f"requests: {len(due)} due in the window, {len(leased)} "
            f"admitted, {len(finished)} finished in it, {len(tl.failed)} "
            f"refused; generator lateness p50 {stats.percentile(late, 50)} "
            f"s, max {max(late) if late else None} s")
        res = collections.Counter(
            (ph, path, impl) for ph, _, _, path, impl
            in eng.plan.resolutions[s["resolutions_from"]:])
        say(f"plan.resolutions in the window (phase, path, impl) x count: "
            f"{sorted(res.items())}")
        before, after = s["ledger_before"], _ledger(eng.plan)
        added = collections.Counter()
        for k, n in after.items():
            added[k[1:]] += n - before.get(k, 0)
        say(f"downgrade ledger, entries added in the window (from, to, "
            f"reason) x count: {sorted((k, n) for k, n in added.items() if n)}")
        say(f"pages used at the end {eng.allocator.used_pages} of "
            f"{eng.allocator.num_pages - 1}, peak {eng.allocator.peak_used};"
            f" occupancy at the end {eng.occupancy:.3f}")

    def free_engine(self) -> None:
        """Drop the program's state (pool, side caches) before the
        reference runs; the weights stay."""
        self.engine.state = None
        self.engine._pending.clear()
        self.engine = None
        gc.collect()

    def check(self, reqs: dict, logits: dict,
              control: bool = False) -> dict:
        chk = self.mix["check"]
        uids = check.sample(reqs, self.seed, int(chk["sample_tokens"]),
                            int(chk["max_sequences"]))
        t = time.perf_counter()
        read = check.readings(self.arch, self.params, self.dims, reqs,
                              logits, uids, self.max_len,
                              int(chk["max_sequences"]), control=control)
        say(f"check: {len(uids)} requests, {read['tokens_checked']} served "
            f"tokens against the float32 reference in "
            f"{time.perf_counter() - t:.3f}s")
        return read


def run(man: Manifest, cell_name: str, seed: int, seconds: float,
        traced: bool, t_start: float, **kw) -> dict:
    """One run of the cell: the result line's object."""
    ses = Session(man, cell_name, seed, **kw)
    plan = ses.plan(seed)
    s = ses.serve(plan, seconds, traced)
    tl = s["timeline"]
    setup_s = s["t_window"] - t_start
    say(f"set-up {setup_s:.3f}s: weights {ses.t_weights:.3f}s, "
        f"{s['n_warm']} programs warmed in {s['t_warm']:.3f}s, "
        f"{len(plan.fill)} steady-state rows filled in {s['t_fill']:.3f}s "
        f"({s['fill_steps']} steps)")
    peak = int((ses.dev.memory_stats() or {}).get("peak_bytes_in_use", 0))
    ses.report(s)
    say(f"peak_bytes_in_use {peak}")
    spans = list(ses.engine.spans)
    logits = ses.engine.logits
    reqs = s["driver"].reqs
    del s["driver"]
    ses.free_engine()

    read = ses.check(reqs, logits)
    del logits
    chk = ses.mix["check"]
    ok, compared = check.verdict(read, {
        "logit_gap": float(chk["logit_gap_limit"]),
        "logit_rel": float(chk["logit_rel_limit"]),
        "tokens_checked": int(chk["tokens_checked_min"])})
    ok = ok and not tl.failed

    events = s["events"]
    data = RunData(timeline=tl, spans=spans, events=events, arch=ses.arch,
                   dims=ses.dims, peaks=ses.peaks, kernels=man.kernels(),
                   setup_s=setup_s, peak_bytes=peak, notes=[])
    metrics = {}
    for m in (man.per_layer(cell_name) if traced
              else man.end_to_end(cell_name)):
        v = man.metric_reader(m["name"])(data)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    for note in data.notes:
        say(note)

    device = {"platform": ses.dev.platform, "kind": ses.dev.device_kind,
              "count": len(ses.devs), "memory_peak_bytes": peak}
    out = {"correct": bool(ok), "attempted": len(stats.due_in_window(tl)),
           "failed": len(tl.failed), "metrics": metrics, "device": device}
    if traced:
        device["busy_s"] = trace.busy_ns(events) / 1e9
        device["window_s"] = trace.window_ns(events) / 1e9
        out["breakdown"] = {"device_ops": trace.top_ops(events),
                            "idle_gaps": trace.idle_gaps(events)}
        out["_events"] = events
    for name, c in compared.items():
        bound = ">=" if name == "tokens_checked" else "<="
        print(f"check {name} {c['value']} {bound} {c['limit']}",
              file=sys.stderr, flush=True)
    out["check"] = compared
    return out
