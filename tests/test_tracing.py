"""The serving flight recorder (serve/tracing.py): nesting and self time,
the ring's bound, the off switch, the engine's and scheduler's counters
against a hand count, and the clock its spans share with the profiler."""

import gc
import glob
import os
import time

import jax
import numpy as np
import pytest

from repro import configs
from repro.models import init_params_and_axes
from repro.serve import (PagedContinuousBatchingEngine, Request,
                         RequestBatcher, make_serving_plan, tracing)


@pytest.fixture(autouse=True)
def fresh():
    tracing.reset()
    yield
    tracing.reset()


def _named(name):
    return [s for s in tracing.spans() if s.name == name]


def test_spans_nest_and_self_time_leaves_out_children():
    with tracing.span("outer", k=1) as attrs:
        time.sleep(0.002)
        with tracing.span("inner"):
            time.sleep(0.003)
        with tracing.span("inner"):
            time.sleep(0.001)
        attrs["late"] = True
    (outer,) = _named("outer")
    inner = _named("inner")
    assert len(inner) == 2
    assert outer.parent is None
    assert all(s.parent == outer.id for s in inner)
    assert all(outer.start_ns <= s.start_ns <= s.end_ns <= outer.end_ns
               for s in inner)
    assert outer.attrs == {"k": 1, "late": True}
    covered = sum(s.end_ns - s.start_ns for s in inner)
    gcs = sum(s.end_ns - s.start_ns for s in _named("host.gc")
              if s.parent == outer.id)
    st = tracing.self_times(tracing.spans())
    assert st["outer"] == outer.end_ns - outer.start_ns - covered - gcs
    assert st["inner"] == covered - sum(
        s.end_ns - s.start_ns for s in _named("host.gc")
        if s.parent in {i.id for i in inner})
    assert st["outer"] >= 2_000_000


def test_ring_drops_the_oldest_at_its_bound():
    gc.disable()                     # no host.gc spans among these
    try:
        for i in range(tracing.CAPACITY + 10):
            with tracing.span("s", i=i):
                pass
    finally:
        gc.enable()
    kept = tracing.spans()
    assert len(kept) == tracing.CAPACITY
    assert kept[0].attrs["i"] == 10
    assert kept[-1].attrs["i"] == tracing.CAPACITY + 9


def test_gc_collection_is_a_span_with_its_generation():
    gc.collect()
    spans = _named("host.gc")
    assert spans and spans[-1].attrs == {"generation": 2}


def test_off_switch_records_nothing(monkeypatch):
    monkeypatch.setattr(tracing, "enabled", False)
    with tracing.span("x", a=1) as attrs:
        attrs["b"] = 2
    tracing.count("c", 5)
    gc.collect()
    assert tracing.spans() == [] and tracing.counters() == {}


@pytest.fixture(scope="module")
def qwen():
    cfg = configs.get_config("qwen3-8b", smoke=True)
    params, _ = init_params_and_axes(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _prompt(cfg, key, n):
    return [int(x) for x in np.asarray(jax.random.randint(
        jax.random.PRNGKey(key), (n,), 0, cfg.vocab_size))]


def _engine(cfg, params, num_pages):
    plan = make_serving_plan(cfg, 48, interpret=True, paged=True,
                             page_size=8)
    return PagedContinuousBatchingEngine(
        params, cfg, batch_size=2, max_len=48, page_size=8,
        num_pages=num_pages, plan=plan, interpret=True)


def test_counters_match_a_hand_count(qwen):
    """Prompts of 7 and 12 tokens, 3 new tokens each, pages of 8 and a
    pool of 3 usable pages.  Step 1 leases 1 + 2 pages, prefills both
    (2 chunks, 2 inserts) and decodes both.  Step 2: row 0 (context 8)
    needs a second page and none is free, so the newest lease (uid 1)
    is preempted, row 0 grows one page, decodes its third token and is
    evicted.  Step 3 resumes uid 1 into slot 0, which decodes its third
    token and is evicted."""
    cfg, params = qwen
    eng = _engine(cfg, params, num_pages=4)
    b = RequestBatcher(batch_size=2, max_len=48)
    for uid, n in enumerate([7, 12]):
        b.submit(Request(uid=uid, prompt=_prompt(cfg, 60 + uid, n),
                         max_new_tokens=3))
    done = b.serve(eng, max_steps=50)
    assert sorted(len(r.generated) for r in done) == [3, 3]
    side = (cfg.n_layers * 2 * cfg.kv_heads * 48 * cfg.head_dim
            * np.dtype(eng.dtype).itemsize)
    readback = 2 * cfg.vocab_size * np.dtype(eng.dtype).itemsize + 2 * 4
    assert tracing.counters() == {
        "sched.steps": 3, "sched.admitted": 3,
        "engine.prefill_chunks": 2, "engine.inserts": 2,
        "engine.decode_launches": 3, "engine.preemptions": 1,
        "engine.resumes": 1, "engine.evictions": 2,
        "engine.table_rows_grown": 1,
        "engine.side_cache_bytes": 2 * side,
        "engine.readback_bytes": 3 * readback,
        # one prefill program per prompt length, one decode program
        "engine.launch_traces": 3,
    }
    admits = [s.attrs for s in _named("sched.admit")]
    assert admits == [{"uid": 0, "slot": 0, "resumed": False},
                      {"uid": 1, "slot": 1, "resumed": False},
                      {"uid": 1, "slot": 0, "resumed": True}]
    steps = _named("sched.step")
    assert [s.attrs["step"] for s in steps] == [0, 1, 2]
    # every engine span of a step lies inside that step
    for s in tracing.spans():
        if s.name.startswith("engine."):
            assert any(t.start_ns <= s.start_ns and s.end_ns <= t.end_ns
                       for t in steps), s
    (pre,) = _named("engine.preempt")
    assert pre.attrs == {"slot": 1}
    decode = _named("engine.decode")
    for child in ("prepare", "launch", "readback"):
        kids = _named(f"engine.decode.{child}")
        assert [k.parent for k in kids] == [d.id for d in decode]
    assert [k.attrs["bytes"] for k in _named("engine.decode.readback")] \
        == [readback] * 3


def test_megakernel_grid_counters_match_a_hand_count(qwen):
    """starcoder2's smoke widths (2 layers, 2 KV heads) on a paged
    engine of 3 rows, max_len 2048, pages of 16: every decode step takes
    the megakernel with the plan's 1,024-token KV block, 64 pages, so
    the grid is 2 heads x 3 rows x 2 blocks a layer.  Rows prefilled to
    1,022, 1,031 and 200 tokens score contexts 1,023 / 1,032 / 201 (1,
    2 and 1 blocks), then 1,024 / 1,033 / 202, then 1,025 / 1,034 / 203:
    row 0 crosses the block edge on the third step.  The qk-norm qwen3
    engine never reaches the megakernel and counts neither."""
    cfg = configs.get_config("starcoder2-7b", smoke=True)
    params, _ = init_params_and_axes(jax.random.PRNGKey(0), cfg)
    plan = make_serving_plan(cfg, 2048, interpret=True, paged=True,
                             page_size=16)
    eng = PagedContinuousBatchingEngine(
        params, cfg, batch_size=3, max_len=2048, page_size=16,
        num_pages=3 * 128 + 1, plan=plan, interpret=True)
    for slot, n in enumerate([1022, 1031, 200]):
        eng.begin_prefill(slot, _prompt(cfg, 80 + slot, n))
    eng._advance_prefills()
    kv, grid = [], []
    for _ in range(3):
        eng.decode_once()
        assert eng.last_dispatch.path == "decode_megakernel"
        kv.append(tracing.counter("engine.decode_kv_blocks"))
        grid.append(tracing.counter("engine.decode_grid_steps"))
    per_step = 2 * 3 * 2 * cfg.n_layers
    assert grid == [per_step, 2 * per_step, 3 * per_step]
    heads_layers = 2 * cfg.n_layers
    assert kv == [4 * heads_layers, 8 * heads_layers, 13 * heads_layers]

    qcfg, qparams = qwen
    tracing.reset()
    q = _engine(qcfg, qparams, num_pages=8)
    q.begin_prefill(0, _prompt(qcfg, 70, 40))
    for _ in range(3):
        q.step()
    assert tracing.counter("engine.decode_launches") == 3
    assert tracing.counter("engine.decode_kv_blocks") == 0
    assert tracing.counter("engine.decode_grid_steps") == 0


def test_launch_traces_stay_put_on_a_steady_step(qwen):
    cfg, params = qwen
    eng = _engine(cfg, params, num_pages=8)
    eng.begin_prefill(0, _prompt(cfg, 70, 9))
    eng.step()                       # prefill + first decode: traced
    eng.step()
    before = tracing.counter("engine.launch_traces")
    eng.step()
    assert tracing.counter("engine.launch_traces") == before
    launches = _named("engine.decode.launch")
    assert launches[0].attrs["traced"] is True
    assert launches[-1].attrs["traced"] is False


def test_spans_share_the_profilers_host_clock(tmp_path):
    """A span's in-memory stamps sit within 50 us of its own annotation
    in a captured profile: the profile's host events are offsets from
    its ``profile_start_time``, a ``time.time_ns()`` reading."""
    from jax.profiler import ProfileData
    x = jax.numpy.ones((64, 64))
    jax.profiler.start_trace(str(tmp_path))
    gc.disable()                     # no collection inside the probe
    try:
        with tracing.span("clock.probe"):
            (x @ x).block_until_ready()
    finally:
        gc.enable()
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                        recursive=True)
    pd = ProfileData.from_file(path)
    start = None
    events = []
    for plane in pd.planes:
        for k, v in plane.stats:
            if k == "profile_start_time":
                start = int(v)
        if plane.name == "/host:CPU":
            events += [e for line in plane.lines for e in line.events
                       if e.name == "clock.probe"]
    assert start is not None and len(events) == 1
    (mem,) = _named("clock.probe")
    ev_start = start + int(events[0].start_ns)
    ev_end = ev_start + int(events[0].duration_ns)
    assert abs(mem.start_ns - ev_start) <= 50_000
    assert abs(mem.end_ns - ev_end) <= 50_000
