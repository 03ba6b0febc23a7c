"""The idle split (bench/harness/program_spans.py) on a small hand-built
trace: idle goes to the innermost span, harness-innermost idle to bench,
and the three metrics with gc, bench and none add up to
device_idle_share.  Last, on a traced tiny run on the CPU, the offset it
finds lays every program decode on its harness decode step."""

import types

import pytest

from conftest import make_tiny_root
from harness import cell, program_spans, trace
from harness.manifest import Manifest
from repro.serve.tracing import Span

#: the profile's start on time_ns, and the lag of each program sched.step
#: behind its harness scheduler span (both found again by the reader)
OFF, LAG = 1_800_000_000_000_000_000, 2

HOST = [["window", 0, 1000], ["generator", 0, 100],
        ["scheduler", 100, 350], ["decode_step", 250, 170],
        ["scheduler", 500, 450], ["decode_step", 600, 340]]
DEVICE = [["a fusion", 50, 70], ["b fusion", 130, 130],
          ["c custom-call:tpu_custom_call", 300, 50],
          ["d fusion", 420, 100], ["e fusion", 700, 100],
          ["f fusion", 990, 10]]
#: (name, start, end) on the trace's clock; parents follow nesting
PROGRAM = [
    ("sched.step", 100, 440), ("sched.admit", 110, 160),
    ("engine.begin_prefill", 120, 150), ("engine.prefill", 170, 240),
    ("engine.decode", 260, 410), ("engine.decode.prepare", 260, 280),
    ("engine.decode.launch", 280, 330), ("host.gc", 290, 300),
    ("engine.decode.readback", 340, 400),
    ("sched.step", 500, 945), ("engine.decode", 610, 930),
    ("engine.decode.prepare", 610, 620), ("engine.decode.launch", 620, 700),
    ("engine.decode.readback", 700, 900), ("engine.evict", 941, 944),
]
# idle: [0,50] [120,130] [260,300] [350,420] [520,700] [800,990]
WANT = {"bench": 50 + 10 + 10 + 5 + 10,
        "engine": 10 + 20 + 10 + 10 + 10 + 80 + 30 + 3,
        "gc": 10, "readback": 50 + 100, "scheduler": 80 + 1 + 1,
        "none": 40}


def _spans():
    out = []
    for i, (name, s, e) in enumerate(PROGRAM):
        parent = max((j for j, (_, ps, pe) in enumerate(PROGRAM[:i])
                      if ps <= s and e <= pe), default=None)
        out.append(Span(name, s + OFF + LAG, e + OFF + LAG, parent, {}, i))
    return out


def _run(events):
    return types.SimpleNamespace(events=events, notes=[])


@pytest.fixture
def run(monkeypatch):
    monkeypatch.setattr(program_spans, "program_spans", _spans)
    return _run({"host": HOST, "device": DEVICE})


def test_offset_from_scheduler_steps():
    ev = {"host": HOST, "device": DEVICE}
    assert program_spans.offset_ns(ev, _spans()) == (OFF + LAG, 2)
    assert program_spans.offset_ns(ev, []) is None


def test_idle_goes_to_the_innermost_span():
    ev = {"host": HOST, "device": DEVICE}
    out = program_spans.attribute(ev, _spans(), OFF + LAG)
    assert out["groups"] == WANT
    # harness-innermost idle (the recorder's work after the program's
    # decode closes) is the benchmark's own
    assert out["by_name"]["decode_step"] == 10 + 10 + 10
    assert out["by_name"]["generator"] == 50
    assert out["gaps"][:2] == [["engine.decode.readback", 190e-9],
                               ["engine.decode.prepare", 180e-9]]
    assert out["clock_ms"] == 10 / 1e6
    assert out["counts"]["engine.decode"] == 2


def test_metrics_and_notes_add_up_to_the_idle_share(run):
    man = Manifest()
    read = {m: man.metric_reader(m)(run) for m in
            ("idle.scheduler", "idle.engine", "idle.readback",
             "device_idle_share")}
    assert read["idle.scheduler"] == pytest.approx(100 * 82 / 1000)
    assert read["idle.engine"] == pytest.approx(100 * 173 / 1000)
    assert read["idle.readback"] == pytest.approx(100 * 150 / 1000)
    rest = sum(100 * WANT[g] / 1000 for g in ("gc", "bench", "none"))
    assert read["idle.scheduler"] + read["idle.engine"] \
        + read["idle.readback"] + rest \
        == pytest.approx(read["device_idle_share"], abs=1e-9)
    # the split is made once: one set of notes for three readers
    assert sum(n.startswith("idle split") for n in run.notes) == 1
    assert any(n.startswith("clock:") for n in run.notes)


def test_no_program_recorder_reads_nothing(monkeypatch):
    monkeypatch.setattr(program_spans, "program_spans", lambda: None)
    run = _run({"host": HOST, "device": DEVICE})
    assert program_spans.share(run, "engine") is None
    assert program_spans.share(_run(None), "engine") is None


def test_traced_cpu_run_lays_decodes_on_decode_steps(tmp_path):
    from repro.serve import tracing
    man = Manifest(make_tiny_root(tmp_path))
    tracing.reset()
    out = cell.run(man, "tiny.mix", 2 ** 33 + 5, 2.0, True, 0.0,
                   compile_cache=False)
    m = out["metrics"]
    for name in ("idle.scheduler", "idle.engine", "idle.readback"):
        assert m[name]["value"] >= 0.0
    ev = out["_events"]
    spans = tracing.spans()
    offset, pairs = program_spans.offset_ns(ev, spans)
    assert pairs > 5
    split = program_spans.attribute(ev, spans, offset)
    assert 100 * sum(split["groups"].values()) / trace.window_ns(ev) \
        == pytest.approx(m["device_idle_share"]["value"])
    steps = [(s, s + d) for n, s, d in ev["host"] if n == "decode_step"]
    for s in spans:
        if s.name == "engine.decode" and s.start_ns - offset > steps[0][0]:
            a, b = s.start_ns - offset, s.end_ns - offset
            assert any(h0 - 1_000_000 <= a and b <= h1 + 1_000_000
                       for h0, h1 in steps), (a, b)
