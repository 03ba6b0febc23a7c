"""The trace reduction (bench/harness/trace.py) on a trace recorded on
the chip: three scheduler steps of sc2-7b.batch-decode, cut and kept as
plain events in tests/bench/data/."""

import json
import pathlib

import pytest

from harness import trace

DATA = pathlib.Path(__file__).parent / "data" / "sc2_batch_decode_3_steps.json"
KERNEL = r" custom-call:tpu_custom_call$"


@pytest.fixture(scope="module")
def ev():
    return json.loads(DATA.read_text())


def _union(intervals):
    """Independent union length: sweep over sorted endpoints."""
    total, cur_s, cur_e = 0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    return total + (cur_e - cur_s if cur_e is not None else 0)


def test_window_is_the_window_span(ev):
    w = [h for h in ev["host"] if h[0] == "window"][0]
    assert trace.window_of(ev) == (w[1], w[1] + w[2])
    assert trace.window_ns(ev) == w[2]


def test_busy_is_the_union_of_device_ops(ev):
    w0, w1 = trace.window_of(ev)
    clipped = [(max(s, w0), min(s + d, w1)) for _, s, d in ev["device"]
               if s + d > w0 and s < w1]
    assert trace.busy_ns(ev) == _union(clipped)
    # nested ops (the layer scan's while holds the kernels) count once
    assert trace.busy_ns(ev) < sum(e - s for s, e in clipped)
    assert 0 < trace.busy_ns(ev) <= trace.window_ns(ev)


def test_kernel_time_by_name_inside_its_host_span(ev):
    steps = [(s, s + d) for n, s, d in ev["host"] if n == "decode_step"]
    want = [d for n, s, d in ev["device"]
            if n.endswith(" custom-call:tpu_custom_call")
            and any(a <= s < b for a, b in steps)]
    total, count = trace.kernel_ns(ev, KERNEL, "decode_step")
    assert (total, count) == (sum(want), len(want))
    # three decode steps of a 16-layer model: one megakernel per layer
    assert count == 3 * 16
    assert trace.kernel_ns(ev, KERNEL, "prefill_chunk") == (0, 0)


def test_top_ops_leave_out_containers(ev):
    top = trace.top_ops(ev, 5)
    assert len(top) == 5
    assert top[0][0].endswith(" custom-call:tpu_custom_call")
    assert not any(name.endswith(" while") for name, _ in top)
    assert [t for _, t in top] == sorted((t for _, t in top), reverse=True)


def test_idle_gaps_named_by_the_host_span_they_fall_in(ev):
    gaps = trace.idle_gaps(ev, 10)
    w0, w1 = trace.window_of(ev)
    idle = trace.window_ns(ev) - trace.busy_ns(ev)
    assert sum(g for _, g in gaps) <= idle / 1e9 + 1e-9
    assert [g for _, g in gaps] == sorted((g for _, g in gaps),
                                          reverse=True)
    assert {n for n, _ in gaps} <= set(trace.HOST_SPANS) | {"idle"}
    # between launches the host is inside the step that reads the
    # logits back: the longest gaps fall in decode_step spans
    assert gaps[0][0] == "decode_step"


def test_idle_gap_attribution_on_made_up_events():
    ev = {"host": [["window", 0, 100], ["scheduler", 0, 100],
                   ["decode_step", 10, 50], ["generator", 80, 20]],
          "device": [["a fusion", 0, 10], ["b fusion", 30, 10],
                     ["c fusion", 50, 30]]}
    assert trace.busy_ns(ev) == 50
    # gaps: 10-30 (decode_step), 40-50 (decode_step), 80-100 (generator)
    assert trace.idle_gaps(ev) == [["decode_step", 20e-9],
                                   ["generator", 20e-9],
                                   ["decode_step", 10e-9]]


@pytest.mark.parametrize("text,label", [
    ('%checkpoint.9 = bf16[32,16,4608]{2,1,0} custom-call(s32[32]{0} '
     '%a), custom_call_target="tpu_custom_call", operand_layout_'
     'constraints={s32[32]{0}}', "checkpoint.9 custom-call:tpu_custom_call"),
    ('%while.5 = (s32[], bf16[32,1,4608]{2,1,0}) while((s32[], '
     'bf16[32,1,4608]{2,1,0}) %tuple), condition=%c, body=%b',
     "while.5 while"),
    ('%fusion.94 = bf16[4097,4,16,128]{3,1,2,0} fusion(bf16[4097] %x), '
     'kind=kCustom', "fusion.94 fusion"),
    ("copy-done.1", "copy-done.1"),
])
def test_op_label(text, label):
    assert trace.op_label(text) == label


def test_cut_keeps_only_the_interval(ev):
    w0, w1 = trace.window_of(ev)
    mid = (w0 + w1) // 2
    half = trace.cut(ev, w0, mid)
    assert trace.window_of(half) == (w0, mid)
    assert all(w0 <= s < mid for _, s, _ in half["device"])
