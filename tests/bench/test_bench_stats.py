"""The end-to-end metric arithmetic (bench/harness/stats.py) on
hand-made timestamps."""

import pytest

from harness import stats
from harness.driver import Timeline
from harness.recorder import Span


def tl(**kw):
    base = dict(t0=10.0, t1=20.0, due={}, submitted={}, leased={},
                tokens={}, failed=[], steps=0)
    base.update(kw)
    return Timeline(**base)


def test_percentile_is_nearest_rank():
    xs = [5, 1, 4, 2, 3, 6, 7, 8, 9, 10]
    assert stats.percentile(xs, 90) == 9
    assert stats.percentile(xs, 95) == 10
    assert stats.percentile(xs, 50) == 5
    assert stats.percentile([7], 99) == 7
    assert stats.percentile([], 90) is None


def test_tokens_over_the_window_only():
    t = tl(tokens={1: [9.0, 10.0, 15.0, 20.0, 20.5], 2: [12.0]})
    assert stats.tokens_in_window(t) == 4        # 10, 15, 20 and 12
    assert t.seconds == 10.0


def test_ttft_from_due_time_and_censored():
    t = tl(due={1: 11.0, 2: 12.0, 3: 19.0, 4: 9.0, 5: 25.0},
           submitted={1: 11.5, 2: 12.0, 3: 19.0},
           tokens={1: [13.0, 14.0], 2: [], 3: [21.0]})
    # 1 timed from its due time (not its late submit); 2 and 3 have no
    # token inside the window: counted with their wait to its end; 4 and
    # 5 were not due in the window
    assert sorted(stats.ttfts(t)) == [1.0, 2.0, 8.0]
    assert stats.lateness(t) == [0.5, 0.0, 0.0]


def test_scheduler_time_outside_the_launch_spans():
    t = tl(step_spans=[(10.0, 11.0), (11.0, 12.5), (13.0, 13.5)])
    spans = [Span("prefill", 10.1, 10.3, None),
             Span("decode", 10.4, 10.9, None),
             Span("decode", 11.2, 12.4, None),
             Span("decode", 12.6, 12.9, None)]   # between steps: not counted
    assert stats.scheduler_time(t, spans) == pytest.approx([0.3, 0.3, 0.5])


def test_gaps_between_consecutive_tokens_inside_the_window():
    t = tl(tokens={1: [9.0, 10.5, 11.0, 13.0], 2: [19.0, 20.0, 21.0],
                   3: [15.0]})
    assert sorted(stats.gaps(t)) == pytest.approx([0.5, 1.0, 2.0])


def test_due_in_window_closed_interval():
    t = tl(due={1: 10.0, 2: 20.0, 3: 9.99, 4: 20.01})
    assert sorted(stats.due_in_window(t)) == [1, 2]
