"""Output tokens delivered in the window over the window's length
(host clock, from the client's side)."""

from harness import stats


def read(run):
    return stats.tokens_in_window(run.timeline) / run.timeline.seconds
