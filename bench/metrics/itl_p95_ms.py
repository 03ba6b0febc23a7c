"""95th percentile over every gap between consecutive tokens of one
request, both tokens inside the window (host clock)."""

from harness import stats


def read(run):
    v = stats.percentile(stats.gaps(run.timeline), 95)
    return None if v is None else 1e3 * v
