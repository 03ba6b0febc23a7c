"""Scheduler: the mean time of a scheduler step (one ``serve`` call)
spent outside the engine's prefill-chunk and decode-step launches:
admission, page accounting, eviction and the eager insert ops (host
clock).  The traced run waits for each prefill chunk, so the launches
cover their device time."""

from harness import stats


def read(run):
    v = stats.scheduler_time(run.timeline, run.spans)
    return 1e3 * sum(v) / len(v) if v else None
