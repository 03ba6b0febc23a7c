"""Device: the share of the traced window in which no operation ran on
the chip (one minus the union of the device-op intervals)."""

from harness import trace


def read(run):
    if run.events is None:
        return None
    return 100.0 * (1.0 - trace.busy_ns(run.events)
                    / trace.window_ns(run.events))
