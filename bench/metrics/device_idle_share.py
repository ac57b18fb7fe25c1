"""device_idle_share: share of the window in which no op ran on a chip, %.

One minus the union of a chip's op intervals over the traced window, mean
over the chips used.
"""
from benchkit import trace as tr


def read(run):
    if run.trace is None or not run.trace.devices:
        return None
    return 100.0 * (1.0 - tr.busy_ns(run.trace) / run.trace.window_ns)
