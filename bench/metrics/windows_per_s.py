"""windows_per_s: candidate windows searched per second of the window.

The sum over every query completed in the measured window of the windows it
searched (``ref_len - query_len + 1``), over the window's seconds (first
dispatch to last completion), host clock.
"""


def read(run):
    if not run.queries:
        return None
    return len(run.queries) * run.n_windows / run.window_s
