"""End to end: mappings answered in the window over the window's length.
The window opens when set-up ends and ends at the last answer completed
before ``--seconds``; a mapping still running then is checked, not
counted."""
from bench.check import in_window, window_bounds


def read(rec: dict):
    done = in_window(rec)
    t0, t1 = window_bounds(rec)
    if not done or t1 <= t0:
        return None
    return len(done) / (t1 - t0)
