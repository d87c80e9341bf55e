"""Planner (``core/multisection.py``): vertex slots the partition calls
processed, padding included, over the real vertices they held, summed over
the window's answers (``stats["padded_vertex_work"]`` and
``stats["real_vertex_work"]``). 1 is no padding."""
from bench.check import in_window


def read(rec: dict):
    done = [a for a in in_window(rec) if a.stats]
    real = sum(a.stats.get("real_vertex_work", 0) for a in done)
    if real <= 0:
        return None
    return sum(a.stats.get("padded_vertex_work", 0) for a in done) / real
