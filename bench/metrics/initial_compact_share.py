"""Partition (``core/partition.py``, the ``initial`` stage): the share of
the dispatches whose initial partition could run cut to a smaller width
that did, because every lane's coarsest graph fitted it, summed over the
window's answers (``stats["initial_compact_dispatches"]`` over
``stats["initial_dispatches"]``). 1 is every such dispatch compact. None
where the program keeps no such counter or no dispatch could shrink."""
from bench.check import in_window


def read(rec: dict):
    done = [a for a in in_window(rec)
            if a.stats and "initial_dispatches" in a.stats]
    total = sum(a.stats["initial_dispatches"] for a in done)
    if total <= 0:
        return None
    return sum(a.stats["initial_compact_dispatches"] for a in done) / total
