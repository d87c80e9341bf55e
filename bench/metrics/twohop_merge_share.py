"""Partition (``core/coarsen.py``, the ``twohop`` scope of the skewed down
scan): the share of the coarsening's pairs that the two-hop and
isolated-vertex pairing made, summed over the window's answers
(``stats["coarsen_twohop_merges"]`` over ``stats["coarsen_merges"]``,
the planner's counter of every lane's down scan). 0 where HEM made every
pair. None where the program keeps no such counter or made no pair."""
from bench.check import in_window


def read(rec: dict):
    done = [a for a in in_window(rec)
            if a.stats and "coarsen_twohop_merges" in a.stats]
    total = sum(a.stats["coarsen_merges"] for a in done)
    if total <= 0:
        return None
    return sum(a.stats["coarsen_twohop_merges"] for a in done) / total
