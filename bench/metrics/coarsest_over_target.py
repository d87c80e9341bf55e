"""Partition (``core/partition.py``, the down scan): how far coarsening
stops from the size the v-cycle's depth was set for, summed over the
window's answers (``stats["coarsest_real_vertices"]``, each lane's
coarsest graph's vertices, over ``stats["coarsening_target"]``, each
lane's ``max(24 k, 64)``). 1 is on target; above it the initial
partition and the coarse levels work on graphs larger than planned.
None where the program keeps no such counter."""
from bench.check import in_window


def read(rec: dict):
    done = [a for a in in_window(rec)
            if a.stats and "coarsening_target" in a.stats]
    target = sum(a.stats["coarsening_target"] for a in done)
    if target <= 0:
        return None
    return sum(a.stats["coarsest_real_vertices"] for a in done) / target
