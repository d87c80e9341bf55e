"""Partition (``core/partition.py``, the fused v-cycle): the share of the
vertex slots the v-cycle's passes ran at that held real vertices, summed
over the window's answers (``stats["vcycle_real_vertex_work"]`` over
``stats["vcycle_padded_vertex_work"]``, the planner's counter of each
lane's graph, its coarse graphs and its finest-level v-cycles). 1 is no
padding. None where the program keeps no such counter."""
from bench.check import in_window


def read(rec: dict):
    done = [a for a in in_window(rec)
            if a.stats and "vcycle_padded_vertex_work" in a.stats]
    padded = sum(a.stats["vcycle_padded_vertex_work"] for a in done)
    if padded <= 0:
        return None
    return sum(a.stats["vcycle_real_vertex_work"] for a in done) / padded
