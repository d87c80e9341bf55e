"""Quickstart: map a task graph onto a supercomputer hierarchy with SharedMap.

    PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np

from repro.compile_cache import use_compile_cache
from repro.core import graph as G
from repro.core.api import SharedMapConfig, shared_map
from repro.core.baselines import identity_mapping, random_mapping
from repro.core.hierarchy import Hierarchy
from repro.core.mapping import evaluate_J


def main():
    use_compile_cache()
    # A sparse communication graph: 6,000 tasks from a random-geometric
    # pattern (typical of domain-decomposed scientific codes).
    g = G.gen_rgg(6_000, seed=0)
    print(f"communication graph: n={int(g.n)} m={int(g.m)//2} undirected edges")

    # The machine: 4 PEs/processor, 2 processors/node, 3 nodes (paper Fig 1)
    h = Hierarchy(a=(4, 2, 3), d=(1.0, 10.0, 100.0))
    print(f"hierarchy {h} -> k={h.k} PEs")

    for strategy in ("naive", "bucket"):
        res = shared_map(g, h, SharedMapConfig(
            eps=0.03, preset="eco", strategy=strategy, seed=0))
        bw = np.bincount(res.pe_of, minlength=h.k)
        print(f"[{strategy:6s}] J = {res.J:12.0f}   "
              f"balance max/avg = {bw.max() / bw.mean():.3f}   "
              f"partition calls = {res.stats['partition_calls']}   "
              f"time = {res.stats['seconds']:.1f}s")

    print(f"[random] J = {evaluate_J(g, h, random_mapping(g, h)):12.0f}")
    print(f"[identy] J = {evaluate_J(g, h, identity_mapping(g, h)):12.0f}")


if __name__ == "__main__":
    main()
