"""SharedMap as an MoE expert-placement engine.

Expert-to-expert token co-activation forms a communication graph; placing
co-activated experts on nearby chips cuts cross-rack/pod all-to-all volume.

    PYTHONPATH=src python examples/moe_placement.py
"""
import numpy as np

from repro.compile_cache import use_compile_cache
from repro.core import graph as G
from repro.core.api import SharedMapConfig, shared_map
from repro.core.hierarchy import Hierarchy
from repro.core.mapping import evaluate_J


def main():
    use_compile_cache()
    rng = np.random.default_rng(0)
    E = 64  # moonshot-style expert count
    # synthetic co-activation: block-structured (experts specialize by topic)
    blocks = 8
    C = rng.random((E, E)) * 0.1
    for b in range(blocks):
        s = slice(b * E // blocks, (b + 1) * E // blocks)
        C[s, s] += rng.random((E // blocks, E // blocks))
    C = np.triu(C, 1)
    u, v = np.nonzero(C)
    g = G.from_edges(E, u, v, C[u, v])

    # place 64 experts over 4 racks x 16 chips (weight: tokens/pair)
    h = Hierarchy(a=(16, 4), d=(1.0, 10.0))
    res = shared_map(g, h, SharedMapConfig(eps=0.25, preset="eco", seed=0))

    rng2 = np.random.default_rng(1)
    naive = (np.arange(E) * h.k) // E
    rand_J = np.mean([evaluate_J(g, h, rng2.permutation(h.k)[(np.arange(E)*h.k)//E])
                      for _ in range(5)])
    print(f"experts={E} chips={h.k}  ({h})")
    print(f"sharedmap placement J = {res.J:10.1f}")
    print(f"naive block placement J = {evaluate_J(g, h, naive):10.1f}")
    print(f"random placement     J = {rand_J:10.1f}")
    cross = res.J / evaluate_J(g, h, naive)
    print(f"-> cross-rack traffic at {cross:.2f}x of naive placement")


if __name__ == "__main__":
    main()
