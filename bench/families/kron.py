"""Graph500 power-law graphs (graph500.org, "Graph generation"), made by
the benchmark's own numpy copy of the Kronecker generator.

The configuration gives ``scale``, ``edgefactor``, the initiator ``A``,
``B``, ``C`` (``D`` = 1 - A - B - C) and ``instance_seeds``: a fixed set
of graphs, whatever the run's seed (a new graph compiles again, as for
``rgg``). Each graph draws 2^scale * edgefactor edges bit by bit from the
initiator, then relabels its vertices by a random permutation and
shuffles its edge list, both from the instance seed, as the specification
does. Self-loops are dropped: a task's traffic to itself is no
communication. Duplicate edges stay as drawn; the program sums them into
edge weights, and the reference sums them over the raw list alike.
Isolated vertices stay: they are part of the published generator.

Nothing here imports the program.
"""
from __future__ import annotations

import numpy as np

from bench import yardstick as Y


def initiator_draws(rng, scale: int, m: int, A: float, B: float,
                    C: float):
    """``m`` edges drawn bit by bit from the initiator, as the Graph500
    reference does: at each of the ``scale`` bits an edge falls in the
    quadrant (0, 0), (0, 1), (1, 0) or (1, 1) of the adjacency matrix with
    probability A, B, C or D. Returns the ``[scale, m]`` bits of ``u`` and
    of ``v``, least significant first."""
    ab = A + B
    c_norm = C / (1.0 - ab)
    a_norm = A / ab
    ii = np.empty((scale, m), bool)
    jj = np.empty((scale, m), bool)
    for bit in range(scale):
        ii[bit] = rng.random(m) > ab
        jj[bit] = rng.random(m) > np.where(ii[bit], c_norm, a_norm)
    return ii, jj


def kronecker_edges(scale: int, edgefactor: int, seed: int,
                    A: float = 0.57, B: float = 0.19, C: float = 0.19):
    """The Graph500 reference generator's edge list ``(u, v)``, self-loops
    included, after the vertex permutation and the edge shuffle."""
    rng = np.random.default_rng(seed)
    n, m = 1 << scale, edgefactor << scale
    ii, jj = initiator_draws(rng, scale, m, A, B, C)
    weights = (np.int64(1) << np.arange(scale, dtype=np.int64))[:, None]
    u = (ii * weights).sum(axis=0)
    v = (jj * weights).sum(axis=0)
    perm = rng.permutation(n)
    u, v = perm[u], perm[v]
    order = rng.permutation(m)
    return u[order], v[order]


def instances(config: dict, seed: int) -> list[Y.Instance]:
    scale, ef = int(config["scale"]), int(config["edgefactor"])
    init = config["initiator"]
    out = []
    for s in config["instance_seeds"]:
        u, v = kronecker_edges(scale, ef, s, init["A"], init["B"], init["C"])
        keep = u != v
        out.append(Y.Instance(f"kron{scale}e{ef}-s{s}", 1 << scale,
                              u[keep], v[keep]))
    return out
