"""The benchmark's own yardstick: inputs from a seed and the plain reference.

Nothing here imports the program. The rgg generator is a copy of the
program's ``core/graph.py:gen_rgg`` (so that a later change there cannot
move the benchmark's inputs); ``numpy_J`` and the compile-span listener
follow ``chip_smoke.py``'s. Everything is float64 numpy.
"""
from __future__ import annotations

import threading

import numpy as np


# --- inputs ------------------------------------------------------------------

def gen_rgg_edges(n: int, seed: int, radius_scale: float = 0.55):
    """Random geometric graph in the unit square (the paper's rgg family):
    the undirected edges ``(u, v)``, ``u < v``, each listed once."""
    rng = np.random.default_rng(seed)
    pts = rng.random((n, 2))
    r = radius_scale * np.sqrt(np.log(max(n, 2)) / n)
    nb = max(1, int(1.0 / r))
    cell = (pts / (1.0 / nb)).astype(np.int64)
    cell_id = cell[:, 0] * nb + cell[:, 1]
    order = np.argsort(cell_id, kind="stable")
    uniq, first = np.unique(cell_id[order], return_index=True)
    starts = dict(zip(uniq.tolist(), first.tolist()))
    bounds = dict(zip(uniq.tolist(), np.append(first[1:], n).tolist()))
    us, vs = [], []
    for cx in range(nb):
        for cy in range(nb):
            cid = cx * nb + cy
            if cid not in starts:
                continue
            a = order[starts[cid]:bounds[cid]]
            cand = [a]
            for dx, dy in ((0, 1), (1, -1), (1, 0), (1, 1)):
                nc = (cx + dx) * nb + (cy + dy)
                if 0 <= cx + dx < nb and 0 <= cy + dy < nb and nc in starts:
                    cand.append(order[starts[nc]:bounds[nc]])
            b = np.concatenate(cand)
            d2 = ((pts[a, None, :] - pts[None, b, :]) ** 2).sum(-1)
            ii, jj = np.nonzero(d2 <= r * r)
            uu, vv = a[ii], b[jj]
            keep = uu < vv
            us.append(uu[keep])
            vs.append(vv[keep])
    u = np.concatenate(us) if us else np.zeros(0, np.int64)
    v = np.concatenate(vs) if vs else np.zeros(0, np.int64)
    return u, v


class Instance:
    """One graph as the benchmark holds it: undirected edges listed once,
    float64 weights, and the name a request carries."""

    def __init__(self, name: str, n: int, u, v, w=None, vwgt=None):
        self.name = name
        self.n = int(n)
        self.u = np.asarray(u, np.int64)
        self.v = np.asarray(v, np.int64)
        self.w = (np.ones(self.u.size) if w is None
                  else np.asarray(w, np.float64))
        self.vwgt = (np.ones(self.n) if vwgt is None
                     else np.asarray(vwgt, np.float64))


def random_J(inst: Instance, a, d) -> float:
    """The expected J of a uniform random placement, which ``J_over_random``
    divides by: each edge's weight times the mean distance of two PEs
    drawn independently and uniformly. Two such PEs first share a group of
    ``s_j`` PEs (``s_0 = 1``, ``s_l = k``) at level j with probability
    ``(s_j - s_{j-1}) / k``, and then lie ``d_j`` apart."""
    s = np.cumprod((1,) + tuple(a))
    mean_d = float(np.sum(np.asarray(d, np.float64) * np.diff(s)) / s[-1])
    return float(inst.w.sum()) * mean_d


# --- the plain reference -----------------------------------------------------

def numpy_J(inst: Instance, pe_of, a, d) -> float:
    """J = sum over undirected edges of w * D(pe_u, pe_v), in float64.
    ``a``/``d``: the hierarchy's arities and distances, innermost first."""
    pe = np.asarray(pe_of, np.int64)
    return float(np.sum(inst.w * edge_distance(pe[inst.u], pe[inst.v], a, d)))


def bf16_J(inst: Instance, pe_of, a, d) -> float:
    """The control: ``numpy_J`` one precision below the program's float32,
    in bfloat16 throughout (weights, distances, products and a pairwise
    sum, each step rounded to bfloat16)."""
    from ml_dtypes import bfloat16
    pe = np.asarray(pe_of, np.int64)
    dist = edge_distance(pe[inst.u], pe[inst.v], a, d).astype(bfloat16)
    terms = inst.w.astype(bfloat16) * dist
    size = 1 << max(int(terms.size - 1).bit_length(), 0)
    terms = np.concatenate([terms, np.zeros(size - terms.size, bfloat16)])
    while terms.size > 1:
        terms = terms[0::2] + terms[1::2]
    return float(terms[0]) if terms.size else 0.0


def edge_distance(pu, pv, a, d) -> np.ndarray:
    """D of each PE pair: the distance of the outermost level they differ
    at (0 on one PE)."""
    below = np.cumprod((1,) + tuple(a[:-1]))
    lvl = sum((pu // b != pv // b).astype(np.int64) for b in below)
    return np.concatenate([[0.0], np.asarray(d, np.float64)])[lvl]


def load_over_limit(inst: Instance, pe_of, k: int, eps: float) -> float:
    """The largest PE load over the load the instance admits on that PE.

    The balance constraint is ``(1 + eps) * ceil(W / k)``. Where a task
    weighs more than ``ceil(W / k)`` no placement meets it, so a PE that
    holds such a task is held to ``(1 + eps)`` times the heaviest task of
    the instance instead, the usual bound for such instances; every other
    PE keeps the constraint. Within it when at most 1.
    """
    pe = np.asarray(pe_of, np.int64)
    loads = np.bincount(pe, weights=inst.vwgt, minlength=k)
    heaviest = np.zeros(k)
    np.maximum.at(heaviest, pe, inst.vwgt)
    fair = np.ceil(inst.vwgt.sum() / k)
    admitted = (1 + eps) * np.where(heaviest > fair, inst.vwgt.max(), fair)
    return float(np.max(loads / admitted))


# --- compile spans (JAX monitoring), as chip_smoke.py listens to them ------

LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"


class CompileSpans:
    """Listens to JAX's compile spans and keeps when each program was
    lowered, on the host clock (``time.time``)."""

    def __init__(self):
        self.starts: list[float] = []
        self._lock = threading.Lock()

    def __call__(self, event, start, end, **_):
        if event == LOWER_EVENT:
            with self._lock:
                self.starts.append(start)

    def lowered(self, t0: float, t1: float) -> int:
        """Programs lowered in [t0, t1]: each is a program this process had
        not built before, compiled or read back from the persistent cache."""
        with self._lock:
            return sum(1 for a in self.starts if t0 <= a <= t1)
