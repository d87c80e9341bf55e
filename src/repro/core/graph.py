"""Graph substrate: static-shape CSR graphs as JAX pytrees.

All partitioning kernels operate on `Graph`, a padded CSR representation
with static array shapes so that the same jitted program serves every
subgraph of a hierarchy level (the LAYER/BUCKET scheduling strategies vmap
over stacked `Graph`s).

Conventions
-----------
* Vertices ``0 .. n-1`` are real, ``n .. N-1`` are padding (weight 0).
* Every undirected edge {u, v} is stored twice (u->v and v->u).
* Edge slots ``m .. M-1`` are padding: ``rows == cols == n_pad_anchor`` and
  ``ewgt == 0`` so they are harmless under segment reductions.
* ``rows`` is sorted ascending over the real slots and ``indptr`` is the
  exact CSR prefix over them (``indptr[r]`` = first edge of row ``r``;
  rows >= the real vertex count all point at ``m``). Every constructor in
  this repo funnels through :func:`padded_csr_indptr` /
  :func:`assemble_padded` so the invariant holds at all hierarchy levels.

ELL adjacency (kernel layout)
-----------------------------
:func:`ell_adjacency` derives a padded ``[N, DEG]`` neighbour/weight matrix
pair from the CSR arrays for the Pallas refinement kernels
(``kernels/lp_gain.py``). ``DEG`` is a *static* degree cap chosen host-side
(:func:`default_ell_deg`: twice the mean directed degree, rounded up to a
multiple of 8, clamped to ``ELL_DEG_CAP``). Rows with more than ``DEG``
neighbours are reported in an ``overflow`` mask; callers pick the policy
(the kernel-backed refiner freezes overflow rows so truncated gains can
never admit a bad move; the rebalancer keeps them movable since balance
only needs the exact weight bookkeeping — see ``core/refine.py``).
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels import ops as kops


class Graph(NamedTuple):
    """Padded CSR graph (pytree; all fields are arrays for vmap-ability)."""

    vwgt: jax.Array    # [N]   f32 vertex weights (0 on padding)
    rows: jax.Array    # [M]   i32 source vertex of each directed edge
    cols: jax.Array    # [M]   i32 target vertex of each directed edge
    ewgt: jax.Array    # [M]   f32 edge weights (0 on padding)
    indptr: jax.Array  # [N+1] i32 CSR row pointers over the padded arrays
    n: jax.Array       # []    i32 number of real vertices
    m: jax.Array       # []    i32 number of real directed edges

    @property
    def N(self) -> int:
        return self.vwgt.shape[0]

    @property
    def M(self) -> int:
        return self.rows.shape[0]

    def total_weight(self) -> jax.Array:
        return jnp.sum(self.vwgt)


def check_i32_range(n: int, m: int) -> None:
    """Overflow guard for the int32 index convention.

    The whole pipeline (device CSR, relabel gathers, `pe_of`) indexes with
    int32; a graph with >= 2^31 vertices or directed edges would silently
    wrap. Every host-side constructor calls this before allocating.
    """
    limit = 2**31
    if n >= limit or m >= limit:
        raise ValueError(
            f"graph exceeds int32 index range: n={n}, m={m} (>= 2^31); "
            "the int32 CSR convention cannot represent it")


def padded_csr_indptr(rows: np.ndarray, m: int, N: int) -> np.ndarray:
    """[N+1] exact CSR prefix over the sorted real directed rows ``rows[:m]``.

    Rows with no edges (including every padding row >= the real vertex
    count) get an empty range; since counts sum to ``m``, all trailing
    entries equal ``m`` — no clamping needed (the old ``np.minimum(indptr,
    m)`` clamp silently flattened offsets whenever a caller passed rows that
    were not already consistent with ``m``).
    """
    counts = np.bincount(np.asarray(rows[:m], np.int64), minlength=N)
    indptr = np.zeros(N + 1, np.int64)
    np.cumsum(counts, out=indptr[1:])
    return indptr


def assemble_padded(
    vwgt: np.ndarray,
    rows: np.ndarray,
    cols: np.ndarray,
    ewgt: np.ndarray,
    n: int,
    N: int,
    M: int,
    host: bool = False,
) -> Graph:
    """Assemble a device `Graph` from REAL (unpadded) host arrays.

    ``rows`` must be sorted ascending; one host->device transfer per field.
    This is the single construction path shared by `from_edges`,
    `pad_graph` and the multisection subgraph extractor. ``host=True``
    keeps the fields as numpy arrays and starts no JAX backend (a process
    that only forwards graphs to worker processes).
    """
    m = int(np.asarray(rows).shape[0])
    check_i32_range(max(n, N), max(m, M))
    if N < n or M < m:
        raise ValueError(f"padding too small: N={N}<{n} or M={M}<{m}")
    r = np.full(M, N - 1, np.int32)
    c = np.full(M, N - 1, np.int32)
    w = np.zeros(M, np.float32)
    r[:m] = rows
    c[:m] = cols
    w[:m] = ewgt
    vw = np.zeros(N, np.float32)
    vw[:n] = vwgt
    g = Graph(vwgt=vw, rows=r, cols=c, ewgt=w,
              indptr=padded_csr_indptr(r, m, N).astype(np.int32),
              n=np.int32(n), m=np.int32(m))
    return g if host else Graph(*map(jnp.asarray, g))


def from_edges(
    n: int,
    u: np.ndarray,
    v: np.ndarray,
    w: np.ndarray | None = None,
    vwgt: np.ndarray | None = None,
    N: int | None = None,
    M: int | None = None,
    host: bool = False,
) -> Graph:
    """Build a padded CSR `Graph` from an undirected edge list (host-side).

    ``u, v`` are endpoints of undirected edges (each listed once); weights
    default to 1. ``N``/``M`` give the padded sizes (default: exact fit).
    ``host`` as in :func:`assemble_padded`.
    """
    u = np.asarray(u, np.int64)
    v = np.asarray(v, np.int64)
    keep = u != v  # drop self loops
    u, v = u[keep], v[keep]
    w = np.ones(u.shape[0], np.float64) if w is None else np.asarray(w, np.float64)[keep]
    vwgt_np = np.ones(n, np.float64) if vwgt is None else np.asarray(vwgt, np.float64)

    du = np.concatenate([u, v])
    dv = np.concatenate([v, u])
    dw = np.concatenate([w, w])
    m = du.shape[0]

    N = int(N if N is not None else n)
    M = int(M if M is not None else max(m, 1))

    order = np.argsort(du, kind="stable")
    return assemble_padded(vwgt_np, du[order], dv[order], dw[order], n, N, M,
                           host=host)


def edge_mask(g: Graph) -> jax.Array:
    """[M] bool — True on real (non-padding) edge slots."""
    return jnp.arange(g.M) < g.m


def vertex_mask(g: Graph) -> jax.Array:
    """[N] bool — True on real vertices."""
    return jnp.arange(g.N) < g.n


def degrees(g: Graph) -> jax.Array:
    return g.indptr[1:] - g.indptr[:-1]


def edge_cut(g: Graph, part: jax.Array) -> jax.Array:
    """Total weight of cut edges (each undirected edge counted once)."""
    cut = (part[g.rows] != part[g.cols]) & edge_mask(g)
    return jnp.sum(jnp.where(cut, g.ewgt, 0.0)) / 2.0


def block_weights(g: Graph, part: jax.Array, k: int) -> jax.Array:
    """[k] f32 — total vertex weight per block (padding contributes 0)."""
    safe = jnp.where(vertex_mask(g), part, 0)
    return jax.ops.segment_sum(g.vwgt, safe, num_segments=k)


# ---------------------------------------------------------------------------
# Synthetic instance generators (the paper's benchmark families, downscaled).
# All host-side numpy, seeded, deterministic.
# ---------------------------------------------------------------------------

def gen_rgg(n: int, seed: int = 0, radius_scale: float = 0.55,
            host: bool = False) -> Graph:
    """Random geometric graph in the unit square (paper: rgg23/rgg24)."""
    rng = np.random.default_rng(seed)
    pts = rng.random((n, 2))
    r = radius_scale * np.sqrt(np.log(max(n, 2)) / n)
    # grid bucketing for near-linear neighbour search
    nb = max(1, int(1.0 / r))
    cell = (pts / (1.0 / nb)).astype(np.int64)
    cell_id = cell[:, 0] * nb + cell[:, 1]
    order = np.argsort(cell_id, kind="stable")
    us, vs = [], []
    starts = {}
    sorted_ids = cell_id[order]
    uniq, first = np.unique(sorted_ids, return_index=True)
    for cid, fi in zip(uniq, first):
        starts[int(cid)] = int(fi)
    bounds = dict(zip(uniq.tolist(), np.append(first[1:], n).tolist()))
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
    return from_edges(n, u, v, host=host)


def gen_grid(side: int, diag: bool = True, host: bool = False) -> Graph:
    """Triangulated grid — a Delaunay-triangulation stand-in (del23/del24)."""
    n = side * side
    idx = np.arange(n).reshape(side, side)
    us = [idx[:, :-1].ravel(), idx[:-1, :].ravel()]
    vs = [idx[:, 1:].ravel(), idx[1:, :].ravel()]
    if diag:
        us.append(idx[:-1, :-1].ravel())
        vs.append(idx[1:, 1:].ravel())
    return from_edges(n, np.concatenate(us), np.concatenate(vs), host=host)


def gen_road(n: int, seed: int = 0) -> Graph:
    """Road-network-like graph (paper: eur/deu): sparse, low degree, long
    paths — a perturbed grid with random shortcuts removed/added."""
    side = int(np.sqrt(n))
    n = side * side
    rng = np.random.default_rng(seed)
    idx = np.arange(n).reshape(side, side)
    u = np.concatenate([idx[:, :-1].ravel(), idx[:-1, :].ravel()])
    v = np.concatenate([idx[:, 1:].ravel(), idx[1:, :].ravel()])
    keep = rng.random(u.shape[0]) > 0.1  # drop 10% of edges -> irregularity
    u, v = u[keep], v[keep]
    ns = n // 50  # sparse shortcuts
    su = rng.integers(0, n, ns)
    sv = np.minimum(su + rng.integers(1, side, ns), n - 1)
    return from_edges(n, np.concatenate([u, su]), np.concatenate([v, sv]))


def gen_kron(scale: int, edge_factor: int = 8, seed: int = 0) -> Graph:
    """Kronecker-style power-law graph (complex-network instance family)."""
    n = 1 << scale
    m = n * edge_factor
    rng = np.random.default_rng(seed)
    A, B, C = 0.57, 0.19, 0.19
    u = np.zeros(m, np.int64)
    v = np.zeros(m, np.int64)
    for bit in range(scale):
        r1 = rng.random(m)
        r2 = rng.random(m)
        ubit = (r1 > A + B).astype(np.int64)
        vbit = np.where(ubit == 0, (r1 > A).astype(np.int64), (r2 > C / (C + (1 - A - B - C))).astype(np.int64))
        u |= ubit << bit
        v |= vbit << bit
    keep = u != v
    return from_edges(n, u[keep], v[keep])


GENERATORS = {
    "rgg": gen_rgg,
    "grid": lambda n, seed=0: gen_grid(int(np.sqrt(n))),
    "road": gen_road,
    "kron": lambda n, seed=0: gen_kron(max(int(np.log2(max(n, 2))), 4), seed=seed),
}


def pad_graph(g: Graph, N: int, M: int) -> Graph:
    """Host-side re-pad to (N, M) >= current real sizes."""
    n = int(g.n)
    m = int(g.m)
    return assemble_padded(
        np.asarray(g.vwgt)[:n],
        np.asarray(g.rows)[:m],
        np.asarray(g.cols)[:m],
        np.asarray(g.ewgt)[:m],
        n, N, M,
    )


# ---------------------------------------------------------------------------
# Device-resident subgraph extraction (the multisection level loop)
# ---------------------------------------------------------------------------

def repad_device(g: Graph, N2: int, M2: int) -> Graph:
    """Trace-time re-pad of a Graph to static shapes ``(N2, M2)`` — the
    on-device analogue of :func:`pad_graph`. Shrinking drops only padding
    slots (callers guarantee the real counts fit); growing extends with
    the standard pad convention (rows/cols anchored at ``N2-1``, weight 0,
    trailing ``indptr`` = m). Works under vmap (all fields sliced/extended
    along the last axis)."""
    N, M = g.N, g.M

    def fit(a: jax.Array, L: int, fill) -> jax.Array:
        if a.shape[-1] >= L:
            return a[..., :L]
        pad = jnp.full(a.shape[:-1] + (L - a.shape[-1],), fill, a.dtype)
        return jnp.concatenate([a, pad], axis=-1)

    ar_m = jnp.arange(M2, dtype=jnp.int32)
    rows = jnp.where(ar_m < g.m, fit(g.rows, M2, 0), N2 - 1)
    cols = jnp.where(ar_m < g.m, fit(g.cols, M2, 0), N2 - 1)
    ewgt = fit(g.ewgt, M2, 0)        # pads are already 0-weight
    vwgt = fit(g.vwgt, N2, 0)
    # indptr: entries past the real vertex count all equal m, so slicing is
    # exact and extension fills with m.
    ar_n = jnp.arange(N2 + 1, dtype=jnp.int32)
    indptr = jnp.where(ar_n < N + 1, fit(g.indptr, N2 + 1, 0), g.m)
    return Graph(vwgt=vwgt, rows=rows, cols=cols, ewgt=ewgt, indptr=indptr,
                 n=g.n, m=g.m)


def take_lanes(g: Graph, sel: jax.Array) -> Graph:
    """Select lanes of a stacked ``[B, ...]`` Graph: fields indexed along
    axis 0 by ``sel`` (device-side; used to regroup resident children)."""
    return jax.tree_util.tree_map(lambda a: jnp.take(a, sel, axis=0), g)


def split_blocks(g: Graph, part: jax.Array, orig: jax.Array, k: int,
                 sentinel: jax.Array) -> tuple[Graph, jax.Array, jax.Array]:
    """On-device induced-subgraph extraction: the ``k`` block subgraphs of
    ``g`` under ``part``, as ONE stacked ``[k, N]``/``[k, M]`` Graph.

    The device analogue of the host ``_split`` (core/multisection.py) —
    stable-sort-by-block + segment offsets + relabel gather, all static
    shapes so it jits and vmaps over hierarchy-level lanes. Child arrays
    are produced in the SAME order as the host path (stable sort preserves
    vertex/edge order within a block, and the parent's sorted-``rows``
    invariant plus the monotone within-block relabel keeps child rows
    sorted), so the two paths are bitwise interchangeable.

    ``orig`` is the [N] original-vertex-id view of ``g``'s lanes (padding
    slots hold ``sentinel``); ``sentinel`` is propagated to child padding
    so leaf scatters can dump pad writes into a spare ``pe_of`` slot.

    Returns ``(children, child_orig, wsum)``: a stacked Graph whose ``n``/
    ``m`` fields are ``[k]`` per-child real counts, the ``[k, N]`` original
    ids, and the ``[k]`` f32 child vertex-weight sums (for the device-side
    adaptive-imbalance rule).
    """
    N, M = g.N, g.M
    ar_n = jnp.arange(N, dtype=jnp.int32)
    ar_m = jnp.arange(M, dtype=jnp.int32)

    # --- vertices: stable compaction by block --------------------------------
    blk = jnp.where(ar_n < g.n, part[:N].astype(jnp.int32), k)
    counts = jnp.zeros(k + 1, jnp.int32).at[blk].add(1)
    voff = jnp.cumsum(counts) - counts                       # exclusive prefix
    order = jnp.argsort(blk, stable=True).astype(jnp.int32)
    rank = ar_n - voff[blk[order]]
    relabel = jnp.zeros(N, jnp.int32).at[order].set(rank)    # parent -> child id
    vsrc = voff[:k, None] + ar_n[None, :]                    # [k, N] source slots
    v_ok = ar_n[None, :] < counts[:k, None]
    vids = jnp.take(order, jnp.clip(vsrc, 0, N - 1))
    cvwgt = jnp.where(v_ok, kops.gather_rows(g.vwgt, vids), 0.0)
    corig = jnp.where(v_ok, kops.gather_rows(orig, vids), sentinel)

    # --- edges: keep intra-block, relabel endpoints --------------------------
    emask = ar_m < g.m       # padding anchors (N-1) may alias a real vertex
    bu = blk[jnp.clip(g.rows, 0, N - 1)]
    bv = blk[jnp.clip(g.cols, 0, N - 1)]
    eb = jnp.where(emask & (bu == bv) & (bu < k), bu, k)
    ecounts = jnp.zeros(k + 1, jnp.int32).at[eb].add(1)
    eoff = jnp.cumsum(ecounts) - ecounts
    eorder = jnp.argsort(eb, stable=True).astype(jnp.int32)
    esrc = eoff[:k, None] + ar_m[None, :]
    e_ok = ar_m[None, :] < ecounts[:k, None]
    eids = jnp.take(eorder, jnp.clip(esrc, 0, M - 1))
    crows = jnp.where(e_ok, kops.gather_rows(relabel[g.rows], eids), N - 1)
    ccols = jnp.where(e_ok, kops.gather_rows(relabel[g.cols], eids), N - 1)
    cewgt = jnp.where(e_ok, kops.gather_rows(g.ewgt, eids), 0.0)

    # --- exact per-child CSR prefix (matches padded_csr_indptr) --------------
    rtar = jnp.where(e_ok, crows, N)  # row N = dropped (see scatter mode)
    rowcnt = (jnp.zeros((k, N + 1), jnp.int32)
              .at[jnp.arange(k)[:, None], rtar].add(1, mode="drop")[:, :N])
    cindptr = jnp.concatenate(
        [jnp.zeros((k, 1), jnp.int32), jnp.cumsum(rowcnt, axis=1)], axis=1)

    wsum = jax.ops.segment_sum(g.vwgt, blk, num_segments=k + 1)[:k]
    children = Graph(vwgt=cvwgt, rows=crows, cols=ccols, ewgt=cewgt,
                     indptr=cindptr, n=counts[:k], m=ecounts[:k])
    return children, corig, wsum


# ---------------------------------------------------------------------------
# ELL adjacency (the Pallas refinement-kernel layout)
# ---------------------------------------------------------------------------

ELL_DEG_CAP = 64  # hard cap on the static neighbour-matrix width


def default_ell_deg(N: int, M: int, cap: int = ELL_DEG_CAP) -> int:
    """Static degree cap for the [N, DEG] ELL layout.

    Twice the mean directed degree, rounded up to a multiple of 8 (VREG
    sublane alignment), clamped to ``[8, cap]``. Mesh-like instances (the
    paper's main families, max degree ~8) fit entirely; power-law tails
    exceed it and land in the overflow mask.
    """
    avg = (M + max(N, 1) - 1) // max(N, 1)
    return int(min(cap, max(8, ((2 * avg + 7) // 8) * 8)))


def ell_adjacency(g: Graph, deg: int) -> tuple[jax.Array, jax.Array, jax.Array]:
    """CSR -> padded ELL, jit-compatible (``deg`` static).

    Returns ``(adj [N, deg], adw [N, deg], overflow [N])`` where ``adj``
    holds neighbour ids (padding slots = N, matching the lp_gain kernel's
    pad convention), ``adw`` the edge weights (0 on padding), and
    ``overflow[u]`` flags vertices whose degree exceeds ``deg`` (their ELL
    row is truncated to the first ``deg`` CSR neighbours).

    Relies on the Graph invariant that ``rows`` is sorted and ``indptr`` is
    its exact prefix, so each edge's within-row position is
    ``index - indptr[row]`` — no argsort needed (cf. ref.csr_to_ell).
    """
    N, M = g.N, g.M
    idx = jnp.arange(M, dtype=jnp.int32)
    emask = idx < g.m
    r = jnp.clip(g.rows, 0, N - 1)
    pos = idx - g.indptr[r]
    valid = emask & (pos >= 0) & (pos < deg)
    slot = jnp.where(valid, r * deg + pos, N * deg)  # N*deg = trimmed slot
    adj = (
        jnp.full((N * deg + 1,), N, jnp.int32)
        .at[slot].set(jnp.where(valid, g.cols, N), mode="drop")[:-1]
    )
    adw = (
        jnp.zeros((N * deg + 1,), g.ewgt.dtype)
        .at[slot].set(jnp.where(valid, g.ewgt, 0.0), mode="drop")[:-1]
    )
    overflow = (g.indptr[1:] - g.indptr[:-1]) > deg
    return adj.reshape(N, deg), adw.reshape(N, deg), overflow


@functools.partial(jax.jit, static_argnames=("num_blocks",))
def quotient_graph_arrays(g: Graph, part: jax.Array, num_blocks: int):
    """Dense quotient adjacency [k,k] + block weights [k] (for small k)."""
    k = num_blocks
    mask = edge_mask(g)
    pu = jnp.where(mask, part[g.rows], 0)
    pv = jnp.where(mask, part[g.cols], 0)
    w = jnp.where(mask & (pu != pv), g.ewgt, 0.0)
    flat = pu * k + pv
    adj = jax.ops.segment_sum(w, flat, num_segments=k * k).reshape(k, k) / 1.0
    bw = block_weights(g, part, k)
    return adj, bw
