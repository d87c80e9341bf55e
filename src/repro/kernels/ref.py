"""Pure-jnp oracles for the Pallas kernels (the correctness references).

Each mapper kernel and its oracle run ONE shared tile body from this
module: the kernel on VMEM tiles, the oracle on the whole array. The
bodies use only elementwise ops, fixed add chains in slot order, and
max/min reductions, so the compiled kernel, the interpreter and XLA agree
bitwise. Everything that is a 1-D gather (``part[adj]``, ``matched[adj]``,
``pe_of[rows]``) is done by XLA before the kernel and streamed in as
dense tiles; Mosaic lowers only 2-D gathers.

Row kernels use the transposed ELL layout ``[DEG, T]``: vertices on the
128-wide lane axis, neighbour slots on sublanes, so every block is
(8, 128)-aligned and per-vertex outputs are lane-dense ``[1, T]`` rows.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

LANES = 128          # lane width of the edge-tile layout
EDGE_TILE_ROWS = 256  # mapcost: edges per tile = EDGE_TILE_ROWS * LANES
SUBLANES = 8         # mapcost partial sums are (SUBLANES, LANES) per tile


# --- mapcost ----------------------------------------------------------------

def edge_tiles(rows, cols, ewgt, pe_of):
    """Gather both endpoint PEs and lay the edges out as ``[R, LANES]``
    tiles, zero-weight padded to a whole number of edge tiles."""
    M = rows.shape[0]
    per = EDGE_TILE_ROWS * LANES
    Mp = max(per, -(-M // per) * per)
    pad = lambda a: jnp.pad(a, (0, Mp - M)).reshape(Mp // LANES, LANES)
    return (pad(pe_of[rows]), pad(pe_of[cols]),
            pad(ewgt.astype(jnp.float32)))


def edge_cost_partials(pu, pv, w, g_below, dvec):
    """Per-tile partial sums of ``w * dist(pu, pv)``.

    ``pu/pv/w`` are ``[..., EDGE_TILE_ROWS, LANES]``; ``g_below``/``dvec``
    are sequences of scalars (group size below, and distance of, each
    hierarchy level). The distance is ``dvec[lvl - 1]`` where ``lvl``
    counts the levels whose group ids differ (0 = same PE). Returns
    ``[..., SUBLANES, LANES]``: the tile's rows folded in a fixed chain.
    """
    lvl = jnp.zeros(pu.shape, jnp.int32)
    for gb in g_below:
        lvl = lvl + ((pu // gb) != (pv // gb)).astype(jnp.int32)
    d = jnp.zeros(pu.shape, jnp.float32)
    for i, dv in enumerate(dvec):
        d = jnp.where(lvl == i + 1, dv, d)
    c = w * d
    acc = c[..., 0:SUBLANES, :]
    for s in range(SUBLANES, c.shape[-2], SUBLANES):
        acc = acc + c[..., s:s + SUBLANES, :]
    return acc


def mapcost_partials_ref(
    rows: jax.Array,      # [M] i32
    cols: jax.Array,      # [M] i32
    ewgt: jax.Array,      # [M] f32 (0 on padding)
    pe_of: jax.Array,     # [N] i32
    g_below: jax.Array,   # [l] i32 group sizes below each level (1, a1, a1a2, ..)
    dvec: jax.Array,      # [l] f32 distances
) -> jax.Array:
    """``[tiles * SUBLANES, LANES]`` partial sums of w * dist(pe_u, pe_v):
    what the mapcost kernel writes."""
    pu, pv, w = edge_tiles(rows, cols, ewgt, pe_of)
    t = pu.shape[0] // EDGE_TILE_ROWS
    tiles = lambda a: a.reshape(t, EDGE_TILE_ROWS, LANES)
    l = g_below.shape[0]
    part = edge_cost_partials(tiles(pu), tiles(pv), tiles(w),
                              [g_below[i] for i in range(l)],
                              [dvec[i] for i in range(l)])
    return part.reshape(t * SUBLANES, LANES)


def fold_partials(part: jax.Array) -> jax.Array:
    """J from the partial sums: each undirected edge is stored twice."""
    return jnp.sum(part) / 2.0


def mapcost_ref(rows, cols, ewgt, pe_of, g_below, dvec) -> jax.Array:
    """J(C,D,Pi): sum over directed edges of w * dist(pe_u, pe_v), halved."""
    return fold_partials(mapcost_partials_ref(rows, cols, ewgt, pe_of,
                                              g_below, dvec))


# --- lp_gain ----------------------------------------------------------------

def neighbour_blocks_t(adj, part, k: int):
    """``[DEG, N]`` block id of every ELL neighbour slot (``k`` = padding)."""
    N = adj.shape[0]
    return jnp.where(adj < N, part[jnp.clip(adj, 0, N - 1)], k).T


def block_conn_t(nbr_t, adw_t, k: int):
    """``[k, T]`` connectivity of ``T`` vertices from their ``[DEG, T]``
    neighbour blocks and weights: a fixed add chain over the DEG slots."""
    blk = jax.lax.broadcasted_iota(jnp.int32, (k, nbr_t.shape[1]), 0)
    conn = jnp.zeros((k, nbr_t.shape[1]), jnp.float32)
    for j in range(nbr_t.shape[0]):
        conn = conn + jnp.where(nbr_t[j:j + 1, :] == blk,
                                adw_t[j:j + 1, :].astype(jnp.float32), 0.0)
    return conn


def best_gain(conn, part):
    """Best alternative block and its gain from ``conn [N, k]``."""
    k = conn.shape[1]
    cur = jnp.take_along_axis(conn, part[:, None], axis=1)[:, 0]
    masked = jnp.where(jax.nn.one_hot(part, k, dtype=bool), -jnp.inf, conn)
    best = jnp.argmax(masked, axis=1).astype(jnp.int32)
    gain = jnp.max(masked, axis=1) - cur
    return best, gain


def lp_gain_ref(
    adj: jax.Array,       # [N, DEG] i32 padded neighbour ids (N = self/pad)
    adw: jax.Array,       # [N, DEG] f32 edge weights (0 on padding)
    part: jax.Array,      # [N] i32 current block of each vertex
    k: int,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Per-vertex block connectivity, best alternative block and its gain.

    Returns (conn [N,k], best [N], gain [N]).
    """
    conn = block_conn_t(neighbour_blocks_t(adj, part, k), adw.T, k).T
    best, gain = best_gain(conn, part)
    return conn, best, gain


def csr_to_ell(rows, cols, ewgt, N: int, DEG: int):
    """Convert directed CSR edge arrays to padded ELL [N, DEG] (jnp).

    Edges beyond DEG per row are dropped (callers choose DEG >= max degree).
    Padding slots hold neighbour id N and weight 0.
    """
    order = jnp.argsort(rows, stable=True)
    r, c, w = rows[order], cols[order], ewgt[order]
    # position of each edge within its (sorted) row
    M = r.shape[0]
    rc = jnp.clip(r, 0, N - 1)
    counts = jax.ops.segment_sum(jnp.ones((M,), jnp.int32), rc, num_segments=N)
    starts = jnp.concatenate([jnp.zeros((1,), jnp.int32), jnp.cumsum(counts)[:-1]])
    pos = jnp.arange(M, dtype=jnp.int32) - starts[rc]
    slot = rc * DEG + pos
    valid = (pos < DEG) & (r < N)
    slot = jnp.where(valid, slot, N * DEG)
    adj = jnp.full((N * DEG + 1,), N, jnp.int32).at[slot].set(c, mode="drop")[:-1]
    adw = jnp.zeros((N * DEG + 1,), w.dtype).at[slot].set(jnp.where(valid, w, 0.0), mode="drop")[:-1]
    return adj.reshape(N, DEG), adw.reshape(N, DEG)


# --- hem_propose --------------------------------------------------------------

def hem_scan_t(adj_t, adw_t, jit_t, nbr_matched_t, own_matched, u, n_ids: int):
    """Shared heaviest-free-neighbour scan (HEM proposal step).

    ``adj_t``/``adw_t``/``jit_t``/``nbr_matched_t`` are ``[DEG, T]`` tiles
    of the transposed ELL adjacency (neighbour id ``n_ids`` = padding) and
    the matched flags of those neighbours; ``own_matched`` and ``u`` are
    the ``[1, T]`` matched flags and global ids of the tile's vertices.
    Returns the ``[1, T]`` i32 proposal per vertex (``n_ids`` = none).

    The score is elementwise f32 and the only reductions are max/min, so
    the kernel (tiles) and the oracle (whole array) agree bitwise.
    """
    valid = ((adj_t < n_ids) & (adj_t != u)
             & (own_matched == 0) & (nbr_matched_t == 0))
    jj = jit_t * 1e-3
    score = jnp.where(valid, adw_t * (1.0 + jj) + jj, -jnp.inf)
    best = jnp.max(score, axis=0, keepdims=True)
    has = best > -jnp.inf
    # tie-break: smallest neighbour id among best-scoring free edges
    cand = jnp.where(valid & (score == best), adj_t, n_ids)
    prop = jnp.min(cand, axis=0, keepdims=True)
    return jnp.where(has, prop, n_ids).astype(jnp.int32)


def neighbour_matched_t(adj, matched):
    """``[DEG, N]`` matched flag of every ELL neighbour slot."""
    Nm = matched.shape[0]
    return matched[jnp.clip(adj, 0, Nm - 1)].T


def hem_propose_ref(adj, adw, jit, matched):
    """jnp oracle for the hem_propose kernel: ``[N]`` i32 proposals."""
    N = adj.shape[0]
    u = jnp.arange(N, dtype=jnp.int32)[None, :]
    return hem_scan_t(adj.T, adw.T, jit.T, neighbour_matched_t(adj, matched),
                      matched[None, :], u, N)[0]


# --- contract_edges -------------------------------------------------------------

def merge_dedup_t(cand_t, candw_t, sent: int):
    """Shared merge/dedup/accumulate (contraction step).

    ``cand_t [D2, T]`` holds coarse neighbour ids per column (``sent`` =
    invalid slot, weight 0 there); returns ``(nbr_t, w_t)`` of the same
    shape where ``nbr_t`` keeps each distinct id at its FIRST slot (others
    ``sent``) and ``w_t`` the per-id weight total.

    Weight totals are accumulated as a FIXED chain of ``D2`` adds in slot
    order — XLA never reassociates distinct f32 adds, so the kernel
    (tiles) and the oracle (whole array) agree bitwise; the first-occurrence
    pass is integer-only.
    """
    D2 = cand_t.shape[0]
    acc = jnp.zeros(cand_t.shape, jnp.float32)
    for i in range(D2):
        acc = acc + jnp.where(cand_t == cand_t[i:i + 1, :],
                              candw_t[i:i + 1, :], 0.0)
    firstpos = jnp.full(cand_t.shape, D2, jnp.int32)
    for i in range(D2 - 1, -1, -1):
        firstpos = jnp.where(cand_t == cand_t[i:i + 1, :], i, firstpos)
    slot = jax.lax.broadcasted_iota(jnp.int32, cand_t.shape, 0)
    is_first = (firstpos == slot) & (cand_t != sent)
    return (jnp.where(is_first, cand_t, sent).astype(jnp.int32),
            jnp.where(is_first, acc, 0.0))


def distinct_counts(nbr, sent: int):
    """``[N]`` distinct neighbour count per row of the merged ``nbr``."""
    return jnp.sum((nbr != sent).astype(jnp.int32), axis=1)


def contract_edges_ref(cand, candw, sent: int):
    """jnp oracle for the contract_edges kernel: ``(nbr, w, cnt)``."""
    nbr_t, w_t = merge_dedup_t(cand.T, candw.T, sent)
    return nbr_t.T, w_t.T, distinct_counts(nbr_t.T, sent)


def flash_ref(q, k, v, causal: bool = True, window: int = 0):
    """Oracle SDPA for the flash kernel. q/k/v [BH, S, D] -> [BH, S, D]."""
    S = q.shape[1]
    scale = q.shape[-1] ** -0.5
    s = jnp.einsum("bqd,bkd->bqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    rows = jnp.arange(S)[:, None]
    cols = jnp.arange(S)[None, :]
    mask = jnp.ones((S, S), bool)
    if causal:
        mask &= cols <= rows
    if window > 0:
        mask &= rows - cols < window
    s = jnp.where(mask[None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bqk,bkd->bqd", p, v.astype(jnp.float32)).astype(q.dtype)
