"""Coarsening: heavy-edge matching (HEM) + contraction, fully vectorized.

Matching uses multi-round handshaking: every unmatched vertex proposes to
its heaviest unmatched neighbour (deterministic jittered tie-breaks, the
jitter re-salted per round so tie-locked configurations break up); mutual
proposals are contracted. This is the standard shared-memory parallel HEM
(cf. Mt-Metis / Mt-KaHyPar coarsening) re-expressed over static-shape
arrays so it vmaps across subgraphs.

Two implementations share this module:

* the **segment path** (:func:`hem_match` / :func:`contract`) — the seed's
  edge-array formulation: ``segment_max``/``segment_min`` proposal passes
  and a sort-based contraction. Exact (no degree cap); kept as the
  reference for the contraction invariants and as the PR 8 comparison
  mode (``partition(..., coarsen="segment")``).
* the **ELL kernel path** (:func:`hem_match_ell` / :func:`contract_ell` /
  :func:`coarsen_once` with ``ell_deg``) — row-tiled scans over the padded
  ``[N, DEG]`` ELL adjacency, dispatched through ``kernels/ops``
  (``hem_propose`` / ``contract_edges``) like the refinement kernels.
  Sort-free: proposals are per-row max scans, contraction merges each
  coarse row's (<= 2) member rows with a fixed-order dedup/accumulate and
  scatters straight into the relabeled CSR (a permutation — no float
  scatter-add races). Rows beyond the static ``DEG`` cap are truncated
  (the refinement kernels' overflow policy); coarsening is purely
  heuristic — partitions stay valid, cut/balance are always evaluated on
  the untruncated fine graph. Backends agree bitwise (kernels/ref.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .graph import Graph, default_ell_deg, edge_mask, ell_adjacency, vertex_mask
from ..kernels import ops as kops

# numpy scalars: a jnp constant here would start a JAX backend at import
_HASH_A = np.uint32(2654435761)
_HASH_B = np.uint32(40503)

# per-round salt stride: any odd constant; mixed into the edge jitter so
# round r+1 re-rolls every tie-break (see hem_match round fix below)
_ROUND_SALT = 101159


def _edge_jitter(rows: jax.Array, cols: jax.Array, salt) -> jax.Array:
    """Deterministic per-edge jitter in [0, 1), symmetric in (u, v).

    ``salt`` may be a Python int or a traced i32 scalar (the round loops
    pass ``base + r * _ROUND_SALT``); mixing happens in uint32 so the
    arithmetic wraps identically either way.
    """
    u = rows.astype(jnp.uint32)
    v = cols.astype(jnp.uint32)
    a, b = jnp.minimum(u, v), jnp.maximum(u, v)
    s = jnp.asarray(salt, jnp.int32).astype(jnp.uint32) * jnp.uint32(0x9E3779B9)
    h = (a * _HASH_A) ^ (b * _HASH_B) ^ s
    h = (h ^ (h >> 16)) * jnp.uint32(0x45D9F3B)
    return (h & jnp.uint32(0xFFFFFF)).astype(jnp.float32) / float(1 << 24)


# ---------------------------------------------------------------------------
# segment path (seed formulation; exact, sort-based)
# ---------------------------------------------------------------------------

def hem_match(g: Graph, rounds: int = 3, salt: int = 0) -> jax.Array:
    """Heavy-edge matching. Returns cluster labels [N]: matched pairs share
    the smaller endpoint's id; unmatched vertices point to themselves."""
    N = g.N
    vmask = vertex_mask(g)
    emask = edge_mask(g)
    idx = jnp.arange(N, dtype=jnp.int32)
    labels = idx
    matched = ~vmask  # padding can never match

    def one_round(r, state):
        labels, matched = state
        free_edge = emask & ~matched[g.rows] & ~matched[g.cols] & (g.rows != g.cols)
        # r is mixed into the salt: with a round-invariant salt, a round
        # that matches nothing (cyclic proposals) reproduces the SAME
        # proposals forever and later rounds are dead weight.
        jit_ = _edge_jitter(g.rows, g.cols, salt * 7 + 13 + r * _ROUND_SALT) * 1e-3
        score = jnp.where(free_edge, g.ewgt * (1.0 + jit_) + jit_, -jnp.inf)
        row_best = jax.ops.segment_max(score, g.rows, num_segments=N)
        is_best = free_edge & (score >= row_best[g.rows]) & jnp.isfinite(score)
        # tie-break: smallest column among best-scoring edges
        prop_col = jax.ops.segment_min(
            jnp.where(is_best, g.cols, N), g.rows, num_segments=N
        )
        proposal = jnp.where((prop_col < N) & ~matched, prop_col, idx)
        # mutual handshake
        mutual = (proposal != idx) & (proposal[proposal] == idx)
        leader = jnp.minimum(idx, proposal)
        new_match = mutual & ~matched
        labels = jnp.where(new_match, leader, labels)
        matched = matched | new_match
        return labels, matched

    labels, matched = jax.lax.fori_loop(0, rounds, one_round, (labels, matched))
    return labels


@functools.partial(jax.jit, donate_argnums=())
def contract(g: Graph, labels: jax.Array) -> tuple[Graph, jax.Array]:
    """Contract clusters given by ``labels``. Returns (coarse graph with the
    SAME padded shapes, fine->coarse vertex map [N])."""
    N, M = g.N, g.M
    vmask = vertex_mask(g)
    emask = edge_mask(g)
    idx = jnp.arange(N, dtype=jnp.int32)

    is_leader = vmask & (labels == idx)
    rank = jnp.cumsum(is_leader.astype(jnp.int32)) - 1  # [N]
    n_coarse = jnp.sum(is_leader.astype(jnp.int32))
    # fine -> coarse id; padding parked at N-1 with zero weight
    newid = jnp.where(vmask, rank[labels], N - 1).astype(jnp.int32)

    vwgt_c = jax.ops.segment_sum(jnp.where(vmask, g.vwgt, 0.0), newid, num_segments=N)

    cu = newid[g.rows]
    cv = newid[g.cols]
    valid = emask & (cu != cv)
    # sort edges by (cu, cv) with invalid parked at cu = N (dropped on scatter)
    cu_s_key = jnp.where(valid, cu, N)
    order1 = jnp.argsort(jnp.where(valid, cv, N), stable=True)
    cu1, cv1, w1 = cu_s_key[order1], cv[order1], jnp.where(valid, g.ewgt, 0.0)[order1]
    order2 = jnp.argsort(cu1, stable=True)
    cu2, cv2, w2 = cu1[order2], cv1[order2], w1[order2]

    valid_s = cu2 < N
    head = valid_s & (
        (jnp.arange(M) == 0)
        | (cu2 != jnp.roll(cu2, 1))
        | (cv2 != jnp.roll(cv2, 1))
    )
    seg = jnp.cumsum(head.astype(jnp.int32)) - 1  # dedup segment id per slot
    agg_w = jax.ops.segment_sum(jnp.where(valid_s, w2, 0.0), jnp.maximum(seg, 0), num_segments=M)

    slot = jnp.where(head, seg, M)  # scatter position (M = drop)
    rows_c = jnp.full((M,), N - 1, jnp.int32).at[slot].set(cu2, mode="drop")
    cols_c = jnp.full((M,), N - 1, jnp.int32).at[slot].set(cv2, mode="drop")
    m_coarse = jnp.sum(head.astype(jnp.int32))
    in_range = jnp.arange(M) < m_coarse
    ewgt_c = jnp.where(in_range, agg_w, 0.0)
    rows_c = jnp.where(in_range, rows_c, N - 1)
    cols_c = jnp.where(in_range, cols_c, N - 1)

    # padded slots (>= m_coarse) anchor at row N-1 but the in_range gate
    # already zeroes their contribution, so counts is exact as-is. (An
    # earlier anchor correction subtracted the padded-slot count from row
    # N-1 a second time — corrupting that row's indptr whenever the coarse
    # graph filled the padded shape and N-1 was a REAL coarse vertex, and
    # leaving indptr[N] < m_coarse otherwise.)
    counts = jax.ops.segment_sum(in_range.astype(jnp.int32), rows_c, num_segments=N)
    indptr_c = jnp.concatenate([jnp.zeros((1,), jnp.int32), jnp.cumsum(counts)]).astype(jnp.int32)

    gc = Graph(
        vwgt=vwgt_c,
        rows=rows_c,
        cols=cols_c,
        ewgt=ewgt_c,
        indptr=indptr_c,
        n=n_coarse.astype(jnp.int32),
        m=m_coarse.astype(jnp.int32),
    )
    return gc, newid


# ---------------------------------------------------------------------------
# ELL kernel path (row-tiled, sort-free; dispatched through kernels/ops)
# ---------------------------------------------------------------------------

def hem_match_ell(g: Graph, adj: jax.Array, adw: jax.Array,
                  rounds: int = 3, salt=0,
                  use_pallas: bool | None = None) -> jax.Array:
    """Heavy-edge matching over the ELL adjacency (kernel path).

    Same contract as :func:`hem_match` (labels [N], pairs share the
    smaller endpoint's id) but proposals come from the row-tiled
    ``kernels/ops.hem_propose`` scan; rows past the DEG cap see only
    their first DEG neighbours.
    """
    N = g.N
    vmask = vertex_mask(g)
    idx = jnp.arange(N, dtype=jnp.int32)
    u2d = jnp.broadcast_to(idx[:, None], adj.shape)
    labels = idx
    matched = (~vmask).astype(jnp.int32)  # padding can never match

    def one_round(r, state):
        labels, matched = state
        jit_ = _edge_jitter(u2d, adj, salt * 7 + 13 + r * _ROUND_SALT)
        prop = kops.hem_propose(adj, adw, jit_, matched, use_pallas)
        proposal = jnp.where((prop < N) & (matched == 0), prop, idx)
        mutual = (proposal != idx) & (proposal[proposal] == idx)
        leader = jnp.minimum(idx, proposal)
        new_match = mutual & (matched == 0)
        labels = jnp.where(new_match, leader, labels)
        matched = matched | new_match.astype(jnp.int32)
        return labels, matched

    labels, matched = jax.lax.fori_loop(0, rounds, one_round, (labels, matched))
    return labels


def contract_ell(g: Graph, labels: jax.Array, adj: jax.Array, adw: jax.Array,
                 use_pallas: bool | None = None) -> tuple[Graph, jax.Array]:
    """Contract matched pairs via the row-merge kernel (sort-free).

    Coarse row ``u`` holds the union of its (<= 2) fine members' ELL rows
    mapped through ``newid`` — deduped and weight-summed by
    ``kernels/ops.contract_edges`` in fixed slot order — then scattered
    straight into the relabeled CSR at ``indptr[u] + rank`` (a
    permutation, so the result is deterministic and ``rows`` stays
    sorted with an exact ``indptr`` prefix). Returns (coarse graph with
    the SAME padded shapes, fine->coarse map [N]).
    """
    N, M = g.N, g.M
    DEG = adj.shape[1]
    vmask = vertex_mask(g)
    idx = jnp.arange(N, dtype=jnp.int32)

    is_leader = vmask & (labels == idx)
    rank = jnp.cumsum(is_leader.astype(jnp.int32)) - 1
    n_coarse = jnp.sum(is_leader.astype(jnp.int32))
    newid = jnp.where(vmask, rank[labels], N - 1).astype(jnp.int32)

    # coarse row u's fine members: the leader and (if matched) its partner
    memA = (jnp.full((N,), N, jnp.int32)
            .at[jnp.where(is_leader, rank, N)].set(idx, mode="drop"))
    nonleader = vmask & (labels != idx)
    memB = (jnp.full((N,), N, jnp.int32)
            .at[jnp.where(nonleader, rank[jnp.clip(labels, 0, N - 1)], N)]
            .set(idx, mode="drop"))
    hasA = memA < N
    hasB = memB < N

    # exact pair sum (each coarse vertex has <= 2 members; pad rows -> 0)
    vwgt_c = (jnp.where(hasA, g.vwgt[jnp.clip(memA, 0, N - 1)], 0.0)
              + jnp.where(hasB, g.vwgt[jnp.clip(memB, 0, N - 1)], 0.0))

    def member_cands(mem, has):
        rowsel = jnp.clip(mem, 0, N - 1)
        a = adj[rowsel]                       # [N, DEG] member neighbour ids
        w = adw[rowsel]
        cn = newid[jnp.clip(a, 0, N - 1)]     # coarse-mapped neighbour
        ok = has[:, None] & (a < N) & (cn != idx[:, None])  # drop pad + intra
        return jnp.where(ok, cn, N), jnp.where(ok, w, 0.0)

    candA, candwA = member_cands(memA, hasA)
    candB, candwB = member_cands(memB, hasB)
    cand = jnp.concatenate([candA, candB], axis=1)    # [N, 2*DEG]
    candw = jnp.concatenate([candwA, candwB], axis=1)

    nbr, wsum, cnt = kops.contract_edges(cand, candw, use_pallas)

    counts = cnt.astype(jnp.int32)                    # [N]; pad rows 0
    indptr_c = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(counts)]).astype(jnp.int32)
    m_coarse = indptr_c[-1]

    first = nbr < N
    rank_in_row = jnp.cumsum(first.astype(jnp.int32), axis=1) - 1
    dest = jnp.where(first, indptr_c[:N, None] + rank_in_row, M).reshape(-1)
    rowid = jnp.broadcast_to(idx[:, None], nbr.shape).reshape(-1)
    rows_c = jnp.full((M,), N - 1, jnp.int32).at[dest].set(rowid, mode="drop")
    cols_c = jnp.full((M,), N - 1, jnp.int32).at[dest].set(
        nbr.reshape(-1), mode="drop")
    ewgt_c = jnp.zeros((M,), adw.dtype).at[dest].set(
        wsum.reshape(-1), mode="drop")

    gc = Graph(
        vwgt=vwgt_c,
        rows=rows_c,
        cols=cols_c,
        ewgt=ewgt_c,
        indptr=indptr_c,
        n=n_coarse.astype(jnp.int32),
        m=m_coarse.astype(jnp.int32),
    )
    return gc, newid


def coarsen_once(g: Graph, salt=0, rounds: int = 3,
                 ell_deg: int | None = None,
                 use_pallas: bool | None = None) -> tuple[Graph, jax.Array]:
    """One HEM + contraction level.

    ``ell_deg=None`` runs the seed segment path; an int routes through the
    ELL kernels (the ELL adjacency is built ONCE and shared by matching
    and contraction — ``ell_adjacency`` needs no argsort thanks to the
    sorted-``rows`` invariant, which :func:`contract_ell` preserves, so
    the whole cascade is sort-free).
    """
    if ell_deg is None:
        labels = hem_match(g, rounds=rounds, salt=salt)
        return contract(g, labels)
    adj, adw, _ = ell_adjacency(g, ell_deg)
    labels = hem_match_ell(g, adj, adw, rounds=rounds, salt=salt,
                           use_pallas=use_pallas)
    return contract_ell(g, labels, adj, adw, use_pallas=use_pallas)


@functools.partial(jax.jit, static_argnames=("levels", "ell_deg", "rounds"))
def coarsen_cascade(g: Graph, levels: int, ell_deg: int | None = None,
                    rounds: int = 3):
    """Run the fused coarsening cascade alone and return per-level sizes
    ``(ns [levels], ms [levels])`` — the telemetry behind
    ``stats["coarsen"]`` and the large-instance benchmark tier. The scan
    carries ONLY the current graph (O(1) memory in ``levels``), so this
    path handles 10^6-vertex instances the full v-cycle's stacked
    uncoarsening arrays would not."""
    deg = default_ell_deg(g.N, g.M) if ell_deg is None else ell_deg
    salts = (jnp.arange(levels, dtype=jnp.int32) + 1) * 131 + 7

    def step(cur, sl):
        gc, _ = coarsen_once(cur, salt=sl, rounds=rounds, ell_deg=deg)
        return gc, (gc.n, gc.m)

    if levels == 0:
        z = jnp.zeros((0,), jnp.int32)
        return z, z
    _, (ns, ms) = jax.lax.scan(step, g, salts)
    return ns, ms
