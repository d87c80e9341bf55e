"""Multilevel partitioner: balance, quality sanity, determinism."""
import functools

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from hypcompat import given, settings, st

from repro.core import graph as G
from repro.core.coarsen import contract, hem_match
from repro.core.graph import block_weights, edge_cut, edge_mask, vertex_mask
from repro.core.partition import num_levels, partition_host
from repro.core.refine import is_balanced, lp_refine, rebalance


def _check(g, k, eps, preset="fast", salt=0):
    part = partition_host(g, k, eps, preset, salt)
    part_np = np.asarray(part)
    n = int(g.n)
    assert part_np[:n].min() >= 0 and part_np[:n].max() < k
    Lmax = (1 + eps) * float(g.total_weight()) / k
    bw = np.asarray(block_weights(g, part, k))
    assert (bw <= Lmax + 1e-4).all(), f"imbalanced: {bw} vs {Lmax}"
    assert bw.min() > 0, "empty block"
    return part, float(edge_cut(g, part))


def test_grid_quality():
    g = G.gen_grid(24)
    part, cut = _check(g, 4, 0.03, "eco")
    # 24x24 triangulated grid, 4 quadrants: ideal cut ~ 2*24*2=96; LP-based
    # multilevel should land well under a random partition (~ 3/4 * m/2).
    assert cut < 350, cut


def test_rgg_balance_many_k():
    g = G.gen_rgg(3000, seed=1)
    for k in (2, 5, 8, 16):
        _check(g, k, 0.05, "fast", salt=k)


def test_determinism():
    g = G.gen_rgg(1500, seed=2)
    p1, c1 = _check(g, 6, 0.03, "fast", salt=3)
    p2, c2 = _check(g, 6, 0.03, "fast", salt=3)
    assert np.array_equal(np.asarray(p1), np.asarray(p2))
    assert c1 == c2


def test_k1_trivial():
    g = G.gen_grid(8)
    part = partition_host(g, 1, 0.03)
    assert np.asarray(part).max() == 0


def test_weighted_vertices_balance():
    rng = np.random.default_rng(0)
    side = 20
    g0 = G.gen_grid(side)
    vw = rng.integers(1, 10, side * side).astype(np.float64)
    u = np.asarray(g0.rows)[: int(g0.m)]
    v = np.asarray(g0.cols)[: int(g0.m)]
    keep = u < v
    g = G.from_edges(side * side, u[keep], v[keep], vwgt=vw)
    _check(g, 4, 0.05, "eco")


# --- coarsening invariants ---------------------------------------------------

@given(st.integers(0, 1000), st.integers(20, 120))
@settings(max_examples=20, deadline=None)
def test_contract_invariants(seed, n):
    rng = np.random.default_rng(seed)
    m = max(n * 2, 4)
    u = rng.integers(0, n, m)
    v = rng.integers(0, n, m)
    keep = u != v
    if keep.sum() == 0:
        return
    g = G.from_edges(n, u[keep], v[keep])
    labels = hem_match(g, rounds=2, salt=seed % 97)
    gc, newid = contract(g, labels)
    # vertex weight conserved
    assert abs(float(gc.total_weight()) - float(g.total_weight())) < 1e-3
    # edge weight: internal (within-cluster) edges removed, rest conserved
    lab = np.asarray(labels)
    rows = np.asarray(g.rows)[: int(g.m)]
    cols = np.asarray(g.cols)[: int(g.m)]
    w = np.asarray(g.ewgt)[: int(g.m)]
    external = lab[rows] != lab[cols]
    assert abs(float(jnp.sum(gc.ewgt)) - float(w[external].sum())) < 1e-2
    # newid maps real vertices into [0, n_coarse)
    nid = np.asarray(newid)[: int(g.n)]
    assert nid.min() >= 0 and nid.max() < int(gc.n)


def test_contract_indptr_exact_with_zero_padding():
    """Regression: when the coarse graph fills the padded shape
    (n_coarse == N), the dropped edge slots share anchor row N-1 with a
    REAL coarse vertex; the old anchor correction double-subtracted the
    padded-slot count there, corrupting that vertex's indptr row."""
    n = 16
    rng = np.random.default_rng(3)
    u = rng.integers(0, n, 40)
    v = rng.integers(0, n, 40)
    keep = u != v
    # generous edge padding, NO vertex padding (N == n)
    g = G.from_edges(n, u[keep], v[keep], N=n, M=256)
    labels = jnp.arange(n, dtype=jnp.int32)  # identity: n_coarse == N
    gc, _ = contract(g, labels)
    assert int(gc.n) == n
    ind = np.asarray(gc.indptr)
    m_c = int(gc.m)
    assert ind[0] == 0 and ind[-1] == m_c, (ind[-1], m_c)
    assert (np.diff(ind) >= 0).all()
    # row N-1's range holds exactly its own edges
    rows_c = np.asarray(gc.rows)[:m_c]
    assert ind[n] - ind[n - 1] == (rows_c == n - 1).sum()


def test_contract_indptr_tail_with_padding():
    """With vertex padding present, every padding row must have an empty
    indptr range ending at m_coarse (the old correction left
    indptr[N] < m_coarse)."""
    g0 = G.gen_rgg(60, seed=9)
    g = G.pad_graph(g0, 128, 1024)
    labels = hem_match(g, rounds=2, salt=1)
    gc, _ = contract(g, labels)
    ind = np.asarray(gc.indptr)
    assert ind[-1] == int(gc.m)
    assert (np.diff(ind) >= 0).all()
    assert (ind[int(gc.n):] == int(gc.m)).all()


def test_matching_is_valid():
    g = G.gen_rgg(800, seed=5)
    labels = np.asarray(hem_match(g, rounds=3, salt=1))
    n = int(g.n)
    for u in range(n):
        l = labels[u]
        assert labels[l] == l, "cluster leader must point to itself"
    # clusters have size <= 2 (matching, not clustering)
    _, counts = np.unique(labels[:n], return_counts=True)
    assert counts.max() <= 2


# --- refinement --------------------------------------------------------------

def test_lp_refine_respects_capacity_and_improves():
    g = G.gen_grid(16)
    k, eps = 4, 0.03
    n = int(g.n)
    rng = np.random.default_rng(0)
    part = jnp.asarray(rng.integers(0, k, g.N), jnp.int32)
    Lmax = (1 + eps) * float(g.total_weight()) / k
    part = rebalance(g, part, k, jnp.float32(Lmax), rounds=8)
    cut0 = float(edge_cut(g, part))
    out = lp_refine(g, part, k, jnp.float32(Lmax), rounds=6)
    cut1 = float(edge_cut(g, out))
    assert is_balanced(g, out, k, Lmax)
    assert cut1 <= cut0 + 1e-6, (cut0, cut1)


def test_rebalance_fixes_overload():
    g = G.gen_grid(12)
    k = 3
    part = jnp.zeros(g.N, jnp.int32)  # everything in block 0
    Lmax = jnp.float32(1.05 * float(g.total_weight()) / k)
    out = rebalance(g, part, k, Lmax, rounds=12)
    assert is_balanced(g, out, k, float(Lmax))


def test_num_levels_monotone():
    assert num_levels(100, 4) <= num_levels(10_000, 4) <= num_levels(1_000_000, 4)


def test_num_levels_matching_stalls():
    """``max_degree`` shapes the cascade depth (PR 9): a star graph stalls
    matching (one pair per round), so depth collapses to 1 instead of
    paying for levels that cannot shrink; hub-heavy graphs EXTEND depth
    (bounded), and low-degree graphs keep the base schedule."""
    n, k = 10_000, 4
    base = num_levels(n, k)
    assert base > 1
    # star: hub adjacent to all -> shrink ~ n/(n-1) -> stop at one level
    assert num_levels(n, k, max_degree=n - 1) == 1
    # mesh-like: max degree far below n leaves the base schedule intact
    assert num_levels(n, k, max_degree=8) == base
    # hub-heavy: shrink between 1.15x and 1.6x extends depth, but bounded
    hubbed = num_levels(n, k, max_degree=int(n * 0.7))
    assert base < hubbed <= 2 * base + 4
    # degenerate graphs never go below one level
    assert num_levels(200, k, max_degree=199) == 1


def test_partition_host_star_graph():
    """End-to-end: partition_host on a star graph must detect the stall
    from the measured max degree and still return a balanced partition."""
    n = 512
    hub = np.zeros(n - 1, np.int32)
    leaf = np.arange(1, n, dtype=np.int32)
    rows = np.concatenate([hub, leaf])
    cols = np.concatenate([leaf, hub])
    order = np.argsort(rows, kind="stable")
    g = G.assemble_padded(np.ones(n, np.float32), rows[order], cols[order],
                          np.ones(2 * (n - 1), np.float32),
                          n, n, 2 * (n - 1))
    k, eps = 4, 0.05
    part = np.asarray(partition_host(g, k, eps, "fast", salt=1))
    assert set(np.unique(part[:n])) <= set(range(k))
    w = np.bincount(part[:n], minlength=k).astype(float)
    assert w.max() <= (1.0 + eps) * n / k + 1


# --- PR3: kernel-backed refinement (ELL backend) ------------------------------

def test_refine_default_matches_seed_xla_path():
    """On this container (no TPU) backend="auto" must resolve to the seed
    XLA path, so default refinement is bit-identical to backend="xla" —
    edge-cut identical-or-better vs the seed by construction."""
    from repro.core.refine import resolve_backend
    g = G.gen_grid(16)
    k, eps = 4, 0.03
    rng = np.random.default_rng(0)
    part = jnp.asarray(rng.integers(0, k, g.N), jnp.int32)
    Lmax = jnp.float32((1 + eps) * float(g.total_weight()) / k)
    part = rebalance(g, part, k, Lmax, rounds=8)
    out_auto = lp_refine(g, part, k, Lmax, rounds=6, backend="auto")
    out_xla = lp_refine(g, part, k, Lmax, rounds=6, backend="xla")
    if resolve_backend("auto") == "xla":
        assert np.array_equal(np.asarray(out_auto), np.asarray(out_xla))


@pytest.mark.parametrize("gen,arg", [("grid", 16), ("rgg", 1500), ("kron", 9)])
def test_lp_refine_ell_backend_quality(gen, arg):
    """The kernel-backed path stays balanced and never worsens the cut it
    was given. On graphs that fit the degree cap (no overflow rows, i.e.
    the paper's mesh families) it must also land within 5% of the XLA
    path's cut; overflow graphs (kron) freeze their truncated rows, so
    only the safety properties are asserted there."""
    g = {"grid": G.gen_grid, "rgg": G.gen_rgg, "kron": G.gen_kron}[gen](arg)
    k, eps = 4, 0.05
    rng = np.random.default_rng(1)
    part = jnp.asarray(rng.integers(0, k, g.N), jnp.int32)
    Lmax = jnp.float32((1 + eps) * float(g.total_weight()) / k)
    part = rebalance(g, part, k, Lmax, rounds=8, backend="ell")
    assert is_balanced(g, part, k, float(Lmax))
    cut0 = float(G.edge_cut(g, part))
    out_e = lp_refine(g, part, k, Lmax, rounds=6, backend="ell")
    out_x = lp_refine(g, part, k, Lmax, rounds=6, backend="xla")
    cut_e = float(G.edge_cut(g, out_e))
    cut_x = float(G.edge_cut(g, out_x))
    assert is_balanced(g, out_e, k, float(Lmax))
    assert cut_e <= cut0 + 1e-6
    from repro.core.graph import default_ell_deg, ell_adjacency
    _, _, overflow = ell_adjacency(g, default_ell_deg(g.N, g.M))
    if not bool(np.asarray(overflow).any()):
        assert cut_e <= 1.05 * cut_x, (cut_e, cut_x)


def test_partition_ell_backend_valid():
    """Full multilevel partition through the ELL backend: balanced, sane."""
    g = G.gen_rgg(1200, seed=4)
    part = partition_host(g, 6, 0.05, "fast", salt=2, backend="ell")
    n = int(g.n)
    p = np.asarray(part)[:n]
    assert p.min() >= 0 and p.max() < 6
    Lmax = 1.05 * float(g.total_weight()) / 6
    bw = np.asarray(block_weights(g, part, 6))
    assert (bw <= Lmax + 1e-4).all()
    assert bw.min() > 0


def test_admit_threshold_respects_capacity():
    """Direct unit test of the argsort-free admission filter."""
    from repro.core.refine import _admit_by_threshold
    rng = np.random.default_rng(3)
    N, k = 512, 4
    cand = jnp.asarray(rng.random(N) < 0.6)
    best = jnp.asarray(rng.integers(0, k, N), jnp.int32)
    gbest = jnp.asarray(np.round(rng.random(N) * 4), jnp.float32)  # heavy ties
    vw = jnp.asarray(rng.integers(1, 4, N), jnp.float32)
    cap = jnp.asarray([10.0, 25.0, 0.0, 1e9], jnp.float32)
    tie = jnp.asarray(rng.random(N), jnp.float32)
    accept = _admit_by_threshold(cand, best, gbest, vw, cap, k, tie)
    acc = np.asarray(accept)
    assert not np.any(acc & ~np.asarray(cand))
    inflow = np.zeros(k)
    np.add.at(inflow, np.asarray(best)[acc], np.asarray(vw)[acc])
    assert (inflow <= np.asarray(cap) + 1e-4).all(), inflow
    # unconstrained block takes every candidate targeting it
    b3 = np.asarray(cand) & (np.asarray(best) == 3)
    assert np.array_equal(acc[b3], np.full(b3.sum(), True))


# --- the initial partition cut to a static width -----------------------------

def _lanes(*gs):
    """A stacked ``[B, ...]`` Graph of same-shape lanes."""
    return jax.tree_util.tree_map(lambda *a: jnp.stack(a), *gs)


def _parity_graph(gen):
    return G.gen_rgg(2000, seed=5) if gen == "rgg" else G.gen_grid(45)


@pytest.mark.parametrize("k", [3, 4, 8])
@pytest.mark.parametrize("gen", ["rgg", "grid"])
@pytest.mark.parametrize("backend", ["xla", "ell"])
def test_initial_phase_cut_to_its_width_is_bitwise_the_full_width(
        backend, gen, k):
    """The coarsest graph's initial partition at ``initial_width`` equals
    the one at the fine graph's (N, M) bit for bit, 0 on the padding."""
    from repro.core.partition import (Preset, _coarsen_down, _initial_lanes,
                                      initial_width)
    g = _parity_graph(gen)
    N, M = g.N, g.M
    levels = num_levels(N, k)
    Nc, Mc = initial_width(N, M, k, levels)
    assert Nc < N
    deg = G.default_ell_deg(N, M)
    coarsest, _, _ = _coarsen_down(g, levels, "ell", deg)
    n = int(coarsest.n)
    assert n <= Nc and int(coarsest.m) <= Mc
    Lmax = jnp.asarray([1.03 * float(g.total_weight()) / k], jnp.float32)
    salts = jnp.asarray([[7, 7926]], jnp.int32)

    def run(width):
        fn = functools.partial(_initial_lanes, k=k, preset=Preset.get("eco"),
                               backend=backend, deg=deg, width=width)
        return np.asarray(jax.jit(fn)(_lanes(coarsest), Lmax, salts))

    full, cut = run((N, M)), run((Nc, Mc))
    assert cut.shape == full.shape == (1, 2, N)
    np.testing.assert_array_equal(cut, full)
    assert (cut[..., n:] == 0).all() and cut[..., :n].max() == k - 1


@pytest.mark.parametrize("coarsen", ["ell", "segment"])
def test_partition_and_batched_equal_the_full_width_branch(coarsen):
    """``partition`` and ``batched_partition`` take the compact branch and
    give the parts the full-width branch gives."""
    from repro.core.partition import (_partition_lanes, batched_partition,
                                      partition)
    g = G.gen_rgg(2000, seed=5)
    k, eps, salt = 3, jnp.float32(0.03), jnp.asarray([5], jnp.int32)
    levels = num_levels(g.N, k)
    full = jax.jit(lambda gs, e, s: _partition_lanes(
        gs, e, s, k, levels, "fast", "xla", None, coarsen,
        width=(g.N, g.M)))
    ref, _, took, _ = full(_lanes(g), eps[None], salt)
    assert int(took) == 0
    np.testing.assert_array_equal(
        np.asarray(partition(g, k, eps, levels, "fast", 5, "xla", None,
                             coarsen)), np.asarray(ref[0]))
    fn = batched_partition(k, levels, "fast", "xla", None, coarsen)
    parts, _, took, _ = fn.with_counters(_lanes(g), eps[None], salt)
    assert int(took) == 1
    np.testing.assert_array_equal(np.asarray(parts), np.asarray(ref))


def _no_fit_graph(shape, n):
    """An edgeless graph or a perfect matching: HEM cannot shrink it below
    ``n / 2``, and no row runs past the ELL cap, so the request keeps the
    ELL down scan. (A star has since gone to the skewed down scan, whose
    two-hop pairing shrinks it.)"""
    if shape == "edgeless":
        return G.from_edges(n, np.zeros(0, np.int64), np.zeros(0, np.int64))
    return G.from_edges(n, np.arange(0, n, 2), np.arange(1, n, 2))


@pytest.mark.parametrize("shape", ["edgeless", "matching"])
def test_initial_full_width_where_the_coarsest_graph_does_not_fit(shape):
    from repro.core.hierarchy import Hierarchy
    from repro.core.multisection import hierarchical_multisection
    from repro.core.partition import initial_width
    n, k, eps = 8192, 4, 0.03
    g = _no_fit_graph(shape, n)
    levels = num_levels(g.N, k)
    assert levels > 0 and initial_width(g.N, g.M, k, levels)[0] < g.N
    res = hierarchical_multisection(g, Hierarchy(a=(k,), d=(1.0,)),
                                    eps=eps, preset="fast")
    assert res.stats["initial_dispatches"] == 1
    assert res.stats["initial_compact_dispatches"] == 0
    # every pass, the coarsest graph's too, counted at the full width
    assert res.stats["vcycle_padded_vertex_work"] == g.N * (1 + levels)
    w = np.bincount(res.pe_of, minlength=k)
    assert w.sum() == n and w.max() <= (1 + eps) * n / k


def test_one_lane_that_does_not_fit_sends_the_dispatch_to_full_width():
    """The fit test is over the whole dispatch: one lane whose coarsest
    graph overflows sends every lane to the full width, and each lane's
    parts equal a run of that lane alone."""
    from repro.core.partition import batched_partition
    fits = G.gen_rgg(2000, seed=5)
    wide = G.from_edges(2000, np.zeros(0, np.int64), np.zeros(0, np.int64),
                        N=fits.N, M=fits.M)
    k = 3
    fn = batched_partition(k, num_levels(fits.N, k), "fast", "xla", None)
    eps = jnp.full((2,), 0.03, jnp.float32)
    salts = jnp.asarray([5, 6], jnp.int32)
    parts, _, took, _ = fn.with_counters(_lanes(fits, wide), eps, salts)
    assert int(took) == 0
    for i, lane in enumerate((fits, wide)):
        alone, _, took, _ = fn.with_counters(_lanes(lane), eps[i:i + 1],
                                          salts[i:i + 1])
        assert int(took) == (i == 0)
        np.testing.assert_array_equal(np.asarray(parts[i]),
                                      np.asarray(alone[0]))
