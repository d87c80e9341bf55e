"""Coarsening kernels vs jnp oracles, and backend invariance of the cascade.

The coarsening path's contract is BITWISE parity across pallas / interpret
/ xla (kernels/ref.py shares the row bodies), so every comparison here is
array_equal, not allclose.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import graph as G
from repro.core.coarsen import (coarsen_cascade, coarsen_once, contract_ell,
                                hem_match, hem_match_ell)
from repro.core.graph import assemble_padded, default_ell_deg, ell_adjacency
from repro.core.partition import clear_batched_partition_cache
from repro.kernels import ops, ref
from repro.kernels.coarsen_kernels import (contract_edges_pallas,
                                           hem_propose_pallas)


def _rand_ell(rng, n, deg, zero_rows=0.2, self_loops=0.1):
    """Random padded ELL adjacency with zero-degree rows and self-loops."""
    adj = rng.integers(0, n + 1, (n, deg))          # n == pad id
    if zero_rows:
        adj[rng.random(n) < zero_rows] = n          # zero-degree vertices
    if self_loops:
        rows = np.nonzero(rng.random(n) < self_loops)[0]
        adj[rows, rng.integers(0, deg, rows.shape[0])] = rows  # self-loops
    adw = rng.random((n, deg)).astype(np.float32) * (adj < n)
    return jnp.asarray(adj, jnp.int32), jnp.asarray(adw)


# --- hem_propose: kernel (interpret) == oracle, bitwise ----------------------

@pytest.mark.parametrize("seed", range(6))
def test_hem_propose_parity_random(seed):
    """Zero-degree rows, self-loops, partially matched vectors — and sizes
    straddling the tile boundary so padded lanes are exercised."""
    rng = np.random.default_rng(seed)
    n = int(rng.integers(8, 600))                    # < and > TILE_V=256
    deg = int(rng.integers(1, 24))
    adj, adw = _rand_ell(rng, n, deg)
    jit_ = jnp.asarray(rng.random((n, deg)), jnp.float32)
    matched = jnp.asarray((rng.random(n) < 0.3).astype(np.int32))
    a = ref.hem_propose_ref(adj, adw, jit_, matched)
    b = hem_propose_pallas(adj, adw, jit_, matched, interpret=True)
    assert a.dtype == b.dtype == jnp.int32
    assert np.array_equal(np.asarray(a), np.asarray(b))


def test_hem_propose_fully_matched():
    """A fully-matched graph proposes nothing (sentinel N everywhere)."""
    rng = np.random.default_rng(0)
    n, deg = 300, 8
    adj, adw = _rand_ell(rng, n, deg, zero_rows=0.0)
    jit_ = jnp.asarray(rng.random((n, deg)), jnp.float32)
    matched = jnp.ones((n,), jnp.int32)
    a = ref.hem_propose_ref(adj, adw, jit_, matched)
    b = hem_propose_pallas(adj, adw, jit_, matched, interpret=True)
    assert np.all(np.asarray(a) == n)
    assert np.array_equal(np.asarray(a), np.asarray(b))


def test_hem_propose_padded_lanes_inert():
    """Tile padding must not leak into real rows: n == TILE_V + 1 forces a
    nearly-empty last tile; every real row still matches the oracle."""
    rng = np.random.default_rng(3)
    n, deg = 257, 8
    adj, adw = _rand_ell(rng, n, deg, zero_rows=0.0, self_loops=0.0)
    jit_ = jnp.asarray(rng.random((n, deg)), jnp.float32)
    matched = jnp.zeros((n,), jnp.int32)
    a = ref.hem_propose_ref(adj, adw, jit_, matched)
    b = hem_propose_pallas(adj, adw, jit_, matched, interpret=True)
    assert np.array_equal(np.asarray(a), np.asarray(b))


# --- contract_edges: kernel (interpret) == oracle, bitwise -------------------

@pytest.mark.parametrize("seed", range(6))
def test_contract_edges_parity_random(seed):
    """Duplicate ids in a row must accumulate bitwise-identically (fixed
    add chain), distinct counts and first-slot placement must agree."""
    rng = np.random.default_rng(100 + seed)
    n = int(rng.integers(8, 600))
    d2 = int(rng.integers(2, 32))
    # few distinct ids per row -> many duplicates to accumulate
    cand = rng.integers(0, max(n // 8, 2), (n, d2))
    cand[rng.random((n, d2)) < 0.3] = n             # invalid slots
    candw = rng.random((n, d2)).astype(np.float32) * (cand < n)
    cand = jnp.asarray(cand, jnp.int32)
    candw = jnp.asarray(candw)
    a = ref.contract_edges_ref(cand, candw, n)
    b = contract_edges_pallas(cand, candw, interpret=True)
    for xa, xb in zip(a, b):
        assert xa.dtype == xb.dtype
        assert np.array_equal(np.asarray(xa), np.asarray(xb))


def test_ops_coarsen_dispatch(monkeypatch):
    """ops wrappers return identical values through either backend flag."""
    monkeypatch.setenv("REPRO_KERNEL_BACKEND", "interpret")
    rng = np.random.default_rng(9)
    n, deg = 200, 8
    adj, adw = _rand_ell(rng, n, deg)
    jit_ = jnp.asarray(rng.random((n, deg)), jnp.float32)
    matched = jnp.zeros((n,), jnp.int32)
    a = ops.hem_propose(adj, adw, jit_, matched, use_pallas=False)
    b = ops.hem_propose(adj, adw, jit_, matched, use_pallas=True)
    assert np.array_equal(np.asarray(a), np.asarray(b))
    cand = jnp.asarray(rng.integers(0, n + 1, (n, 2 * deg)), jnp.int32)
    candw = jnp.asarray(
        rng.random((n, 2 * deg)).astype(np.float32) * (np.asarray(cand) < n))
    for xa, xb in zip(ops.contract_edges(cand, candw, use_pallas=False),
                      ops.contract_edges(cand, candw, use_pallas=True)):
        assert np.array_equal(np.asarray(xa), np.asarray(xb))


# --- the ELL coarsening path: invariants + backend invariance ----------------

def _check_coarse_invariants(g, gc, newid):
    """Structural invariants the v-cycle relies on (note: total EDGE weight
    is conserved only without ELL overflow; vertex weight always is)."""
    N = g.N
    n, nc = int(g.n), int(gc.n)
    mc = int(gc.m)
    newid_np = np.asarray(newid)
    assert 0 < nc <= n
    assert np.all((newid_np[:n] >= 0) & (newid_np[:n] < nc))
    np.testing.assert_allclose(float(jnp.sum(gc.vwgt)),
                               float(jnp.sum(g.vwgt)), rtol=1e-5)
    rows = np.asarray(gc.rows)
    cols = np.asarray(gc.cols)
    ind = np.asarray(gc.indptr)
    assert ind[0] == 0 and ind[-1] == mc == ind[nc]
    assert np.all(np.diff(rows[:mc]) >= 0)           # sorted rows
    counts = np.bincount(rows[:mc], minlength=N)
    assert np.array_equal(np.cumsum(counts)[:N], ind[1:])
    assert np.all(rows[:mc] != cols[:mc])            # no self-loops
    assert np.all(np.asarray(gc.ewgt)[:mc] > 0)
    assert np.all(np.asarray(gc.ewgt)[mc:] == 0)


@pytest.mark.parametrize("seed", [0, 1, 5])
def test_coarsen_ell_invariants(seed):
    g = G.gen_rgg(400, seed=seed)
    deg = default_ell_deg(int(g.n), int(g.m))
    gc, newid = coarsen_once(g, salt=seed, ell_deg=deg)
    _check_coarse_invariants(g, gc, newid)
    # matching validity: clusters have size <= 2 (HEM matches pairs)
    lab = np.asarray(newid)[: int(g.n)]
    assert np.bincount(lab).max() <= 2


def test_coarsen_ell_overflow_rows():
    """Rows past the DEG cap are truncated but the result is still a valid
    coarse graph (heuristic-only contract; cut is evaluated on the fine
    graph elsewhere)."""
    g = G.gen_rgg(300, seed=2)
    assert int(np.asarray(G.degrees(g))[: int(g.n)].max()) > 4
    gc, newid = coarsen_once(g, salt=1, ell_deg=8)   # cap below max degree
    _check_coarse_invariants(g, gc, newid)


def test_coarsen_ell_matches_segment_weightsum():
    """Without overflow the ELL path conserves total edge weight exactly,
    like the segment path (different matchings, same invariant)."""
    g = G.gen_rgg(300, seed=4)
    deg = int(np.asarray(G.degrees(g))[: int(g.n)].max())
    deg = (deg + 7) // 8 * 8
    gc, _ = coarsen_once(g, salt=3, ell_deg=deg)
    # contracted intra-pair weight + coarse weight == fine weight
    fine_w = float(jnp.sum(g.ewgt))
    coarse_w = float(jnp.sum(gc.ewgt))
    adj, adw, _ = ell_adjacency(g, deg)
    labels = hem_match_ell(g, adj, adw, salt=3)
    # each matched pair removes its (directed) intra edges from the total
    rows_np, cols_np = np.asarray(g.rows), np.asarray(g.cols)
    lab_np = np.asarray(labels)
    gone = (lab_np[rows_np] == lab_np[cols_np]) & (np.asarray(g.ewgt) > 0)
    np.testing.assert_allclose(
        coarse_w, fine_w - float(np.asarray(g.ewgt)[gone].sum()), rtol=1e-5)


def _flip_backend(monkeypatch, be):
    monkeypatch.setenv("REPRO_KERNEL_BACKEND", be)
    jax.clear_caches()
    clear_batched_partition_cache()


def test_coarsen_backend_invariant(monkeypatch):
    """coarsen_once + coarsen_cascade produce bit-identical coarse graphs
    under xla and interpret dispatch (trace-time env, hence cache clears)."""
    g = G.gen_rgg(500, seed=11)
    deg = default_ell_deg(int(g.n), int(g.m))
    outs = {}
    for be in ("xla", "interpret"):
        _flip_backend(monkeypatch, be)
        gc, newid = coarsen_once(g, salt=5, ell_deg=deg)
        ns, ms = coarsen_cascade(g, 3, ell_deg=deg)
        outs[be] = jax.tree_util.tree_map(np.asarray, (gc, newid, ns, ms))
    for a, b in zip(jax.tree_util.tree_leaves(outs["xla"]),
                    jax.tree_util.tree_leaves(outs["interpret"])):
        assert a.dtype == b.dtype and np.array_equal(a, b)


@pytest.mark.parametrize("preset", ["fast", "eco", "strong"])
def test_partition_backend_invariant_presets(monkeypatch, preset):
    """The fused v-cycle's final partition is bitwise backend-invariant for
    every preset. Refinement is pinned to its kernel-free CSR path
    (backend="xla" — the ELL lp_gain kernel is allclose-, not bitwise-,
    parity), so the env flip exercises ONLY the coarsening kernels."""
    from repro.core.partition import partition_host
    g = G.gen_rgg(250, seed=21)
    outs = {}
    for be in ("xla", "interpret"):
        _flip_backend(monkeypatch, be)
        outs[be] = np.asarray(
            partition_host(g, 4, 0.05, preset, salt=3, backend="xla"))
    assert np.array_equal(outs["xla"], outs["interpret"])


@pytest.mark.parametrize("strategy", ["device", "bucket", "layer"])
def test_multisection_backend_invariant_strategies(monkeypatch, strategy):
    """End-to-end hierarchical multisection is bitwise backend-invariant
    for every scheduling strategy (the coarsening + split kernels flip;
    refinement pinned to the CSR path as above)."""
    from repro.core.hierarchy import Hierarchy
    from repro.core.multisection import hierarchical_multisection
    g = G.gen_rgg(220, seed=31)
    h = Hierarchy(a=(2, 2), d=(1.0, 10.0))
    outs = {}
    for be in ("xla", "interpret"):
        _flip_backend(monkeypatch, be)
        res = hierarchical_multisection(g, h, eps=0.05, preset="fast",
                                        strategy=strategy, seed=2,
                                        backend="xla")
        outs[be] = np.asarray(res.pe_of)
    assert np.array_equal(outs["xla"], outs["interpret"])


# --- satellite 1: round-salt regression --------------------------------------

def _cycle_graph(n):
    """Unit-weight n-cycle: all scores tie, so matching is pure jitter."""
    u = np.arange(n, dtype=np.int32)
    v = (u + 1) % n
    rows = np.concatenate([u, v]).astype(np.int32)
    cols = np.concatenate([v, u]).astype(np.int32)
    order = np.argsort(rows, kind="stable")  # Graph invariant: sorted rows
    w = np.ones(2 * n, np.float32)
    return assemble_padded(np.ones(n, np.float32), rows[order], cols[order],
                           w, n, n, 2 * n)


@pytest.mark.parametrize("matcher", ["segment", "ell"])
def test_round_salt_breaks_proposal_cycles(matcher):
    """A round whose proposals form a cycle matches nothing; with the old
    round-invariant jitter the SAME proposals repeated every round, so
    rounds 2..r were dead weight. The fix re-salts per round: some salt
    that stalls at rounds=1 must match a pair by rounds=3."""
    g = _cycle_graph(6)
    deg = 8
    adj, adw, _ = ell_adjacency(g, deg)

    def match(rounds, salt):
        if matcher == "segment":
            labels = hem_match(g, rounds=rounds, salt=salt)
        else:
            labels = hem_match_ell(g, adj, adw, rounds=rounds, salt=salt)
        lab = np.asarray(labels)[: int(g.n)]
        return int((lab != np.arange(int(g.n))).sum()) // 2  # matched pairs

    stalled = [s for s in range(200) if match(1, s) == 0]
    assert stalled, "no salt produced a fully cyclic first round (test graph too easy)"
    recovered = sum(1 for s in stalled if match(3, s) >= 1)
    # the re-salted rounds must rescue the overwhelming majority of stalls
    assert recovered >= len(stalled) * 3 // 4, (recovered, len(stalled))


def test_coarsen_cascade_telemetry_shapes():
    ns, ms = coarsen_cascade(G.gen_rgg(400, seed=1), 4)
    ns, ms = np.asarray(ns), np.asarray(ms)
    assert ns.shape == ms.shape == (4,)
    assert np.all(np.diff(ns) <= 0) and ns[-1] >= 1   # monotone shrink
