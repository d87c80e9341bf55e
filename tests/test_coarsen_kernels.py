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


# --- the skewed path: two-hop and isolated pairing, exact contraction -------

def _skewed_graph(kind: str, seed: int = 0):
    """Small graphs whose rows run past an ELL cap of 8: a Kronecker graph,
    a star with isolated vertices, hubs whose leaves share them, and a
    path among isolated vertices. Duplicate edges merged; padded past the
    real counts."""
    from repro.core.taskgraph import TaskGraph
    rng = np.random.default_rng(seed)
    if kind == "kron":
        g0 = G.gen_kron(7, seed=seed)
        n = int(g0.n)
        u = np.asarray(g0.rows)[: int(g0.m)]
        v = np.asarray(g0.cols)[: int(g0.m)]
    elif kind == "star":
        n = 90
        u = np.zeros(60, np.int64)
        v = np.arange(1, 61)                          # 29 isolated
    elif kind == "hubs":
        n = 150
        hub = rng.integers(0, 4, 120)                 # leaves of 4 hubs
        u = np.concatenate([hub, hub[:30] + 1, rng.integers(4, 124, 20)])
        v = np.concatenate([np.arange(4, 124), np.arange(4, 34),
                            rng.integers(4, 124, 20)])
    else:                                             # "isolated"
        n = 100
        u, v = np.arange(0, 29), np.arange(1, 30)     # 70 isolated
    tg = TaskGraph.from_edges(n, u, v)
    N = 1 << int(n).bit_length()
    return tg.to_graph(N=N, M=max(8, 4 << int(max(tg.m, 1)).bit_length()))


def _quotient(g, newid):
    """numpy: the graph of g's vertex classes under ``newid``, edges
    merged and summed, listed by (row, col)."""
    n, m = int(g.n), int(g.m)
    nid = np.asarray(newid)[:n]
    r = nid[np.asarray(g.rows)[:m]]
    c = nid[np.asarray(g.cols)[:m]]
    w = np.asarray(g.ewgt, np.float64)[:m]
    keep = r != c
    key, inv = np.unique(r[keep] * g.N + c[keep], return_inverse=True)
    wsum = np.zeros(key.size)
    np.add.at(wsum, inv, w[keep])
    vw = np.bincount(nid, weights=np.asarray(g.vwgt, np.float64)[:n])
    return key // g.N, key % g.N, wsum, vw, float(w[~keep].sum())


@pytest.mark.parametrize("backend", ["xla", "interpret"])
@pytest.mark.parametrize("kind", ["kron", "star", "hubs", "isolated"])
def test_skewed_coarsen_is_the_quotient_graph(monkeypatch, kind, backend):
    """Three levels of the skewed path: each coarse graph is the numpy
    quotient of its fine graph under the labels, rows past the cap kept
    whole, and the coarse edge weight plus the weight contracted inside
    coarse vertices is the fine total on every level."""
    from repro.core.coarsen import coarsen_skewed_once
    _flip_backend(monkeypatch, backend)
    g = _skewed_graph(kind)
    deg = 8
    ind = np.asarray(g.indptr)
    assert kind == "isolated" or np.diff(ind).max() > deg
    total = float(np.asarray(g.ewgt, np.float64).sum())
    for level in range(3):
        gc, newid, pairs = coarsen_skewed_once(g, salt=level, ell_deg=deg,
                                               max_vwgt=8.0)
        n, nc, mc = int(g.n), int(gc.n), int(gc.m)
        nid = np.asarray(newid)[:n]
        sizes = np.bincount(nid, minlength=nc)
        assert sizes.min() >= 1 and sizes.max() <= 2       # pairs only
        rows, cols, w, vw, inside = _quotient(g, newid)
        assert mc == rows.size and nc == vw.size
        assert np.array_equal(np.asarray(gc.rows)[:mc], rows)
        assert np.array_equal(np.asarray(gc.cols)[:mc], cols)
        np.testing.assert_array_equal(np.asarray(gc.ewgt)[:mc], w)
        np.testing.assert_array_equal(np.asarray(gc.vwgt)[:nc], vw)
        assert np.array_equal(np.asarray(gc.indptr),
                              G.padded_csr_indptr(np.asarray(gc.rows), mc,
                                                  g.N))
        assert float(np.asarray(gc.ewgt, np.float64).sum()) + inside == total
        total -= inside
        assert int(pairs) <= n - nc
        g = gc
    if kind in ("star", "isolated"):
        assert int(g.n) < _skewed_graph(kind).n // 2  # leaves, isolated pair


def _twohop_reference(g, labels, matched, adj, salt, max_vwgt):
    """numpy: pairs of unmatched vertices with the same set of neighbours
    (rows within the cap), or both isolated; each group in order of
    (weight, the program's salted jitter, id), its 1st with its 2nd, 3rd
    with 4th, ..., each pair at most ``max_vwgt``."""
    from repro.core.coarsen import _edge_jitter
    N, DEG = adj.shape
    n = int(g.n)
    deg = np.diff(np.asarray(g.indptr))[:n]
    adj = np.asarray(adj)
    w = np.asarray(g.vwgt)
    idx = jnp.arange(N, dtype=jnp.int32)
    jit_ = np.asarray(_edge_jitter(idx, idx, salt * 7 + 29))
    labels = np.asarray(labels).copy()
    groups: dict = {}
    for v in range(n):
        if np.asarray(matched)[v] or deg[v] > DEG:
            continue
        key = frozenset(adj[v][adj[v] < N].tolist()) if deg[v] else "iso"
        groups.setdefault(key, []).append(v)
    made = 0
    for members in groups.values():
        members.sort(key=lambda v: (w[v], jit_[v], v))
        for a, b in zip(members[0::2], members[1::2]):
            if w[a] + w[b] <= max_vwgt:
                labels[a] = labels[b] = min(a, b)
                made += 1
    return labels, made


@pytest.mark.parametrize("backend", ["xla", "interpret"])
@pytest.mark.parametrize("kind,seed", [("hubs", 0), ("hubs", 1),
                                       ("kron", 2), ("star", 3)])
def test_twohop_pairs_equal_the_reference(monkeypatch, kind, seed, backend):
    from repro.core.coarsen import _hem_rounds_ell, pair_twohop
    _flip_backend(monkeypatch, backend)
    g = _skewed_graph(kind, seed)
    adj, adw, _ = ell_adjacency(g, 8)
    labels, matched = _hem_rounds_ell(g, adj, adw, 3, seed, None)
    got, made = pair_twohop(g, labels, matched, adj, seed, 3.0)
    want, want_made = _twohop_reference(g, labels, matched, adj, seed, 3.0)
    assert want_made > 0
    assert int(made) == want_made
    assert np.array_equal(np.asarray(got), want)
