"""Workload ingestion layer (PR 10): TaskGraph normalization, validation,
fingerprint stability, CSR lowering, and end-to-end equivalence of the
TaskGraph route with the raw-Graph route (direct call AND via the mapping
service, where the cache keys on the TaskGraph fingerprint)."""
import subprocess
import sys

import numpy as np
import pytest

from repro.core import graph as G
from repro.core.api import SharedMapConfig, shared_map, shared_map_direct
from repro.core.hierarchy import Hierarchy
from repro.core.taskgraph import TaskGraph

H = Hierarchy(a=(4, 2), d=(1.0, 10.0))
CFG = SharedMapConfig(preset="fast")


# ------------------------------------------------------------ normalization


def test_normalization_canonical_form():
    # raw: a self-loop, a duplicate (in both directions), unsorted order
    tg = TaskGraph.from_edges(
        4,
        u=[2, 1, 0, 3, 0, 2],
        v=[2, 0, 1, 1, 2, 0],
        w=[9.0, 2.0, 3.0, 4.0, 1.0, 6.0])
    # self-loop (2,2) dropped; {0,1} coalesced to 2+3=5; {0,2} to 1+6=7
    assert tg.n == 4 and tg.m == 3
    assert tg.u.tolist() == [0, 0, 1]
    assert tg.v.tolist() == [1, 2, 3]
    assert tg.w.tolist() == [5.0, 7.0, 4.0]
    assert np.all(tg.u < tg.v)


def test_zero_weight_edges_dropped_and_default_weights():
    tg = TaskGraph.from_edges(3, [0, 1], [1, 2], [0.0, 2.0])
    assert tg.m == 1 and tg.w.tolist() == [2.0]
    tg1 = TaskGraph.from_edges(3, [0, 1], [1, 2])  # w defaults to ones
    assert tg1.w.tolist() == [1.0, 1.0]
    assert tg1.vwgt.tolist() == [1.0, 1.0, 1.0]


def test_from_coo_sums_both_directions():
    # directed traffic matrix: 3 bytes u->v plus 4 bytes v->u = 7 undirected
    tg = TaskGraph.from_coo(2, rows=[0, 1], cols=[1, 0], vals=[3.0, 4.0])
    assert tg.m == 1 and tg.w.tolist() == [7.0]


def test_dtypes_are_device_currency():
    tg = TaskGraph.from_edges(3, [0], [1], [2.5], vwgt=[1.0, 2.0, 3.0])
    assert tg.u.dtype == np.int32 and tg.v.dtype == np.int32
    assert tg.w.dtype == np.float32 and tg.vwgt.dtype == np.float32


# -------------------------------------------------------------- validation


@pytest.mark.parametrize("kwargs,msg", [
    (dict(n=0, u=[], v=[]), "n >= 1"),
    (dict(n=2, u=[0], v=[2]), "out of range"),
    (dict(n=2, u=[0], v=[-1]), "out of range"),
    (dict(n=2, u=[0], v=[1], w=[-1.0]), "non-negative"),
    (dict(n=2, u=[0], v=[1], w=[float("nan")]), "finite"),
    (dict(n=2, u=[0, 1], v=[1]), "differ in length"),
    (dict(n=2, u=[0], v=[1], w=[1.0, 2.0]), "does not match"),
    (dict(n=2, u=[0], v=[1], vwgt=[1.0]), "does not match"),
    (dict(n=2, u=[0], v=[1], vwgt=[1.0, float("inf")]), "finite"),
])
def test_builder_rejects_malformed(kwargs, msg):
    with pytest.raises(ValueError, match=msg):
        TaskGraph.from_edges(**kwargs)


# ------------------------------------------------------------- fingerprint


def test_fingerprint_invariant_to_edge_order_and_direction():
    u = np.array([0, 1, 2, 0, 3])
    v = np.array([1, 2, 3, 2, 4])
    w = np.array([1.0, 2.0, 3.0, 4.0, 5.0])
    base = TaskGraph.from_edges(5, u, v, w)
    rng = np.random.default_rng(0)
    for _ in range(3):
        p = rng.permutation(u.size)
        flip = rng.random(u.size) < 0.5  # swap direction of random edges
        uu = np.where(flip, v, u)[p]
        vv = np.where(flip, u, v)[p]
        other = TaskGraph.from_edges(5, uu, vv, w[p])
        assert other.fingerprint() == base.fingerprint()


def test_fingerprint_sensitive_to_content():
    base = TaskGraph.from_edges(4, [0, 1], [1, 2], [1.0, 2.0])
    for other in (
        TaskGraph.from_edges(5, [0, 1], [1, 2], [1.0, 2.0]),   # n
        TaskGraph.from_edges(4, [0, 1], [1, 3], [1.0, 2.0]),   # topology
        TaskGraph.from_edges(4, [0, 1], [1, 2], [1.0, 2.5]),   # edge weight
        TaskGraph.from_edges(4, [0, 1], [1, 2], [1.0, 2.0],
                             vwgt=[2, 1, 1, 1]),               # vertex weight
    ):
        assert other.fingerprint() != base.fingerprint()


def test_fingerprint_ignores_meta():
    a = TaskGraph.from_edges(3, [0], [1], [1.0], meta={"source": "x"})
    b = TaskGraph.from_edges(3, [0], [1], [1.0], meta={"source": "y", "z": 1})
    assert a.fingerprint() == b.fingerprint()


def test_fingerprint_deterministic_across_processes():
    code = (
        "from repro.core.taskgraph import TaskGraph\n"
        "tg = TaskGraph.from_edges(5, [3, 0, 1], [1, 1, 2], [2.0, 1.0, 4.0],\n"
        "                          vwgt=[1, 2, 3, 4, 5])\n"
        "print(tg.fingerprint().hex())\n"
    )
    digests = {
        subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, check=True).stdout.strip()
        for _ in range(2)
    }
    here = TaskGraph.from_edges(5, [3, 0, 1], [1, 1, 2], [2.0, 1.0, 4.0],
                                vwgt=[1, 2, 3, 4, 5]).fingerprint().hex()
    assert digests == {here}


# ------------------------------------------------------------ CSR lowering


def test_to_graph_csr_invariants_and_cache():
    tg = TaskGraph.from_edges(6, [0, 1, 2, 4], [1, 2, 3, 5], [1.0, 2, 3, 4])
    g = tg.to_graph()
    assert int(g.n) == 6 and int(g.m) == 2 * tg.m  # each edge stored twice
    m = int(g.m)
    # total CSR weight mass is exactly twice the undirected mass
    assert float(np.asarray(g.ewgt)[:m].sum()) == \
        pytest.approx(2 * tg.total_edge_weight())
    assert tg.to_graph() is g  # default-padding lowering is memoized
    g2 = tg.to_graph(N=64, M=64)  # explicit padding bypasses the memo
    assert int(g2.N) == 64 and int(g2.n) == 6


def test_from_graph_roundtrip_preserves_fingerprint():
    g = G.gen_rgg(500, seed=3)
    tg = TaskGraph.from_graph(g)
    rt = TaskGraph.from_graph(tg.to_graph())
    assert rt.fingerprint() == tg.fingerprint()
    assert rt.m == tg.m and rt.n == tg.n


# ------------------------------------------- end-to-end route equivalence


def test_shared_map_taskgraph_bit_identical_to_graph():
    g = G.gen_rgg(400, seed=7)
    tg = TaskGraph.from_graph(g)
    via_tg = shared_map(tg, H, CFG)
    via_g = shared_map(tg.to_graph(), H, CFG)
    assert np.array_equal(via_tg.pe_of, via_g.pe_of)
    assert via_tg.J == via_g.J


def test_service_taskgraph_bit_identical_and_cached():
    from repro.serve.mapper import MappingService
    g = G.gen_rgg(400, seed=8)
    tg = TaskGraph.from_graph(g)
    # the direct baseline runs on the CANONICAL CSR (normalization may
    # reorder the generator's edge slots; the contract is TaskGraph-route
    # == Graph-route for the same canonical graph)
    direct = shared_map_direct(tg.to_graph(), H, CFG)
    svc = MappingService()
    try:
        r1 = svc.map(tg, H, CFG)
        assert np.array_equal(r1.pe_of, direct.pe_of) and r1.J == direct.J
        assert not r1.stats["result_cache"]["hit"]
        # repeat submit is served from the fingerprint-keyed cache
        r2 = svc.map(tg, H, CFG)
        assert r2.stats["result_cache"]["hit"]
        assert np.array_equal(r2.pe_of, direct.pe_of)
        # a rebuilt TaskGraph (same content, different object/edge order)
        # hits the same cache entry: the key is the content fingerprint
        m = tg.m
        perm = np.random.default_rng(0).permutation(m)
        tg2 = TaskGraph.from_edges(tg.n, tg.v.astype(np.int64)[perm],
                                   tg.u.astype(np.int64)[perm], tg.w[perm],
                                   vwgt=tg.vwgt)
        assert tg2.fingerprint() == tg.fingerprint()
        r3 = svc.map(tg2, H, CFG)
        assert r3.stats["result_cache"]["hit"]
        assert np.array_equal(r3.pe_of, direct.pe_of)
    finally:
        svc.close()


def test_to_graph_host_matches_device():
    """host=True gives the same padded CSR as numpy arrays, unmemoized."""
    tg = TaskGraph.from_edges(5, [0, 1, 2, 3], [1, 2, 3, 4], [1.0, 2.0, 3.0, 4.0])
    dev, host = tg.to_graph(), tg.to_graph(host=True)
    for a, b in zip(dev, host):
        assert isinstance(b, np.ndarray) or np.isscalar(b)
        assert np.asarray(a).dtype == np.asarray(b).dtype
        assert np.array_equal(np.asarray(a), np.asarray(b))
    assert tg.to_graph() is dev
