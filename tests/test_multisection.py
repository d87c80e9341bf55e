"""Hierarchical multisection: the paper's core (§4, §5) + baselines."""
import numpy as np
import pytest

from repro.core import graph as G
from repro.core.api import SharedMapConfig, shared_map
from repro.core.baselines import (global_multisection, identity_mapping,
                                  kaffpa_map_style, random_mapping)
from repro.core.hierarchy import Hierarchy
from repro.core.mapping import evaluate_J
from repro.core.multisection import STRATEGIES, hierarchical_multisection

H_PAPER = Hierarchy(a=(4, 2, 3), d=(1.0, 10.0, 100.0))  # Fig 1


@pytest.fixture(scope="module")
def g():
    return G.gen_rgg(2500, seed=7)


def _balance(g, pe_of, k, eps):
    bw = np.bincount(pe_of, weights=np.asarray(g.vwgt)[: int(g.n)], minlength=k)
    Lmax = (1 + eps) * float(g.total_weight()) / k
    return bw, Lmax, bool((bw <= Lmax + 1e-4).all())


def test_final_partition_eps_balanced(g):
    res = shared_map(g, H_PAPER, SharedMapConfig(eps=0.03, preset="fast"))
    bw, Lmax, ok = _balance(g, res.pe_of, H_PAPER.k, 0.03)
    assert ok, (bw.max(), Lmax)
    assert (bw > 0).all(), "idle PE"


def test_beats_naive_mappings(g):
    res = shared_map(g, H_PAPER, SharedMapConfig(eps=0.03, preset="fast"))
    j_rand = evaluate_J(g, H_PAPER, random_mapping(g, H_PAPER))
    j_ident = evaluate_J(g, H_PAPER, identity_mapping(g, H_PAPER))
    assert res.J < 0.5 * j_rand
    assert res.J < j_ident


@pytest.mark.parametrize("strategy", STRATEGIES)
def test_all_strategies_valid(g, strategy):
    res = shared_map(g, H_PAPER, SharedMapConfig(eps=0.03, preset="fast",
                                                 strategy=strategy))
    bw, Lmax, ok = _balance(g, res.pe_of, H_PAPER.k, 0.03)
    assert ok


def test_strategies_agree_on_quality(g):
    js = {}
    for s in STRATEGIES:
        js[s] = shared_map(g, H_PAPER, SharedMapConfig(eps=0.03, preset="fast",
                                                       strategy=s)).J
    base = min(js.values())
    for s, j in js.items():
        assert j <= 1.25 * base, js  # same algorithm modulo padding effects


def test_strategy_determinism(g):
    a = shared_map(g, H_PAPER, SharedMapConfig(preset="fast", strategy="bucket", seed=4))
    b = shared_map(g, H_PAPER, SharedMapConfig(preset="fast", strategy="bucket", seed=4))
    assert np.array_equal(a.pe_of, b.pe_of)


# sha256 (first 16 hex digits) of the int32 pe_of bytes on g, recorded on
# XLA's CPU backend from the program before its stages ran under
# jax.named_scope: scopes change op metadata, never a mapping.
PARENT_MAPPINGS = {
    ("fast", "bucket", 0): "e6c5d6267fcb5602",
    ("fast", "bucket", 4): "6c67c96d8b87879b",
    ("fast", "device", 0): "e79ab9fe1bdc0a05",
    ("eco", "bucket", 0): "9975672e84ef1cdc",
}


@pytest.mark.parametrize("preset,strategy,seed", list(PARENT_MAPPINGS))
def test_mappings_bitwise_as_recorded(g, preset, strategy, seed):
    import hashlib
    res = shared_map(g, H_PAPER, SharedMapConfig(
        eps=0.03, preset=preset, strategy=strategy, seed=seed))
    pe = np.asarray(res.pe_of, np.int32)
    assert hashlib.sha256(pe.tobytes()).hexdigest()[:16] == \
        PARENT_MAPPINGS[(preset, strategy, seed)]


def test_adaptive_beats_fixed_eps_on_balance():
    """GM (fixed eps) can exceed L_max where SharedMap cannot (paper §5/§6.4)."""
    g = G.gen_rgg(1200, seed=3)
    h = Hierarchy(a=(4, 4), d=(1.0, 10.0))
    viol_adaptive = 0
    for seed in range(3):
        res = hierarchical_multisection(g, h, eps=0.03, preset="fast",
                                        seed=seed, adaptive=True)
        _, _, ok = _balance(g, res.pe_of, h.k, 0.03)
        viol_adaptive += (not ok)
    assert viol_adaptive == 0


def test_kaffpa_map_style_baseline(g):
    h = Hierarchy(a=(4, 2, 2), d=(1.0, 10.0, 100.0))  # k=16 (power of two)
    res = kaffpa_map_style(g, h, eps=0.05, preset="fast")
    bw, Lmax, ok = _balance(g, res.pe_of, h.k, 0.05)
    assert ok
    j = evaluate_J(g, h, res.pe_of)
    j_rand = evaluate_J(g, h, random_mapping(g, h))
    assert j < j_rand


def test_global_multisection_baseline(g):
    res = global_multisection(g, H_PAPER, eps=0.03, preset="fast")
    j = evaluate_J(g, H_PAPER, res.pe_of)
    j_rand = evaluate_J(g, H_PAPER, random_mapping(g, H_PAPER))
    assert j < j_rand


def test_sharedmap_quality_vs_baselines(g):
    """The paper's mechanism claim, isolated: with EQUAL mapping-phase
    machinery (both sides get the swap pass — our substrate partitioner is
    weaker than KaFFPa, so unlike the paper it needs one), adaptive-eps
    hierarchical multisection is competitive-or-better vs GM's fixed-eps.
    The 60/40 best-solution split lives in benchmarks/quality_profiles."""
    h = H_PAPER
    j_sm = shared_map(g, h, SharedMapConfig(eps=0.03, preset="strong",
                                            refine_mapping=True)).J
    j_gm = evaluate_J(g, h, global_multisection(g, h, 0.03, "strong").pe_of)
    assert j_sm <= 1.2 * j_gm, (j_sm, j_gm)


# --- PR3: CSR round-trip, queue rewrite, compile cache ------------------------

def test_to_device_csr_roundtrip():
    """_HostGraph.to_device must produce a VALID padded CSR: exact indptr
    prefix (no clamping artifacts), sorted rows, and per-row neighbour
    multisets identical to the host arrays."""
    from repro.core.multisection import _HostGraph, host_graph_from

    g0 = G.gen_rgg(300, seed=11)
    hg = host_graph_from(g0)
    N, M = 512, 4096  # generous padding
    g = hg.to_device(N, M)
    ind = np.asarray(g.indptr)
    rows = np.asarray(g.rows)
    cols = np.asarray(g.cols)
    m = int(g.m)
    n = int(g.n)
    assert ind.shape == (N + 1,)
    assert ind[0] == 0 and ind[-1] == m
    assert (np.diff(ind) >= 0).all()
    # padding rows (>= n) are empty and all point at the tail
    assert (ind[n:] == m).all()
    # rows sorted over real slots, consistent with indptr
    assert (np.diff(rows[:m]) >= 0).all()
    for u in range(n):
        lo, hi = ind[u], ind[u + 1]
        assert (rows[lo:hi] == u).all()
        expect = np.sort(hg.cols[hg.rows == u])
        got = np.sort(cols[lo:hi])
        assert np.array_equal(got, expect), u
    # padded edge slots are weight-0 anchors
    assert (np.asarray(g.ewgt)[m:] == 0).all()
    assert (rows[m:] == N - 1).all()


def test_queue_equals_naive():
    """queue and naive pad subgraphs identically and salt by hierarchy
    position, so their mappings must be bit-equal for a fixed seed."""
    g = G.gen_rgg(800, seed=5)
    h = Hierarchy(a=(3, 4), d=(1.0, 10.0))
    a = hierarchical_multisection(g, h, eps=0.03, preset="fast", strategy="queue", seed=9)
    b = hierarchical_multisection(g, h, eps=0.03, preset="fast", strategy="naive", seed=9)
    assert np.array_equal(a.pe_of, b.pe_of)
    assert a.stats["partition_calls"] == b.stats["partition_calls"]


def test_compile_cache_reuse():
    """A repeat run must be all cache hits (no new XLA programs)."""
    g = G.gen_rgg(700, seed=6)
    h = Hierarchy(a=(4, 2), d=(1.0, 10.0))
    hierarchical_multisection(g, h, preset="fast", strategy="bucket", seed=1)
    res = hierarchical_multisection(g, h, preset="fast", strategy="bucket", seed=1)
    cc = res.stats["compile_cache"]
    assert cc["misses"] == 0 and cc["hits"] > 0, cc


# --- PR5: planner/executor split, cross-request coalescing, ell deg ----------

def test_ell_deg_pooled_mean():
    """_ell_deg_for must use the REAL pooled mean degree sum(m)/sum(n), not
    the max of per-member ceil-means (which over-padded mixed buckets)."""
    import dataclasses
    from repro.core.graph import default_ell_deg
    from repro.core.multisection import _ell_deg_for

    @dataclasses.dataclass
    class Fake:
        n: int
        m: int

    members = [Fake(n=100, m=400), Fake(n=10, m=300)]  # means 4 and 30
    # pooled: ceil(700/110) = 7, NOT max(4, 30) = 30
    assert _ell_deg_for(members, "ell") == default_ell_deg(1, 7)
    assert _ell_deg_for(members, "xla") is None


def test_bucket_equals_naive_bitwise(g):
    """Non-circular oracle for the planner path: bucket pads each subgraph
    to the SAME pow2 shapes naive uses, and vmap lanes are independent, so
    the bucket strategy (which now runs entirely on LevelPlanner +
    execute_group_batch) must reproduce the naive strategy's mapping
    bit-for-bit. A planning/batching bug shows up here even though both
    in-process bucket paths share the planner code."""
    _assert_bucket_equals_naive(g)


def test_bucket_equals_naive_bitwise_on_a_power_law_graph(fresh_programs):
    """The same oracle where every dispatch takes the skewed down scan."""
    _assert_bucket_equals_naive(G.gen_kron(9, seed=4))


def _assert_bucket_equals_naive(g):
    a = hierarchical_multisection(g, H_PAPER, eps=0.03, preset="fast",
                                  strategy="bucket", seed=2)
    b = hierarchical_multisection(g, H_PAPER, eps=0.03, preset="fast",
                                  strategy="naive", seed=2)
    assert np.array_equal(a.pe_of, b.pe_of)


def test_level_planner_matches_run_loop(g):
    """Manually stepping a LevelPlanner (the mapping service's usage
    pattern) must match the one-shot driver exactly."""
    from repro.core.multisection import (LevelPlanner, execute_group_batch)

    direct = hierarchical_multisection(g, H_PAPER, eps=0.03, preset="fast",
                                       strategy="bucket", seed=2)
    planner = LevelPlanner(g, H_PAPER, eps=0.03, preset="fast", seed=2)
    while True:
        groups = planner.plan()
        if not groups:
            break
        planner.advance([execute_group_batch([gr], planner.cache_stats)[0]
                         for gr in groups])
    res = planner.result()
    assert np.array_equal(direct.pe_of, res.pe_of)
    assert direct.stats["partition_calls"] == res.stats["partition_calls"]
    assert direct.stats["padded_vertex_work"] == res.stats["padded_vertex_work"]


# --- PR7: device-resident multisection ---------------------------------------

H_SMALL = Hierarchy(a=(2, 2), d=(1.0, 10.0))


@pytest.fixture(scope="module")
def g_small():
    return G.gen_rgg(300, seed=13)


def test_split_blocks_matches_host_split():
    """graph.split_blocks (the on-device induced-subgraph op) must be
    BITWISE identical to the host `_split` extraction — every child array
    including padding slots, sizes and weights."""
    import jax.numpy as jnp
    from repro.core.multisection import _split, host_graph_from

    g0 = G.gen_rgg(400, seed=21)
    hg = host_graph_from(g0)
    rng = np.random.default_rng(0)
    k = 3
    part = rng.integers(0, k, hg.n).astype(np.int32)
    hg.depth = 2
    host_children = _split(hg, part, k, 1, 1, k)

    N, M = g0.N, g0.M
    pb = np.full(N, k, np.int32)
    pb[: hg.n] = part
    orig = jnp.asarray(
        np.concatenate([np.arange(hg.n), np.full(N - hg.n, hg.n)]).astype(np.int32))
    ch, corig, wsum = G.split_blocks(g0, jnp.asarray(pb), orig, k,
                                     jnp.int32(hg.n))
    for b, hc in enumerate(host_children):
        dev = hc.to_device(N, M)  # children keep the parent's padded shapes
        assert int(ch.n[b]) == hc.n and int(ch.m[b]) == hc.m
        assert np.array_equal(np.asarray(ch.vwgt[b]), np.asarray(dev.vwgt))
        assert np.array_equal(np.asarray(ch.rows[b]), np.asarray(dev.rows))
        assert np.array_equal(np.asarray(ch.cols[b]), np.asarray(dev.cols))
        assert np.array_equal(np.asarray(ch.ewgt[b]), np.asarray(dev.ewgt))
        assert np.array_equal(np.asarray(ch.indptr[b]), np.asarray(dev.indptr))
        co = np.asarray(corig[b])
        assert np.array_equal(co[: hc.n], hc.orig_ids)
        assert (co[hc.n:] == hg.n).all()  # pads hit the sentinel
        assert np.float32(wsum[b]) == np.float32(hc.vwgt.sum())


@pytest.mark.parametrize("preset", ["fast", "eco", "strong"])
def test_device_equals_host_reference_presets(g_small, preset):
    """The fully device-resident level loop must be bit-identical to its
    host-reference twin (resident=False under the same strategy) — the
    regression contract for the on-device split/eps/scatter pipeline."""
    a = hierarchical_multisection(g_small, H_SMALL, eps=0.03, preset=preset,
                                  strategy="device", seed=3)
    b = hierarchical_multisection(g_small, H_SMALL, eps=0.03, preset=preset,
                                  strategy="device", seed=3, resident=False)
    assert np.array_equal(a.pe_of, b.pe_of)
    assert a.stats["partition_calls"] == b.stats["partition_calls"]


@pytest.mark.parametrize("backend", ["auto", "ell", "xla"])
def test_device_equals_host_reference_backends(g_small, backend):
    a = hierarchical_multisection(g_small, H_SMALL, eps=0.03, preset="fast",
                                  strategy="device", seed=5, backend=backend)
    b = hierarchical_multisection(g_small, H_SMALL, eps=0.03, preset="fast",
                                  strategy="device", seed=5, backend=backend,
                                  resident=False)
    assert np.array_equal(a.pe_of, b.pe_of)


def test_bucket_resident_equals_host_mirror(g):
    """bucket with the device-resident level loop (the default) must equal
    the PR-5 host-mirror loop (resident=False) bit-for-bit — and therefore
    naive too (test_bucket_equals_naive_bitwise closes that triangle)."""
    a = hierarchical_multisection(g, H_PAPER, eps=0.03, preset="fast",
                                  strategy="bucket", seed=2)
    b = hierarchical_multisection(g, H_PAPER, eps=0.03, preset="fast",
                                  strategy="bucket", seed=2, resident=False)
    assert np.array_equal(a.pe_of, b.pe_of)
    assert a.stats["partition_calls"] == b.stats["partition_calls"]
    assert a.stats["resident"] and not b.stats["resident"]


def test_device_strategy_single_array_fetch(g_small):
    """The device strategy's acceptance contract: exactly ONE device->host
    array fetch per request (the final pe_of) — no bulk label or mirror
    traffic, no per-level metadata fetches either."""
    from repro.core.multisection import (reset_transfer_stats,
                                         transfer_stats)

    # warm: compiles + memoized program construction must not pollute the
    # measured counters
    hierarchical_multisection(g_small, H_SMALL, preset="fast",
                              strategy="device", seed=1)
    reset_transfer_stats()
    res = hierarchical_multisection(g_small, H_SMALL, preset="fast",
                                    strategy="device", seed=1)
    xf = transfer_stats()
    assert xf["d2h_array_fetches"] == 1, xf
    assert xf["d2h_bytes"] == res.pe_of.nbytes, xf
    # the root metadata read (n, m ints) is the only per-request meta cost
    assert xf["d2h_meta_fetches"] <= 1, xf


def test_bucket_resident_meta_only_transfers(g_small):
    """bucket-resident moves METADATA per level (child sizes/weights), one
    bulk fetch total; the PR-5 host mirror fetched full arrays per level."""
    from repro.core.multisection import (reset_transfer_stats,
                                         transfer_stats)

    hierarchical_multisection(g_small, H_SMALL, preset="fast",
                              strategy="bucket", seed=1)
    reset_transfer_stats()
    hierarchical_multisection(g_small, H_SMALL, preset="fast",
                              strategy="bucket", seed=1)
    res_xf = transfer_stats()
    reset_transfer_stats()
    hierarchical_multisection(g_small, H_SMALL, preset="fast",
                              strategy="bucket", seed=1, resident=False)
    host_xf = transfer_stats()
    assert res_xf["d2h_array_fetches"] == 1, res_xf
    assert host_xf["d2h_array_fetches"] > res_xf["d2h_array_fetches"]
    assert host_xf["d2h_bytes"] > res_xf["d2h_bytes"]


def test_i32_overflow_guard():
    """Graphs at/above 2^31 vertices or edge slots must be rejected before
    any int32 index array silently wraps."""
    from repro.core.graph import check_i32_range

    check_i32_range(2**31 - 1, 2**31 - 1)  # max representable: fine
    with pytest.raises(ValueError, match="int32"):
        check_i32_range(2**31, 8)
    with pytest.raises(ValueError, match="int32"):
        check_i32_range(8, 2**31)


def test_host_graph_dtypes_and_result_dtype(g_small):
    """The unified store is f32/i32 end-to-end: no silent f64/i64 upcasts
    in the host view, and pe_of comes back int32 from every strategy."""
    from repro.core.multisection import host_graph_from

    hg = host_graph_from(g_small)
    assert hg.vwgt.dtype == np.float32 and hg.ewgt.dtype == np.float32
    assert hg.rows.dtype == np.int32 and hg.cols.dtype == np.int32
    assert hg.orig_ids.dtype == np.int32
    for strategy in ("naive", "bucket", "device"):
        res = hierarchical_multisection(g_small, H_SMALL, preset="fast",
                                        strategy=strategy, seed=1)
        assert res.pe_of.dtype == np.int32, strategy


def test_merged_dispatch_lane_independent(g):
    """execute_group_batch over same-key groups of DIFFERENT hierarchies
    returns bit-identical per-member results vs solo dispatches — the
    invariant the mapping service's cross-request coalescing rests on."""
    from repro.core.multisection import LevelPlanner, execute_group_batch

    g2 = G.gen_rgg(2500, seed=8)
    p1 = LevelPlanner(g, H_PAPER, eps=0.03, preset="fast", seed=0)
    p2 = LevelPlanner(g2, H_PAPER, eps=0.03, preset="fast", seed=5)
    g1s, g2s = p1.plan(), p2.plan()
    assert len(g1s) == len(g2s) == 1  # one root group each
    assert g1s[0].exec_key == g2s[0].exec_key
    cs = {"hits": 0, "misses": 0}
    solo1 = execute_group_batch([g1s[0]], cs)[0]
    solo2 = execute_group_batch([g2s[0]], cs)[0]
    merged = execute_group_batch([g1s[0], g2s[0]], cs, pad_batch_pow2=True)
    assert np.array_equal(merged[0], solo1)
    assert np.array_equal(merged[1], solo2)


def test_initial_compact_counter_counts_the_dispatches_that_can_shrink(
        g, monkeypatch):
    """``initial_dispatches`` counts the dispatches whose initial partition
    can run cut to ``initial_width``; on a small rgg every one of them
    does."""
    from repro.core import multisection as MS
    from repro.core.partition import initial_width
    can_shrink = []
    dispatch = MS.dispatch_group_batch

    def spy(groups, *a, **kw):
        gr = groups[0]
        can_shrink.append(gr.arity > 1 and initial_width(
            gr.N, gr.M, gr.arity, gr.levels)[0] < gr.N)
        return dispatch(groups, *a, **kw)

    monkeypatch.setattr(MS, "dispatch_group_batch", spy)
    res = shared_map(g, H_PAPER, SharedMapConfig(eps=0.03, preset="fast"))
    assert 0 < sum(can_shrink) < len(can_shrink)
    assert res.stats["initial_dispatches"] == sum(can_shrink)
    assert res.stats["initial_compact_dispatches"] == sum(can_shrink)


def test_vcycle_counter_counts_the_coarsest_pass_at_its_width(g):
    _assert_vcycle_counter(g, "rgg")


def test_vcycle_counter_counts_a_skewed_dispatch(fresh_programs):
    """A power-law root takes the skewed down scan: the counter counts
    the levels it ran, its two-hop pairs and its coarsest graph."""
    _assert_vcycle_counter(G.gen_kron(11, seed=5), "kron")


def _assert_vcycle_counter(g, kind):
    import jax
    from repro.core.graph import default_ell_deg
    from repro.core.partition import (Preset, _coarsen_down,
                                      _coarsen_skewed_lanes, initial_width,
                                      num_levels)
    res = hierarchical_multisection(g, Hierarchy(a=(3,), d=(1.0,)),
                                    preset="eco")
    n, m = int(g.n), int(g.m)
    N0, M0 = 1 << (n - 1).bit_length(), 1 << (m - 1).bit_length()
    levels = num_levels(N0, 3)
    deg = default_ell_deg(N0, M0)
    # the root's down scan, run alone
    root = G.pad_graph(g, N0, M0)
    if kind == "kron":
        lanes = jax.tree_util.tree_map(lambda a: a[None], root)
        c, _, sizes, (pairs, ran) = jax.jit(lambda gs: _coarsen_skewed_lanes(
            gs, levels, deg, 3))(lanes)
        c, sizes = jax.tree_util.tree_map(lambda a: a[0], c), sizes[0]
        twohop, ran = int(pairs.sum()), np.asarray(ran) > 0
        # the levels that ran come first; a stall makes the rest identities
        assert ran[0] and np.all(np.diff(ran.astype(int)) <= 0)
    else:
        c, _, sizes = jax.jit(lambda g0: _coarsen_down(
            g0, levels, "ell", deg))(root)
        twohop, ran = 0, np.ones(levels, bool)
    Nc, Mc = initial_width(N0, M0, 3, levels, kind == "kron")
    compact = int(c.n) <= Nc and int(c.m) <= Mc
    assert Nc < N0 and res.stats["initial_dispatches"] == 1
    assert res.stats["initial_compact_dispatches"] == compact
    assert compact == (kind == "rgg")  # the kron root stalls past Nc
    passes = 1 + Preset.get("eco").vcycles
    assert res.stats["vcycle_padded_vertex_work"] == \
        N0 * (passes + int(ran.sum())) - compact * (N0 - Nc)
    sizes = np.asarray(sizes)
    assert res.stats["vcycle_real_vertex_work"] == \
        n * passes + int(sizes[ran].sum())
    coarsest = int(sizes[-1])
    assert res.stats["coarsest_real_vertices"] == coarsest
    assert res.stats["coarsening_target"] == 72
    assert res.stats["coarsen_merges"] == n - coarsest
    assert res.stats["coarsen_twohop_merges"] == twohop
    assert (twohop > 0) == (kind == "kron")


@pytest.mark.parametrize("make,mode", [
    (lambda: G.gen_rgg(1000, seed=7), "ell"),
    (lambda: G.gen_rgg(400, seed=2), "ell"),   # rows just past the cap
    (lambda: G.gen_grid(30), "ell"),
    (lambda: G.gen_road(900), "ell"),
    (lambda: G.gen_kron(9, seed=1), "skewed"),
    (lambda: G.from_edges(300, np.zeros(200, np.int64), np.arange(1, 201)),
     "skewed"),                                # a star, 99 isolated
])
def test_the_request_decides_its_down_scan(make, mode, monkeypatch,
                                          fresh_programs):
    """A request coarsens by the skewed down scan where more than an
    eighth of its root's edge slots lie past the root's ELL cap, by
    today's programs otherwise; every dispatch of it alike, on every
    strategy that runs the planner and on the host loops."""
    from repro.core import multisection as MS
    seen = []
    real = MS.batched_partition
    monkeypatch.setattr(MS, "batched_partition",
                        lambda *a: seen.append(a[5]) or real(*a))
    for strategy in ("bucket", "device"):
        seen.clear()
        res = hierarchical_multisection(make(), H_PAPER, preset="fast",
                                        strategy=strategy)
        assert set(seen) == {mode}, strategy
        assert (res.stats["coarsen_twohop_merges"] > 0) == (mode == "skewed")
    planner = MS.LevelPlanner(make(), H_PAPER, resident=False)
    assert planner.coarsen == mode


def test_kron_mapping_against_the_benchmark_reference(fresh_programs):
    """``shared_map`` on a Graph500 scale-10 graph (the benchmark's own
    generator) on 4:8:3, checked as the chip benchmark checks it: one PE
    in [0, k) per task, every PE's load within what the instance admits,
    and J as reported equal to the float64 reference; the counters of
    the skewed down scan come back with it."""
    import sys
    from pathlib import Path
    root = str(Path(__file__).resolve().parents[1])
    if root not in sys.path:
        sys.path.insert(0, root)
    from bench import yardstick as Y
    from bench.families.kron import instances
    from repro.core.taskgraph import TaskGraph
    h = Hierarchy(a=(4, 8, 3), d=(1.0, 10.0, 100.0))
    inst = instances({"scale": 10, "edgefactor": 16, "instance_seeds": [1],
                      "initiator": {"A": 0.57, "B": 0.19, "C": 0.19}}, 0)[0]
    tg = TaskGraph.from_edges(inst.n, inst.u, inst.v, inst.w)
    res = shared_map(tg, h, SharedMapConfig(eps=0.03, preset="eco"))
    pe = np.asarray(res.pe_of)
    assert pe.shape == (inst.n,) and pe.min() >= 0 and pe.max() < h.k
    assert Y.load_over_limit(inst, pe, h.k, 0.03) <= 1.0
    J64 = Y.numpy_J(inst, pe, h.a, h.d)
    assert abs(res.J - J64) <= 1e-5 * J64
    assert J64 < 0.7 * Y.random_J(inst, h.a, h.d)
    st = res.stats
    assert 0 < st["coarsen_twohop_merges"] <= st["coarsen_merges"]
    assert st["coarsest_real_vertices"] + st["coarsen_merges"] > inst.n
    assert 0 <= st["initial_compact_dispatches"] <= st["initial_dispatches"]


def test_load_limit_admits_the_whole_weight_ceiling():
    """With whole vertex weights no partition beats ceil(W / k): a lane
    whose eps leaves less than one unit above W / k gets that ceiling;
    fractional weights keep (1 + eps) W / k."""
    import jax.numpy as jnp
    from repro.core.partition import load_limit
    g = G.from_edges(174, np.arange(173), np.arange(1, 174))
    assert float(load_limit(g, jnp.float32(0.01), 4)) == 44.0
    assert float(load_limit(g, jnp.float32(0.03), 4)) == \
        pytest.approx(1.03 * 174 / 4)
    half = G.from_edges(174, np.arange(173), np.arange(1, 174),
                        vwgt=np.full(174, 0.5))
    assert float(load_limit(half, jnp.float32(0.01), 4)) == \
        pytest.approx(1.01 * 87 / 4)
