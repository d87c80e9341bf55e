"""Mapping service: coalescing, result cache, warmup, bit-identity."""
import asyncio
import time

import numpy as np
import pytest

from repro.core import graph as G
from repro.core.api import (SharedMapConfig, current_service, shared_map,
                            shared_map_direct)
from repro.core.hierarchy import Hierarchy
from repro.serve.mapper import MappingService, request_fingerprint

H = Hierarchy(a=(4, 2), d=(1.0, 10.0))
CFG = SharedMapConfig(preset="fast")


@pytest.fixture(scope="module")
def graphs():
    return [G.gen_rgg(300, seed=40 + i) for i in range(4)]


@pytest.fixture()
def svc():
    s = MappingService()
    yield s
    s.close()


def test_solo_request_bit_identical(graphs, svc):
    d = shared_map_direct(graphs[0], H, CFG)
    r = svc.map(graphs[0], H, CFG)
    assert np.array_equal(d.pe_of, r.pe_of)
    assert d.J == r.J


def test_concurrent_requests_bit_identical_and_coalesced(graphs, svc):
    """Cross-request merging must not change any request's result — vmap
    lanes are independent — and must actually merge dispatches."""
    direct = [shared_map_direct(g, H, CFG) for g in graphs]
    futs = [svc.submit(g, H, CFG) for g in graphs]
    res = [f.result(timeout=600) for f in futs]
    for d, r in zip(direct, res):
        assert np.array_equal(d.pe_of, r.pe_of)
        assert d.J == r.J
    co = svc.stats()["coalesce"]
    assert co["groups"] > co["dispatches"], co  # merging happened


def test_result_cache_hit_fast_and_identical(graphs, svc):
    first = svc.map(graphs[0], H, CFG)
    assert first.stats["result_cache"]["hit"] is False
    t0 = time.time()
    again = svc.map(graphs[0], H, CFG)
    hit_s = time.time() - t0
    assert again.stats["result_cache"]["hit"] is True
    assert np.array_equal(first.pe_of, again.pe_of)
    assert again.J == first.J
    assert hit_s < 0.1  # microseconds-scale in practice; generous CI bound
    # a different seed is a different request
    other = svc.map(graphs[0], H, SharedMapConfig(preset="fast", seed=3))
    assert other.stats["result_cache"]["hit"] is False


def test_result_cache_lru_bound(graphs):
    svc = MappingService(cache_entries=2)
    try:
        for g in graphs[:3]:
            svc.map(g, H, CFG)
        st = svc.stats()["result_cache"]
        assert st["entries"] == 2
        assert st["evictions"] == 1
        # oldest entry was evicted -> recompute (miss)
        r = svc.map(graphs[0], H, CFG)
        assert r.stats["result_cache"]["hit"] is False
    finally:
        svc.close()


def test_inflight_dedup(graphs, svc):
    """Identical concurrent requests coalesce onto ONE computation."""
    futs = [svc.submit(graphs[1], H, CFG) for _ in range(3)]
    res = [f.result(timeout=600) for f in futs]
    for r in res[1:]:
        assert np.array_equal(res[0].pe_of, r.pe_of)
    assert svc.stats()["inflight_dedup"] >= 2


def test_fingerprint_ignores_padding(graphs):
    g = graphs[0]
    padded = G.pad_graph(g, g.N * 2, g.M * 2)
    assert request_fingerprint(g, H, CFG) == request_fingerprint(padded, H, CFG)
    assert request_fingerprint(g, H, CFG) != request_fingerprint(
        g, H, SharedMapConfig(preset="fast", seed=1))


def test_shared_map_routing(graphs):
    d = shared_map(graphs[2], H, CFG)  # no service installed
    with MappingService() as svc:
        assert current_service() is svc
        r = shared_map(graphs[2], H, CFG)
        assert "result_cache" in r.stats
        assert np.array_equal(d.pe_of, r.pe_of)
    assert current_service() is None


def test_fallback_strategies_supported(graphs, svc):
    cfg = SharedMapConfig(preset="fast", strategy="queue")
    d = shared_map_direct(graphs[3], H, cfg)
    r = svc.map(graphs[3], H, cfg)
    assert np.array_equal(d.pe_of, r.pe_of)
    # cached on repeat like any other request
    again = svc.map(graphs[3], H, cfg)
    assert again.stats["result_cache"]["hit"] is True


def test_amap_asyncio(graphs, svc):
    async def run():
        return await asyncio.gather(
            *(svc.amap(g, H, CFG) for g in graphs[:2]))

    res = asyncio.run(run())
    direct = [shared_map_direct(g, H, CFG) for g in graphs[:2]]
    for d, r in zip(direct, res):
        assert np.array_equal(d.pe_of, r.pe_of)


def test_warmup_precompiles(svc):
    """A dispatch whose (shape, k, batch) was warmed is a pure program-cache
    hit — no new XLA compile."""
    from repro.core.multisection import PlanGroup, execute_group_batch
    from repro.core.partition import num_levels
    from repro.serve.mapper import _dummy_host_graph

    N, M, k, B = 1024, 8192, 4, 2  # unique shape: not used by other tests
    w = svc.warmup(shapes=[(N, M)], ks=[k], preset="fast", batch_sizes=(B,))
    assert w["programs"] == 1
    hg = _dummy_host_graph(N, M)
    gr = PlanGroup(members=[hg] * B, N=N, M=M, arity=k,
                   levels=num_levels(N, k), preset="fast", backend="xla",
                   deg=None, eps=[0.03] * B, salts=[0, 1])
    stats = {"hits": 0, "misses": 0}
    execute_group_batch([gr], stats)
    assert stats == {"hits": 1, "misses": 0}


# --- PR7: device-resident strategy through the service ------------------------

def test_device_strategy_via_service(graphs, svc):
    """The device strategy is plannable: served requests must be
    bit-identical to the direct path, like bucket/layer."""
    cfg = SharedMapConfig(preset="fast", strategy="device")
    d = shared_map_direct(graphs[0], H, cfg)
    r = svc.map(graphs[0], H, cfg)
    assert np.array_equal(d.pe_of, r.pe_of)
    assert d.J == r.J
    again = svc.map(graphs[0], H, cfg)
    assert again.stats["result_cache"]["hit"] is True


def test_device_requests_coalesce(graphs):
    """Same-shape device-strategy requests share exec keys level by level,
    so a concurrent burst merges into shared dispatches — and merging must
    not change any request's labels."""
    cfgs = [SharedMapConfig(preset="fast", strategy="device", seed=s)
            for s in (1, 2, 3)]  # same graph: identical root (N0, M0) keys
    direct = [shared_map_direct(graphs[0], H, c) for c in cfgs]
    svc = MappingService(cache_entries=0)
    try:
        futs = svc.submit_many([(graphs[0], H, c) for c in cfgs])
        res = [f.result(timeout=600) for f in futs]
        co = svc.stats()["coalesce"]
    finally:
        svc.close()
    for d, r in zip(direct, res):
        assert np.array_equal(d.pe_of, r.pe_of)
    assert co["groups"] > co["dispatches"], co


def test_device_single_fetch_through_service(graphs):
    """The single-device-fetch contract survives the service plumbing: one
    array fetch for the multisection labels per request (evaluate_J of the
    final result is a separate, documented scalar read)."""
    from repro.core.multisection import (reset_transfer_stats,
                                         transfer_stats)

    cfg = SharedMapConfig(preset="fast", strategy="device")
    svc = MappingService(cache_entries=0)
    try:
        svc.map(graphs[1], H, cfg)  # warm compiles
        reset_transfer_stats()
        svc.map(graphs[1], H, cfg)
        xf = transfer_stats()
    finally:
        svc.close()
    assert xf["d2h_array_fetches"] == 1, xf


def test_submit_after_close_raises():
    svc = MappingService()
    svc.close()
    with pytest.raises(RuntimeError):
        svc.submit(G.gen_rgg(50, seed=1), H, CFG)


# --- worker processes on an accelerator host ----------------------------------

@pytest.mark.parametrize("options", [
    {}, {"degrade_on_failure": False, "degrade_on_overload": True},
    {"degrade_on_failure": False, "shadow_verify_fraction": 0.5}])
def test_pinned_workers_refuse_local_mapping(monkeypatch, options):
    """Where workers hold the chips, every option that would map in the
    parent is refused before any worker is spawned."""
    import repro.serve.mapper as SM
    monkeypatch.setattr(SM, "worker_platform", lambda: ("tpu", 4))
    with pytest.raises(ValueError, match="holds no chip"):
        MappingService(workers=2, **options)


def test_pinned_workers_refuse_warmup():
    """warmup compiles in this process: refused where workers hold chips."""
    svc = MappingService()
    svc._platform = "tpu"  # what workers=N sets on a TPU host
    try:
        with pytest.raises(ValueError, match="workers hold the chips"):
            svc.warmup([(64, 256)], [2])
    finally:
        svc.close()


@pytest.mark.parametrize("kind", ["graph", "taskgraph"])
def test_worker_mode_parent_starts_no_backend(kind):
    """The parent of a worker pool forwards host arrays and never starts a
    JAX backend; the worker reports the platform it mapped on."""
    import os
    import subprocess
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    script = (
        "from jax._src import xla_bridge\n"
        "from repro.core import graph as G\n"
        "from repro.core.api import SharedMapConfig\n"
        "from repro.core.hierarchy import Hierarchy\n"
        "from repro.core.taskgraph import TaskGraph\n"
        "from repro.serve.mapper import MappingService\n"
        "g = G.gen_grid(12, host=True)\n"
        f"if {kind!r} == 'taskgraph':\n"
        "    g = TaskGraph.from_edges(int(g.n), g.rows[g.rows < g.cols],\n"
        "                             g.cols[g.rows < g.cols])\n"
        "h = Hierarchy(a=(2, 2), d=(1.0, 10.0))\n"
        "with MappingService(workers=1, degrade_on_failure=False) as svc:\n"
        "    res = svc.map(g, h, SharedMapConfig(preset='fast'))\n"
        "assert res.stats['device']['platform'] == 'cpu', res.stats['device']\n"
        "assert sorted(set(res.pe_of.tolist())) == [0, 1, 2, 3]\n"
        "assert not xla_bridge.backends_are_initialized()\n"
        "print('OK')\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(root, "src"))
    proc = subprocess.run([sys.executable, "-c", script], env=env, cwd=root,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "OK" in proc.stdout


def test_skewed_and_unskewed_requests_keep_their_direct_answers():
    """A power-law graph coarsens by its own down scan (the dispatch key
    names it), so the service never stacks it with a mesh's lanes, and
    each request gets the answer the direct path gives."""
    from repro.core.multisection import LevelPlanner
    mesh = G.gen_rgg(300, seed=40)
    # a hub over 200 leaves and a path: the mesh's shape bucket
    u = np.concatenate([np.zeros(200, np.int64), np.arange(200, 299)])
    v = np.concatenate([np.arange(1, 201), np.arange(201, 300)])
    skewed = G.from_edges(300, u, v)
    keys = [LevelPlanner(g, H, preset="fast").plan()[0].exec_key
            for g in (mesh, skewed)]
    assert [k[-1] for k in keys] == ["ell", "skewed"]
    assert keys[0][:-1] == keys[1][:-1]  # one shape bucket, two programs
    direct = [shared_map_direct(g, H, CFG) for g in (mesh, skewed)]
    with MappingService() as s:
        futs = [s.submit(g, H, CFG) for g in (mesh, skewed)]
        res = [f.result(timeout=600) for f in futs]
    for d, r in zip(direct, res):
        assert np.array_equal(d.pe_of, r.pe_of)
        assert d.J == r.J
