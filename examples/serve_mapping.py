"""Mapping-as-a-service: concurrent requests, coalesced dispatch, caching,
and the PR6 robustness layer (deadlines, overload shedding, degradation).

    PYTHONPATH=src python examples/serve_mapping.py

Simulates a burst of mapping traffic (distinct communication graphs on a
deep hierarchy, plus one hot repeat) against a MappingService and prints
the coalescing and cache telemetry next to the sequential baseline; then
saturates a deliberately tiny service to show load shedding, deadlines,
and the tracker's view of it all.
"""
import asyncio
import time

import numpy as np

from repro.compile_cache import use_compile_cache
from repro.core import graph as G
from repro.core.api import SharedMapConfig, shared_map, shared_map_direct
from repro.core.hierarchy import Hierarchy
from repro.serve.admission import DeadlineExceededError, ServiceOverloadError
from repro.serve.mapper import MappingService
from repro.serve.tracker import InMemoryTracker


async def traffic(svc: MappingService, gs, h, cfg):
    """A burst of concurrent requests (the asyncio front of the service)."""
    return await asyncio.gather(*(svc.amap(g, h, cfg) for g in gs))


def main():
    use_compile_cache()
    h = Hierarchy(a=(2, 2, 2, 2), d=(1.0, 5.0, 10.0, 100.0))  # 16 PEs
    cfg = SharedMapConfig(preset="fast")
    gs = [G.gen_rgg(64, seed=100 + i) for i in range(8)]

    # sequential baseline (direct path, warmed by a first sweep)
    for g in gs:
        shared_map_direct(g, h, cfg)
    t0 = time.time()
    direct = [shared_map_direct(g, h, cfg) for g in gs]
    seq_s = time.time() - t0

    # throughput service: cache off so the repeat burst measures compute
    svc = MappingService(cache_entries=0)
    t0 = time.time()
    asyncio.run(traffic(svc, gs, h, cfg))
    cold_s = time.time() - t0  # pays the merged-batch-width compiles once
    t0 = time.time()
    served = asyncio.run(traffic(svc, gs, h, cfg))
    warm_s = time.time() - t0  # steady state: what sustained traffic sees

    for d, r in zip(direct, served):
        assert np.array_equal(d.pe_of, r.pe_of), "service must be bit-identical"
    co = svc.stats()["coalesce"]
    svc.close()

    # caching service: a hot repeat is answered from the result cache, and
    # plain shared_map routes through it while installed
    cache_svc = MappingService()
    with cache_svc.installed():
        shared_map(gs[0], h, cfg)
        t0 = time.time()
        rep = shared_map(gs[0], h, cfg)
        hit_s = time.time() - t0
    assert rep.stats["result_cache"]["hit"]
    cache_svc.close()

    print(f"burst of {len(gs)}: sequential {seq_s*1e3:.0f}ms, "
          f"service cold {cold_s*1e3:.0f}ms (compiles merged widths), "
          f"steady {warm_s*1e3:.0f}ms ({seq_s/warm_s:.2f}x)")
    print(f"coalesced {co['groups']} groups into {co['dispatches']} dispatches "
          f"({co['members']} member partitions)")
    print(f"cached repeat: {hit_s*1e6:.0f}us "
          f"(J={rep.J:.0f}, identical to first answer)")

    # --- overload-safe serving: bounds, deadlines, tracker -----------------
    # Tiny bounds so this demo saturates; production bounds are sized to
    # the host. A tracker streams admission/shed/cache counters (swap
    # InMemoryTracker for JsonlTracker("mapper.jsonl") to keep a file).
    tracker = InMemoryTracker()
    svc = MappingService(max_inflight=1, max_queue=2, tracker=tracker)
    try:
        # a request that cannot wait: deadline_s cancels it wherever it is
        # (queued, or between multisection levels) once the budget is spent
        urgent = svc.submit(gs[0], h, cfg, priority=5, deadline_s=30.0)

        # a burst past the bounds: overflow is shed with a typed error (not
        # silently queued), admitted requests complete normally
        futs = svc.submit_many([(g, h, cfg) for g in gs])
        outcomes = {"ok": 0, "shed": 0, "deadline": 0}
        for f in [urgent] + futs:
            try:
                f.result(timeout=600)
                outcomes["ok"] += 1
            except ServiceOverloadError:
                outcomes["shed"] += 1   # back off and retry elsewhere
            except DeadlineExceededError:
                outcomes["deadline"] += 1
        adm = svc.stats()["admission"]
    finally:
        svc.close()
    print(f"overloaded burst: {outcomes['ok']} served, {outcomes['shed']} "
          f"shed, {outcomes['deadline']} past deadline "
          f"(queue bound {2}, inflight bound {1})")
    print(f"tracker counters: " + ", ".join(
        f"{k}={v}" for k, v in sorted(tracker.counters.items())
        if k.startswith("service.")))
    assert adm["shed"] == outcomes["shed"]


if __name__ == "__main__":
    main()
