"""Benchmark harness — one function per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--quick] [--scale small|large]

Prints ``name,us_per_call,derived`` CSV rows per the repo contract; each
section maps to a paper artifact (DESIGN.md §8):

    quality_profiles   Fig 5/6  — solution quality vs baselines
    thread_strategies  Fig 3    — NAIVE/LAYER/BUCKET/QUEUE scheduling
    presets            Fig 2    — FAST/ECO/STRONG trade-off
    scalability        Fig 4    — restart-lane scaling (vmap width)
    mapping_vs_default —        — SharedMap device order for the prod mesh
    kernels            —        — Pallas kernel oracles timing
    serve              —        — mapping service: cached-repeat latency and
                                  cross-request batched throughput (PR5)
    serve_overload     —        — admission control under an arrival-rate
                                  ramp (p50/p99 latency, shed rate) and a
                                  fault-injection sweep (PR6)
    device_pipeline    —        — device-resident multisection vs the PR5
                                  host-mirror loop: per-request wall time
                                  and host<->device transfer traffic (PR7)
    durability         —        — persistent result store: warm-restart
                                  hit latency vs cold compute vs in-memory
                                  LRU hit, and the persistence-tier write
                                  overhead on the compute path (PR8)
    coarsen_kernels    —        — device-resident coarsening + fused
                                  v-cycle at 10^5/10^6 vertices: per-stage
                                  cold wall, per-level shrink, peak RSS,
                                  fused vs unrolled-segment cold path (PR9)
    model_graphs       —        — the ingestion closed loop: compile a
                                  model-zoo arch, extract its HLO comm
                                  graph (TaskGraph), SharedMap it onto the
                                  physical hierarchy, J vs the default
                                  placement (PR10; doubles as CI smoke)
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np

ROWS: list[tuple[str, float, str]] = []

# structured telemetry merged into BENCH_PR3.json at exit (perf trajectory
# tracking from PR 3 onward: strategy wall times, partition_calls,
# padded-vs-real vertex work, compile-cache hits, map costs).
BENCH: dict = {"sections": {}}


def emit(name: str, us: float, derived: str = ""):
    ROWS.append((name, us, derived))
    print(f"{name},{us:.1f},{derived}", flush=True)


def bench_quality_profiles(scale: str, quick: bool):
    from benchmarks.instances import instances, paper_hierarchies
    from repro.core.api import SharedMapConfig, shared_map
    from repro.core.baselines import (global_multisection, kaffpa_map_style,
                                      random_mapping)
    from repro.core.mapping import evaluate_J

    # sharedmap_r = SharedMap + the same swap pass GM gets (apples-to-apples
    # with our substrate partitioner; DESIGN.md §2.3, EXPERIMENTS deviations)
    algos = ["sharedmap", "sharedmap_r", "gm", "random"] + ([] if quick else ["kaffpamap"])
    hs = list(paper_hierarchies(1 if quick else 2))
    results = {a: [] for a in algos}
    for gname, tg in instances(scale):
        g = tg.to_graph()  # baselines + evaluate_J run on the CSR form
        for h in hs:
            for algo in algos:
                t0 = time.time()
                if algo == "sharedmap":
                    # fed the TaskGraph form: exercises the ingestion layer
                    J = shared_map(tg, h, SharedMapConfig(preset="fast")).J
                elif algo == "sharedmap_r":
                    J = shared_map(tg, h, SharedMapConfig(preset="fast",
                                                          refine_mapping=True)).J
                elif algo == "gm":
                    res = global_multisection(g, h, preset="fast")
                    J = evaluate_J(g, h, res.pe_of)
                elif algo == "kaffpamap":
                    try:
                        res = kaffpa_map_style(g, h, preset="fast")
                        J = evaluate_J(g, h, res.pe_of)
                    except ValueError:
                        continue  # non power-of-two k
                else:
                    J = evaluate_J(g, h, random_mapping(g, h))
                dt = time.time() - t0
                results[algo].append((gname, str(h), J, dt))
                emit(f"quality/{algo}/{gname}/k{h.k}", dt * 1e6, f"J={J:.0f}")
    # performance profile at tau=1 (fraction of instances with best J)
    keys = [(g0, h0) for (g0, h0, _, _) in results["sharedmap"]]
    best_count = {a: 0 for a in algos}
    for i, key in enumerate(keys):
        js = {a: results[a][i][2] for a in algos if i < len(results[a])}
        best = min(js.values())
        for a, j in js.items():
            if j <= best * 1.0001:
                best_count[a] += 1
    for a in algos:
        emit(f"profile_tau1/{a}", 0.0, f"best_on={best_count[a]}/{len(keys)}")


def bench_thread_strategies(scale: str, quick: bool):
    from benchmarks.instances import instances
    from repro.core.api import SharedMapConfig, shared_map
    from repro.core.hierarchy import Hierarchy

    import jax
    h = Hierarchy(a=(4, 8, 2), d=(1.0, 10.0, 100.0))
    strategies = ["naive", "layer", "bucket", "queue"]
    from repro.core.multisection import clear_compile_cache
    section = BENCH["sections"].setdefault("thread_strategies", {})
    for gname, g in instances(scale):
        jax.clear_caches()
        clear_compile_cache()
        times = {}
        reps = 3  # min-of-reps: wall clock on shared/throttled hosts is noisy
        for s in strategies:
            shared_map(g, h, SharedMapConfig(preset="fast", strategy=s))  # warm
            best = float("inf")
            for _ in range(reps):
                t0 = time.time()
                res = shared_map(g, h, SharedMapConfig(preset="fast", strategy=s))
                best = min(best, time.time() - t0)
            times[s] = best
            waste = res.stats["padded_vertex_work"] / max(res.stats["real_vertex_work"], 1)
            cc = res.stats["compile_cache"]
            emit(f"strategy/{s}/{gname}", times[s] * 1e6,
                 f"padwaste={waste:.2f} cache={cc['hits']}h/{cc['misses']}m")
            section[f"{s}/{gname}"] = {
                "wall_s": times[s],
                "J": res.J,
                "partition_calls": res.stats["partition_calls"],
                "padded_vertex_work": res.stats["padded_vertex_work"],
                "real_vertex_work": res.stats["real_vertex_work"],
                "compile_cache_hits": cc["hits"],
                "compile_cache_misses": cc["misses"],
                "backend": res.stats["backend"],
            }
        base = times["layer"]
        for s in strategies:
            emit(f"strategy_speedup_vs_layer/{s}/{gname}", times[s] * 1e6,
                 f"speedup={base / times[s]:.2f}")
        if quick:
            break


def bench_presets(scale: str, quick: bool):
    from benchmarks.instances import instances
    from repro.core.api import SharedMapConfig, shared_map
    from repro.core.hierarchy import Hierarchy

    h = Hierarchy(a=(4, 8), d=(1.0, 10.0))
    presets = ["fast", "eco"] + ([] if quick else ["strong"])
    for gname, g in instances(scale):
        ref = None
        for p in presets:
            t0 = time.time()
            res = shared_map(g, h, SharedMapConfig(preset=p))
            dt = time.time() - t0
            ref = ref or res.J
            emit(f"preset/{p}/{gname}", dt * 1e6, f"J={res.J:.0f} vs_fast={res.J/ref:.3f}")
        if quick:
            break


def bench_scalability(scale: str, quick: bool):
    """Lane scaling: vmapped seeded restarts are the TPU analogue of adding
    threads to one partition call (KaFFPa-style repetitions)."""
    import jax
    import jax.numpy as jnp
    from benchmarks.instances import instances
    from repro.core.partition import num_levels, partition

    gname, tg = next(instances(scale))
    g = tg.to_graph()
    lv = num_levels(int(g.n), 8)
    for lanes in ([1, 4] if quick else [1, 2, 4, 8]):
        def run(salts):
            return jax.vmap(lambda s: partition(g, 8, jnp.float32(0.03), lv, "fast", s))(salts)
        salts = jnp.arange(lanes, dtype=jnp.int32)
        run(salts)  # compile
        t0 = time.time()
        jax.block_until_ready(run(salts))
        dt = time.time() - t0
        emit(f"scalability/lanes{lanes}/{gname}", dt * 1e6,
             f"per_lane_us={dt*1e6/lanes:.0f}")


def bench_mapping_vs_default(scale: str, quick: bool):
    from repro.core.mapping import evaluate_J
    from repro.launch.mesh import (logical_comm_graph, physical_hierarchy,
                                   sharedmap_device_order)

    for multi_pod in (False, True):
        g = logical_comm_graph(multi_pod).to_graph()
        h = physical_hierarchy(multi_pod)
        k = h.k
        t0 = time.time()
        perm = sharedmap_device_order(multi_pod)
        dt = time.time() - t0
        j_sm = evaluate_J(g, h, perm)
        j_def = evaluate_J(g, h, np.arange(k))
        rng = np.random.default_rng(0)
        j_rnd = float(np.mean([evaluate_J(g, h, rng.permutation(k)) for _ in range(3)]))
        emit(f"device_order/sharedmap/pod{2 if multi_pod else 1}", dt * 1e6,
             f"J={j_sm:.0f} default={j_def:.0f} random={j_rnd:.0f}")


def bench_refine_backends(scale: str, quick: bool):
    """ELL/Pallas-backed refinement vs the seed XLA scatter path: final
    edge-cut parity and wall time of whole partition calls."""
    import jax
    from benchmarks.instances import instances
    from repro.core.graph import edge_cut
    from repro.core.partition import partition_host

    section = BENCH["sections"].setdefault("refine_backends", {})
    for gname, tg in instances(scale):
        g = tg.to_graph()
        row = {}
        for be in ("xla", "ell"):
            jax.block_until_ready(partition_host(g, 8, 0.03, "fast", salt=1, backend=be))  # warm
            dt = float("inf")
            for _ in range(3):  # min-of-reps (noisy shared host)
                t0 = time.time()
                part = jax.block_until_ready(partition_host(g, 8, 0.03, "fast", salt=1, backend=be))
                dt = min(dt, time.time() - t0)
            cut = float(edge_cut(g, part))
            row[be] = {"wall_s": dt, "edge_cut": cut}
            emit(f"refine_backend/{be}/{gname}", dt * 1e6, f"cut={cut:.0f}")
        section[gname] = row
        if quick:
            break


def bench_kernels(scale: str, quick: bool):
    import jax
    import jax.numpy as jnp
    from repro.core import graph as G
    from repro.core.hierarchy import Hierarchy
    from repro.kernels import ops, ref

    g = G.gen_rgg(20_000, seed=0)
    h = Hierarchy(a=(16, 16), d=(1.0, 10.0))
    rng = np.random.default_rng(0)
    pe = jnp.asarray(rng.integers(0, h.k, g.N), jnp.int32)
    gb = jnp.asarray((1,) + h.strides[:-1], jnp.int32)
    dv = jnp.asarray(h.d, jnp.float32)
    f = jax.jit(lambda: ref.mapcost_ref(g.rows, g.cols, g.ewgt, pe, gb, dv))
    jax.block_until_ready(f())
    t0 = time.time()
    for _ in range(10):
        jax.block_until_ready(f())
    us = (time.time() - t0) / 10 * 1e6
    emit("kernel/mapcost_ref_20k", us, f"edges_per_s={int(g.m)/(us/1e6):.2e}")

    k = 16
    part = jnp.asarray(rng.integers(0, k, g.N), jnp.int32)
    adj, adw = ref.csr_to_ell(g.rows, g.cols, g.ewgt, g.N, 16)
    f2 = jax.jit(lambda: ref.lp_gain_ref(adj, adw, part, k))
    jax.block_until_ready(f2())
    t0 = time.time()
    for _ in range(10):
        jax.block_until_ready(f2())
    us = (time.time() - t0) / 10 * 1e6
    emit("kernel/lp_gain_ref_20k", us, f"vertices_per_s={int(g.n)/(us/1e6):.2e}")
    BENCH["sections"].setdefault("kernels", {})["lp_gain_ref_20k_us"] = us

    # mapcost through the single dispatch helper (pallas on TPU, oracle here)
    f3 = jax.jit(lambda: ops.mapcost(g.rows, g.cols, g.ewgt, pe, gb, dv))
    jax.block_until_ready(f3())
    t0 = time.time()
    for _ in range(10):
        jax.block_until_ready(f3())
    us = (time.time() - t0) / 10 * 1e6
    emit("kernel/mapcost_dispatch_20k", us, f"backend={ops.kernel_backend()}")
    BENCH["sections"]["kernels"]["mapcost_dispatch_20k_us"] = us
    BENCH["sections"]["kernels"]["backend"] = ops.kernel_backend()


def bench_serve(scale: str, quick: bool):
    """Mapping service vs sequential shared_map: cached-repeat latency and
    cross-request coalesced throughput.

    Workload: a burst of distinct small communication graphs on a DEEP
    hierarchy — the service's target traffic. Small instances and many
    hierarchy levels mean many tiny per-request dispatches, which is where
    per-dispatch overhead rivals partition compute and coalescing pays;
    large single mappings stay compute-bound and gain little (that regime
    is benchmarked by thread_strategies).

    The throughput service runs with the result cache DISABLED and the
    timed reps reuse the warm seeds: burst composition (and therefore the
    compiled batch widths) is deterministic, so the measurement is
    steady-state compute, free of both compile noise and cache shortcuts.
    """
    from repro.core import graph as G
    from repro.core.api import SharedMapConfig, shared_map_direct
    from repro.core.hierarchy import Hierarchy
    from repro.serve.mapper import MappingService

    h = Hierarchy(a=(2, 2, 2, 2), d=(1.0, 5.0, 10.0, 100.0))
    R = 8 if quick else 24
    n = 64
    seeds = (1, 2) if quick else (1, 2, 3)
    gs = [G.gen_rgg(n, seed=100 + i) for i in range(R)]
    cfg = SharedMapConfig(preset="fast")
    section = BENCH["sections"].setdefault("serve", {})

    # sequential baseline (direct path), warmed by its own first sweep
    for s in seeds:
        for g in gs:
            shared_map_direct(g, h, SharedMapConfig(preset="fast", seed=s))
    seq = float("inf")
    for s in seeds:
        t0 = time.time()
        for g in gs:
            shared_map_direct(g, h, SharedMapConfig(preset="fast", seed=s))
        seq = min(seq, time.time() - t0)
    emit(f"serve/sequential_direct/{R}x_rgg{n}", seq * 1e6,
         f"per_req_ms={seq/R*1e3:.1f}")

    svc = MappingService(cache_entries=0)  # throughput: no result cache
    try:
        # COLD first-request latency: the service's vmapped B=1 programs
        # are distinct from the direct path's, so this pays their compiles
        # — the number warmup() exists to hide.
        t0 = time.time()
        first = svc.map(gs[0], h, cfg)
        cold_s = time.time() - t0
        emit(f"serve/first_request_cold/rgg{n}", cold_s * 1e6,
             f"cache_hit={first.stats['result_cache']['hit']}")

        for s in seeds:  # warm the merged batch widths
            for f in svc.submit_many([(g, h, SharedMapConfig(preset="fast",
                                                             seed=s))
                                      for g in gs]):
                f.result()
        bat = float("inf")
        for s in seeds:
            t0 = time.time()
            futs = svc.submit_many([(g, h, SharedMapConfig(preset="fast",
                                                           seed=s))
                                    for g in gs])
            for f in futs:
                f.result()
            bat = min(bat, time.time() - t0)
        tput = seq / bat
        emit(f"serve/batched_service/{R}x_rgg{n}", bat * 1e6,
             f"throughput_vs_sequential={tput:.2f}x")
        co = svc.stats()["coalesce"]
    finally:
        svc.close()

    # cached-repeat latency on a caching service (identical request twice)
    svc2 = MappingService()
    try:
        svc2.map(gs[0], h, cfg)
        t0 = time.time()
        hit_reps = 20
        for _ in range(hit_reps):
            res = svc2.map(gs[0], h, cfg)
        hit_s = (time.time() - t0) / hit_reps
        assert res.stats["result_cache"]["hit"] is True
        cached_speedup = (seq / R) / hit_s
        emit(f"serve/cached_repeat/rgg{n}", hit_s * 1e6,
             f"speedup_vs_compute={cached_speedup:.0f}x")
        rc = svc2.stats()["result_cache"]
    finally:
        svc2.close()

    section.update({
        "requests": R,
        "instance": f"rgg{n}",
        "hierarchy": "x".join(map(str, h.a)),
        "sequential_wall_s": seq,
        "batched_wall_s": bat,
        "throughput_speedup": tput,
        "cached_repeat_s": hit_s,
        "cached_speedup": cached_speedup,
        "coalesce": co,
        "result_cache": rc,
    })


def bench_serve_overload(scale: str, quick: bool):
    """Overload behavior of the admission-controlled service (PR6).

    Two experiments on deliberately small bounds (max_inflight=2,
    max_queue=4 — the point is to saturate, whatever the host):

    * **Arrival-rate ramp** — open-loop Poisson-ish arrivals at increasing
      rates; per-rate p50/p99 completion latency of ADMITTED requests and
      the shed rate. Past saturation the shed rate climbs while admitted
      latency stays bounded — that is the load-shedding contract (an
      unbounded queue would instead blow up latency for everyone).
    * **Fault-injection sweep** — a burst under a 25% transient dispatch
      failure rate: every future must resolve with a result (possibly
      degraded) or a typed ServiceOverloadError; retries/degradations are
      reported from the service's own telemetry.
    """
    from repro.core import graph as G
    from repro.core.api import SharedMapConfig
    from repro.core.hierarchy import Hierarchy
    from repro.faults import FaultInjector
    from repro.serve.admission import RetryPolicy, ServiceOverloadError
    from repro.serve.mapper import MappingService

    h = Hierarchy(a=(2, 2, 2), d=(1.0, 10.0, 100.0))
    n = 64
    R = 12 if quick else 32
    gs = [G.gen_rgg(n, seed=300 + i) for i in range(R)]
    section = BENCH["sections"].setdefault("serve_overload", {})

    # warm the programs the BOUNDED service will actually run: with
    # max_inflight=2 the coalesced widths are 1-2, so feed pairs
    # closed-loop (a big submit_many burst would only warm the wide
    # merged widths and the ramp would measure compiles, not serving)
    warm = MappingService(cache_entries=0, max_inflight=2)
    try:
        for j in range(0, R, 2):
            for f in warm.submit_many([(g, h, SharedMapConfig(preset="fast",
                                                              seed=i))
                                       for i, g in enumerate(gs[j:j + 2], j)]):
                f.result()
    finally:
        warm.close()

    for rate in ([50, 400] if quick else [25, 100, 400]):  # requests/s
        svc = MappingService(max_inflight=2, max_queue=4, cache_entries=0)
        lat: list[float] = []
        shed = 0
        try:
            futs = []
            t_start = time.time()
            for i, g in enumerate(gs):
                target = t_start + i / rate  # open-loop arrivals
                delay = target - time.time()
                if delay > 0:
                    time.sleep(delay)
                t0 = time.time()
                try:
                    f = svc.submit(g, h, SharedMapConfig(preset="fast", seed=i))
                except ServiceOverloadError:
                    shed += 1
                    continue

                def _done(fut, t0=t0):
                    if fut.exception() is None:
                        lat.append(time.time() - t0)

                f.add_done_callback(_done)
                futs.append(f)
            for f in futs:
                f.exception(timeout=600)  # wait; sheds were counted above
        finally:
            svc.close()
        lat.sort()
        p50 = lat[len(lat) // 2] if lat else float("nan")
        p99 = lat[min(int(len(lat) * 0.99), len(lat) - 1)] if lat else float("nan")
        shed_rate = shed / R
        emit(f"serve_overload/rate{rate}/p99", p99 * 1e6,
             f"p50_ms={p50*1e3:.1f} shed_rate={shed_rate:.2f}")
        section[f"rate{rate}"] = {
            "requests": R, "admitted": len(lat), "shed": shed,
            "shed_rate": shed_rate, "p50_s": p50, "p99_s": p99,
        }

    # fault-injection sweep: all futures resolve, typed errors only. The
    # queue admits the whole burst (this experiment is about containment,
    # not shedding — the ramp above measures that).
    inj = FaultInjector(seed=1, rates={"dispatch": 0.25})
    svc = MappingService(max_inflight=2, max_queue=R, fault_injector=inj,
                         retry=RetryPolicy(max_retries=1,
                                           backoff_base_s=0.001))
    ok = shed = degraded = 0
    try:
        t0 = time.time()
        futs = svc.submit_many([(g, h, SharedMapConfig(preset="fast",
                                                       seed=1000 + i))
                                for i, g in enumerate(gs)])
        for f in futs:
            exc = f.exception(timeout=600)
            if exc is None:
                ok += 1
                if f.result().stats["degradation"]["level"] > 0:
                    degraded += 1
            elif isinstance(exc, ServiceOverloadError):
                shed += 1
            else:
                raise AssertionError(f"untyped failure escaped: {exc!r}")
        wall = time.time() - t0
        flt = svc.stats()["faults"]
    finally:
        svc.close()
    assert ok + shed == R, (ok, shed, R)
    emit(f"serve_overload/fault_sweep/{R}x_rgg{n}", wall * 1e6,
         f"ok={ok} shed={shed} degraded={degraded} retries={flt['retries']}")
    section["fault_sweep"] = {
        "requests": R, "ok": ok, "shed": shed, "degraded": degraded,
        "dispatch_failures": flt["dispatch_failures"],
        "retries": flt["retries"], "contained": flt["contained"],
        "wall_s": wall,
    }


def bench_device_pipeline(scale: str, quick: bool):
    """Device-resident level loop vs the PR5 host-mirror loop (PR7).

    Workload: a burst of rgg64-class graphs on a DEEP hierarchy — many
    levels of small dispatches, where per-level host round-trips dominate.
    Three pipelines, bit-identical outputs (tested in tests/):

    * ``host_mirror``     — bucket, resident=False: the PR5 reference;
                            per-level bulk label fetch + child re-upload.
    * ``bucket_resident`` — bucket, resident=True (the new default):
                            children stay on device, [B] metadata per level.
    * ``device``          — strategy=device: fixed root-shape schedule,
                            exactly ONE array fetch per request (asserted).

    Per mode we report min-of-reps wall time per request plus the transfer
    counters (bytes and fetch counts per request) from one instrumented
    sweep — the protocol cost an accelerator-attached host would pay.
    """
    from repro.core import graph as G
    from repro.core.hierarchy import Hierarchy
    from repro.core.multisection import (hierarchical_multisection,
                                         reset_transfer_stats,
                                         transfer_stats)

    h = Hierarchy(a=(2, 2, 2, 2), d=(1.0, 5.0, 10.0, 100.0))
    R = 4 if quick else 12
    n = 64
    gs = [G.gen_rgg(n, seed=500 + i) for i in range(R)]
    reps = 2 if quick else 3
    modes = [
        ("host_mirror", dict(strategy="bucket", resident=False)),
        ("bucket_resident", dict(strategy="bucket")),
        ("device", dict(strategy="device")),
    ]
    section = BENCH["sections"].setdefault("device_pipeline", {})
    base = None
    for mode, kw in modes:
        for i, g in enumerate(gs):  # warm every program this mode needs
            hierarchical_multisection(g, h, preset="fast", seed=i, **kw)
        reset_transfer_stats()
        for i, g in enumerate(gs):  # instrumented sweep (warm)
            res = hierarchical_multisection(g, h, preset="fast", seed=i, **kw)
        xf = transfer_stats()
        best = float("inf")
        for _ in range(reps):
            t0 = time.time()
            for i, g in enumerate(gs):
                hierarchical_multisection(g, h, preset="fast", seed=i, **kw)
            best = min(best, time.time() - t0)
        per_req = best / R
        fetches = xf["d2h_array_fetches"] / R
        d2h_kb = (xf["d2h_bytes"] + xf["d2h_meta_bytes"]) / R / 1e3
        h2d_kb = xf["h2d_bytes"] / R / 1e3
        if mode == "device":
            assert xf["d2h_array_fetches"] == R, xf  # ONE fetch per request
        base = base or per_req
        emit(f"device_pipeline/{mode}/{R}x_rgg{n}", per_req * 1e6,
             f"speedup_vs_host={base/per_req:.2f} d2h_fetches_per_req="
             f"{fetches:.1f} d2h_kb_per_req={d2h_kb:.1f}")
        section[mode] = {
            "requests": R, "instance": f"rgg{n}",
            "hierarchy": "x".join(map(str, h.a)),
            "wall_s_per_request": per_req,
            "speedup_vs_host_mirror": base / per_req,
            "J": res.J if hasattr(res, "J") else None,
            "transfers_per_request": {
                "d2h_array_fetches": fetches,
                "d2h_meta_fetches": xf["d2h_meta_fetches"] / R,
                "d2h_kb": d2h_kb,
                "h2d_transfers": xf["h2d_transfers"] / R,
                "h2d_kb": h2d_kb,
            },
        }


def bench_durability(scale: str, quick: bool):
    """Persistence tier of the mapping service (PR8).

    Three latencies for the SAME request: cold compute (empty caches),
    in-memory LRU repeat, and a store hit after a "process restart" (a
    fresh service opened on the same store directory — LRU cold, disk
    warm). Plus the write overhead the durable tier adds to the compute
    path: a burst of distinct requests with and without a store attached.
    The store hit pays decode + checksum but skips partitioning entirely,
    so it should land between the LRU hit and cold compute — orders of
    magnitude below the latter.
    """
    import shutil
    import tempfile

    from repro.core import graph as G
    from repro.core.api import SharedMapConfig
    from repro.core.hierarchy import Hierarchy
    from repro.serve.mapper import MappingService

    h = Hierarchy(a=(2, 2, 2), d=(1.0, 10.0, 100.0))
    n = 64
    R = 4 if quick else 12
    gs = [G.gen_rgg(n, seed=200 + i) for i in range(R)]
    cfg = SharedMapConfig(preset="fast")
    section = BENCH["sections"].setdefault("durability", {})
    root = tempfile.mkdtemp(prefix="bench_durability_")
    try:
        path = f"{root}/store"
        svc = MappingService(store_path=path, batch_window_s=0.0)
        try:
            t0 = time.time()
            cold = svc.map(gs[0], h, cfg)
            cold_s = time.time() - t0
            assert cold.stats["result_cache"]["hit"] is False
            emit(f"durability/cold_compute/rgg{n}", cold_s * 1e6, "")

            hit_reps = 20
            t0 = time.time()
            for _ in range(hit_reps):
                res = svc.map(gs[0], h, cfg)
            lru_s = (time.time() - t0) / hit_reps
            assert res.stats["result_cache"]["hit"] is True
            emit(f"durability/lru_hit/rgg{n}", lru_s * 1e6,
                 f"speedup_vs_cold={cold_s/lru_s:.0f}x")
        finally:
            svc.close()

        # "restarted process": fresh service, same directory. First map()
        # must come from disk, not recompute — assert via store telemetry.
        svc2 = MappingService(store_path=path, batch_window_s=0.0)
        try:
            reps = 5
            warm_s = float("inf")
            for i in range(reps):
                t0 = time.time()
                res = svc2.map(gs[0], h, cfg)
                warm_s = min(warm_s, time.time() - t0)
                if i == 0:
                    assert svc2.stats()["store"]["hits"] == 1
                    first_restart_s = time.time() - t0
                # evict so every rep re-reads the disk tier, not the LRU
                svc2._cache.clear()
                svc2._by_graph.clear()
            assert res.stats["result_cache"]["hit"] is True
            emit(f"durability/store_hit_after_restart/rgg{n}", warm_s * 1e6,
                 f"speedup_vs_cold={cold_s/warm_s:.0f}x")
        finally:
            svc2.close()

        # persistence overhead on the compute path: distinct requests so
        # every one computes AND (with a store) encodes + fsync-renames.
        # Every rgg graph has its own padded M, hence its own jitted
        # programs — warm ALL of them first (result cache off, so the
        # timed bursts below still compute) or the first burst eats the
        # compiles and the comparison measures compilation, not writes.
        warm = MappingService(batch_window_s=0.0, cache_entries=0)
        try:
            for g in gs:
                warm.map(g, h, cfg)
        finally:
            warm.close()

        def _burst(store_path):
            kw = {"store_path": store_path} if store_path else {}
            s = MappingService(batch_window_s=0.0, **kw)
            try:
                t0 = time.time()
                for g in gs:
                    s.map(g, h, cfg)
                wall = time.time() - t0
                writes = s.stats()["store"]["writes"] if store_path else 0
            finally:
                s.close()
            return wall, writes

        nostore_s, _ = _burst(None)
        store_s, writes = _burst(f"{root}/store2")
        assert writes == R
        over = (store_s - nostore_s) / R
        emit(f"durability/persist_overhead/{R}x_rgg{n}", store_s * 1e6,
             f"per_write_overhead_us={over*1e6:.0f}")

        section.update({
            "instance": f"rgg{n}",
            "hierarchy": "x".join(map(str, h.a)),
            "cold_compute_s": cold_s,
            "lru_hit_s": lru_s,
            "store_hit_s": warm_s,
            "store_hit_first_restart_s": first_restart_s,
            "store_hit_speedup_vs_cold": cold_s / warm_s,
            "burst_requests": R,
            "burst_no_store_s": nostore_s,
            "burst_with_store_s": store_s,
            "per_write_overhead_s": over,
        })
    finally:
        shutil.rmtree(root, ignore_errors=True)


def bench_coarsen_kernels(scale: str, quick: bool):
    """Device-resident coarsening + the scan-fused v-cycle at scale (PR 9).

    Per instance: stage wall times for the ELL kernels (adjacency build,
    one coarsen level, the full cascade), per-level shrink from the
    O(1)-memory cascade, peak host RSS — and the headline number, the COLD
    path (compile + run, caches cleared) of a full partition call through
    the fused ``coarsen="ell"`` v-cycle vs the PR 8 unrolled
    ``coarsen="segment"`` path. Full runs add a 10^6-vertex cascade-only
    tier (the fused v-cycle's stacked uncoarsening arrays are the memory
    bound there; the cascade carries one graph).
    """
    import resource

    import jax
    from repro.core import graph as G
    from repro.core.coarsen import coarsen_cascade, coarsen_once
    from repro.core.graph import default_ell_deg, ell_adjacency
    from repro.core.multisection import clear_compile_cache
    from repro.core.partition import num_levels, partition_host

    section = BENCH["sections"].setdefault("coarsen_kernels", {})

    def rss_mb():
        return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0

    def cold(fn):
        jax.clear_caches()
        clear_compile_cache()
        t0 = time.time()
        jax.block_until_ready(fn())
        return time.time() - t0

    def warm(fn, reps=3):
        jax.block_until_ready(fn())  # ensure compiled
        best = float("inf")
        for _ in range(reps):
            t0 = time.time()
            jax.block_until_ready(fn())
            best = min(best, time.time() - t0)
        return best

    def levels_telemetry(g, lv, deg):
        ns, ms = coarsen_cascade(g, lv, ell_deg=deg)
        ns, ms = np.asarray(ns), np.asarray(ms)
        per, prev = [], int(g.n)
        for i in range(lv):
            per.append({"n": int(ns[i]), "m": int(ms[i]),
                        "shrink": round(prev / max(int(ns[i]), 1), 3)})
            prev = int(ns[i])
        return per

    side = 100 if quick else 317            # 10^4 / ~10^5 vertices
    insts = [(f"grid{side * side}", G.gen_grid(side))]
    if not quick:
        insts.append(("rgg100k", G.gen_rgg(100_000, seed=1)))
    for gname, g in insts:
        n, m = int(g.n), int(g.m)
        deg = default_ell_deg(n, m)
        lv = num_levels(n, 4)
        row = {"n": n, "m": m, "ell_deg": deg, "levels": lv}

        jf_ell = jax.jit(lambda gg: ell_adjacency(gg, deg)[0])
        t_ell = warm(lambda: jf_ell(g))
        emit(f"coarsen/{gname}/ell_build", t_ell * 1e6, f"deg={deg}")

        jf_once = jax.jit(lambda gg: coarsen_once(gg, salt=1, ell_deg=deg))
        t_once_c = cold(lambda: jf_once(g))
        t_once = warm(lambda: jf_once(g))
        emit(f"coarsen/{gname}/coarsen_once", t_once * 1e6,
             f"cold_s={t_once_c:.2f}")

        t_casc_c = cold(lambda: coarsen_cascade(g, lv, ell_deg=deg))
        t_casc = warm(lambda: coarsen_cascade(g, lv, ell_deg=deg))
        per = levels_telemetry(g, lv, deg)
        emit(f"coarsen/{gname}/cascade{lv}", t_casc * 1e6,
             f"cold_s={t_casc_c:.2f} shrink0={per[0]['shrink']:.2f} "
             f"coarsest_n={per[-1]['n']}")
        row.update({"ell_build_s": t_ell,
                    "coarsen_once_s": t_once,
                    "coarsen_once_cold_s": t_once_c,
                    "cascade_s": t_casc, "cascade_cold_s": t_casc_c,
                    "per_level": per})

        # headline: COLD fused ELL v-cycle vs the PR 8 unrolled segment path
        walls = {}
        for mode in ("ell", "segment"):
            t_c = cold(lambda: partition_host(g, 4, 0.03, "fast", salt=1,
                                              coarsen=mode))
            t_w = warm(lambda: partition_host(g, 4, 0.03, "fast", salt=1,
                                              coarsen=mode), reps=2)
            walls[mode] = {"cold_s": t_c, "warm_s": t_w}
            emit(f"coarsen/{gname}/partition_cold_{mode}", t_c * 1e6,
                 f"warm_s={t_w:.2f}")
        speedup = walls["segment"]["cold_s"] / walls["ell"]["cold_s"]
        emit(f"coarsen/{gname}/fused_cold_speedup",
             walls["ell"]["cold_s"] * 1e6, f"vs_segment={speedup:.2f}x")
        row["partition"] = walls
        row["fused_cold_speedup_vs_segment"] = speedup
        row["peak_rss_mb"] = rss_mb()
        section[gname] = row

    if not quick:
        # 10^6 tier: cascade only (O(1) memory in levels), within container RAM
        g6 = G.gen_grid(1000)
        n6, m6 = int(g6.n), int(g6.m)
        deg6 = default_ell_deg(n6, m6)
        lv6 = num_levels(n6, 4)
        t6_c = cold(lambda: coarsen_cascade(g6, lv6, ell_deg=deg6))
        t6 = warm(lambda: coarsen_cascade(g6, lv6, ell_deg=deg6), reps=2)
        per6 = levels_telemetry(g6, lv6, deg6)
        emit(f"coarsen/grid1000000/cascade{lv6}", t6 * 1e6,
             f"cold_s={t6_c:.2f} shrink0={per6[0]['shrink']:.2f} "
             f"coarsest_n={per6[-1]['n']} rss_mb={rss_mb():.0f}")
        section["grid1000000"] = {
            "n": n6, "m": m6, "ell_deg": deg6, "levels": lv6,
            "cascade_s": t6, "cascade_cold_s": t6_c, "per_level": per6,
            "peak_rss_mb": rss_mb(),
        }


def bench_model_graphs(scale: str, quick: bool):
    """The PR 10 closed loop: HLO → TaskGraph → shared_map on the physical
    chip hierarchy, for real model-zoo archs.

    Per arch: compile a tiny single-device train cell (abstract params),
    extract the per-op communication graph (``launch/comm_graph.py``), map
    it onto ``physical_hierarchy()`` (k=256), and compare ``evaluate_J``
    against the default program-order placement. Extraction and mapping
    walls are COLD (one-shot, compile-dominated) — the gateable cost of
    "map the model you're about to launch". The J improvement must be
    strict: this section doubles as the CI model-graph smoke.
    """
    from repro.core.api import SharedMapConfig, shared_map_direct
    from repro.core.mapping import evaluate_J
    from repro.launch.comm_graph import default_placement, model_comm_graph
    from repro.launch.mesh import physical_hierarchy

    archs = ["whisper-tiny"] if quick else ["whisper-tiny", "xlstm-125m"]
    h = physical_hierarchy(False)
    section = BENCH["sections"].setdefault("model_graphs", {})
    for arch in archs:
        t0 = time.time()
        tg = model_comm_graph(arch, min_tasks=2 * h.k)
        extract_cold_s = time.time() - t0
        g = tg.to_graph()
        t0 = time.time()
        res = shared_map_direct(g, h, SharedMapConfig(preset="fast"))
        map_cold_s = time.time() - t0
        j_def = evaluate_J(g, h, default_placement(tg.n, h.k))
        improvement = j_def / max(res.J, 1e-12)
        assert res.J < j_def, (
            f"{arch}: shared_map J={res.J} did not beat default placement "
            f"J={j_def} — the closed-loop contract is broken")
        emit(f"model_graphs/extract/{arch}", extract_cold_s * 1e6,
             f"tasks={tg.n} edges={tg.m} gran={tg.meta['granularity']}")
        emit(f"model_graphs/map/{arch}", map_cold_s * 1e6,
             f"J={res.J:.3g} J_default={j_def:.3g} "
             f"improvement={improvement:.2f}x")
        section[arch] = {
            "tasks": tg.n, "task_edges": tg.m,
            "granularity": tg.meta["granularity"],
            "fingerprint": tg.fingerprint().hex(),
            "extract_cold_s": extract_cold_s,
            "map_cold_s": map_cold_s,
            "J_sharedmap": res.J,
            "J_default": j_def,
            "improvement": improvement,
            "k": h.k,
        }


SECTIONS = {
    "quality_profiles": bench_quality_profiles,
    "thread_strategies": bench_thread_strategies,
    "presets": bench_presets,
    "scalability": bench_scalability,
    "mapping_vs_default": bench_mapping_vs_default,
    "refine_backends": bench_refine_backends,
    "kernels": bench_kernels,
    "serve": bench_serve,
    "serve_overload": bench_serve_overload,
    "device_pipeline": bench_device_pipeline,
    "durability": bench_durability,
    "coarsen_kernels": bench_coarsen_kernels,
    "model_graphs": bench_model_graphs,
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--scale", choices=["small", "large", "paper"], default="small")
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of: " + ",".join(SECTIONS))
    ap.add_argument("--out", default="BENCH_PR10.json",
                    help="telemetry JSON path ('' disables)")
    args = ap.parse_args()
    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    only = set(args.only.split(",")) if args.only else None
    if only and not only <= set(SECTIONS):
        ap.error(f"unknown sections: {sorted(only - set(SECTIONS))}")
    print("name,us_per_call,derived")
    rows_by_section: dict[str, list] = {}
    for name, fn in SECTIONS.items():
        if only and name not in only:
            continue
        print(f"# --- {name} ---", flush=True)
        t0 = time.time()
        row_mark = len(ROWS)
        fn(args.scale, args.quick)
        rows_by_section[name] = [
            {"name": n, "us": u, "derived": d} for n, u, d in ROWS[row_mark:]
        ]
        print(f"# {name} done in {time.time()-t0:.1f}s", flush=True)
        # each section compiles many (shape x k x preset) programs; drop the
        # jit caches so a long full run stays within host RAM, and the
        # multisection memo/telemetry with them (its compiled executables
        # live inside those jit caches, so hits after a clear would lie).
        import jax
        from repro.core.multisection import clear_compile_cache
        jax.clear_caches()
        clear_compile_cache()
    if args.out:
        # merge into an existing telemetry file: a partial --only run must
        # not wipe the other sections' trajectory data.
        merged = {"sections": {}}
        try:
            with open(args.out) as f:
                merged = json.load(f)
        except (OSError, json.JSONDecodeError):
            pass
        merged.setdefault("sections", {}).update(BENCH["sections"])
        merged["argv"] = sys.argv[1:]
        # rows are merged per section, like sections: a partial run only
        # replaces the rows of the sections it actually ran.
        rows = merged.setdefault("rows", {})
        if isinstance(rows, list):  # pre-merge flat format
            rows = merged["rows"] = {}
        rows.update(rows_by_section)
        with open(args.out, "w") as f:
            json.dump(merged, f, indent=2)
        print(f"# telemetry -> {args.out}", flush=True)


if __name__ == "__main__":
    main()
