"""Smoke run of the mapper on a TPU: the quickest proof that it starts.

Run from the root of a checkout:

    python chip_smoke.py             # one chip: phases a-e below
    python chip_smoke.py --chips 4   # only the worker pool, one per chip

One chip, one process:

a. device   the backend is ``tpu`` and the kernels run compiled (pallas)
b. kernels  each Pallas kernel against its XLA reference at N=100,000,
            bitwise
c. direct   ``shared_map`` maps rgg 10^5 onto 4:8:3 (eco, bucket)
d. device   the same request with ``strategy="device"``, at DEVICE_N
e. service  ``MappingService`` answers four concurrent requests

``--chips 4`` runs ``MappingService(workers=4)`` with one worker pinned to
each chip, and nothing else. This process starts no JAX backend until the
workers are gone.

Every phase prints one JSON line with its compile seconds and run seconds
kept apart and the device's ``peak_bytes_in_use``. A mapping is checked
for PE ids in range, balance, ``J`` against a numpy recomputation, and
``J`` against a random or program-order placement. The last line is
``{"ok": true, "device": {...}}``; any failed phase exits non-zero, and so
does a run that finds no TPU.
"""
from __future__ import annotations

import argparse
import functools
import json
import sys
import threading
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
try:
    import numpy as np

    from repro.compile_cache import use_compile_cache
    from repro.core import graph as G
    from repro.core.api import SharedMapConfig, shared_map
    from repro.core.hierarchy import Hierarchy
except ImportError as e:
    sys.exit(f"chip_smoke: the repro package was not found next to this "
             f"script ({e})")

N_KERNEL = 100_000
HIER = Hierarchy(a=(4, 8, 3), d=(1.0, 10.0, 100.0))   # k = 96
# strategy="device" pads every lane of every level to the root's shape.
# Its widest dispatch (level 3: 24 lanes of eco at (N, M) = (2^17, 2^20))
# fits the 16 GB of one v5e up to n = 2^17: memory_analysis() of that
# program, compiled for a described v5e, puts it at 14.5 GB. On the chip
# that size took 720-777 s to map, which leaves no room for the other
# phases in the smoke's 20 minutes, so phase d maps a smaller rgg.
DEVICE_N_FITS = 131_072
DEVICE_N = 16_384
CFG = SharedMapConfig(eps=0.03, preset="eco")
# the pool phase tests the process model, not quality: the cheapest preset
POOL_CFG = SharedMapConfig(eps=0.03, preset="fast")
POOL_WAIT_S = 600  # a worker that takes longer has hung: fail the phase


class Failed(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise Failed(what)


# --- compile time: union of JAX's trace/lower/compile spans ----------------

_SPANS: list[tuple[float, float]] = []
_SPAN_LOCK = threading.Lock()
_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")


def _on_span(event, start, end, **_):
    if event in _COMPILE_EVENTS:
        with _SPAN_LOCK:
            _SPANS.append((start, end))


def compile_seconds(t0: float, t1: float) -> float:
    """Wall seconds in [t0, t1] covered by at least one compile span
    (nested and concurrent spans counted once)."""
    with _SPAN_LOCK:
        spans = sorted((max(a, t0), min(b, t1)) for a, b in _SPANS
                       if b > t0 and a < t1)
    total, end = 0.0, t0
    for a, b in spans:
        if b > end:
            total += b - max(a, end)
            end = b
    return total


def peak_bytes() -> int | None:
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def run_phase(name: str, fn, results: dict) -> None:
    """Run one phase, print its line, record pass/fail."""
    t0 = time.time()
    line = {"phase": name}
    try:
        line.update(fn() or {})
        line["ok"] = True
    except Exception as e:  # noqa: BLE001 — every failure fails the phase
        line["ok"] = False
        line["error"] = f"{type(e).__name__}: {e}"[:2000]
    t1 = time.time()
    comp = compile_seconds(t0, t1)
    line["compile_s"] = comp
    line["run_s"] = (t1 - t0) - comp
    if name != "pool":
        line["peak_bytes_in_use"] = peak_bytes()
    print(json.dumps(line), flush=True)
    results[name] = line["ok"]


# --- the plain reference ---------------------------------------------------

def numpy_J(rows, cols, ewgt, pe_of, h: Hierarchy) -> float:
    """J = sum over undirected edges of w * D(pe_u, pe_v), from directed
    edge arrays (each edge stored twice), in float64."""
    pu = np.asarray(pe_of, np.int64)[np.asarray(rows)]
    pv = np.asarray(pe_of, np.int64)[np.asarray(cols)]
    below = np.cumprod((1,) + tuple(h.a[:-1]))
    lvl = sum((pu // b != pv // b).astype(np.int64) for b in below)
    dist = np.concatenate([[0.0], np.asarray(h.d, np.float64)])[lvl]
    return float(np.sum(np.asarray(ewgt, np.float64) * dist) / 2.0)


def real_edges(g):
    n, m = int(g.n), int(g.m)
    return (np.asarray(g.rows)[:m], np.asarray(g.cols)[:m],
            np.asarray(g.ewgt)[:m], np.asarray(g.vwgt)[:n])


def check_mapping(g, h: Hierarchy, pe_of, J: float, eps: float,
                  balance: bool = True, baseline: str = "random") -> dict:
    """The checks of phase c; returns the numbers they compared."""
    rows, cols, ewgt, vwgt = real_edges(g)
    n, k = vwgt.shape[0], h.k
    pe_of = np.asarray(pe_of)
    check(pe_of.shape == (n,), f"pe_of has shape {pe_of.shape}, not ({n},)")
    check(int(pe_of.min()) >= 0 and int(pe_of.max()) < k, "pe_of out of [0, k)")
    ref = numpy_J(rows, cols, ewgt, pe_of, h)
    check(abs(J - ref) <= 1e-5 * max(abs(ref), 1.0),
          f"J={J} but the numpy reference gives {ref}")
    out = {"n": n, "k": k, "J": J, "J_numpy": ref}
    if balance:
        load = np.bincount(pe_of, weights=vwgt, minlength=k)
        out["max_load_over_avg"] = float(load.max() * k / vwgt.sum())
        check(load.max() <= (1 + eps) * vwgt.sum() / k + 1e-6,
              f"largest PE load {load.max()} > {(1 + eps) * vwgt.sum() / k}")
    if baseline == "random":
        other = np.random.default_rng(0).integers(0, k, n)
        limit = 0.3
    else:
        from repro.launch.comm_graph import default_placement
        other = default_placement(n, k)
        limit = 1.0
    J_base = numpy_J(rows, cols, ewgt, other, h)
    out[f"J_{baseline}"] = J_base
    check(J < limit * J_base, f"J={J} is not below {limit} x {baseline} "
                              f"placement's {J_base}")
    return out


# --- phases ------------------------------------------------------------------

def phase_device() -> dict:
    import jax

    from repro.kernels import ops
    backend = jax.default_backend()
    if backend != "tpu":
        raise Failed(f"no TPU found: JAX's backend is {backend!r}")
    kb = ops.kernel_backend()
    check(kb == "pallas", f"kernel backend is {kb!r}, not 'pallas'")
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices()), "kernel_backend": kb,
            "bytes_limit": (d.memory_stats() or {}).get("bytes_limit")}


def phase_kernels(n: int = N_KERNEL, k: int = 16) -> dict:
    """Each kernel compiled against its XLA reference, outputs bitwise."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops, ref
    from repro.kernels.mapcost import mapcost_pallas

    g = G.gen_rgg(n, seed=1)
    deg = G.default_ell_deg(int(g.n), int(g.m))
    adj, adw, _ = G.ell_adjacency(g, deg)
    rng = np.random.default_rng(0)
    part = jnp.asarray(rng.integers(0, k, g.N), jnp.int32)
    jit_ = jnp.asarray(rng.random(adj.shape), jnp.float32)
    matched = jnp.asarray(rng.integers(0, 2, g.N), jnp.int32)
    cand = rng.integers(0, g.N + 1, (g.N, 2 * deg))
    candw = np.where(cand < g.N, rng.random(cand.shape), 0.0)
    cand, candw = jnp.asarray(cand, jnp.int32), jnp.asarray(candw, jnp.float32)
    interpret = ops.dispatch()[1]  # False on a TPU: phase a checked it

    def same(a, b) -> bool:
        la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
        return len(la) == len(lb) and all(
            np.asarray(x).dtype == np.asarray(y).dtype
            and np.asarray(x).tobytes() == np.asarray(y).tobytes()
            for x, y in zip(la, lb))

    def jitted(fn, *args, **kw):
        """Kernel (p=True) or reference (p=False), each one program, as
        the main path runs them inside its jitted partition."""
        return lambda p: jax.jit(functools.partial(fn, use_pallas=p,
                                                   **kw))(*args)

    cases = {
        "lp_gain": jitted(ops.lp_gain, adj, adw, part, k=k),
        "hem_propose": jitted(ops.hem_propose, adj, adw, jit_, matched),
        "contract_edges": jitted(ops.contract_edges, cand, candw),
    }
    for h in (HIER, Hierarchy(a=(16, 16), d=(1.0, 10.0))):
        pe = jnp.asarray(rng.integers(0, h.k, g.N), jnp.int32)
        gb = jnp.asarray((1,) + h.strides[:-1], jnp.int32)
        dv = jnp.asarray(h.d, jnp.float32)
        args = (g.rows, g.cols, g.ewgt, pe, gb, dv)
        cases[f"mapcost_partials_k{h.k}"] = (
            lambda p, a=args: mapcost_pallas(*a, interpret=interpret) if p
            else jax.jit(ref.mapcost_partials_ref)(*a))
        # J as evaluate_J computes it: partials, then one shared fold
        cases[f"mapcost_k{h.k}"] = (
            lambda p, a=args: ops.mapcost(*a, use_pallas=p))
    out = {"n": n, "ell_deg": deg, "k": k, "interpret": interpret}
    for name, fn in cases.items():
        a = jax.block_until_ready(fn(True))
        b = jax.block_until_ready(fn(False))
        out[name] = "bitwise" if same(a, b) else "DIFFERS"
    bad = [name for name, v in out.items() if v == "DIFFERS"]
    check(not bad, f"kernel output differs from its reference: {bad}")
    return out


def timed_map(g, h, cfg) -> tuple:
    import jax
    t = time.time()
    res = shared_map(g, h, cfg)
    jax.block_until_ready(res.pe_of)
    return res, time.time() - t


def phase_direct(state: dict, n: int = 100_000) -> dict:
    g = G.gen_rgg(n, seed=1)
    res, wall = timed_map(g, HIER, CFG)
    state["rgg"] = (g, res)
    out = check_mapping(g, HIER, res.pe_of, res.J, CFG.eps)
    out["map_s"] = wall
    return out


def phase_device_strategy(n: int = DEVICE_N) -> dict:
    g = G.gen_rgg(n, seed=1)
    cfg = SharedMapConfig(eps=CFG.eps, preset=CFG.preset, strategy="device")
    res, wall = timed_map(g, HIER, cfg)
    out = check_mapping(g, HIER, res.pe_of, res.J, cfg.eps)
    out["map_s"] = wall
    out["n_fits_hbm"] = DEVICE_N_FITS
    return out


def phase_service(state: dict, grid_side: int = 317,
                  model_arch: str = "whisper-tiny") -> dict:
    """Four concurrent requests; the rgg request must hit the cache."""
    from repro.launch.comm_graph import model_comm_graph
    from repro.launch.mesh import physical_hierarchy
    from repro.serve.mapper import MappingService

    g_rgg, direct = state["rgg"]
    phys = physical_hierarchy()
    grid = G.gen_grid(grid_side)
    rgg2 = G.gen_rgg(int(g_rgg.n), seed=2)
    model = model_comm_graph(model_arch, min_tasks=2 * phys.k)
    svc = MappingService(workers=0, degrade_on_failure=False)
    try:
        first = svc.map(g_rgg, HIER, CFG)
        check(np.array_equal(first.pe_of, direct.pe_of) and first.J == direct.J,
              "the service's rgg mapping differs from the direct one")
        reqs = [("rgg_again", g_rgg, HIER), ("grid", grid, phys),
                ("rgg_seed2", rgg2, phys), (model_arch, model, phys)]
        t = time.time()
        futs = svc.submit_many([(g, h, CFG) for _, g, h in reqs])
        res = [f.result() for f in futs]
        wall = time.time() - t
        stats = svc.stats()
    finally:
        svc.close()
    out = {"burst_s": wall, "coalesce": stats["coalesce"]}
    for (name, g, h), r in zip(reqs, res):
        check(r.stats["degradation"]["level"] == 0,
              f"{name} was degraded: {r.stats['degradation']}")
        is_model = name == model_arch
        gg = g.to_graph() if is_model else g
        out[name] = check_mapping(gg, h, r.pe_of, r.J, CFG.eps,
                                  balance=not is_model,
                                  baseline="program_order" if is_model
                                  else "random")
        out[name]["cache_hit"] = r.stats["result_cache"]["hit"]
    hit = res[0]
    check(hit.stats["result_cache"]["hit"], "the repeated rgg request missed")
    check(np.array_equal(hit.pe_of, direct.pe_of) and hit.J == direct.J,
          "the cached rgg answer differs from phase c")
    check(not stats["shadow"]["device_quarantined"], "device quarantined")
    co = stats["coalesce"]
    check(co["groups"] > co["dispatches"],
          "no dispatch carried the groups of two requests")
    return out


def phase_pool(state: dict, n: int = 100_000, chips: int = 4) -> dict:
    """``chips`` workers, one per chip; 4 graphs, each mapped twice."""
    from repro.serve.mapper import MappingService

    side = int(round(n ** 0.5))
    graphs = {f"rgg_seed{s}": G.gen_rgg(n, seed=s, host=True) for s in (1, 2)}
    graphs.update({f"grid{s}": G.gen_grid(s, host=True)
                   for s in (side, side + 1)})
    names = list(graphs)
    svc = MappingService(workers=chips, cache_entries=0,
                         degrade_on_failure=False)
    try:
        check(svc.supervisor.platform == "tpu",
              f"no TPU found: workers would run on {svc.supervisor.platform}")
        rounds = []
        for order in (names, names[::-1]):  # second round: other workers
            futs = svc.submit_many([(graphs[x], HIER, POOL_CFG)
                                    for x in order])
            rounds.append(dict(zip(order, (f.result(timeout=POOL_WAIT_S)
                                           for f in futs))))
    finally:
        svc.close()
    out, chips_seen = {}, set()
    for name in names:
        a, b = rounds[0][name], rounds[1][name]
        check(np.array_equal(a.pe_of, b.pe_of) and a.J == b.J,
              f"the two mappings of {name} differ")
        for r in (a, b):
            dev = r.stats["device"]
            check(dev["platform"] == "tpu", f"{name} ran on {dev['platform']}")
            chips_seen.add((dev["visible_chips"], tuple(dev["coords"])))
        out[name] = check_mapping(graphs[name], HIER, a.pe_of, a.J,
                                  POOL_CFG.eps)
        out[name]["devices"] = [r.stats["device"] for r in (a, b)]
    check(len(chips_seen) == chips,
          f"workers reported {len(chips_seen)} distinct chips, not {chips}")
    from jax._src import xla_bridge
    check(not xla_bridge.backends_are_initialized(),
          "this process started a JAX backend while the workers ran")
    state["kind"] = out[names[0]]["devices"][0]["kind"]
    return out


def device_line() -> dict:
    import jax
    d = jax.devices()
    return {"platform": d[0].platform, "kind": d[0].device_kind,
            "count": len(d)}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the worker pool, one worker per chip")
    args = ap.parse_args()
    if args.chips == 1:
        import jax
        if jax.default_backend() != "tpu":
            print(f"chip_smoke: no TPU found (JAX's backend is "
                  f"{jax.default_backend()!r})", file=sys.stderr)
            return 1
    print(json.dumps({"compile_cache": use_compile_cache()}), flush=True)
    from jax import monitoring
    monitoring.register_event_time_span_listener(_on_span)

    results: dict[str, bool] = {}
    state: dict = {}
    if args.chips == 4:
        run_phase("pool", lambda: phase_pool(state), results)
        if not results["pool"]:
            return 1
        dev = device_line()
        if dev["platform"] != "tpu":
            print("chip_smoke: no TPU found", file=sys.stderr)
            return 1
    else:
        run_phase("device", phase_device, results)
        if not results["device"]:
            print("chip_smoke: no TPU found, or the kernels would not run "
                  "compiled", file=sys.stderr)
            return 1
        run_phase("kernels", phase_kernels, results)
        run_phase("direct", lambda: phase_direct(state), results)
        run_phase("device_strategy", phase_device_strategy, results)
        if results["direct"]:
            run_phase("service", lambda: phase_service(state), results)
        else:
            results["service"] = False
        dev = device_line()
    failed = [name for name, ok in results.items() if not ok]
    if failed:
        print(f"chip_smoke: failed phases: {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
