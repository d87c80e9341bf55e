"""Multilevel k-way partitioner (the KaFFPa/Mt-KaHyPar substrate, in JAX).

V-cycle: HEM-coarsen until the graph is small, greedy-grow an initial
k-way partition, project back up with LP refinement + rebalance per level.
Presets FAST/ECO/STRONG trade rounds/restarts for quality; restarts are
vectorized with `vmap` over salts (the TPU-native analogue of KaFFPa's
repeated runs) and the best balanced partition wins.

The whole pipeline is static-shape: one compiled program per
(N, M, k, levels, preset), reused across all subgraphs of a hierarchy level
and `vmap`-able for the LAYER/BUCKET scheduling strategies.
"""
from __future__ import annotations

import dataclasses
import functools
import math
import threading
from typing import Callable

import jax
import jax.numpy as jnp

from .coarsen import coarsen_once
from .graph import Graph, block_weights, default_ell_deg, edge_cut
from .initial import initial_partition
from .refine import lp_refine, rebalance


@dataclasses.dataclass(frozen=True)
class Preset:
    name: str
    refine_rounds: int      # LP rounds per uncoarsening level
    coarsest_polish: int    # LP rounds on the coarsest graph
    restarts: int           # vmapped seeded restarts
    vcycles: int            # extra refine-only cycles at the finest level

    @staticmethod
    def get(name: str) -> "Preset":
        return _PRESETS[name.lower()]


_PRESETS = {
    "fast": Preset("fast", refine_rounds=2, coarsest_polish=4, restarts=1, vcycles=0),
    "eco": Preset("eco", refine_rounds=4, coarsest_polish=8, restarts=2, vcycles=1),
    "strong": Preset("strong", refine_rounds=8, coarsest_polish=12, restarts=4, vcycles=2),
}


def num_levels(n: int, k: int, coarse_factor: int = 24,
               max_degree: int | None = None) -> int:
    """Static coarsening depth: HEM shrinks ~1.6x/level; stop near 24*k.

    ``max_degree`` (when the caller has a host graph to measure it on)
    guards against matching stalls: a degree-``d`` hub serializes its
    whole neighbourhood behind one matching edge, so at most
    ``n - max_degree`` pairs can form per level. On star-like graphs the
    implied shrink collapses toward 1x — deeper levels would barely
    shrink, so we STOP at one level; on merely hub-heavy graphs the
    shrink lands between 1x and 1.6x and the depth is EXTENDED (capped)
    so the coarsest graph still approaches the target size.
    """
    target = max(coarse_factor * k, 64)
    if n <= target:
        return 0
    base = max(1, math.ceil(math.log(n / target) / math.log(1.6)))
    if max_degree is None:
        return base
    pairs = max(1, min(n // 2, n - int(max_degree)))
    shrink = n / max(1.0, n - pairs)
    if shrink < 1.15:
        return 1  # stalled: coarsening cannot help, don't pay for depth
    shrink = min(1.6, shrink)
    lv = math.ceil(math.log(n / target) / math.log(shrink))
    return max(1, min(lv, 2 * base + 4))


def _partition_single(
    g: Graph, k: int, eps: jax.Array, levels: int, preset: Preset, salt: jax.Array,
    backend: str = "auto", ell_deg: int | None = None, coarsen: str = "ell",
) -> tuple[jax.Array, jax.Array]:
    """One seeded multilevel run; all shapes stay (N, M). Returns the
    partition and the ``[levels]`` i32 vertex counts of the coarse graphs,
    level 1 first.

    The stages run under ``jax.named_scope``s (``coarsen``, ``initial``,
    ``refine``), so every op's ``op_name`` metadata, and with it a profiler
    trace, says which stage it belongs to.

    ``coarsen="ell"`` (default) is the fused v-cycle: coarsening runs
    through the ELL kernels and both the downward (coarsen) and upward
    (project + refine) level loops are ``lax.scan``s over stacked
    same-shape graphs — ONE compiled loop body per (N, M, k, preset)
    regardless of depth, instead of ``levels`` unrolled copies. That
    removes the per-level retrace/compile cost that dominated the cold
    path at 10^5+ vertices. ``coarsen="segment"`` keeps the seed's
    unrolled segment-reduction path (the PR 8 baseline, and the bench
    comparison mode).
    """
    total = g.total_weight()
    Lmax = (1.0 + eps) * total / k

    def initial(coarsest):
        with jax.named_scope("initial"):
            return initial_partition(
                coarsest, k, Lmax, salt=salt,
                polish_rounds=preset.coarsest_polish,
                backend=backend, ell_deg=ell_deg)

    if levels == 0:
        sizes = jnp.zeros((0,), jnp.int32)
        part = initial(g)
    elif coarsen == "segment":
        graphs = [g]
        maps = []
        cur = g
        with jax.named_scope("coarsen"):
            for lvl in range(levels):
                cur, newid = coarsen_once(cur, salt=(lvl + 1) * 131 + 7)
                graphs.append(cur)
                maps.append(newid)
            sizes = jnp.stack([c.n for c in graphs[1:]]).astype(jnp.int32)
        part = initial(graphs[-1])
        with jax.named_scope("refine"):
            for lvl in range(levels - 1, -1, -1):
                part = part[maps[lvl]]  # project to finer level
                part = lp_refine(
                    graphs[lvl], part, k, Lmax, rounds=preset.refine_rounds,
                    salt=salt + 1000 + lvl, backend=backend, ell_deg=ell_deg,
                )
                part = rebalance(graphs[lvl], part, k, Lmax, rounds=4,
                                 salt=salt + 2000 + lvl, backend=backend,
                                 ell_deg=ell_deg)
    else:
        # static DEG cap for the coarsening kernels; reuse the refinement
        # cap when the ELL refinement backend pinned one
        deg_c = ell_deg if ell_deg is not None else default_ell_deg(g.N, g.M)

        def down(cur, sl):
            gc, newid = coarsen_once(cur, salt=sl, ell_deg=deg_c)
            # emit the FINE graph of this level and the coarse size
            return gc, (cur, newid, gc.n.astype(jnp.int32))

        with jax.named_scope("coarsen"):
            csalts = (jnp.arange(levels, dtype=jnp.int32) + 1) * 131 + 7
            coarsest, (fines, maps, sizes) = jax.lax.scan(down, g, csalts)
        part = initial(coarsest)

        def up(part, x):
            gf, mp, lvl = x
            part = part[mp]  # project to finer level
            part = lp_refine(gf, part, k, Lmax, rounds=preset.refine_rounds,
                             salt=salt + 1000 + lvl, backend=backend,
                             ell_deg=ell_deg)
            part = rebalance(gf, part, k, Lmax, rounds=4,
                             salt=salt + 2000 + lvl, backend=backend,
                             ell_deg=ell_deg)
            return part, None

        with jax.named_scope("refine"):
            lvls = jnp.arange(levels, dtype=jnp.int32)
            part, _ = jax.lax.scan(up, part, (fines, maps, lvls),
                                   reverse=True)

    with jax.named_scope("refine"):
        for cyc in range(preset.vcycles):
            part = lp_refine(g, part, k, Lmax, rounds=preset.refine_rounds,
                             salt=salt + 3000 + cyc, backend=backend,
                             ell_deg=ell_deg)
            part = rebalance(g, part, k, Lmax, rounds=4,
                             salt=salt + 4000 + cyc, backend=backend,
                             ell_deg=ell_deg)
    return part, sizes


def _best_of_restarts(g: Graph, k: int, eps: jax.Array, levels: int,
                      preset_name: str, salt, backend: str,
                      ell_deg: int | None,
                      coarsen: str) -> tuple[jax.Array, jax.Array]:
    """:func:`partition`'s body: the best restart's partition and the
    ``[levels]`` coarse vertex counts of its v-cycle."""
    preset = Preset.get(preset_name)
    salt = jnp.asarray(salt, jnp.int32)
    if k == 1:
        return jnp.zeros((g.N,), jnp.int32), jnp.zeros((levels,), jnp.int32)

    salts = salt * 131 + jnp.arange(preset.restarts, dtype=jnp.int32) * 7919

    def run(s):
        p, sizes = _partition_single(g, k, eps, levels, preset, s, backend,
                                     ell_deg, coarsen)
        with jax.named_scope("select"):
            cut = edge_cut(g, p)
            Lmax = (1.0 + eps) * g.total_weight() / k
            over = jnp.maximum(block_weights(g, p, k) - Lmax, 0.0).sum()
            return p, sizes, cut + 1e6 * over

    parts, sizes, scores = jax.vmap(run)(salts)
    with jax.named_scope("select"):
        best = jnp.argmin(scores)
        return parts[best], sizes[best]


@functools.partial(
    jax.jit,
    static_argnames=("k", "levels", "preset_name", "backend", "ell_deg", "coarsen"),
)
def partition(
    g: Graph,
    k: int,
    eps: jax.Array,
    levels: int,
    preset_name: str = "eco",
    salt: int | jax.Array = 0,
    backend: str = "auto",
    ell_deg: int | None = None,
    coarsen: str = "ell",
) -> jax.Array:
    """Balanced k-way partition of ``g`` minimizing edge-cut.

    Restarts run vectorized over salts; the winner is the best *balanced*
    partition by edge-cut (unbalanced runs are heavily penalized).
    ``ell_deg`` (static) pins the ELL degree cap for the kernel-backed
    refinement; pass one computed from the REAL vertex/edge counts (pow2
    padding skews the in-jit default by up to 2x; see core/refine.py).
    ``coarsen`` selects the coarsening implementation: ``"ell"`` (default)
    is the fused kernel v-cycle, ``"segment"`` the seed's unrolled
    segment-reduction path (see ``_partition_single``).
    """
    return _best_of_restarts(g, k, eps, levels, preset_name, salt, backend,
                             ell_deg, coarsen)[0]


class BatchedPartition:
    """A jitted vmapped partition: calling it gives the ``[B, N]`` parts;
    :meth:`with_level_sizes` also gives the ``[B, levels]`` i32 vertex
    counts of each lane's coarse graphs (the winning restart's), from the
    same program and without a transfer."""

    def __init__(self, fn: Callable):
        self._fn = fn

    def __call__(self, gs: Graph, eps: jax.Array, salts: jax.Array):
        return self._fn(gs, eps, salts)[0]

    def with_level_sizes(self, gs: Graph, eps: jax.Array, salts: jax.Array):
        return self._fn(gs, eps, salts)


_BATCHED_CACHE: dict[tuple, BatchedPartition] = {}
_BATCHED_LOCK = threading.Lock()


def batched_partition(k: int, levels: int, preset: str, backend: str,
                      ell_deg: int | None,
                      coarsen: str = "ell") -> BatchedPartition:
    """Memoized jitted vmapped partition callable ``(gs, eps, salts) ->
    [B, N] parts`` — the dispatch unit of every bucket/layer/device-level
    partition call (one executable per static key, shared process-wide
    across hierarchy levels, strategies and requests).

    Lives here (not in multisection) so every consumer of batched
    partitions — the level planner, the device-resident loop, external
    tools — shares one memo. The memoized jitted wrapper hits jit's C++
    fast path on repeat calls with the same shapes (an AOT
    ``.lower().compile()`` executable measured SLOWER: its Python
    ``Compiled.__call__`` costs more than jit dispatch).

    The key includes the process-wide kernel backend (REPRO_KERNEL_BACKEND):
    coarsening + refinement dispatch through kernels/ops at TRACE time, so
    a memoized callable is only valid for the backend it traced under
    (the backend-invariance tests flip the env between calls).
    """
    from ..kernels import ops as kops
    key = (k, levels, preset, backend, ell_deg, coarsen, kops.kernel_backend())
    with _BATCHED_LOCK:
        fn = _BATCHED_CACHE.get(key)
        if fn is None:
            fn = BatchedPartition(jax.jit(lambda gs, ee, ss: jax.vmap(
                lambda g1, e1, s1: _best_of_restarts(
                    g1, k, e1, levels, preset, s1, backend, ell_deg, coarsen)
            )(gs, ee, ss)))
            _BATCHED_CACHE[key] = fn
    return fn


def clear_batched_partition_cache() -> None:
    with _BATCHED_LOCK:
        _BATCHED_CACHE.clear()


def partition_host(g: Graph, k: int, eps: float, preset: str = "eco", salt: int = 0,
                   backend: str = "auto", coarsen: str = "ell") -> jax.Array:
    """Convenience wrapper choosing level count + ELL degree cap from the
    REAL sizes (not the padded shapes); with a host graph in hand it also
    measures the max degree so ``num_levels`` can detect matching stalls
    (star-like graphs) and size the cascade accordingly."""
    import numpy as np
    from .refine import resolve_backend
    n = int(g.n)
    ind = np.asarray(g.indptr)
    maxdeg = int((ind[1:n + 1] - ind[:n]).max()) if n > 0 else 0
    lv = num_levels(n, k, max_degree=maxdeg)
    deg = (default_ell_deg(int(g.n), int(g.m))
           if resolve_backend(backend) == "ell" else None)
    return partition(g, k, jnp.float32(eps), lv, preset, salt, backend, deg,
                     coarsen)
