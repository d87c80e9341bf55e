"""Multilevel k-way partitioner (the KaFFPa/Mt-KaHyPar substrate, in JAX).

V-cycle: HEM-coarsen until the graph is small, greedy-grow an initial
k-way partition, project back up with LP refinement + rebalance per level.
Presets FAST/ECO/STRONG trade rounds/restarts for quality; restarts are
vectorized with `vmap` over salts (the TPU-native analogue of KaFFPa's
repeated runs) and the best balanced partition wins.

The whole pipeline is static-shape: one compiled program per
(N, M, k, levels, preset), reused across all subgraphs of a hierarchy level
and batched over them for the LAYER/BUCKET scheduling strategies. Every
level runs at the fine graph's (N, M) except the initial partition, which
runs on the coarsest graphs cut to a smaller static width wherever they
all fit it (``initial_width``).
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
from .graph import (Graph, block_weights, default_ell_deg, edge_cut,
                    repad_device)
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


def _coarsening_target(k: int, coarse_factor: int = 24) -> int:
    """The vertex count the v-cycle coarsens toward for a ``k``-way cut."""
    return max(coarse_factor * k, 64)


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
    target = _coarsening_target(k, coarse_factor)
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


def initial_width(N: int, M: int, k: int, levels: int) -> tuple[int, int]:
    """Static ``(Nc, Mc)`` the initial partition of a coarsest graph runs
    at when every lane's coarsest graph fits: four times the coarsening
    target rounded up to a power of two, and four edge slots per vertex
    slot. ``(N, M)`` where nothing is coarsened or nothing would shrink."""
    Nc = 1 << (4 * _coarsening_target(k) - 1).bit_length()
    if levels == 0 or Nc >= N:
        return N, M
    return Nc, min(M, 4 * Nc)


def _coarsen_down(g: Graph, levels: int, coarsen: str, deg: int):
    """The down scan of one lane: its coarsest graph, what the up scan
    reads (each level's fine graph and fine-to-coarse map) and the
    ``[levels]`` i32 vertex counts of the coarse graphs, level 1 first.
    It reads no restart salt, so the restarts share it."""
    if levels == 0:
        return g, None, jnp.zeros((0,), jnp.int32)
    if coarsen == "segment":
        graphs, maps = [g], []
        for lvl in range(levels):
            cur, newid = coarsen_once(graphs[-1], salt=(lvl + 1) * 131 + 7)
            graphs.append(cur)
            maps.append(newid)
        sizes = jnp.stack([c.n for c in graphs[1:]]).astype(jnp.int32)
        return graphs[-1], (graphs[:-1], maps), sizes

    def down(cur, sl):
        gc, newid = coarsen_once(cur, salt=sl, ell_deg=deg)
        # emit the FINE graph of this level and the coarse size
        return gc, (cur, newid, gc.n.astype(jnp.int32))

    csalts = (jnp.arange(levels, dtype=jnp.int32) + 1) * 131 + 7
    coarsest, (fines, maps, sizes) = jax.lax.scan(down, g, csalts)
    return coarsest, (fines, maps), sizes


def _initial_lanes(coarsest: Graph, Lmax: jax.Array, salts: jax.Array,
                   k: int, preset: Preset, backend: str, deg: int,
                   width: tuple[int, int]) -> jax.Array:
    """Initial partition of each lane's coarsest graph for each restart
    salt (``salts`` ``[B, R]``), cut to the static ``width``; the
    ``[B, R, N]`` parts, 0 on the padding as at full width. Real vertices
    and edges are a prefix of a coarse graph's arrays, so a lane whose
    coarsest graph fits ``width`` gets the parts it gets at ``(N, M)``."""
    N, M = coarsest.vwgt.shape[-1], coarsest.rows.shape[-1]
    Nc, Mc = width

    def one(c, L, s):
        if (Nc, Mc) != (N, M):
            c = repad_device(c, Nc, Mc)
        p = initial_partition(c, k, L, salt=s,
                              polish_rounds=preset.coarsest_polish,
                              backend=backend, ell_deg=deg)
        return jnp.pad(p, (0, N - Nc))

    return jax.vmap(jax.vmap(one, in_axes=(None, None, 0)))(
        coarsest, Lmax, salts)


def _refine_up(g: Graph, trail, part: jax.Array, k: int, Lmax: jax.Array,
               levels: int, preset: Preset, salt: jax.Array, backend: str,
               ell_deg: int | None, coarsen: str) -> jax.Array:
    """Project one restart's initial partition up through the levels with
    LP refinement and rebalance, then the preset's finest-level v-cycles."""
    def refine(gf, part, lvl):
        part = lp_refine(gf, part, k, Lmax, rounds=preset.refine_rounds,
                         salt=salt + 1000 + lvl, backend=backend,
                         ell_deg=ell_deg)
        return rebalance(gf, part, k, Lmax, rounds=4, salt=salt + 2000 + lvl,
                         backend=backend, ell_deg=ell_deg)

    if levels and coarsen == "segment":
        graphs, maps = trail
        for lvl in range(levels - 1, -1, -1):
            part = refine(graphs[lvl], part[maps[lvl]], lvl)
    elif levels:
        def up(part, x):
            gf, mp, lvl = x
            return refine(gf, part[mp], lvl), None  # project, then refine

        fines, maps = trail
        lvls = jnp.arange(levels, dtype=jnp.int32)
        part, _ = jax.lax.scan(up, part, (fines, maps, lvls), reverse=True)
    for cyc in range(preset.vcycles):
        part = lp_refine(g, part, k, Lmax, rounds=preset.refine_rounds,
                         salt=salt + 3000 + cyc, backend=backend,
                         ell_deg=ell_deg)
        part = rebalance(g, part, k, Lmax, rounds=4, salt=salt + 4000 + cyc,
                         backend=backend, ell_deg=ell_deg)
    return part


def _partition_lanes(gs: Graph, eps: jax.Array, salts: jax.Array, k: int,
                     levels: int, preset_name: str, backend: str,
                     ell_deg: int | None, coarsen: str,
                     width: tuple[int, int] | None = None):
    """The body of :func:`partition` and :func:`batched_partition`: each
    of the ``B`` lanes of the stacked ``gs`` partitioned with its ``eps``
    and ``salt``, the best of the preset's restarts kept. Returns the
    ``[B, N]`` parts, the ``[B, levels]`` i32 vertex counts of each lane's
    coarse graphs (level 1 first) and an i32 scalar, 1 where the initial
    partition ran cut to :func:`initial_width` (``width`` overrides it).

    Three phases, each vmapped over the lanes (and over the restarts where
    it reads the restart's salt), run under ``jax.named_scope``s, so each
    op's ``op_name`` names its stage:

    1. ``coarsen``: the down scan. ``coarsen="ell"`` (default) is the
       fused v-cycle: ELL kernels, and both level loops ``lax.scan``s over
       stacked same-shape graphs, ONE compiled loop body per (N, M, k,
       preset) whatever the depth. ``"segment"`` keeps the seed's unrolled
       segment-reduction path (the baseline of the fused one).
    2. ``initial``: greedy growth and polish on the coarsest graphs. When
       every lane's coarsest graph fits ``(Nc, Mc)`` it runs cut to that
       width, else at ``(N, M)``: one ``lax.cond`` on a predicate over the
       whole dispatch, formed outside the vmaps (a ``cond`` on a per-lane
       predicate under ``vmap`` lowers to ``select`` and runs both).
    3. ``refine`` (project, LP, rebalance per level; finest v-cycles) and
       ``select`` (each restart's cut and overload, the best kept).
    """
    preset = Preset.get(preset_name)
    B, N = gs.vwgt.shape
    M = gs.rows.shape[-1]
    if k == 1:
        return (jnp.zeros((B, N), jnp.int32),
                jnp.zeros((B, levels), jnp.int32), jnp.int32(0))
    rsalts = (salts[:, None] * 131
              + jnp.arange(preset.restarts, dtype=jnp.int32) * 7919)
    Lmax = jax.vmap(lambda g, e: (1.0 + e) * g.total_weight() / k)(gs, eps)
    # static DEG cap for the ELL kernels: the refinement cap when the
    # caller pinned one, else the fine graph's (also at a cut width)
    deg = ell_deg if ell_deg is not None else default_ell_deg(N, M)

    with jax.named_scope("coarsen"):
        coarsest, trail, sizes = jax.vmap(
            lambda g: _coarsen_down(g, levels, coarsen, deg))(gs)

    Nc, Mc = initial_width(N, M, k, levels) if width is None else width
    initial = functools.partial(_initial_lanes, k=k, preset=preset,
                                backend=backend, deg=deg)
    with jax.named_scope("initial"):
        if Nc < N:
            fits = jnp.all((coarsest.n <= Nc) & (coarsest.m <= Mc))
            part = jax.lax.cond(
                fits, functools.partial(initial, width=(Nc, Mc)),
                functools.partial(initial, width=(N, M)),
                coarsest, Lmax, rsalts)
            compact = fits.astype(jnp.int32)
        else:
            part = initial(coarsest, Lmax, rsalts, width=(N, M))
            compact = jnp.int32(0)

    def finish(g, trail, L, part, s):
        with jax.named_scope("refine"):
            part = _refine_up(g, trail, part, k, L, levels, preset, s,
                              backend, ell_deg, coarsen)
        with jax.named_scope("select"):
            over = jnp.maximum(block_weights(g, part, k) - L, 0.0).sum()
            return part, edge_cut(g, part) + 1e6 * over

    restarts = jax.vmap(finish, in_axes=(None, None, None, 0, 0))
    parts, scores = jax.vmap(restarts)(gs, trail, Lmax, part, rsalts)
    with jax.named_scope("select"):
        best = jax.vmap(lambda p, sc: p[jnp.argmin(sc)])(parts, scores)
    return best, sizes, compact


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
    segment-reduction path (see ``_partition_lanes``, which this runs
    with a lane axis of one).
    """
    one = lambda a: jnp.reshape(a, (1,) + jnp.shape(a))
    return _partition_lanes(
        jax.tree_util.tree_map(one, g), one(eps),
        one(jnp.asarray(salt, jnp.int32)), k, levels, preset_name, backend,
        ell_deg, coarsen)[0][0]


class BatchedPartition:
    """A jitted vmapped partition: calling it gives the ``[B, N]`` parts;
    :meth:`with_counters` also gives the ``[B, levels]`` i32 vertex counts
    of each lane's coarse graphs and the dispatch's i32 0/1 flag of a
    compact initial partition (``_partition_lanes``), from the same program
    and without a transfer."""

    def __init__(self, fn: Callable):
        self._fn = fn

    def __call__(self, gs: Graph, eps: jax.Array, salts: jax.Array):
        return self._fn(gs, eps, salts)[0]

    def with_counters(self, gs: Graph, eps: jax.Array, salts: jax.Array):
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
            fn = BatchedPartition(jax.jit(lambda gs, ee, ss: _partition_lanes(
                gs, ee, ss, k, levels, preset, backend, ell_deg, coarsen)))
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
