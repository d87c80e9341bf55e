"""Hierarchical multisection (the paper's §4) with scheduling strategies.

The communication graph is partitioned along the hierarchy
``H = a_1 : ... : a_l`` (top-down: first a_l, then a_{l-1}, ...), with the
adaptive imbalance of Lemma 5.1 applied at every sub-partition, so the final
k-way partition is eps-balanced and the identity mapping solves the mapping
phase.

Scheduling strategies (§4.2-4.5), adapted from C++ threads to JAX/XLA:

* ``naive``   — partition one subgraph at a time (all compute on one task).
* ``layer``   — all subgraphs of one hierarchy level padded to a common
                shape and partitioned by ONE vmapped program (the level
                barrier is the program boundary). Paper: Algorithm 1.
* ``bucket``  — the NON-BLOCKING LAYER analogue: subgraphs of a level are
                grouped into power-of-two size buckets; each bucket is its
                own vmapped program, so small subgraphs do not pay the
                padding (idle-lane) cost of the largest one.
* ``queue``   — the PRIORITY QUEUE analogue: worker threads pop the largest
                pending subgraph from a condition-variable-guarded heap and
                dispatch its partition call (XLA dispatch is asynchronous,
                so one worker's host-side subgraph extraction overlaps
                another's device compute). Paper: Algorithm 2.
* ``device``  — the fully DEVICE-RESIDENT level loop: every level keeps all
                lanes at the ROOT's padded shape, subgraph extraction runs
                on device (graph.split_blocks), the adaptive imbalance is
                evaluated on device (hierarchy.adaptive_epsilon_jnp) and the
                PE labels accumulate in a device buffer — the whole pipeline
                is ONE asynchronous dispatch chain with exactly one
                device->host fetch (the final ``pe_of``) per request.

Single graph representation
---------------------------
All strategies now share the padded device CSR `Graph` as the ONE graph
store. ``bucket``/``layer`` default to the device-resident planner
(``resident=True``): children stay on device in stacked per-group
containers and only a [B]-sized metadata fetch (child n/m/weight — needed
for data-dependent bucket shapes and the f64 imbalance rule) crosses the
bus per level. ``resident=False`` restores the PR-5 host-mirror loop
(`_HostGraph` round-trip per level) — kept as the bitwise reference and
for the naive/queue strategies, where `_HostGraph` survives as a thin
host-side metadata + extraction view.

Planner / executor split
------------------------
The LAYER/BUCKET/DEVICE strategies are expressed as a reusable two-phase
planner so that an external scheduler can interleave work from MANY
in-flight hierarchies (serve/mapper.MappingService):

* :func:`plan_level` turns one hierarchy level's pending subgraphs into
  :class:`PlanGroup`s — pure bookkeeping, no device work. Each group
  carries everything a dispatch needs (members, padded shapes, arity,
  preset/backend/ELL-degree, per-member eps and salts; resident groups
  additionally reference their stacked device batch).
* :func:`execute_group_batch` runs one stacked vmapped dispatch for one or
  MORE groups sharing :attr:`PlanGroup.exec_key` — the cross-request
  coalescing primitive. vmap lanes are independent, so a member's result
  is bit-identical whatever batch it rides in (tested).
* :class:`LevelPlanner` is the level-stepped state machine driving one
  hierarchy: ``plan() -> execute -> advance`` until done. The in-process
  planner path of :func:`hierarchical_multisection` runs on the SAME
  planner, so the direct path and the mapping service share every
  planning decision — the precondition for bit-identical results.

Skewed requests
---------------
A request whose root has more than an eighth of its edge slots past the
root's ELL degree cap (a power-law graph: :func:`_coarsen_mode`, read with
the root's n and m in one fetch) coarsens every dispatch by the skewed down
scan (``coarsen="skewed"`` in ``core/partition.py``). The choice is part
of :attr:`PlanGroup.exec_key` and of the program key, so such a request
runs programs of its own and is never stacked with a mesh's lanes.

Compile-cache policy
--------------------
Single-subgraph calls go straight to the jitted ``partition``; batched
calls go through :func:`partition.batched_partition`, a process-wide memo
of jitted vmapped wrappers keyed by ``(k, levels, preset, backend,
ell_deg, coarsen)``. The device-resident split/repack/eps/scatter programs live in
their own memo (:func:`_jit_op`), keyed by static shapes (+ the kernel
backend for programs that dispatch through kernels/ops). Both are shared
across hierarchy levels, strategies and calls. :func:`_note_program`
tracks every distinct XLA partition-program key ``(N, M, batch, k,
levels, preset, backend, ell_deg, coarsen)``: first sighting in the process =
compile (miss), later sightings = reuse (hit); per-run counts land in
``stats["compile_cache"]``.

Transfer accounting
-------------------
Module-level counters (:func:`transfer_stats` / :func:`reset_transfer_stats`)
record every host<->device array movement the multisection performs:
bulk graph uploads (`_stack_to_device`, `_partition_one`), bulk label /
mirror fetches (``d2h_array_fetches``), per-level metadata fetches
(``d2h_meta_fetches``; the root's n, m and degree figure are one) and
the one fetch of the v-cycle counter after the
mapping is complete (``d2h_counter_fetches``). On the ``device`` strategy
a request costs exactly ONE array fetch — the final ``pe_of`` — which the
``device_pipeline`` benchmark and tests assert. (On CPU hosts the
"transfer" is a copy; the counters measure the protocol an accelerator
would pay.)

Tracing
-------
The planner's host boundaries are ``jax.profiler.TraceAnnotation`` spans
(``repro.plan``, ``repro.dispatch``, ``repro.advance``, ``repro.fetch``;
``core/api.py`` adds ``repro.map`` and ``repro.finalize``), each with the
request's ``req`` id and, where they apply, ``depth`` and ``lanes``. The
device programs run under ``jax.named_scope``s: ``level_ops`` here,
``coarsen``/``initial``/``refine``/``select`` in ``core/partition.py``,
``evaluate`` around the J evaluation. Both cost nothing while no profiler
runs, and a profiler trace puts each device op and each idle gap down to
one of them.

All strategies use salts derived from the subgraph's position in the
hierarchy (not traversal order), so results are reproducible per strategy
— and identical ACROSS strategies up to padding effects (`queue` and
`naive` pad identically, so they produce bit-equal mappings; `bucket` is
bit-equal to `naive` too, resident or not; `device` is bit-equal to its
own host-reference twin, tested).
"""
from __future__ import annotations

import dataclasses
import heapq
import itertools
import threading
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from .graph import (ELL_DEG_CAP, Graph, assemble_padded, default_ell_deg,
                    padded_csr_indptr, repad_device, split_blocks, take_lanes)
from .hierarchy import Hierarchy, adaptive_epsilon, adaptive_epsilon_jnp
from .partition import (BatchedPartition, Preset, _coarsening_target,
                        batched_partition, clear_batched_partition_cache,
                        initial_width, num_levels, partition)
from .refine import resolve_backend
from ..kernels import ops as kops


# ---------------------------------------------------------------------------
# host<->device transfer accounting
# ---------------------------------------------------------------------------

_XFER_LOCK = threading.Lock()


def _zero_xfer() -> dict:
    return {"h2d_bytes": 0, "h2d_transfers": 0,
            "d2h_bytes": 0, "d2h_array_fetches": 0,
            "d2h_meta_bytes": 0, "d2h_meta_fetches": 0,
            "d2h_counter_bytes": 0, "d2h_counter_fetches": 0}


_XFER = _zero_xfer()


def _acct(**kw) -> None:
    with _XFER_LOCK:
        for key, v in kw.items():
            _XFER[key] += int(v)


def transfer_stats() -> dict:
    """Snapshot of the process-wide transfer counters (see module doc)."""
    with _XFER_LOCK:
        return dict(_XFER)


def reset_transfer_stats() -> None:
    with _XFER_LOCK:
        _XFER.update(_zero_xfer())


# ---------------------------------------------------------------------------
# host-side subgraph extraction (the resident=False reference + naive/queue)
# ---------------------------------------------------------------------------

def _next_pow2(x: int) -> int:
    return 1 << max(int(np.ceil(np.log2(max(x, 1)))), 0)


@dataclasses.dataclass
class _HostGraph:
    """Numpy mirror of a (sub)graph + bookkeeping for the recursion.

    float32/int32 end-to-end — the device arrays are f32/i32, so the old
    f64/i64 up-casts only doubled the residual transfer volume (and i64
    indices past 2^31 are rejected at construction; graph.check_i32_range).
    """

    vwgt: np.ndarray   # [n] f32
    rows: np.ndarray   # [m] i32 directed
    cols: np.ndarray   # [m] i32
    ewgt: np.ndarray   # [m] f32
    orig_ids: np.ndarray  # [n] i32 vertex ids in the ORIGINAL graph
    depth: int         # hierarchy depth (l at the root, 0 at leaves)
    pe_base: int       # PE id offset accumulated along the recursion
    uid: int           # stable id along the hierarchy path (for salts)

    @property
    def n(self) -> int:
        return self.vwgt.shape[0]

    @property
    def m(self) -> int:
        return self.rows.shape[0]

    @property
    def wsum(self) -> float:
        return float(self.vwgt.sum())

    def to_device(self, N: int, M: int) -> Graph:
        """Padded device Graph via the shared CSR builder (exact indptr)."""
        return assemble_padded(self.vwgt, self.rows, self.cols, self.ewgt,
                               self.n, N, M)


def _stack_to_device(members: list[_HostGraph], N: int, M: int) -> Graph:
    """Batched [B, ...] Graph for a bucket — ONE host->device transfer per
    field instead of one per member per field."""
    B = len(members)
    vwgt = np.zeros((B, N), np.float32)
    rows = np.full((B, M), N - 1, np.int32)
    cols = np.full((B, M), N - 1, np.int32)
    ewgt = np.zeros((B, M), np.float32)
    indptr = np.zeros((B, N + 1), np.int32)
    ns = np.zeros((B,), np.int32)
    ms = np.zeros((B,), np.int32)
    for i, hg in enumerate(members):
        m = hg.m
        vwgt[i, : hg.n] = hg.vwgt
        rows[i, :m] = hg.rows
        cols[i, :m] = hg.cols
        ewgt[i, :m] = hg.ewgt
        indptr[i] = padded_csr_indptr(rows[i], m, N)
        ns[i] = hg.n
        ms[i] = m
    _acct(h2d_bytes=vwgt.nbytes + rows.nbytes + cols.nbytes + ewgt.nbytes
          + indptr.nbytes + ns.nbytes + ms.nbytes, h2d_transfers=7)
    return Graph(
        vwgt=jnp.asarray(vwgt),
        rows=jnp.asarray(rows),
        cols=jnp.asarray(cols),
        ewgt=jnp.asarray(ewgt),
        indptr=jnp.asarray(indptr),
        n=jnp.asarray(ns),
        m=jnp.asarray(ms),
    )


def host_graph_from(g: Graph) -> _HostGraph:
    n = int(g.n)
    m = int(g.m)
    _acct(d2h_bytes=4 * (g.N + 3 * g.M), d2h_array_fetches=1,
          d2h_meta_bytes=8, d2h_meta_fetches=1)
    return _HostGraph(
        vwgt=np.asarray(g.vwgt)[:n],
        rows=np.asarray(g.rows)[:m].astype(np.int32, copy=False),
        cols=np.asarray(g.cols)[:m].astype(np.int32, copy=False),
        ewgt=np.asarray(g.ewgt)[:m],
        orig_ids=np.arange(n, dtype=np.int32),
        depth=0,
        pe_base=0,
        uid=0,
    )


def _split(hg: _HostGraph, part: np.ndarray, k: int, child_depth: int,
           stride: int, arity: int) -> list[_HostGraph]:
    """Extract the k induced block subgraphs of ``hg`` under ``part``
    (host reference of graph.split_blocks — bitwise interchangeable)."""
    part = part[: hg.n]
    relabel = np.zeros(hg.n, np.int32)
    children = []
    for b in range(k):
        sel = np.nonzero(part == b)[0]
        relabel[sel] = np.arange(sel.shape[0])
        emask = (part[hg.rows] == b) & (part[hg.cols] == b)
        children.append(
            _HostGraph(
                vwgt=hg.vwgt[sel],
                rows=relabel[hg.rows[emask]],
                cols=relabel[hg.cols[emask]],
                ewgt=hg.ewgt[emask],
                orig_ids=hg.orig_ids[sel],
                depth=child_depth,
                pe_base=hg.pe_base + b * stride,
                uid=hg.uid * arity + b + 1,
            )
        )
    return children


def _host_coarsen_mode(hg: _HostGraph) -> str:
    """:func:`_coarsen_mode` of a host graph."""
    deg = np.bincount(hg.rows, minlength=hg.n)
    return _coarsen_mode(hg.n, hg.m, _slots_past_caps(deg))


# ---------------------------------------------------------------------------
# the compiled-callable caches
# ---------------------------------------------------------------------------

_SEEN_SHAPES: set[tuple] = set()         # partition program keys ever compiled
_DEVICE_OPS: dict[tuple, Callable] = {}  # split/repack/eps/scatter programs
_EXEC_LOCK = threading.Lock()

# backward-compat alias: the memo itself now lives in core/partition.py so
# every batched-partition consumer shares one cache.
_batched_partition = batched_partition


def _jit_op(key: tuple, fn: Callable) -> Callable:
    """Process-wide memo for the device-resident helper programs (split,
    lane gather/repack, eps, leaf scatter). Keys are static shapes — and
    the kernel backend where the program dispatches through kernels/ops."""
    with _EXEC_LOCK:
        f = _DEVICE_OPS.get(key)
        if f is None:
            f = jax.jit(fn)
            _DEVICE_OPS[key] = f
    return f


def _ell_deg_for(members, backend: str) -> int | None:
    """Static ELL degree cap for a dispatch, from the REAL mean directed
    degree pooled over the member subgraphs: ``ceil(sum m / sum n)``
    (pow2-padded shapes skew the in-jit default by up to 2x — see
    core/refine.py). Taking the MAX of per-member ceil-means, as this used
    to, over-padded mixed buckets and fragmented the jit cache per outlier
    member. None when the xla backend doesn't need it (avoids fragmenting
    the jit cache key)."""
    if backend != "ell":
        return None
    tot_m = sum(m.m for m in members)
    tot_n = max(sum(m.n for m in members), 1)
    mean = (tot_m + tot_n - 1) // tot_n
    return default_ell_deg(1, mean)  # N=1, M=mean -> cap from the real mean


# every ELL cap default_ell_deg can give: multiples of 8 up to the hard cap
_ELL_CAPS = np.arange(8, ELL_DEG_CAP + 1, 8, dtype=np.int32)


def _slots_past_caps(deg) -> np.ndarray:
    """[len(_ELL_CAPS)] i32: for each cap, the edge slots of the rows'
    degrees ``deg`` that lie past it (numpy or traced)."""
    xp = np if isinstance(deg, np.ndarray) else jnp
    over = xp.maximum(deg[:, None] - xp.asarray(_ELL_CAPS)[None, :], 0)
    return xp.sum(over, axis=0).astype(xp.int32)


def _coarsen_mode(n: int, m: int, past) -> str:
    """``"skewed"`` where more than an eighth of the root's ``m`` directed
    edge slots lie past its ELL cap (the cap ``_ell_deg_for`` gives the
    root dispatch), else ``"ell"``. Every dispatch of the request then
    coarsens that way. A mesh keeps under 1.1% of its slots past the cap
    (rgg 100 to 16,384 vertices); a Graph500 graph has 28-53% there."""
    cap = default_ell_deg(1, (m + max(n, 1) - 1) // max(n, 1))
    return "skewed" if 8 * int(past[cap // 8 - 1]) > m else "ell"


def _note_program(N: int, M: int, batch: int, k: int, levels: int, preset: str,
                  backend: str, ell_deg: int | None, cache_stats: dict,
                  coarsen: str = "ell") -> None:
    """Track XLA program reuse: the first sighting of a program key in the
    process is a compile (miss), every later one a cache hit."""
    key = (N, M, batch, k, levels, preset, backend, ell_deg, coarsen,
           kops.kernel_backend())
    with _EXEC_LOCK:
        hit = key in _SEEN_SHAPES
        _SEEN_SHAPES.add(key)
        # increment inside the lock: queue workers call this concurrently
        cache_stats["hits" if hit else "misses"] += 1


def compile_cache_size() -> int:
    with _EXEC_LOCK:
        return len(_SEEN_SHAPES)


def clear_compile_cache() -> None:
    """Drop the memoized callables AND the program-sighting telemetry.

    Call alongside ``jax.clear_caches()`` — that drops the compiled
    executables inside the memoized jit wrappers, so keeping
    ``_SEEN_SHAPES`` would report 'hits' for programs XLA must recompile.
    """
    with _EXEC_LOCK:
        _SEEN_SHAPES.clear()
        _DEVICE_OPS.clear()
    clear_batched_partition_cache()


# ---------------------------------------------------------------------------
# device-resident level state
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class _DeviceLevel:
    """One dispatch group's children, resident on device: a stacked
    ``[B, ...]`` Graph plus the [B, N] original-vertex-id view."""

    g: Graph           # stacked children (n/m fields are [B])
    orig: jax.Array    # [B, N] ids into the ROOT graph (pad -> sentinel)
    depth: int


@dataclasses.dataclass
class _LaneRef:
    """Thin host-side metadata view of one device-resident lane — all the
    planner needs (shape keys, eps inputs, salt derivation) without
    touching the arrays. The successor of `_HostGraph` in resident mode;
    ``n``/``m``/``wsum`` stay unset (-1) on the ``device`` strategy where
    planning is shape-oblivious and eps lives on device."""

    level: _DeviceLevel
    lane: int
    depth: int
    pe_base: int
    uid: int
    n: int = -1
    m: int = -1
    wsum: float = 0.0


def _level_op(key: tuple, fn: Callable) -> Callable:
    """:func:`_jit_op` of ``fn`` traced under the ``level_ops`` scope."""
    def run(*args):
        with jax.named_scope("level_ops"):
            return fn(*args)
    return _jit_op(key, run)


def _root_op(Ns: int, Ms: int, N0: int, M0: int) -> Callable:
    """g -> ([1,...] repadded batch, [1, N0] orig ids, f32 total weight)."""
    def run(g: Graph):
        g2 = repad_device(g, N0, M0)
        ar = jnp.arange(N0, dtype=jnp.int32)
        orig = jnp.where(ar < g2.n, ar, g2.n)  # sentinel = n (spare pe slot)
        batch = jax.tree_util.tree_map(lambda a: a[None], g2)
        return batch, orig[None], jnp.sum(g2.vwgt)
    return _level_op(("root", Ns, Ms, N0, M0), run)


def _root_meta_op(Ns: int, Ms: int) -> Callable:
    """g -> i32 [2 + len(_ELL_CAPS)]: the root's n, m and the edge slots
    past each ELL cap (:func:`_slots_past_caps`), read in one fetch."""
    def run(g: Graph):
        past = _slots_past_caps(g.indptr[1:] - g.indptr[:-1])
        return jnp.concatenate([jnp.stack([g.n, g.m]).astype(jnp.int32),
                                past])
    return _level_op(("root_meta", Ns, Ms), run)


def _zeros_op(n: int) -> Callable:
    """() -> [n] i32 zeros: the resident pe buffer."""
    return _level_op(("zeros", n), lambda: jnp.zeros(n, jnp.int32))


def _concat_op(N: int, M: int, lanes: tuple[int, ...]) -> Callable:
    """Stack per-container [B_i, ...] batches and [B_i, N] id views into
    one dispatch batch."""
    def run(batches, origs):
        cat = lambda *a: jnp.concatenate(a, axis=0)
        return (jax.tree_util.tree_map(cat, *batches),
                jnp.concatenate(origs, axis=0))
    return _level_op(("concat", N, M, lanes), run)


def _split_op(B: int, N: int, M: int, arity: int) -> Callable:
    """[B]-lane batch -> [B*arity]-lane children (+ orig ids + weights)."""
    def run(gb: Graph, parts, ob, sent):
        ch, co, ws = jax.vmap(
            lambda g1, p1, o1: split_blocks(g1, p1, o1, arity, sent)
        )(gb, parts, ob)
        flat = lambda a: a.reshape((B * arity,) + a.shape[2:])
        return (jax.tree_util.tree_map(flat, ch), flat(co), flat(ws))
    return _level_op(("split", B, N, M, arity, kops.kernel_backend()), run)


def _gather_op(Ns: int, Ms: int, Nd: int, Md: int, nsel: int) -> Callable:
    """Select ``nsel`` lanes of a [B,...] container and repad to (Nd, Md)
    — how resident bucket/layer groups assemble their dispatch batches."""
    def run(gb: Graph, ob, sel, sent):
        sub = take_lanes(gb, sel)
        sub = jax.vmap(lambda g1: repad_device(g1, Nd, Md))(sub)
        o = jnp.take(ob, sel, axis=0)
        if Nd <= Ns:
            o = o[:, :Nd]
        else:
            pad = jnp.broadcast_to(sent, (nsel, Nd - Ns)).astype(jnp.int32)
            o = jnp.concatenate([o, pad], axis=1)
        return sub, o
    return _level_op(("gather", Ns, Ms, Nd, Md, nsel), run)


def _eps_op(B: int, k: int, k_sub: int, depth: int, eps: float,
            adaptive: bool) -> Callable:
    """[B] f32 subgraph weights -> [B] f32 adaptive eps (Lemma 5.1).

    ONE program serves both the device path (fed split_blocks weights) and
    the host-reference path (fed numpy f32 sums) so their eps bits match.
    """
    def run(wsums, total):
        if not adaptive or depth <= 0:
            return jnp.full((B,), eps, jnp.float32)
        return adaptive_epsilon_jnp(eps, total, wsums, k, k_sub, depth)
    return _level_op(("eps", B, k, k_sub, depth, float(eps), bool(adaptive)),
                     run)


def _scatter_op(B: int, N: int) -> Callable:
    """Leaf write: pe[orig[b, v]] = base[b] + part[b, v] (pads hit the
    sentinel slot; the buffer has one spare entry for exactly that)."""
    def run(pe, ob, parts, bases):
        vals = bases[:, None] + parts[:, :N].astype(jnp.int32)
        return pe.at[ob.reshape(-1)].set(vals.reshape(-1), mode="drop")
    return _level_op(("scatter", B, N), run)


# ---------------------------------------------------------------------------
# the level planner (shared by the in-process strategies and serve/mapper)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class PlanGroup:
    """One bucket dispatch planned from a single hierarchy's current level.

    Host groups (``resident=False``) are pure bookkeeping — members are
    `_HostGraph`s stacked/uploaded at dispatch time. Resident groups carry
    their stacked device ``batch`` (built by the planner from the previous
    level's on-device children) plus the [B, N] original-id view; their
    ``eps`` may live on device (``eps_dev``) for the ``device`` strategy.
    ``eps``/``salts`` are per-member (position-derived, so independent of
    which batch the member eventually rides in). ``req`` is the id of the
    request the group belongs to (trace spans only); ``counters`` is set
    by the dispatch: the dispatch's ``[B', levels]`` coarse vertex counts,
    its compact-initial flag and its two-hop pairs and levels run (None
    unless ``coarsen="skewed"``; ``BatchedPartition.with_counters``), on
    device, and this group's first row in the counts. ``coarsen`` names
    the down scan, the same for every group of a request
    (:func:`_coarsen_mode`).
    """

    members: list
    N: int                # padded vertex shape of the dispatch
    M: int                # padded edge shape
    arity: int            # k of each member's sub-partition
    levels: int           # static coarsening depth for (N, arity)
    preset: str
    backend: str
    deg: int | None       # static ELL degree cap (None for xla)
    eps: list[float]
    salts: list[int]
    resident: bool = False
    batch: Graph | None = None           # [B, ...] device input (resident)
    batch_orig: jax.Array | None = None  # [B, N] root ids (resident)
    eps_dev: jax.Array | None = None     # [B] f32 device eps (device strategy)
    req: int = 0
    counters: tuple | None = None
    coarsen: str = "ell"  # the down scan (partition.py): "ell" or "skewed"

    @property
    def exec_key(self) -> tuple:
        """Groups with equal keys run the same compiled executable and may
        be stacked into ONE dispatch (the cross-request coalescing key)."""
        return (self.N, self.M, self.arity, self.levels, self.preset,
                self.backend, self.deg, self.coarsen)

    def eps_array(self) -> jax.Array:
        if self.eps_dev is not None:
            return self.eps_dev
        return jnp.asarray(self.eps, jnp.float32)

    def salts_array(self) -> jax.Array:
        return jnp.asarray(self.salts, jnp.int32)

    def graph_batch(self) -> Graph:
        if self.resident:
            return self.batch
        return _stack_to_device(self.members, self.N, self.M)


def plan_level(work: list, h: Hierarchy, eps: float, preset: str,
               seed: int, total_weight: float, adaptive: bool, backend: str,
               bucketed: bool = True, coarsen: str = "ell") -> list[PlanGroup]:
    """Group one level's pending subgraphs into dispatch units.

    ``bucketed=True`` is the BUCKET strategy (power-of-two shape buckets);
    ``False`` is LAYER (one group per arity, padded to the level max).
    Members may be `_HostGraph`s or `_LaneRef`s — planning only reads the
    ``n/m/depth/uid/wsum`` metadata either exposes. ``coarsen`` is the
    request's down scan (:func:`_coarsen_mode`).
    """
    groups: dict[tuple[int, int, int], list] = {}
    for hg in work:
        if bucketed:
            key_n = _next_pow2(hg.n)
            key_m = _next_pow2(max(hg.m, 1))
        else:
            key_n = key_m = 0  # one group per arity; padded to layer max below
        arity = h.a[hg.depth - 1]
        groups.setdefault((key_n, key_m, arity), []).append(hg)

    out = []
    for (kn, km, arity), members in groups.items():
        N = kn or _next_pow2(max(m.n for m in members))
        M = km or _next_pow2(max(max(m.m, 1) for m in members))
        out.append(PlanGroup(
            members=members, N=N, M=M, arity=arity,
            levels=num_levels(N, arity), preset=preset, backend=backend,
            deg=_ell_deg_for(members, backend),
            eps=[_eps_for(m, h, eps, total_weight, adaptive) for m in members],
            salts=[seed * 100003 + m.uid for m in members],
            coarsen=coarsen,
        ))
    return out


def dispatch_group_batch(groups: list[PlanGroup], cache_stats: dict,
                         pad_batch_pow2: bool = False) -> tuple:
    """Stack and dispatch ONE vmapped call for PlanGroups sharing
    ``exec_key``; returns an opaque handle for :func:`fetch_group_batch`.

    XLA dispatch is asynchronous, so a scheduler can dispatch every merged
    set of a level before fetching any — host-side stacking of the next
    set overlaps device compute of the previous one (serve/mapper).
    Host groups upload their stacked members; resident groups contribute
    their on-device batches directly (a device-side concat when several
    groups merge) — coalescing works across the two kinds.

    ``pad_batch_pow2`` replicates the last lane up to the next power of
    two (spare lanes dropped): the service uses it to bound the number of
    distinct batch widths XLA must compile for, at the cost of idle-lane
    compute on ragged batches.
    """
    key = groups[0].exec_key
    for gr in groups[1:]:
        if gr.exec_key != key:
            raise ValueError(f"mismatched exec keys: {gr.exec_key} != {key}")
    g0 = groups[0]
    B = sum(len(gr.members) for gr in groups)
    Bp = _next_pow2(B) if pad_batch_pow2 else B
    reqs = sorted({gr.req for gr in groups})
    with jax.profiler.TraceAnnotation(
            "repro.dispatch", req=",".join(map(str, reqs)),
            depth=g0.members[0].depth, lanes=Bp):
        _note_program(g0.N, g0.M, Bp, g0.arity, g0.levels, g0.preset,
                      g0.backend, g0.deg, cache_stats, g0.coarsen)
        fn = batched_partition(g0.arity, g0.levels, g0.preset, g0.backend,
                               g0.deg, g0.coarsen)

        batches = [gr.graph_batch() for gr in groups]
        eps_parts = [gr.eps_array() for gr in groups]
        salt_parts = [gr.salts_array() for gr in groups]
        if len(groups) == 1:
            batch, eps, salts = batches[0], eps_parts[0], salt_parts[0]
        else:
            cat = lambda xs: jnp.concatenate(xs, axis=0)
            batch = jax.tree_util.tree_map(lambda *a: cat(a), *batches)
            eps = cat(eps_parts)
            salts = cat(salt_parts)
        if Bp > B:
            rep = lambda a: jnp.concatenate(
                [a, jnp.repeat(a[-1:], Bp - B, axis=0)], axis=0)
            batch = jax.tree_util.tree_map(rep, batch)
            eps = rep(eps)
            salts = rep(salts)
        if isinstance(fn, BatchedPartition):
            parts, *counters = fn.with_counters(batch, eps, salts)
        else:  # a stand-in callable gives the parts alone
            parts, counters = fn(batch, eps, salts), None
    ofs = 0
    for gr in groups:
        gr.counters = None if counters is None else (*counters, ofs)
        ofs += len(gr.members)
    return parts, groups


def fetch_group_batch(handle: tuple) -> list:
    """Resolve a dispatched batch into one ``[B_i, N]`` array per group.

    Host groups are fetched to numpy (the d2h sync point); resident groups
    get lazy device slices — no transfer, the labels feed the next level's
    on-device split."""
    parts, groups = handle
    parts_np = None
    out = []
    ofs = 0
    for gr in groups:
        B = len(gr.members)
        if gr.resident:
            out.append(parts[ofs: ofs + B])
        else:
            if parts_np is None:
                parts_np = np.asarray(parts)
                _acct(d2h_bytes=parts_np.nbytes, d2h_array_fetches=1)
            out.append(parts_np[ofs: ofs + B])
        ofs += B
    return out


def execute_group_batch(groups: list[PlanGroup], cache_stats: dict,
                        pad_batch_pow2: bool = False) -> list:
    """Dispatch + fetch in one call (the in-process strategies' path).

    Returns one ``[B_i, N]`` partition array per input group, in order.
    Because vmap lanes are independent, each member's partition is
    bit-identical to what a solo dispatch would produce — so coalescing
    groups from different requests cannot change any request's result.
    """
    return fetch_group_batch(
        dispatch_group_batch(groups, cache_stats, pad_batch_pow2))


_PLANNER_STRATEGIES = ("layer", "bucket", "device")
_REQUEST_IDS = itertools.count(1)


def new_request_id() -> int:
    """A process-unique id for one mapping request's trace spans."""
    return next(_REQUEST_IDS)


class LevelPlanner:
    """Level-stepped multisection state machine for ONE hierarchy.

    Alternates ``plan()`` (PlanGroups for the current level; pure host
    work) with ``advance(results)`` (feed partition results, split
    children, step to the next level) until ``plan()`` returns ``[]``.
    The executor is external, so a scheduler holding several planners can
    merge their same-``exec_key`` groups into shared dispatches
    (serve/mapper.MappingService) — while the in-process path executes
    each group alone, yielding identical per-member programs.

    ``resident=True`` (default for all planner strategies) keeps every
    level's subgraphs on device: ``advance`` feeds the partition labels
    straight into the on-device split, and only metadata crosses the bus —
    nothing at all on the ``device`` strategy, a [B]-sized child-size/
    weight fetch on bucket/layer (their bucket shapes are data-dependent).
    ``resident=False`` is the PR-5 host-mirror loop, planning-identical
    and bit-identical in its results (the regression reference).

    ``req`` names the request in the planner's trace spans (a fresh id from
    :func:`new_request_id` by default). ``result()`` adds the v-cycle
    counter to the stats: ``vcycle_real_vertex_work``, the vertices of
    every graph the partition calls' v-cycles processed (each lane's graph,
    its coarse graphs, and the graph again for each finest-level v-cycle),
    and ``vcycle_padded_vertex_work``, the vertex slots those passes ran
    at (each lane's padded N per pass, the initial partition's width for
    the coarsest graph; a skewed dispatch's levels count where they ran);
    ``initial_dispatches``, the dispatches whose
    initial partition could run cut to a smaller width
    (``partition.initial_width``), and ``initial_compact_dispatches``, those
    of them where every lane's coarsest graph fitted it;
    ``coarsen_merges``, the pairs every lane's down scan made (its vertices
    less its coarsest graph's), and ``coarsen_twohop_merges``, those of
    them the two-hop and isolated pairing made (``coarsen="skewed"``);
    ``coarsest_real_vertices`` and ``coarsening_target``, the sums over the
    lanes of the coarsest graph's vertices and of the target the depth was
    set for (``max(24 k, 64)``). All come in the one counter fetch.

    ``coarsen`` is the request's down scan, decided from the root's
    degrees as it is read (:func:`_coarsen_mode`).
    """

    def __init__(self, g: Graph, h: Hierarchy, eps: float = 0.03,
                 preset: str = "eco", seed: int = 0, adaptive: bool = True,
                 backend: str = "auto", bucketed: bool = True,
                 checkpoint: Callable[[], None] | None = None,
                 strategy: str | None = None, resident: bool | None = None,
                 req: int | None = None):
        if strategy is None:
            strategy = "bucket" if bucketed else "layer"
        if strategy not in _PLANNER_STRATEGIES:
            raise ValueError(f"unknown planner strategy {strategy!r}")
        self.h = h
        self.checkpoint = checkpoint
        self.eps = eps
        self.preset = preset
        self.seed = seed
        self.adaptive = adaptive
        self.backend = resolve_backend(backend)
        self.strategy = strategy
        self.bucketed = strategy == "bucket"
        self.resident = True if resident is None else bool(resident)
        self.stats = {"partition_calls": 0, "levels": [],
                      "strategy": strategy, "resident": self.resident,
                      "padded_vertex_work": 0, "real_vertex_work": 0,
                      "backend": self.backend,
                      "compile_cache": {"hits": 0, "misses": 0}}
        self.cache_stats = self.stats["compile_cache"]
        self.req = new_request_id() if req is None else int(req)
        # per dispatched group: (level sizes, compact flag, lane sizes,
        # first row, N, Nc, levels)
        self._vcycle: list[tuple] = []
        self._groups: list[PlanGroup] | None = None
        self._done = False
        self._work: list = []
        self.pe_of: np.ndarray | None = None
        if self.resident:
            self._init_resident(g)
        else:
            self._init_host(g)

    # -- construction ------------------------------------------------------

    def _init_host(self, g: Graph) -> None:
        root = host_graph_from(g)
        root.depth = self.h.l
        self.n_root = root.n
        self.N0 = _next_pow2(root.n)
        self.M0 = _next_pow2(max(root.m, 1))
        self.total_weight = root.wsum
        self._tw_f32 = jnp.float32(np.float32(root.vwgt.sum()))
        self._root_deg = _ell_deg_for([root], self.backend)
        self.coarsen = _host_coarsen_mode(root)
        self.pe_of = np.zeros(root.n, np.int32)
        self._current: list = [root]

    def _init_resident(self, g: Graph) -> None:
        meta = np.asarray(_root_meta_op(g.N, g.M)(g))
        _acct(d2h_meta_bytes=meta.nbytes, d2h_meta_fetches=1)
        n_root, m_root = int(meta[0]), int(meta[1])
        self.coarsen = _coarsen_mode(n_root, m_root, meta[2:])
        self.n_root = n_root
        self.N0 = _next_pow2(n_root)
        self.M0 = _next_pow2(max(m_root, 1))
        batch, orig, tw = _root_op(g.N, g.M, self.N0, self.M0)(g)
        root_level = _DeviceLevel(g=batch, orig=orig, depth=self.h.l)
        self._sent = batch.n[0]          # spare pe slot for pad writes
        self._pe = _zeros_op(n_root + 1)()
        self._tw_dev = tw
        self._root_deg = None
        if self.backend == "ell":
            mean = (m_root + max(n_root, 1) - 1) // max(n_root, 1)
            self._root_deg = default_ell_deg(1, mean)
        if self.strategy == "device":
            self.total_weight = None      # never fetched
            d = self.h.l
            self._eps_dev = _eps_op(1, self.h.k, self.h.k, d, self.eps,
                                    self.adaptive)(tw[None], tw)
        else:
            # bucket/layer need host shape keys + the f64 imbalance rule:
            # one scalar metadata fetch, bit-compatible with the host path
            # for integer weights (f32 sums are exact below 2^24).
            self.total_weight = float(tw)
            _acct(d2h_meta_bytes=4, d2h_meta_fetches=1)
        self._current = [_LaneRef(level=root_level, lane=0, depth=self.h.l,
                                  pe_base=0, uid=0, n=n_root, m=m_root,
                                  wsum=self.total_weight or 0.0)]

    # -- the plan/advance cycle -------------------------------------------

    @property
    def done(self) -> bool:
        return self._done

    def plan(self) -> list[PlanGroup]:
        """PlanGroups for the current level; ``[]`` once fully partitioned.
        Idempotent until ``advance`` consumes the results."""
        if self._done:
            return []
        if self._groups is None:
            # cooperative cancellation checkpoint: a deadline/shutdown hook
            # may abort here, BETWEEN levels, instead of after the full
            # pipeline (serve/mapper deadlines, close(wait=False)).
            if self.checkpoint is not None:
                self.checkpoint()
            if not self.resident:
                for hg in self._current:
                    if hg.depth == 0:
                        self.pe_of[hg.orig_ids] = hg.pe_base
            self._work = [w for w in self._current if w.depth > 0]
            if not self._work:
                self._done = True
                return []
            with jax.profiler.TraceAnnotation(
                    "repro.plan", req=self.req, depth=self._work[0].depth,
                    lanes=len(self._work)):
                if self.strategy == "device":
                    groups = self._plan_root_shape()
                else:
                    groups = plan_level(
                        self._work, self.h, self.eps, self.preset, self.seed,
                        self.total_weight, self.adaptive, self.backend,
                        self.bucketed, self.coarsen)
                    if self.resident:
                        for gr in groups:
                            gr.resident = True
                            gr.batch, gr.batch_orig = self._gather_group(gr)
                for gr in groups:
                    gr.req = self.req
            self._groups = groups
        return self._groups

    def _plan_root_shape(self) -> list[PlanGroup]:
        """The ``device`` strategy's fixed-shape schedule: every level is
        ONE group at the root's (N0, M0) padding — lane count, uids and
        salts are host-deterministic, so planning needs no device data."""
        work = self._work
        d = work[0].depth
        arity = self.h.a[d - 1]
        gr = PlanGroup(
            members=list(work), N=self.N0, M=self.M0, arity=arity,
            levels=num_levels(self.N0, arity), preset=self.preset,
            backend=self.backend, deg=self._root_deg,
            eps=[], salts=[self.seed * 100003 + w.uid for w in work],
            coarsen=self.coarsen)
        if self.resident:
            lvl = work[0].level
            gr.resident = True
            gr.batch = lvl.g
            gr.batch_orig = lvl.orig
            gr.eps_dev = self._eps_dev
        else:
            # host-reference twin: same eps PROGRAM as the device path, fed
            # numpy f32 sums — identical inputs give identical eps bits.
            wsums = jnp.asarray(
                np.asarray([w.wsum for w in work], np.float32))
            k_sub = int(np.prod(self.h.a[:d]))
            fn = _eps_op(len(work), self.h.k, k_sub, d, self.eps,
                         self.adaptive)
            gr.eps = [float(x) for x in np.asarray(fn(wsums, self._tw_f32))]
        return [gr]

    def _gather_group(self, gr: PlanGroup) -> tuple[Graph, jax.Array]:
        """Assemble a resident bucket/layer group's [B,...] dispatch batch
        from the per-container children (runs of members sharing a
        container become one lane-take + repad program each)."""
        batches: list[Graph] = []
        origs: list[jax.Array] = []
        i = 0
        members = gr.members
        while i < len(members):
            lv = members[i].level
            j = i
            lanes = []
            while j < len(members) and members[j].level is lv:
                lanes.append(members[j].lane)
                j += 1
            # lane widths, NOT Graph.N/M: those read shape[0], which on a
            # stacked [B, ...] container is the batch axis.
            Ns, Ms = lv.g.vwgt.shape[-1], lv.g.rows.shape[-1]
            fn = _gather_op(Ns, Ms, gr.N, gr.M, len(lanes))
            sub, o = fn(lv.g, lv.orig, jnp.asarray(lanes, jnp.int32),
                        self._sent)
            batches.append(sub)
            origs.append(o)
            i = j
        if len(batches) == 1:
            return batches[0], origs[0]
        lanes = tuple(int(o.shape[0]) for o in origs)
        return _concat_op(gr.N, gr.M, lanes)(batches, origs)

    def advance(self, results: list) -> None:
        """Feed one ``[B_i, N]`` partition array per group from ``plan()``."""
        groups = self.plan()
        if len(results) != len(groups):
            raise ValueError(f"expected {len(groups)} results, got {len(results)}")
        with jax.profiler.TraceAnnotation(
                "repro.advance", req=self.req, depth=self._work[0].depth,
                lanes=len(self._work)):
            for gr in groups:
                self._note_vcycle(gr)
            if self.resident:
                self._advance_resident(groups, results)
            else:
                nxt: list[_HostGraph] = []
                for gr, parts in zip(groups, results):
                    parts = np.asarray(parts)
                    for i, hg in enumerate(gr.members):
                        self._record(gr.N, hg.n)
                        nxt.extend(_children_of(hg, parts[i][: hg.n], self.h))
                self._current = nxt
        self.stats["levels"].append({"graphs": len(self._work)})
        self._groups = None

    def _note_vcycle(self, gr: PlanGroup) -> None:
        """Keep a dispatched group's v-cycle counters, on device, for the
        counters ``result()`` sums."""
        if gr.counters is None or gr.arity == 1:
            return  # no counters came back, or no v-cycle ran (k = 1)
        sizes, compact, skew, first = gr.counters
        if gr.members[0].n < 0:  # device-strategy lanes: sizes on device
            ns = gr.batch.n
        else:
            ns = [m.n for m in gr.members]
        Nc, _ = initial_width(gr.N, gr.M, gr.arity, gr.levels)
        self._vcycle.append((sizes, compact, ns, skew, first, gr.N, Nc,
                             gr.levels, _coarsening_target(gr.arity)))

    def _advance_resident(self, groups: list[PlanGroup], results: list) -> None:
        nxt: list[_LaneRef] = []
        for gr, parts in zip(groups, results):
            B = len(gr.members)
            d = gr.members[0].depth
            arity = gr.arity
            self.stats["partition_calls"] += B
            self.stats["padded_vertex_work"] += B * gr.N
            if self.strategy == "device":
                # each level's lanes partition a disjoint cover of the root
                self.stats["real_vertex_work"] += self.n_root
            else:
                self.stats["real_vertex_work"] += sum(r.n for r in gr.members)
            if d == 1:
                bases = jnp.asarray([r.pe_base for r in gr.members], jnp.int32)
                self._pe = _scatter_op(B, gr.N)(
                    self._pe, gr.batch_orig, parts, bases)
                continue
            stride = int(np.prod(self.h.a[: d - 1]))
            ch, co, ws = _split_op(B, gr.N, gr.M, arity)(
                gr.batch, parts, gr.batch_orig, self._sent)
            lvl = _DeviceLevel(g=ch, orig=co, depth=d - 1)
            if self.strategy == "device":
                nxt.extend(
                    _LaneRef(level=lvl, lane=i * arity + b, depth=d - 1,
                             pe_base=r.pe_base + b * stride,
                             uid=r.uid * arity + b + 1)
                    for i, r in enumerate(gr.members) for b in range(arity))
                k_sub = int(np.prod(self.h.a[: d - 1]))
                self._eps_dev = _eps_op(B * arity, self.h.k, k_sub, d - 1,
                                        self.eps, self.adaptive)(
                    ws, self._tw_dev)
            else:
                # bucket/layer shapes are data-dependent: fetch the child
                # metadata (sizes + weights), NOT the arrays.
                ns = np.asarray(ch.n)
                ms = np.asarray(ch.m)
                wv = np.asarray(ws)
                _acct(d2h_meta_bytes=ns.nbytes + ms.nbytes + wv.nbytes,
                      d2h_meta_fetches=3)
                for i, r in enumerate(gr.members):
                    for b in range(arity):
                        j = i * arity + b
                        nxt.append(_LaneRef(
                            level=lvl, lane=j, depth=d - 1,
                            pe_base=r.pe_base + b * stride,
                            uid=r.uid * arity + b + 1,
                            n=int(ns[j]), m=int(ms[j]), wsum=float(wv[j])))
        self._current = nxt

    def _record(self, batchN: int, realn: int) -> None:
        self.stats["partition_calls"] += 1
        self.stats["padded_vertex_work"] += int(batchN)
        self.stats["real_vertex_work"] += int(realn)

    def result(self) -> "MultisectionResult":
        if not self._done:
            raise RuntimeError("planner has pending levels")
        if self.resident and self.pe_of is None:
            # THE device->host sync point: one fetch per request.
            with jax.profiler.TraceAnnotation("repro.fetch", req=self.req):
                pe = np.asarray(self._pe[: self.n_root])
            _acct(d2h_bytes=pe.nbytes, d2h_array_fetches=1)
            self.pe_of = pe
        if self._vcycle:
            self._count_vcycle()
        return MultisectionResult(pe_of=self.pe_of, stats=self.stats)

    def _count_vcycle(self) -> None:
        """Fetch every dispatch's v-cycle counters at once, after the
        mapping is complete, and sum them into the stats. The coarsest
        graph's pass counts at the width its initial partition ran at."""
        on_device = [v[:4] for v in self._vcycle]
        _acct(d2h_counter_bytes=sum(a.nbytes for row in on_device
                                    for a in row if isinstance(a, jax.Array)),
              d2h_counter_fetches=1)
        fetched = jax.device_get(on_device)
        passes = 1 + Preset.get(self.preset).vcycles
        real = padded = compact_n = dispatches = 0
        merges = twohop = coarsest = target = 0
        for (sizes, compact, ns, skew), (*_, first, N, Nc, levels, tgt) in \
                zip(fetched, self._vcycle):
            ns = np.asarray(ns, np.int64)
            rows = slice(first, first + ns.shape[0])
            lanes = np.asarray(sizes, np.int64)[rows]
            ran = np.ones(levels, bool)
            if skew is not None:  # a skewed dispatch: its levels that ran
                twohop += int(np.asarray(skew[0], np.int64)[rows].sum())
                ran = np.asarray(skew[1]) > 0
            real += int(ns.sum()) * passes + int(lanes[:, ran].sum())
            padded += ns.shape[0] * N * (passes + int(ran.sum()))
            if Nc < N:
                dispatches += 1
                if compact:
                    compact_n += 1
                    padded -= ns.shape[0] * (N - Nc)
            last = lanes[:, -1] if levels else ns
            merges += int((ns - last).sum())
            coarsest += int(last.sum())
            target += ns.shape[0] * tgt
        self.stats["vcycle_real_vertex_work"] = real
        self.stats["vcycle_padded_vertex_work"] = padded
        self.stats["initial_compact_dispatches"] = compact_n
        self.stats["initial_dispatches"] = dispatches
        self.stats["coarsen_merges"] = merges
        self.stats["coarsen_twohop_merges"] = twohop
        self.stats["coarsest_real_vertices"] = coarsest
        self.stats["coarsening_target"] = target
        self._vcycle = []


# ---------------------------------------------------------------------------
# the multisection driver
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class MultisectionResult:
    pe_of: np.ndarray            # [n] i32 PE assignment (the mapping Pi)
    stats: dict                   # timing / scheduling telemetry


PartitionFn = Callable[..., jax.Array]


def _eps_for(hg, h: Hierarchy, eps: float, total_weight: float,
             adaptive: bool) -> float:
    if not adaptive:
        return eps
    d = hg.depth
    k_sub = int(np.prod(h.a[:d])) if d > 0 else 1
    return adaptive_epsilon(eps, total_weight, hg.wsum, h.k, k_sub, d)


def _partition_one(hg: _HostGraph, k: int, eps_val: float, preset: str,
                   salt: int, backend: str, cache_stats: dict,
                   coarsen: str = "ell") -> np.ndarray:
    N = _next_pow2(hg.n)
    M = _next_pow2(max(hg.m, 1))
    lv = num_levels(N, k)
    deg = _ell_deg_for([hg], backend)
    _note_program(N, M, 0, k, lv, preset, backend, deg, cache_stats, coarsen)
    g = hg.to_device(N, M)
    _acct(h2d_bytes=4 * (N + 3 * M + N + 1 + 2), h2d_transfers=7)
    part = np.asarray(partition(g, k, jnp.float32(eps_val), lv, preset,
                                jnp.int32(salt), backend, deg, coarsen))
    _acct(d2h_bytes=part.nbytes, d2h_array_fetches=1)
    return part[: hg.n]


def hierarchical_multisection(
    g: Graph,
    h: Hierarchy,
    eps: float = 0.03,
    preset: str = "eco",
    strategy: str = "bucket",
    seed: int = 0,
    adaptive: bool = True,
    backend: str = "auto",
    checkpoint: Callable[[], None] | None = None,
    resident: bool | None = None,
    req: int | None = None,
) -> MultisectionResult:
    """Partition ``g`` along ``h`` and return the (identity) mapping.

    ``checkpoint`` is an optional cooperative-cancellation hook invoked
    between levels (and before each naive/queue task); raising inside it
    aborts the multisection — the mechanism behind service deadlines.
    ``resident`` applies to the planner strategies (layer/bucket/device):
    ``None``/``True`` keeps the level loop on device, ``False`` forces the
    host-mirror reference loop (bit-identical results either way).
    ``req`` names the request in the planner's trace spans.
    """
    backend = resolve_backend(backend)
    if strategy in _PLANNER_STRATEGIES:
        # the planner path: identical planning to serve/mapper, each group
        # executed alone (no cross-request members to coalesce here).
        planner = LevelPlanner(g, h, eps=eps, preset=preset, seed=seed,
                               adaptive=adaptive, backend=backend,
                               strategy=strategy, resident=resident,
                               checkpoint=checkpoint, req=req)
        while True:
            groups = planner.plan()
            if not groups:
                break
            planner.advance([execute_group_batch([gr], planner.cache_stats)[0]
                             for gr in groups])
        return planner.result()
    if strategy not in ("naive", "queue"):
        raise ValueError(f"unknown strategy {strategy!r}")
    if resident is not None:
        # naive/queue run entirely on the host path; silently ignoring a
        # residency request would let e.g. a shadow-verification caller
        # believe it exercised the device pipeline when it never existed.
        raise ValueError(f"resident= applies only to the planner strategies "
                         f"{_PLANNER_STRATEGIES}; strategy {strategy!r} has "
                         f"no device-resident variant")

    root = host_graph_from(g)
    root.depth = h.l
    total_weight = root.wsum
    coarsen = _host_coarsen_mode(root)
    pe_of = np.zeros(root.n, np.int32)
    stats = {"partition_calls": 0, "levels": [], "strategy": strategy,
             "padded_vertex_work": 0, "real_vertex_work": 0,
             "backend": backend,
             "compile_cache": {"hits": 0, "misses": 0}}
    cache_stats = stats["compile_cache"]
    rec_lock = threading.Lock()

    def record(batchN, realn):
        with rec_lock:
            stats["partition_calls"] += 1
            stats["padded_vertex_work"] += int(batchN)
            stats["real_vertex_work"] += int(realn)

    ctx = (h, eps, preset, seed, total_weight, adaptive, backend, record,
           cache_stats, checkpoint, coarsen)
    current = [root]
    while current:
        if checkpoint is not None:
            checkpoint()
        nxt: list[_HostGraph] = []
        leaves = [hg for hg in current if hg.depth == 0]
        for hg in leaves:
            pe_of[hg.orig_ids] = hg.pe_base
        work = [hg for hg in current if hg.depth > 0]
        if not work:
            break
        if strategy == "naive":
            produced = _run_naive(work, ctx)
        else:
            produced = _run_queue(work, ctx)
        stats["levels"].append({"graphs": len(work)})
        nxt.extend(produced)
        current = nxt
    return MultisectionResult(pe_of=pe_of, stats=stats)


def _children_of(hg: _HostGraph, part: np.ndarray, h: Hierarchy) -> list[_HostGraph]:
    d = hg.depth
    arity = h.a[d - 1]
    child_stride = int(np.prod(h.a[: d - 1])) if d > 1 else 1
    return _split(hg, part, arity, d - 1, child_stride, arity)


def _run_naive(work, ctx):
    (h, eps, preset, seed, total_weight, adaptive, backend, record,
     cache_stats, checkpoint, coarsen) = ctx
    out = []
    for hg in work:
        if checkpoint is not None:
            checkpoint()
        arity = h.a[hg.depth - 1]
        e = _eps_for(hg, h, eps, total_weight, adaptive)
        part = _partition_one(hg, arity, e, preset, seed * 100003 + hg.uid,
                              backend, cache_stats, coarsen)
        record(_next_pow2(hg.n), hg.n)
        out.extend(_children_of(hg, part, h))
    return out


def _run_queue(work, ctx, workers: int | None = None):
    """PRIORITY QUEUE (Algorithm 2): workers pop the largest pending
    subgraph from a condition-variable-guarded heap; children re-enter the
    queue until only leaves remain. XLA dispatch is asynchronous, so while
    one worker blocks on device results another extracts subgraphs on the
    host — the JAX analogue of the paper's thread groups. No polling: the
    seed's 1 ms sleep-poll loop (and its unreachable ``done.is_set()``
    early-return) is replaced by ``Condition.wait``/``notify_all``.

    Worker count defaults to the host core count clamped to [2, 4]. The
    floor of 2 is deliberate even on a 1-core host: XLA releases the GIL
    while a dispatched program executes, so a second worker keeps host-side
    subgraph extraction overlapping device compute. The ceiling avoids
    oversubscription — XLA:CPU multithreads each program itself, and going
    2 -> 4 workers on a 2-core container measured ~4% SLOWER.
    """
    if workers is None:
        import os
        workers = max(2, min(4, os.cpu_count() or 2))
    (h, eps, preset, seed, total_weight, adaptive, backend, record,
     cache_stats, checkpoint, coarsen) = ctx
    cv = threading.Condition()
    heap: list[tuple[int, int, _HostGraph]] = []
    out: list[_HostGraph] = []
    pending = [0]   # queued + in-flight tasks, guarded by cv
    errors: list[BaseException] = []

    for hg in work:
        heapq.heappush(heap, (-hg.n, hg.uid, hg))
        pending[0] += 1

    def worker():
        while True:
            with cv:
                while not heap and pending[0] > 0 and not errors:
                    cv.wait()
                if errors or pending[0] == 0:
                    return
                task = heapq.heappop(heap)[2]
            try:
                if checkpoint is not None:
                    checkpoint()  # cooperative cancellation per task
                arity = h.a[task.depth - 1]
                e = _eps_for(task, h, eps, total_weight, adaptive)
                part = _partition_one(task, arity, e, preset,
                                      seed * 100003 + task.uid, backend,
                                      cache_stats, coarsen)
                record(_next_pow2(task.n), task.n)
                children = _children_of(task, part, h)
            except BaseException as exc:  # propagate to the caller
                with cv:
                    errors.append(exc)
                    cv.notify_all()
                return
            with cv:
                pending[0] -= 1
                for c in children:
                    if c.depth > 0:
                        heapq.heappush(heap, (-c.n, c.uid, c))
                        pending[0] += 1
                    else:
                        out.append(c)
                cv.notify_all()

    threads = [threading.Thread(target=worker) for _ in range(workers)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if errors:
        raise errors[0]
    return out


STRATEGIES = ("naive", "layer", "bucket", "queue", "device")
