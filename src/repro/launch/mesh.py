"""Production meshes — with SharedMap-driven device placement.

``make_production_mesh`` builds the assigned meshes:
  single-pod: (data=16, model=16) = 256 chips
  multi-pod : (pod=2, data=16, model=16) = 512 chips

``device_order="sharedmap"`` is the paper-as-placement-engine integration
(DESIGN.md §3): the logical communication graph of a sharded training step
(heavy TP collectives over `model`, DP ring over `data`, DCN over `pod`) is
mapped onto the physical chip hierarchy by hierarchical multisection, and
the mesh's device array is laid out accordingly. On the homogeneous
hierarchy this reproduces the default row-major order up to group symmetry
(asserted in tests) and strictly beats scrambled orders (benchmarks).
"""
from __future__ import annotations

import numpy as np

import jax

from repro.core.api import SharedMapConfig, shared_map
from repro.core.hierarchy import Hierarchy
from repro.core.taskgraph import TaskGraph


def make_production_mesh(*, multi_pod: bool = False, device_order: str = "default"):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    if device_order == "default":
        return jax.make_mesh(shape, axes,
                             axis_types=(jax.sharding.AxisType.Auto,) * len(axes))
    if device_order == "sharedmap":
        perm = sharedmap_device_order(multi_pod=multi_pod)
        devices = np.asarray(jax.devices())[perm].reshape(shape)
        return jax.sharding.Mesh(
            devices, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))
    raise ValueError(device_order)


def logical_comm_graph(multi_pod: bool = False,
                       w_model: float = 100.0, w_data: float = 10.0,
                       w_pod: float = 1.0) -> TaskGraph:
    """Communication graph of one train step between LOGICAL mesh positions,
    as a workload-layer :class:`TaskGraph` (PR 10 ingestion refactor —
    ``.to_graph()`` lowers it to the CSR the mapping kernels consume).

    Edge weights ~ relative bytes: TP collectives (all-gather/all-reduce
    over `model`) dominate, DP gradient ring over `data` is second, pod-axis
    DCN gradient reduction is third (but rides the slowest link — the
    hierarchy's top level).
    """
    pods = 2 if multi_pod else 1
    k = pods * 16 * 16
    idx = np.arange(k).reshape(pods, 16, 16)
    us, vs, ws = [], [], []

    def add(u, v, w):
        us.append(u.ravel())
        vs.append(v.ravel())
        ws.append(np.full(u.size, w))

    # model axis: ring segments (XLA lowers all-gather/reduce-scatter to rings)
    add(idx[:, :, :-1], idx[:, :, 1:], w_model)
    add(idx[:, :, -1], idx[:, :, 0], w_model)        # ring wrap
    # data axis: gradient reduction ring
    add(idx[:, :-1, :], idx[:, 1:, :], w_data)
    add(idx[:, -1, :], idx[:, 0, :], w_data)
    # pod axis: DCN all-reduce pairs
    if pods > 1:
        add(idx[0], idx[1], w_pod)

    u = np.concatenate(us)
    v = np.concatenate(vs)
    w = np.concatenate(ws)
    return TaskGraph.from_edges(
        k, u, v, w,
        meta={"source": "logical_mesh", "multi_pod": multi_pod,
              "weights": {"model": w_model, "data": w_data, "pod": w_pod}})


def physical_hierarchy(multi_pod: bool = False) -> Hierarchy:
    """Chip topology as a process-mapping hierarchy (innermost first):
    16 chips/rack : 16 racks/pod : pods, D = intra-rack ICI 1, inter-rack
    ICI 10, DCN 100."""
    if multi_pod:
        return Hierarchy(a=(16, 16, 2), d=(1.0, 10.0, 100.0))
    return Hierarchy(a=(16, 16), d=(1.0, 10.0))


def sharedmap_device_order(multi_pod: bool = False, seed: int = 0) -> np.ndarray:
    """perm[logical_flat_position] = physical chip id.

    n == k makes this the ONE-TO-ONE process mapping problem (OPMP/QAP), so
    the right machinery is the mapping phase of the two-phase approach
    (paper §3): Müller-Merbach greedy construction + distance-restricted
    pair swaps on the dense logical communication matrix. (Hierarchical
    multisection with singleton blocks degenerates here.) The result is
    seeded from the default (hierarchy-aligned) order, so SharedMap can only
    improve on it."""
    from repro.core.mapping import greedy_mapping, map_cost_dense, swap_refine

    tg = logical_comm_graph(multi_pod=multi_pod)
    h = physical_hierarchy(multi_pod=multi_pod)
    k = h.k
    C = np.zeros((k, k))
    np.add.at(C, (tg.u, tg.v), tg.w.astype(np.float64))
    np.add.at(C, (tg.v, tg.u), tg.w.astype(np.float64))
    D = h.distance_table()

    candidates = [np.arange(k, dtype=np.int64)]           # default order
    candidates.append(greedy_mapping(C, h))                # greedy QAP
    best = min(candidates, key=lambda p: map_cost_dense(C, D, p))
    return swap_refine(C, h, best, seed=seed)
