"""SharedMap public API.

>>> from repro.core.api import shared_map, SharedMapConfig
>>> res = shared_map(graph, hierarchy)          # the paper's algorithm
>>> res.pe_of                                    # vertex -> PE mapping
>>> res.J                                        # communication cost
"""
from __future__ import annotations

import dataclasses

import jax
import numpy as np

from .graph import Graph
from .hierarchy import Hierarchy
from .mapping import evaluate_J
from .multisection import hierarchical_multisection, new_request_id
from .taskgraph import TaskGraph


@dataclasses.dataclass(frozen=True)
class SharedMapConfig:
    eps: float = 0.03
    preset: str = "eco"          # fast | eco | strong
    strategy: str = "bucket"     # naive | layer | bucket | queue | device
    # ("device" = the fully device-resident level loop: fixed root-shape
    #  schedule, on-device split/eps/pe accumulation, exactly ONE
    #  device->host fetch per request; see core/multisection.py.)
    seed: int = 0
    adaptive: bool = True        # Lemma 5.1 adaptive imbalance
    backend: str = "auto"        # refinement kernels: auto | ell | xla
    # ("ell" = Pallas lp_gain kernels over the padded [N, DEG] adjacency;
    #  "auto" picks it whenever kernels.ops.kernel_backend() is live.)
    refine_mapping: bool = False  # optional block<->PE swap pass. The paper's
    # SharedMap deliberately has none (§6.4) — with a KaFFPa-strength
    # partitioner it is unnecessary. Our JAX substrate partitioner is weaker,
    # so this evens the comparison against GM (which does refine); see
    # DESIGN.md §2.3.


@dataclasses.dataclass
class SharedMapResult:
    pe_of: np.ndarray
    J: float
    stats: dict


# An installed serve.mapper.MappingService (None = direct execution). The
# service registers itself here so `shared_map` callers transparently gain
# cross-request batching and the result cache; the hook lives on this side
# to keep core free of any serve import (serve.mapper imports core).
_SERVICE = None


def install_service(service) -> object | None:
    """Route ``shared_map`` through ``service`` (None = direct path).
    Returns the previously installed service."""
    global _SERVICE
    prev = _SERVICE
    _SERVICE = service
    return prev


def current_service():
    return _SERVICE


def shared_map(g: Graph | TaskGraph, h: Hierarchy,
               config: SharedMapConfig | None = None) -> SharedMapResult:
    """Solve GPMP for communication graph ``g`` on hierarchy ``h``.

    ``g`` is either the canonical CSR :class:`Graph` or a workload-layer
    :class:`TaskGraph` (``core/taskgraph.py``); a TaskGraph is lowered via
    its cached ``to_graph()``, so both spellings produce bit-identical
    results, and the service keys its caches on ``TaskGraph.fingerprint()``.

    When a mapping service is installed (serve.mapper), the request is
    served through it — coalesced with concurrent requests and answered
    from the result cache when possible; results are bit-identical to the
    direct path either way.
    """
    cfg = config or SharedMapConfig()
    if _SERVICE is not None:
        return _SERVICE.map(g, h, cfg)
    return shared_map_direct(g, h, cfg)


def shared_map_direct(g: Graph | TaskGraph, h: Hierarchy, cfg: SharedMapConfig,
                      checkpoint=None, resident=None) -> SharedMapResult:
    """The in-process path (no service indirection); also the fallback the
    service itself uses for the non-plannable strategies (naive/queue).

    ``checkpoint`` (optional zero-arg callable) is invoked between
    multisection levels; raising inside it aborts the run — the service
    uses it to enforce deadlines and shutdown on fallback requests.

    ``resident`` overrides the planner strategies' device residency
    (None = strategy default): the service's shadow verifier passes
    ``resident=False`` to run a request on the bitwise host-ref twin of
    the device pipeline, and its worker processes forward the session's
    device-quarantine decision the same way.

    The call is the trace span ``repro.map``; its finalize and J
    evaluation are ``repro.finalize``. Both carry the request's ``req``
    id, as the planner's spans inside do."""
    req = new_request_id()
    with jax.profiler.TraceAnnotation("repro.map", req=req):
        if isinstance(g, TaskGraph):
            g = g.to_graph()
        res = hierarchical_multisection(
            g, h, eps=cfg.eps, preset=cfg.preset, strategy=cfg.strategy,
            seed=cfg.seed, adaptive=cfg.adaptive, backend=cfg.backend,
            checkpoint=checkpoint, resident=resident, req=req,
        )
        with jax.profiler.TraceAnnotation("repro.finalize", req=req):
            res.pe_of = finalize_mapping(g, h, cfg, res.pe_of, res.stats)
            J = evaluate_J(g, h, res.pe_of)
    return SharedMapResult(pe_of=res.pe_of, J=J, stats=res.stats)


def finalize_mapping(g: Graph, h: Hierarchy, cfg: SharedMapConfig,
                     pe_of: np.ndarray, stats: dict) -> np.ndarray:
    """The shared post-multisection step: optional block<->PE swap pass.
    Split out so the service's planner path applies EXACTLY the same
    finalization as the direct path (bit-identity)."""
    if cfg.refine_mapping:
        from .mapping import quotient_matrix, swap_refine
        C = quotient_matrix(g, pe_of, h.k)
        perm = swap_refine(C, h, np.arange(h.k, dtype=np.int32), seed=cfg.seed)
        pe_of = perm[pe_of].astype(np.int32, copy=False)
        stats["refined"] = True
    return pe_of
