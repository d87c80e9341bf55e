"""Refinement: balance-constrained label-propagation (parallel FM analogue).

Per round, every vertex computes its connectivity to all k blocks in one
sparse pass, proposes the best positive-gain move that respects capacity,
and an admission filter caps inflow per target block at its remaining
capacity. A hash-coloring alternation damps oscillation. A separate forced
`rebalance` pass repairs over-capacity blocks at minimal edge-cut loss
(used after uncoarsening projections).

Backends
--------
The per-round (conn, best, gain) computation has two interchangeable
implementations, selected per call via ``backend=``:

* ``"xla"``  — the original path: a ``segment_sum`` scatter over the
  ``g.rows * k + pcols`` flattened index (O(M) random scatter) and a global
  ``argsort`` + cumsum-prefix admission filter.
* ``"ell"``  — the kernel path: the CSR arrays are reshaped once per call
  into a padded ``[N, DEG]`` ELL adjacency (``graph.ell_adjacency``; DEG is
  the static ``graph.default_ell_deg(N, M)`` cap) and per-round
  connectivity comes from ``kernels.ops.lp_gain`` — the Pallas
  ``lp_gain_pallas`` kernel on TPU, its jnp oracle elsewhere. Admission
  replaces the global argsort with per-block *gain-threshold bisection*
  (``_admit_by_threshold``): ~16 masked segment-sums find, independently
  per target block, the smallest gain cutoff whose admitted inflow fits the
  remaining capacity — O(it·N) work, no sort, no [N, k] cumsum tensor.

  Degree-cap policy: vertices whose degree exceeds DEG (``overflow`` rows)
  have truncated ELL connectivity. `lp_refine` FREEZES them — they are
  excluded from the move candidates, so a truncated gain estimate can
  never admit a cut-worsening move (their neighbours still see them
  through their own rows). `rebalance` keeps them movable with the
  truncated conn: balance feasibility depends only on the exact
  weight/capacity bookkeeping, so forced draining still converges — only
  the min-loss ORDERING is approximate on overflow rows. Both policies
  are branch-free on purpose: a ``lax.cond`` guard would lower to
  ``select`` under ``vmap`` (the bucket/layer batched path) and execute
  the dense scatter AND the kernel every round. On the paper's mesh
  families no row overflows and both passes are exact.

  Ties in the threshold bisection are split by a deterministic per-vertex
  hash jitter (relative magnitude 1e-3) so a tie group larger than the
  remaining capacity is admitted partially, like the argsort prefix,
  instead of being rejected wholesale.
* ``"auto"`` — ``"ell"`` when the Pallas kernels are live
  (``kernels.ops.kernel_backend() != "xla"``, i.e. on TPU or when forced
  via ``REPRO_KERNEL_BACKEND``), else ``"xla"``. Resolution happens at
  trace time: flipping ``REPRO_KERNEL_BACKEND`` mid-process does not
  invalidate programs already compiled under ``backend="auto"``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .graph import (Graph, block_weights, default_ell_deg, edge_mask,
                    ell_adjacency, vertex_mask)
from ..kernels import ops as kops

_NEG = -1e30
_THRESHOLD_ITERS = 24   # bisection resolution: max_gain * 2^-24
_TIE_JITTER = 1e-3      # relative per-vertex jitter splitting gain ties


def _vhash(n: int, salt) -> jax.Array:
    s = jnp.asarray(salt).astype(jnp.uint32) * jnp.uint32(0x9E3779B9)
    x = jnp.arange(n, dtype=jnp.uint32) * jnp.uint32(2654435761) ^ s
    x = (x ^ (x >> 15)) * jnp.uint32(0x2C1B3C6D)
    return x ^ (x >> 12)


def resolve_backend(backend: str, platform: str | None = None) -> str:
    """``auto`` -> ``ell`` or ``xla`` (module docstring). ``platform``
    names the JAX platform the mapping runs on; None asks this process."""
    if backend == "auto":
        return "ell" if kops.kernel_backend(platform) != "xla" else "xla"
    if backend not in ("ell", "xla"):
        raise ValueError(f"unknown refine backend {backend!r}")
    return backend


def connectivity(g: Graph, part: jax.Array, k: int) -> jax.Array:
    """conn[u, b] = summed weight of edges from u into block b.  [N, k]."""
    emask = edge_mask(g)
    pcols = jnp.where(emask, part[g.cols], 0)
    flat = g.rows * k + pcols
    w = jnp.where(emask, g.ewgt, 0.0)
    return jax.ops.segment_sum(w, flat, num_segments=g.N * k).reshape(g.N, k)


def _make_conn_of(g: Graph, k: int, backend: str, ell_deg: int | None):
    """Per-round connectivity closure for the resolved backend.

    Returns ``(conn_of, overflow)``. ``"ell"`` builds the padded [N, DEG]
    adjacency once per call and routes rounds through
    ``kernels.ops.lp_gain`` (the Pallas kernel on TPU); rows flagged in
    ``overflow`` carry TRUNCATED connectivity — callers choose the policy
    (see module docstring). Deliberately branch-free: no ``lax.cond`` on
    the overflow mask, which would lower to ``select`` under ``vmap`` and
    execute both the dense scatter and the kernel. Only the kernel's conn
    output is consumed — best and gain are recomputed under the caller's
    capacity mask.

    ``ell_deg`` is the static degree cap. Callers that know the REAL
    vertex/edge counts (the multisection driver, ``partition_host``)
    should pass one derived from them: the in-jit fallback
    ``default_ell_deg(N, M)`` sees only the padded shapes, and pow2
    padding skews the mean-degree estimate by up to 2x either way.
    """
    if backend != "ell":
        return (lambda part: connectivity(g, part, k)), jnp.zeros((g.N,), bool)
    deg = ell_deg if ell_deg is not None else default_ell_deg(g.N, g.M)
    adj, adw, overflow = ell_adjacency(g, deg)
    return (lambda part: kops.lp_gain(adj, adw, part, k)[0]), overflow


def _admit_by_threshold(cand, best, gbest, vw, cap, k: int, tiebreak,
                        iters: int = _THRESHOLD_ITERS) -> jax.Array:
    """Per-block gain-threshold admission (the argsort-free prefix filter).

    For each target block b, bisect the smallest threshold t_b such that
    the total vertex weight of candidates with ``gbest >= t_b`` targeting b
    fits in ``cap[b]``; admit exactly those. Monotonicity of inflow in t
    makes the bisection exact up to float resolution; the invariant
    ``inflow(hi) <= cap`` holds throughout, so the admitted set always
    respects capacity. ``tiebreak`` ([N] in [0, 1)) perturbs each positive
    gain by a relative ``_TIE_JITTER`` so equal-gain groups admit a partial
    prefix (in hash order) rather than all-or-nothing.
    """
    gbest = gbest * (1.0 + _TIE_JITTER * tiebreak)
    safe_best = jnp.where(cand, best, 0)
    w_cand = jnp.where(cand, vw, 0.0)
    cap = jnp.maximum(cap, 0.0)

    def inflow(t):
        acc = cand & (gbest >= t[safe_best])
        return jax.ops.segment_sum(jnp.where(acc, w_cand, 0.0), safe_best,
                                   num_segments=k)

    hi0 = jnp.max(jnp.where(cand, gbest, 0.0)) + 1.0
    lo = jnp.zeros((k,), jnp.float32)
    hi = jnp.full((k,), hi0, jnp.float32)

    def body(_, lohi):
        lo, hi = lohi
        mid = 0.5 * (lo + hi)
        ok = inflow(mid) <= cap
        return jnp.where(ok, lo, mid), jnp.where(ok, mid, hi)

    lo, hi = jax.lax.fori_loop(0, iters, body, (lo, hi))
    t = jnp.where(inflow(jnp.zeros((k,), jnp.float32)) <= cap, 0.0, hi)
    return cand & (gbest >= t[safe_best])


def _admit_by_argsort(cand, best, gbest, vw, cap, k: int, N: int) -> jax.Array:
    """The original global gain-ranked capacity prefix (xla backend)."""
    order = jnp.argsort(jnp.where(cand, -gbest, jnp.inf), stable=True)
    tgt_s = best[order]
    cand_s = cand[order]
    w_s = jnp.where(cand_s, vw[order], 0.0)
    inflow = jnp.cumsum(jax.nn.one_hot(tgt_s, k, dtype=jnp.float32) * w_s[:, None], axis=0)
    ok_s = cand_s & (
        jnp.take_along_axis(inflow, tgt_s[:, None], axis=1)[:, 0]
        <= jnp.maximum(cap[tgt_s], 0.0)
    )
    return jnp.zeros((N,), bool).at[order].set(ok_s)


@functools.partial(jax.jit, static_argnames=("k", "rounds", "backend", "ell_deg"))
def lp_refine(
    g: Graph,
    part: jax.Array,
    k: int,
    Lmax: jax.Array,
    rounds: int = 4,
    salt: int = 0,
    backend: str = "auto",
    ell_deg: int | None = None,
) -> jax.Array:
    """Gain-positive, capacity-respecting label propagation refinement."""
    backend = resolve_backend(backend)
    N = g.N
    vmask = vertex_mask(g)
    h = _vhash(N, salt)
    tiebreak = (h & jnp.uint32(0xFFFF)).astype(jnp.float32) / float(1 << 16)

    conn_of, overflow = _make_conn_of(g, k, backend, ell_deg)
    movable = vmask & ~overflow  # freeze truncated rows (degree-cap policy)

    def one_round(r, part):
        conn = conn_of(part)
        W = block_weights(g, part, k)
        cur_conn = jnp.take_along_axis(conn, part[:, None], axis=1)[:, 0]
        gain = conn - cur_conn[:, None]
        own = jax.nn.one_hot(part, k, dtype=bool)
        fits = (W[None, :] + g.vwgt[:, None]) <= Lmax
        cand_gain = jnp.where(fits & ~own, gain, _NEG)
        best = jnp.argmax(cand_gain, axis=1).astype(jnp.int32)
        gbest = jnp.max(cand_gain, axis=1)
        color = ((h + jnp.uint32(r)) & jnp.uint32(1)) == 0
        cand = movable & (gbest > 0.0) & color
        cap = Lmax - W
        if backend == "ell":
            accept = _admit_by_threshold(cand, best, gbest, g.vwgt, cap, k, tiebreak)
        else:
            accept = _admit_by_argsort(cand, best, gbest, g.vwgt, cap, k, N)
        return jnp.where(accept, best, part)

    return jax.lax.fori_loop(0, rounds, one_round, part)


@functools.partial(jax.jit, static_argnames=("k", "rounds", "backend", "ell_deg"))
def rebalance(
    g: Graph,
    part: jax.Array,
    k: int,
    Lmax: jax.Array,
    rounds: int = 8,
    salt: int = 1,
    backend: str = "auto",
    ell_deg: int | None = None,
) -> jax.Array:
    """Force epsilon-balance: drain over-capacity blocks via min-loss moves.

    With ``backend="ell"`` connectivity comes from the lp_gain kernel.
    Overflow rows stay MOVABLE on truncated conn — balance feasibility
    rests on the exact weight/capacity bookkeeping, truncation only
    perturbs the min-loss ordering for those rows (see module docstring).
    The min-loss argsort admission is kept (it only bites on over-capacity
    rounds).
    """
    backend = resolve_backend(backend)
    N = g.N
    vmask = vertex_mask(g)
    conn_of, _ = _make_conn_of(g, k, backend, ell_deg)

    def one_round(r, part):
        conn = conn_of(part)
        W = block_weights(g, part, k)
        overflow_w = jnp.maximum(W - Lmax, 0.0)  # [k]
        cur_conn = jnp.take_along_axis(conn, part[:, None], axis=1)[:, 0]
        loss = cur_conn[:, None] - conn  # cost of moving u -> b
        own = jax.nn.one_hot(part, k, dtype=bool)
        fits = (W[None, :] + g.vwgt[:, None]) <= Lmax
        cand_loss = jnp.where(fits & ~own, loss, jnp.inf)
        tgt = jnp.argmin(cand_loss, axis=1).astype(jnp.int32)
        lbest = jnp.min(cand_loss, axis=1)
        src_over = overflow_w[part] > 0.0
        cand = vmask & src_over & jnp.isfinite(lbest) & (g.vwgt > 0.0)
        order = jnp.argsort(jnp.where(cand, lbest, jnp.inf), stable=True)
        src_s = part[order]
        tgt_s = tgt[order]
        cand_s = cand[order]
        w_s = jnp.where(cand_s, g.vwgt[order], 0.0)
        outflow = jnp.cumsum(jax.nn.one_hot(src_s, k, dtype=jnp.float32) * w_s[:, None], axis=0)
        inflow = jnp.cumsum(jax.nn.one_hot(tgt_s, k, dtype=jnp.float32) * w_s[:, None], axis=0)
        # drain only what is needed (allow the boundary-crossing move), fill
        # targets only up to capacity.
        out_ok = (jnp.take_along_axis(outflow, src_s[:, None], axis=1)[:, 0] - w_s) < overflow_w[src_s]
        in_ok = jnp.take_along_axis(inflow, tgt_s[:, None], axis=1)[:, 0] <= jnp.maximum(Lmax - W, 0.0)[tgt_s]
        ok_s = cand_s & out_ok & in_ok
        accept = jnp.zeros((N,), bool).at[order].set(ok_s)
        return jnp.where(accept, tgt, part)

    return jax.lax.fori_loop(0, rounds, one_round, part)


def is_balanced(g: Graph, part: jax.Array, k: int, Lmax) -> jax.Array:
    return jnp.all(block_weights(g, part, k) <= Lmax + 1e-6)
