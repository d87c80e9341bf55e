"""Pallas TPU kernels for coarsening: HEM proposals + contraction merge.

Coarsening is the last multilevel stage without a kernel path: the seed's
``coarsen.hem_match`` runs two ``segment_max``/``segment_min`` scatter
passes per round over the ``[M]`` edge arrays, and ``coarsen.contract``
two stable argsorts. The TPU-native restatement works per vertex over the
transposed ELL adjacency ``[DEG, N]`` (vertices on lanes, as in
``lp_gain``):

``hem_propose`` — XLA gathers the matched flags of every neighbour slot
    (``matched[adj]``); one program instance then scans ``TILE_V``
    vertices: the per-vertex max jittered score and the smallest-id
    tie-break, written as a lane-dense ``[1, TILE_V]`` row. Replaces both
    segment passes with a single streaming pass, no scatters.

``contract_edges`` — one program instance merges ``TILE_C`` coarse rows:
    each column holds the ``2*DEG`` coarse-mapped neighbour candidates of
    its (<= 2) fine members; a fixed-order compare/accumulate chain dedups
    ids and sums weights. The distinct count per row is an integer sum
    left to XLA. Fully tiled — NO resident vectors — so it scales with
    HBM, not VMEM.

Both kernel bodies execute the SAME jnp code as the oracles
(kernels/ref.py: ``hem_scan_t`` / ``merge_dedup_t``), so pallas /
interpret / xla agree BITWISE: score math is elementwise f32, reductions
are max/min/int-only, and weight totals are a fixed add chain XLA never
reassociates. Tested in tests/test_coarsen_kernels.py; compiled for the
TPU in tests/test_tpu_compile.py.

VMEM per instance: hem_propose holds four ``[DEG, TILE_V]`` tiles;
contract_edges about ``TILE_C * 2*DEG * 4 * 4`` bytes. See DESIGN.md §13.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import ref

TILE_V = 256   # hem_propose vertices (lanes) per program instance
TILE_C = 256   # contract_edges rows (lanes) per program instance


def _hem_propose_kernel(adj_ref, adw_ref, jit_ref, nbm_ref, own_ref, prop_ref,
                        *, n_ids: int):
    T = adj_ref.shape[1]
    u = pl.program_id(0) * T + jax.lax.broadcasted_iota(jnp.int32, (1, T), 1)
    prop_ref[...] = ref.hem_scan_t(adj_ref[...], adw_ref[...], jit_ref[...],
                                   nbm_ref[...], own_ref[...], u, n_ids)


@functools.partial(jax.jit, static_argnames=("interpret",))
def hem_propose_pallas(adj: jax.Array, adw: jax.Array, jit: jax.Array,
                       matched: jax.Array, *, interpret: bool) -> jax.Array:
    """Per-vertex HEM proposal over ELL adjacency. Returns [N] i32 (N = none).

    ``matched`` is the [N] 0/1 i32 matched vector; tile-padding vertices
    are marked matched so they propose nothing.
    """
    N, DEG = adj.shape
    Np = -(-N // TILE_V) * TILE_V
    padc = lambda a, v=0: jnp.pad(a, ((0, 0), (0, Np - N)), constant_values=v)
    row = pl.BlockSpec((1, TILE_V), lambda i: (0, i))
    tile = pl.BlockSpec((DEG, TILE_V), lambda i: (0, i))
    prop = pl.pallas_call(
        functools.partial(_hem_propose_kernel, n_ids=N),
        grid=(Np // TILE_V,),
        in_specs=[tile, tile, tile, tile, row],
        out_specs=row,
        out_shape=jax.ShapeDtypeStruct((1, Np), jnp.int32),
        interpret=interpret,
    )(padc(adj.T, N), padc(adw.T), padc(jit.T),
      padc(ref.neighbour_matched_t(adj, matched), 1),
      padc(matched[None, :], 1))
    return prop[0, :N]


def _contract_edges_kernel(cand_ref, candw_ref, nbr_ref, w_ref, *, sent: int):
    nbr_ref[...], w_ref[...] = ref.merge_dedup_t(cand_ref[...], candw_ref[...],
                                                 sent)


@functools.partial(jax.jit, static_argnames=("interpret",))
def contract_edges_pallas(cand: jax.Array, candw: jax.Array, *,
                          interpret: bool):
    """Row-local merge/dedup/accumulate for contraction.

    ``cand [N, D2]`` holds coarse neighbour ids (sentinel ``N`` = invalid,
    weight 0). Returns ``(nbr [N, D2], w [N, D2], cnt [N])`` — see
    ref.merge_dedup_t. Fully tiled: no resident vectors.
    """
    N, D2 = cand.shape
    Np = -(-N // TILE_C) * TILE_C
    tile = pl.BlockSpec((D2, TILE_C), lambda i: (0, i))
    nbr_t, w_t = pl.pallas_call(
        functools.partial(_contract_edges_kernel, sent=N),
        grid=(Np // TILE_C,),
        in_specs=[tile, tile],
        out_specs=[tile, tile],
        out_shape=[
            jax.ShapeDtypeStruct((D2, Np), jnp.int32),
            jax.ShapeDtypeStruct((D2, Np), jnp.float32),
        ],
        interpret=interpret,
    )(jnp.pad(cand.T, ((0, 0), (0, Np - N)), constant_values=N),
      jnp.pad(candw.astype(jnp.float32).T, ((0, 0), (0, Np - N))))
    nbr = nbr_t[:, :N].T
    return nbr, w_t[:, :N].T, ref.distinct_counts(nbr, N)
