"""Pallas TPU kernel: J(C,D,Pi) communication-cost evaluation.

Edge-parallel: XLA gathers both endpoint PE ids (``pe_of[rows]``,
``pe_of[cols]``) and lays the edges out as ``[R, 128]`` tiles
(``ref.edge_tiles``); the grid streams ``(EDGE_TILE_ROWS, 128)`` tiles of
(pe_u, pe_v, w) from HBM into VMEM, computes the hierarchy distance with
the mixed-radix group-id comparison in registers, and writes one aligned
``(8, 128)`` block of partial sums per tile. ``ops.mapcost`` folds the
partials into J with one program shared by both backends
(``ref.fold_partials``).

TPU adaptation notes:
* The hot operation in the C++ code is a scalar hash-table / array gather
  per edge; here the per-edge distance is a dense [TILE, l] integer-divide
  + compare + count on the VPU — no MXU involvement.
* The tiny ``g_below``/``dvec`` tables live in SMEM as scalars.
* The tile body (``ref.edge_cost_partials``) is shared with the oracle and
  folds its rows in a fixed add chain, so the partials are bitwise equal
  across backends.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import ref


def _mapcost_kernel(gb_ref, dv_ref, pu_ref, pv_ref, w_ref, out_ref):
    l = gb_ref.shape[0]
    out_ref[...] = ref.edge_cost_partials(
        pu_ref[...], pv_ref[...], w_ref[...],
        [gb_ref[i] for i in range(l)], [dv_ref[i] for i in range(l)])


@functools.partial(jax.jit, static_argnames=("interpret",))
def mapcost_pallas(
    rows: jax.Array,
    cols: jax.Array,
    ewgt: jax.Array,
    pe_of: jax.Array,
    g_below: jax.Array,
    dvec: jax.Array,
    *,
    interpret: bool,
) -> jax.Array:
    """Partial sums of J(C,D,Pi) via the Pallas kernel (edges padded to
    whole tiles); bitwise equal to ``ref.mapcost_partials_ref``."""
    pu, pv, w = ref.edge_tiles(rows, cols, ewgt, pe_of)
    t = pu.shape[0] // ref.EDGE_TILE_ROWS
    tile = pl.BlockSpec((ref.EDGE_TILE_ROWS, ref.LANES), lambda i: (i, 0))
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    partial = pl.pallas_call(
        _mapcost_kernel,
        grid=(t,),
        in_specs=[smem, smem, tile, tile, tile],
        out_specs=pl.BlockSpec((ref.SUBLANES, ref.LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((t * ref.SUBLANES, ref.LANES),
                                       jnp.float32),
        interpret=interpret,
    )(g_below.astype(jnp.int32), dvec.astype(jnp.float32), pu, pv, w)
    return partial
