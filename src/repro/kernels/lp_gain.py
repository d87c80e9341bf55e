"""Pallas TPU kernel: label-propagation connectivity (refinement hot spot).

The C++ hot loop iterates each vertex's adjacency list and accumulates
per-block connectivity in a sparse map. The TPU-native layout is the
transposed ELL adjacency ``[DEG, N]``: vertices on the 128-wide lane axis,
neighbour slots on sublanes. XLA gathers the neighbours' block ids
(``part[adj]``) before the kernel; each program instance then streams
``[DEG, TILE_V]`` tiles of block ids and weights from HBM into VMEM and
accumulates the ``[k, TILE_V]`` connectivity on the VPU with one
compare+select+add per neighbour slot (``ref.block_conn_t``, shared with
the oracle, so the backends agree bitwise). The best alternative block and
its gain are cheap ``[N, k]`` reductions left to XLA (``ref.best_gain``).

VMEM per instance: 2 * DEG * TILE_V * 4 B of input tiles plus
k * TILE_V * 4 B of output, double-buffered — about 0.4 MB at DEG=64,
k=16.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import ref

TILE_V = 256  # vertices (lanes) per program instance


def _lp_conn_kernel(nbr_ref, adw_ref, conn_ref, *, k: int):
    conn_ref[...] = ref.block_conn_t(nbr_ref[...], adw_ref[...], k)


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def lp_gain_pallas(
    adj: jax.Array,   # [N, DEG] i32 (padded neighbour id == N)
    adw: jax.Array,   # [N, DEG] f32
    part: jax.Array,  # [N] i32
    k: int,
    *,
    interpret: bool,
):
    """Returns (conn [N,k], best [N], gain [N]) for every vertex."""
    N, DEG = adj.shape
    Np = -(-N // TILE_V) * TILE_V
    nbr_t = jnp.pad(ref.neighbour_blocks_t(adj, part, k),
                    ((0, 0), (0, Np - N)), constant_values=k)
    adw_t = jnp.pad(adw.astype(jnp.float32).T, ((0, 0), (0, Np - N)))
    conn_t = pl.pallas_call(
        functools.partial(_lp_conn_kernel, k=k),
        grid=(Np // TILE_V,),
        in_specs=[
            pl.BlockSpec((DEG, TILE_V), lambda i: (0, i)),
            pl.BlockSpec((DEG, TILE_V), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((k, TILE_V), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((k, Np), jnp.float32),
        interpret=interpret,
    )(nbr_t, adw_t)
    conn = conn_t[:, :N].T
    best, gain = ref.best_gain(conn, part)
    return conn, best, gain
