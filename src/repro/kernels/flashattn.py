"""Pallas TPU kernel: flash attention (tiled online-softmax SDPA).

Why it's here (§Roofline): every prefill/train cell's memory term is
dominated by materializing [B,H,Sq,Sk] logits/probs in HBM. Flash keeps the
whole softmax in VMEM: HBM traffic collapses to Q/K/V/O streaming —
per (q-tile, k-tile) pass nothing but the inputs moves.

Layout: inputs [BH, S, D] (heads pre-flattened, GQA pre-expanded by ops.py).
Grid (BH, Sq/BQ, Sk/BK); the innermost grid dim is "arbitrary" (sequential)
so VMEM scratch (running max m, normalizer l, accumulator acc) carries
across k-tiles — the standard TPU flash pattern. Tile sizes are multiples
of (8, 128): BQ=128, BK=128, D padded to 128.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30
BQ = 128
BK = 128


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
                  *, scale: float, causal: bool, nk: int, window: int,
                  s_real: int):
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)                       # [BQ, D]
    k = k_ref[0].astype(jnp.float32)                       # [BK, D]
    v = v_ref[0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale  # [BQ, BK]

    rows = iq * BQ + jax.lax.broadcasted_iota(jnp.int32, (BQ, BK), 0)
    cols = ik * BK + jax.lax.broadcasted_iota(jnp.int32, (BQ, BK), 1)
    mask = cols < s_real  # padded key columns never win the softmax
    if causal:
        mask &= cols <= rows
    if window > 0:
        mask &= rows - cols < window
    s = jnp.where(mask, s, NEG)

    m_prev = m_scr[...]                                    # [BQ]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    alpha = jnp.exp(m_prev - m_new)                        # [BQ]
    p = jnp.exp(s - m_new[:, None])                        # [BQ, BK]
    l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=1)
    acc_scr[...] = alpha[:, None] * acc_scr[...] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())))
    m_scr[...] = m_new

    @pl.when(ik == nk - 1)
    def _finish():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "interpret"))
def flash_attention_pallas(q, k, v, causal: bool = True, window: int = 0,
                           *, interpret: bool):
    """q/k/v [BH, S, D] -> o [BH, S, D]. D and S padded to tile multiples."""
    BH, S, D = q.shape
    scale = D ** -0.5
    Sp = ((S + BQ - 1) // BQ) * BQ
    Dp = ((D + 127) // 128) * 128
    pad = lambda x: jnp.pad(x, ((0, 0), (0, Sp - S), (0, Dp - D)))
    qp, kp, vp = pad(q), pad(k), pad(v)
    # padded k rows must never win the softmax: handled by the causal/window
    # masks for in-range rows; for pure bidirectional pads, mask via column
    # index >= S:
    nq, nk = Sp // BQ, Sp // BK

    kern = functools.partial(_flash_kernel, scale=scale, causal=causal,
                             nk=nk, window=window, s_real=S)
    out = pl.pallas_call(
        kern,
        grid=(BH, nq, nk),
        in_specs=[
            pl.BlockSpec((1, BQ, Dp), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, BK, Dp), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, BK, Dp), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, BQ, Dp), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Sp, Dp), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((BQ,), jnp.float32),
            pltpu.VMEM((BQ,), jnp.float32),
            pltpu.VMEM((BQ, Dp), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qp, kp, vp)
    return out[:, :S, :D]
