"""Mixture-of-Experts with expert parallelism over the `model` mesh axis.

Design (DESIGN.md §5): activations are replicated over `model` (the
Megatron invariant), so expert dispatch needs NO all-to-all — each device
locally gathers the tokens routed to the experts it owns, computes, and the
per-layer TP all-reduce (psum) combines expert outputs and d_ff shards in
one collective.

Virtual-expert layout: the E physical experts are laid out over the
``V = |model axis|`` devices as ``[V, E_loc, D, F_v]``:

* E >= V: each device owns ``E_loc = E/V`` full experts   (F_v = F)
* E <  V: each expert is split into ``V/E`` d_ff shards    (E_loc = 1,
  F_v = F*E/V); the shards of one expert gather the same tokens and the
  final psum sums their partial w_down outputs — numerically identical to
  the unsharded expert.

Capacity dispatch: per (device, physical expert), the ``C`` highest-router-
probability tokens of the LOCAL batch shard are kept (standard
prob-priority capacity policy, cf. GShard/Switch); dropped tokens pass
through the residual stream. C = ceil(T_loc * top_k / E * capacity_factor).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from .config import ModelConfig
from .layers import dense_init
from .sharding import ShardCtx


def moe_layout(cfg: ModelConfig, V: int) -> tuple[int, int]:
    """(E_loc, F_v) for a given virtual-expert count V."""
    E, F = cfg.num_experts, cfg.d_ff
    if E >= V:
        if E % V:
            raise ValueError(f"num_experts {E} not divisible by mesh model axis {V}")
        return E // V, F
    if V % E or F % (V // E):
        raise ValueError(f"cannot split {E} experts / d_ff {F} over {V} devices")
    return 1, F * E // V


def moe_params(cfg: ModelConfig, key, V: int = 1):
    D, E = cfg.d_model, cfg.num_experts
    E_loc, F_v = moe_layout(cfg, V)
    ks = jax.random.split(key, 4)
    return {
        "router": dense_init(ks[0], (D, E)),
        "w_gate": dense_init(ks[1], (V, E_loc, D, F_v)),
        "w_up": dense_init(ks[2], (V, E_loc, D, F_v)),
        "w_down": dense_init(ks[3], (V, E_loc, F_v, D)),
    }


def _phys_expert_ids(cfg: ModelConfig, V: int, virt: jax.Array) -> jax.Array:
    """[E_loc] physical expert ids owned by virtual shard ``virt``."""
    E = cfg.num_experts
    E_loc, _ = moe_layout(cfg, V)
    if E >= V:
        return virt * E_loc + jnp.arange(E_loc, dtype=jnp.int32)
    return (virt // (V // E))[None].astype(jnp.int32)


def moe_ffn_shard(cfg: ModelConfig, x, router, w_gate, w_up, w_down, virt, V: int):
    """Per-shard MoE: x [T, D] local tokens; w_* [E_loc, D|F_v, F_v|D].

    Returns the PARTIAL output [T, D]; caller psums over the model axis.
    """
    T, D = x.shape
    E = cfg.num_experts
    E_loc, F_v = moe_layout(cfg, V)
    C = max(int(-(-T * cfg.top_k * cfg.capacity_factor // E)), 4)
    C = min(C, T)

    logits = (x @ router.astype(x.dtype)).astype(jnp.float32)      # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    top_vals, top_idx = jax.lax.top_k(probs, cfg.top_k)            # [T, K]
    gates = top_vals / jnp.sum(top_vals, axis=-1, keepdims=True)   # renormalized

    mine = _phys_expert_ids(cfg, V, virt)                          # [E_loc]
    # score[e_loc, t] = gate if token t routed my expert e_loc else -inf
    hit = top_idx[None, :, :] == mine[:, None, None]               # [E_loc, T, K]
    score = jnp.max(jnp.where(hit, gates[None], -jnp.inf), axis=-1)  # [E_loc, T]
    cap_vals, cap_idx = jax.lax.top_k(score, C)                    # [E_loc, C]
    keep = jnp.isfinite(cap_vals)
    w_tok = jnp.where(keep, cap_vals, 0.0).astype(x.dtype)         # [E_loc, C]
    xe = jnp.take(x, jnp.where(keep, cap_idx, 0), axis=0)          # [E_loc, C, D]

    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, w_gate.astype(x.dtype)))
    h = h * jnp.einsum("ecd,edf->ecf", xe, w_up.astype(x.dtype))
    ye = jnp.einsum("ecf,efd->ecd", h, w_down.astype(x.dtype))     # [E_loc, C, D]
    ye = ye * w_tok[..., None]

    out = jnp.zeros((T, D), x.dtype)
    out = out.at[cap_idx.reshape(-1)].add(
        ye.reshape(-1, D), mode="drop",
        indices_are_sorted=False, unique_indices=False,
    )
    return out


def apply_moe(cfg: ModelConfig, p, x, ctx: ShardCtx | None):
    """x [B, S, D] -> [B, S, D]."""
    B, S, D = x.shape

    if ctx is None or ctx.model_size == 1:
        out = moe_ffn_shard(
            cfg, x.reshape(-1, D), p["router"], p["w_gate"][0], p["w_up"][0],
            p["w_down"][0], jnp.asarray(0, jnp.int32), V=1,
        )
        return out.reshape(B, S, D)

    V = ctx.model_size
    from .sharding import batch_spec as _bspec
    bspec = _bspec(ctx)
    maxis = ctx.model_axis
    wspec = P(maxis, None, "data" if ctx.zero3 else None, None)

    def shard_fn(xs, router, wg, wu, wd):
        # xs [B_loc, S, D] replicated over model; w* [1, E_loc, D(/dp), F_v]
        virt = jax.lax.axis_index(maxis)
        if ctx.zero3:
            wg = jax.lax.all_gather(wg, "data", axis=2, tiled=True)
            wu = jax.lax.all_gather(wu, "data", axis=2, tiled=True)
            wd = jax.lax.all_gather(wd, "data", axis=3, tiled=True)
        out = moe_ffn_shard(
            cfg, xs.reshape(-1, D), router, wg[0], wu[0], wd[0], virt, V=V
        )
        return jax.lax.psum(out.reshape(xs.shape), maxis)

    return shard_map(
        shard_fn,
        mesh=ctx.mesh,
        in_specs=(
            P(bspec, None, None),
            P(None, None),
            wspec, wspec,
            P(maxis, None, None, "data" if ctx.zero3 else None),
        ),
        out_specs=P(bspec, None, None),
        check_vma=False,
    )(x, p["router"], p["w_gate"], p["w_up"], p["w_down"])
