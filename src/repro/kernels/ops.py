"""Jitted public wrappers for the kernels — the single dispatch point.

Every caller that wants a kernel (refinement gain pass, coarsening,
mapping-cost evaluation, attention) goes through this module; nothing else
in the repo decides pallas-vs-XLA on its own. The policy lives in one
helper:

``kernel_backend()`` returns one of

* ``"pallas"``    — the default on a TPU: Pallas kernels run COMPILED
                    (``interpret=False``).
* ``"interpret"`` — only when ``REPRO_KERNEL_BACKEND=interpret`` asks for
                    it: Pallas kernels run under the interpreter (parity
                    testing on the CPU; slow).
* ``"xla"``       — the default off a TPU: the pure-jnp reference
                    implementations, which XLA fuses well.

``REPRO_KERNEL_BACKEND`` overrides the device-derived default with any of
the three values; any other value raises. A per-call ``use_pallas=`` picks
kernel or reference but never the mode: the kernel runs compiled unless
the environment asks for interpret mode.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp

from . import ref
from .coarsen_kernels import contract_edges_pallas, hem_propose_pallas
from .flashattn import flash_attention_pallas
from .lp_gain import lp_gain_pallas
from .mapcost import mapcost_pallas

BACKENDS = ("pallas", "interpret", "xla")


def kernel_backend(platform: str | None = None) -> str:
    """Resolve the kernel dispatch policy (see module docstring).

    ``platform`` names the JAX platform the kernels will run on; None asks
    this process's default backend (which starts it).
    """
    forced = os.environ.get("REPRO_KERNEL_BACKEND", "")
    if forced:
        if forced not in BACKENDS:
            raise ValueError(f"REPRO_KERNEL_BACKEND={forced!r} is not one of "
                             f"{BACKENDS}")
        return forced
    if platform is None:
        platform = jax.default_backend()
    return "pallas" if platform == "tpu" else "xla"


def dispatch(use_pallas: bool | None = None) -> tuple[bool, bool]:
    """(use_pallas, interpret) for a kernel call.

    ``use_pallas=None`` defers to :func:`kernel_backend`; an explicit bool
    chooses kernel or reference. Interpret mode is on only where
    ``REPRO_KERNEL_BACKEND=interpret`` says so.
    """
    backend = kernel_backend()
    if use_pallas is None:
        use_pallas = backend != "xla"
    return use_pallas, backend == "interpret"


def _evaluating(fn):
    """``fn`` traced under the ``evaluate`` scope, so that a profiler trace
    puts the J evaluation's device ops down to it. A scope around an eager
    call does not reach the ops of the programs it runs: it has to be
    inside the traced function."""
    def run(*args, **kwargs):
        with jax.named_scope("evaluate"):
            return fn(*args, **kwargs)
    return run


_mapcost_partials_ref = jax.jit(_evaluating(ref.mapcost_partials_ref))
_mapcost_partials_pallas = jax.jit(_evaluating(mapcost_pallas),
                                   static_argnames=("interpret",))
_fold_partials = jax.jit(_evaluating(ref.fold_partials))


def mapcost(rows, cols, ewgt, pe_of, g_below, dvec, use_pallas: bool | None = None):
    """J(C, D, Pi) over directed edge arrays (padding weight must be 0).

    Kernel and reference produce bitwise-equal partial sums, and one
    separately compiled program folds either, so J agrees bitwise too.
    """
    use_pallas, interpret = dispatch(use_pallas)
    if use_pallas:
        part = _mapcost_partials_pallas(rows, cols, ewgt, pe_of, g_below,
                                        dvec, interpret=interpret)
    else:
        part = _mapcost_partials_ref(rows, cols, ewgt, pe_of, g_below, dvec)
    return _fold_partials(part)


def lp_gain(adj, adw, part, k: int, use_pallas: bool | None = None):
    """(conn, best, gain) for balanced LP refinement over an ELL adjacency."""
    use_pallas, interpret = dispatch(use_pallas)
    if use_pallas:
        return lp_gain_pallas(adj, adw, part, k, interpret=interpret)
    return ref.lp_gain_ref(adj, adw, part, k)


def gather_rows(src, idx):
    """Masked-compaction gather for the split op: out[b,j] = src[idx[b,j]].

    ``idx`` is clipped in range. This stays in XLA on every backend: it is
    a 1-D gather, which Mosaic does not lower, and pure data movement, so
    there is nothing for a kernel to add.
    """
    return jnp.take(src, jnp.clip(idx, 0, src.shape[0] - 1))


def hem_propose(adj, adw, jit, matched, use_pallas: bool | None = None):
    """Per-row HEM proposal scan over the [N, DEG] ELL adjacency.

    ``matched`` is the [N] 0/1 i32 matched vector; returns [N] i32
    proposals (N = no proposal). Score math is elementwise f32 and the
    only reductions are max/min, so pallas/interpret/xla agree BITWISE
    (the coarsening cascade's determinism depends on this; tested in
    test_coarsen_kernels).
    """
    use_pallas, interpret = dispatch(use_pallas)
    if use_pallas:
        return hem_propose_pallas(adj, adw, jit, matched, interpret=interpret)
    return ref.hem_propose_ref(adj, adw, jit, matched)


def contract_edges(cand, candw, use_pallas: bool | None = None):
    """Row-local merge/dedup/accumulate for contraction.

    ``cand [N, D2]`` holds the coarse-mapped neighbour candidates of each
    coarse row's fine members (sentinel N = invalid, weight 0). Returns
    ``(nbr, w, cnt)``; weight totals use a fixed add chain, so backends
    agree BITWISE (see kernels/ref.py:merge_dedup_t).
    """
    use_pallas, interpret = dispatch(use_pallas)
    if use_pallas:
        return contract_edges_pallas(cand, candw, interpret=interpret)
    return ref.contract_edges_ref(cand, candw, cand.shape[0])


def flash_attention(q, k, v, causal: bool = True, window: int = 0,
                    use_pallas: bool | None = None):
    """Tiled-softmax SDPA. q [B,S,H,D], k/v [B,S,Hkv,D] (GQA expanded here).

    On TPU this is the fix for the prefill/train memory roofline term:
    no [B,H,S,S] logits ever touch HBM (see kernels/flashattn.py)."""
    B, S, H, D = q.shape
    rep = H // k.shape[2]
    if rep > 1:
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    flat = lambda x: jnp.swapaxes(x, 1, 2).reshape(B * H, S, D)
    use_pallas, interpret = dispatch(use_pallas)
    if use_pallas:
        o = flash_attention_pallas(flat(q), flat(k), flat(v), causal, window,
                                   interpret=interpret)
    else:
        o = ref.flash_ref(flat(q), flat(k), flat(v), causal, window)
    return jnp.swapaxes(o.reshape(B, H, S, D), 1, 2)
