"""Operations and bytes of the program's Pallas kernels, from the shapes a
TPU trace gives for each kernel call, and the chip's peaks.

A TPU trace names each op event by its HLO text, so a kernel call reads
``%vmap_jit_lp_gain_pallas___.3 = f32[B,k,Np]{...} custom-call(...),
custom_call_target="tpu_custom_call", operand_layout_constraints={...}``;
the leading batch dimensions are the lanes a vmap adds.
"""
from __future__ import annotations

import json
import math
import re
from pathlib import Path

_ARRAY = re.compile(r"\b(pred|s8|u8|s16|u16|s32|u32|s64|u64|bf16|f16|f32|f64)"
                    r"\[([0-9,]*)\]")
_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
          "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
          "f64": 8}


def peaks(kind: str) -> dict:
    """The row of ``peaks.json`` for a device kind; an unknown kind is an
    error, not a default."""
    table = json.loads((Path(__file__).with_name("peaks.json")).read_text())
    if kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    return table["devices"][kind]


def arrays(text: str) -> list[tuple[str, tuple[int, ...]]]:
    return [(dt, tuple(int(x) for x in dims.split(",") if x))
            for dt, dims in _ARRAY.findall(text)]


def nbytes(arr: tuple[str, tuple[int, ...]]) -> int:
    return _BYTES[arr[0]] * math.prod(arr[1])


def _bracketed(text: str, opener: str) -> str | None:
    """What follows ``opener`` up to its matching closing bracket."""
    at = text.find(opener)
    if at < 0:
        return None
    depth = 1
    for i in range(at + len(opener), len(text)):
        depth += text[i] in "([{"
        depth -= text[i] in ")]}"
        if depth == 0:
            return text[at + len(opener):i]
    return None


def kernel_call_shapes(name: str, kernel: str):
    """``(results, operands)`` of a call of the Pallas kernel whose wrapper
    is named ``kernel``, from the HLO text a TPU trace names its op by, or
    None when the op is not such a call. XLA names a Mosaic call after the
    JAX function that made it (``%vmap_vmap_jit_lp_gain_pallas___.1``); the
    operands' shapes are in the operand list or, where that gives names
    only, in ``operand_layout_constraints``."""
    if " = " not in name:
        return None
    head, rhs = name.split(" = ", 1)
    if kernel not in head or "custom-call(" not in rhs:
        return None
    results = arrays(rhs.split("custom-call(", 1)[0])
    operands = arrays(_bracketed(rhs, "custom-call(") or "")
    if not operands:
        operands = arrays(_bracketed(rhs, "operand_layout_constraints={")
                          or "")
    return results, operands


def lp_gain_cost(name: str):
    """``(ops, bytes)`` of one ``lp_gain`` kernel call (``kernels/lp_gain.py``),
    or None when the op is something else. Block ids ``s32[..., DEG, Np]``
    and weights ``f32[..., DEG, Np]`` in, the connectivity
    ``f32[..., k, Np]`` out; the leading dimensions are lanes, and a vmap
    that shares the weights between lanes leaves some of them off the
    weights. Per vertex slot of each lane and neighbour slot it does a
    compare, a select and an add into each of the k blocks; it needs each
    input read and the output written once."""
    shapes = kernel_call_shapes(name, "lp_gain_pallas")
    if shapes is None:
        return None
    res, ops_in = shapes
    if len(res) != 1 or len(ops_in) != 2:
        return None
    (rt, rdims), (at, adims), (wt, wdims) = res[0], ops_in[0], ops_in[1]
    if (rt, at, wt) != ("f32", "s32", "f32") or len(wdims) < 2 \
            or adims[len(adims) - len(wdims):] != wdims \
            or len(rdims) != len(adims) or rdims[-1] != adims[-1] \
            or rdims[:-2] != adims[:-2]:
        return None
    k, deg = rdims[-2], adims[-2]
    slots = math.prod(adims[:-2]) * adims[-1]   # lanes x vertices
    return 3 * k * deg * slots, sum(nbytes(a) for a in res + ops_in)
