"""Hand-written HLO fixtures for the parsing layer (PR 10 satellite).

The jax-compiled tests in test_mesh_and_hlo.py cover whatever HLO the
installed XLA happens to emit; these fixtures pin the parser's behaviour on
the syntax variants we must keep handling: tuple-typed ops, operands with
inlined types, empty operand lists, nested `while` multipliers, fusion-body
dot attribution — for both ``analyze_hlo`` and ``extract_comm_graph``.
"""
import warnings

import pytest

from repro.launch.comm_graph import extract_comm_graph
from repro.launch.hlo_analysis import (Op, _dot_flops, _operands,
                                       _shape_bytes, analyze_hlo,
                                       parse_computations)

# One `while` around a 4x4 matmul body; the loop state is a tuple
# (f32[4,4], s32[]) — 64 + 4 = 68 bytes.
WHILE_HLO = """\
HloModule fixture_while

%body (p.1: (f32[4,4], s32[])) -> (f32[4,4], s32[]) {
  %p.1 = (f32[4,4], s32[]) parameter(0)
  %g0 = f32[4,4] get-tuple-element(%p.1), index=0
  %g1 = s32[] get-tuple-element(%p.1), index=1
  %mm = f32[4,4] dot(%g0, %g0), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %one = s32[] constant(1)
  %ni = s32[] add(%g1, %one)
  ROOT %t = (f32[4,4], s32[]) tuple(%mm, %ni)
}

%cond (p.2: (f32[4,4], s32[])) -> pred[] {
  %p.2 = (f32[4,4], s32[]) parameter(0)
  %i = s32[] get-tuple-element(%p.2), index=1
  %lim = s32[] constant(5)
  ROOT %lt = pred[] compare(%i, %lim), direction=LT
}

ENTRY %main (a: f32[4,4]) -> (f32[4,4], s32[]) {
  %a = f32[4,4] parameter(0)
  %z = s32[] constant(0)
  %init = (f32[4,4], s32[]) tuple(%a, %z)
  ROOT %wh = (f32[4,4], s32[]) while(%init), condition=%cond, body=%body
}
"""


def test_while_fixture_parses_tuple_typed_ops():
    comps = parse_computations(WHILE_HLO)
    assert set(comps) == {"body", "cond", "main"}
    assert comps["main"].is_entry
    wh = comps["main"].ops[-1]
    assert wh.kind == "while" and wh.type_str.startswith("(")
    assert _shape_bytes(wh.type_str) == 68  # 4*4*f32 + s32


def test_while_fixture_comm_graph_structure():
    tg = extract_comm_graph(WHILE_HLO, trip_hints=[5])
    # tasks in parse order: mm=0, ni=1 (body), lt=2 (cond), wh=3 (entry)
    assert tg.n == 4 and tg.m == 3
    assert tg.meta["while_trips"] == [5]
    assert not tg.meta["hints_exhausted"]
    edges = {(int(a), int(b)): float(w)
             for a, b, w in zip(tg.u, tg.v, tg.w)}
    # boundary edges: 68 output bytes x 5 trips, split over the two body
    # roots (mm, ni); the cond root keeps the full 340
    assert edges == {(0, 3): 170.0, (1, 3): 170.0, (2, 3): 340.0}
    # the dot runs 5 times: vwgt = 5 * (2 * 16 * 4); FLOP-free tasks floor at 1
    assert tg.vwgt.tolist() == [640.0, 1.0, 1.0, 1.0]


def test_while_fixture_analyze_hlo_agrees():
    an = analyze_hlo(WHILE_HLO, trip_hints=[5])
    assert an.flops == 5 * 2 * 16 * 4
    assert an.while_trips == [5] and not an.hints_exhausted


# `while` in a `while`: hints consumed in nesting order, multipliers multiply.
NESTED_HLO = """\
HloModule fixture_nested

%ibody (p.1: (f32[2,2], s32[])) -> (f32[2,2], s32[]) {
  %p.1 = (f32[2,2], s32[]) parameter(0)
  %g = f32[2,2] get-tuple-element(%p.1), index=0
  %i = s32[] get-tuple-element(%p.1), index=1
  %d = f32[2,2] dot(%g, %g), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %c1 = s32[] constant(1)
  %j = s32[] add(%i, %c1)
  ROOT %t.1 = (f32[2,2], s32[]) tuple(%d, %j)
}

%icond (p.2: (f32[2,2], s32[])) -> pred[] {
  %p.2 = (f32[2,2], s32[]) parameter(0)
  %i.2 = s32[] get-tuple-element(%p.2), index=1
  %lim.1 = s32[] constant(5)
  ROOT %lt.1 = pred[] compare(%i.2, %lim.1), direction=LT
}

%obody (p.3: (f32[2,2], s32[])) -> (f32[2,2], s32[]) {
  %p.3 = (f32[2,2], s32[]) parameter(0)
  ROOT %w2 = (f32[2,2], s32[]) while(%p.3), condition=%icond, body=%ibody
}

%ocond (p.4: (f32[2,2], s32[])) -> pred[] {
  %p.4 = (f32[2,2], s32[]) parameter(0)
  %i.4 = s32[] get-tuple-element(%p.4), index=1
  %lim.2 = s32[] constant(3)
  ROOT %lt.2 = pred[] compare(%i.4, %lim.2), direction=LT
}

ENTRY %main (a: f32[2,2]) -> (f32[2,2], s32[]) {
  %a = f32[2,2] parameter(0)
  %z = s32[] constant(0)
  %init = (f32[2,2], s32[]) tuple(%a, %z)
  ROOT %w1 = (f32[2,2], s32[]) while(%init), condition=%ocond, body=%obody
}
"""


def test_nested_while_multipliers_multiply():
    an = analyze_hlo(NESTED_HLO, trip_hints=[3, 5])
    # the inner dot (2*4*2 = 16 flops) runs 3 * 5 times
    assert an.flops == 3 * 5 * 16
    assert an.while_trips == [3, 5]
    tg = extract_comm_graph(NESTED_HLO, trip_hints=[3, 5])
    # the dot task carries the multiplied compute weight
    assert float(tg.vwgt.max()) == 3 * 5 * 16


def test_hints_exhausted_flag_and_warning():
    # two `while` ops, one hint: the last hint is reused and flagged
    with pytest.warns(UserWarning, match="2 `while` ops but only 1"):
        an = analyze_hlo(NESTED_HLO, trip_hints=[3])
    assert an.hints_exhausted and an.while_hints_needed == 2
    assert an.while_trips == [3, 3]
    assert an.flops == 3 * 3 * 16
    tg = extract_comm_graph(NESTED_HLO, trip_hints=[3])
    assert tg.meta["hints_exhausted"]
    # no hints at all: trips default to 1 — still flagged as a guess, but
    # silently (an explicit "I have no hints" caller shouldn't be nagged)
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        an0 = analyze_hlo(NESTED_HLO)
    assert an0.hints_exhausted and an0.while_hints_needed == 2
    # exact hints: flag off, no warning
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        an2 = analyze_hlo(NESTED_HLO, trip_hints=[3, 5])
    assert not an2.hints_exhausted


# A collective whose payload is distributed over its replica group.
COLLECTIVE_HLO = """\
HloModule fixture_collective

%sum (x: f32[], y: f32[]) -> f32[] {
  %x = f32[] parameter(0)
  %y = f32[] parameter(1)
  ROOT %add.1 = f32[] add(%x, %y)
}

ENTRY %main (a: f32[64]) -> f32[64] {
  %a = f32[64] parameter(0)
  %sq = f32[64] multiply(%a, %a)
  %ar = f32[64] all-reduce(%sq), replica_groups={{0,1,2,3}}, to_apply=%sum
  ROOT %out = f32[64] add(%ar, %ar)
}
"""


def test_collective_bytes_distributed_over_group():
    tg = extract_comm_graph(COLLECTIVE_HLO)
    # tasks in parse order: add.1 (reducer body) = 0, sq = 1, ar = 2, out = 3.
    # The reducer's scalar add stays an ISOLATED task — all-reduce bodies are
    # applied element-wise inside the collective, not a dataflow boundary.
    assert tg.n == 4
    edges = {(int(a), int(b)): float(w)
             for a, b, w in zip(tg.u, tg.v, tg.w)}
    # sq -> ar: 256 dataflow bytes + 256/4 per-shard collective share
    # ar -> out: consumed twice at 256 bytes each
    assert edges == {(1, 2): 256.0 + 64.0, (2, 3): 512.0}
    an = analyze_hlo(COLLECTIVE_HLO)
    assert an.collective_bytes == {"all-reduce": 256.0}
    assert an.num_collectives == {"all-reduce": 1}


# Fusion with a dot in its body: the fusion op absorbs the body's FLOPs at
# fused granularity; op granularity expands the body into its own task.
FUSION_HLO = """\
HloModule fixture_fusion

%fused_dot (fa: f32[8,8], fb: f32[8,8]) -> f32[8,8] {
  %fa = f32[8,8] parameter(0)
  %fb = f32[8,8] parameter(1)
  ROOT %fd = f32[8,8] dot(%fa, %fb), lhs_contracting_dims={1}, rhs_contracting_dims={0}
}

ENTRY %main (x: f32[8,8]) -> f32[8,8] {
  %x = f32[8,8] parameter(0)
  %e = f32[8,8] exponential(%x)
  ROOT %f = f32[8,8] fusion(%e, %x), kind=kOutput, calls=%fused_dot
}
"""


def test_fusion_body_dot_attribution():
    dot_flops = 2 * 64 * 8
    an = analyze_hlo(FUSION_HLO)
    assert an.flops == dot_flops

    fused = extract_comm_graph(FUSION_HLO)  # tasks: e=0, f=1
    assert fused.n == 2 and fused.meta["granularity"] == "fused"
    assert fused.vwgt.tolist() == [1.0, float(dot_flops)]

    op = extract_comm_graph(FUSION_HLO, granularity="op")
    # body expands: fd=0 (body parses first), e=1, f=2; the dot's weight
    # moves to the body task, and a boundary edge fd—f appears
    assert op.n == 3
    assert op.vwgt.tolist() == [float(dot_flops), 1.0, 1.0]
    edges = {(int(a), int(b)): float(w) for a, b, w in zip(op.u, op.v, op.w)}
    assert edges == {(0, 2): 256.0, (1, 2): 256.0}


def test_min_tasks_escalates_granularity():
    assert extract_comm_graph(FUSION_HLO, min_tasks=3).meta["granularity"] \
        == "op"
    assert extract_comm_graph(FUSION_HLO, min_tasks=2).meta["granularity"] \
        == "fused"
    with pytest.raises(ValueError, match="granularity"):
        extract_comm_graph(FUSION_HLO, granularity="bogus")


# ------------------------------------------------------- parser unit tests


def test_operands_with_inlined_types():
    op = Op("add.2", "f32[8]{0}", "add",
            "  %add.2 = f32[8]{0} add(f32[8]{0} %a, f32[8]{0} %b)")
    assert _operands(op) == ["a", "b"]


def test_operands_empty_list():
    op = Op("tok", "token[]", "after-all", "  %tok = token[] after-all()")
    assert _operands(op) == []


def test_operands_tuple_typed_depth_aware_split():
    op = Op("t", "(f32[4,4], s32[])", "tuple",
            "  ROOT %t = (f32[4,4], s32[]) tuple(f32[4,4]{1,0} %mm, s32[] %ni)")
    assert _operands(op) == ["mm", "ni"]


def test_dot_flops_exact():
    shapes = {"lhs": "f32[128,256]", "rhs": "f32[256,512]"}
    op = Op("d", "f32[128,512]", "dot",
            "  %d = f32[128,512] dot(%lhs, %rhs), "
            "lhs_contracting_dims={1}, rhs_contracting_dims={0}")
    assert _dot_flops(op, shapes) == 2 * 128 * 512 * 256


# TPU-compiled HLO: layouts carry tiling in braces ({1,0:T(4,128)S(1)}),
# and async copies return nested tuple types.
TPU_HLO = """\
HloModule fixture_tpu, is_scheduled=true

%fused_computation.1 (p0: f32[4,384]) -> f32[4,384] {
  %p0 = f32[4,384]{1,0:T(4,128)} parameter(0)
  ROOT %neg = f32[4,384]{1,0:T(4,128)} negate(%p0)
}

ENTRY %main.3 (a: f32[4,384]) -> f32[4,384] {
  %a = f32[4,384]{1,0:T(4,128)} parameter(0)
  %copy-start = (f32[4,384]{1,0:T(4,128)S(1)}, f32[4,384]{1,0:T(4,128)}, u32[]{:S(2)}) copy-start(%a)
  %copy-done = f32[4,384]{1,0:T(4,128)S(1)} copy-done(%copy-start)
  ROOT %f = f32[4,384]{1,0:T(4,128)} fusion(%copy-done), kind=kLoop, calls=%fused_computation.1
}
"""


def test_tpu_layouts_and_async_tuples_parse():
    comps = parse_computations(TPU_HLO)
    entry = comps["main.3"]
    assert [op.kind for op in entry.ops] == [
        "parameter", "copy-start", "copy-done", "fusion"]
    start = entry.ops[1]
    assert _shape_bytes(start.type_str) == 2 * 4 * 384 * 4 + 4
    assert _operands(entry.ops[3]) == ["copy-done"]
    tg = extract_comm_graph(TPU_HLO)
    assert tg.n >= 2 and tg.m >= 1
