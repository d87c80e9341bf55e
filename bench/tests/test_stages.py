"""Stage shares and span-named idle gaps from a trace (``bench/stages.py``),
and the v-cycle counter's reader, on a hand-built xplane. No chip and no
program run here."""
from __future__ import annotations

import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from bench import stages as S  # noqa: E402
from bench import trace as T  # noqa: E402
from bench.spec import Cell  # noqa: E402

CELL = json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"][0]

LP = ('%lp_gain_pallas.2 = f32[2,3,8192]{2,1,0:T(4,128)S(1)} '
      'custom-call(s32[2,16,8192]{2,1,0:T(8,128)S(1)} %fusion.1487, '
      'f32[16,8192]{1,0:T(8,128)S(1)} %bitcast.2270), '
      'custom_call_target="tpu_custom_call"')
# (HLO text, op_name as a TPU trace's tf_op stat holds it, start, end) in ns;
# op_name None: no stat; "ref:" an op_name kept as a reference to a name
OPS = [
    ("%while.3 = (s32[]) while(s32[] %t), body=%b",
     "jit(<lambda>)/vmap(vmap(coarsen))/while:", 0, 50),
    ("%fusion.7 = f32[8]{0} fusion(f32[8]{0} %x), kind=kLoop",
     "ref:jit(<lambda>)/vmap(vmap(refine))/jit(rebalance)/while/body/add:",
     10, 40),
    # a copy XLA added inside the coarsening loop: no op_name of its own
    ("%copy.5 = f32[8]{0} copy(f32[8]{0} %z)", None, 42, 45),
    ("%fusion.8 = f32[8]{0} fusion(f32[8]{0} %y), kind=kLoop", None, 60, 70),
    (LP, "jit(<lambda>)/vmap(vmap(initial))/jit(lp_refine)/lp_gain:",
     80, 85),
    ("%concatenate.1 = s32[4]{0} concatenate(s32[2]{0} %a, s32[2]{0} %b)",
     "jit(run)/level_ops/concatenate:", 85, 90),
]
PROGRAM_ID = 77


def _msg(*fields) -> bytes:
    """Protobuf wire bytes of (field number, int | bytes | str) pairs."""
    def varint(x):
        out = b""
        while True:
            out += bytes([(x & 0x7F) | (0x80 if x > 0x7F else 0)])
            x >>= 7
            if not x:
                return out
    out = b""
    for f, v in fields:
        if isinstance(v, int):
            out += varint(f << 3) + varint(v)
        else:
            v = v.encode() if isinstance(v, str) else v
            out += varint(f << 3 | 2) + varint(len(v)) + v
    return out


def _hlo_proto() -> bytes:
    """The program of OPS: the loop ``while.3`` (its own op_name) runs
    computation 2, which holds ``fusion.7`` and ``copy.5`` (none)."""
    ins = lambda name, op=None, called=(): _msg(
        (1, name), *([(7, _msg((2, op)))] if op else []),
        *[(38, c) for c in called])
    entry = _msg((1, "main"), (5, 1),
                 (2, ins("while.3", OPS[0][1].rstrip(":"), called=[2])),
                 (2, ins("fusion.8")))
    body = _msg((1, "body"), (5, 2), (2, ins("fusion.7", "x/refine/add")),
                (2, ins("copy.5")))
    return _msg((1, _msg((1, "jit_f"), (3, entry), (3, body))))


BENCH_SPANS = [("bench.map", 0, 100)]
REPRO_SPANS = [("repro.map", 0, 94), ("repro.advance", 50, 58),
               ("repro.fetch", 70, 78)]


def _xplane(spans) -> str:
    """The text proto of one device plane with OPS and one host thread."""
    meta, stat_meta, events = [], ['stat_metadata { key: 1 value { id: 1 '
                                   'name: "tf_op" } }',
                                   'stat_metadata { key: 2 value { id: 2 '
                                   'name: "program_id" } }'], []
    for i, (name, op, a, b) in enumerate(OPS, start=1):
        stat = ""
        if op is not None and op.startswith("ref:"):
            stat_meta.append(f'stat_metadata {{ key: {100 + i} value {{ '
                             f'id: {100 + i} name: {json.dumps(op[4:])} }} }}')
            stat = f"stats {{ metadata_id: 1 ref_value: {100 + i} }}"
        elif op is not None:
            stat = f"stats {{ metadata_id: 1 str_value: {json.dumps(op)} }}"
        stat += f" stats {{ metadata_id: 2 uint64_value: {PROGRAM_ID} }}"
        meta.append(f"event_metadata {{ key: {i} value {{ id: {i} "
                    f"name: {json.dumps(name)} {stat} }} }}")
        events.append(f"events {{ metadata_id: {i} offset_ps: {a * 1000} "
                      f"duration_ps: {(b - a) * 1000} }}")
    host_meta, host_events = [], []
    for i, (name, a, b) in enumerate(spans, start=1):
        host_meta.append(f'event_metadata {{ key: {i} value {{ id: {i} '
                         f'name: "{name}" }} }}')
        host_events.append(f"events {{ metadata_id: {i} offset_ps: "
                           f"{a * 1000} duration_ps: {(b - a) * 1000} "
                           f"stats {{ metadata_id: 1 int64_value: 7 }} }}")
    return (
        'planes { id: 1 name: "/device:TPU:0" '
        'lines { id: 1 name: "XLA Modules" timestamp_ns: 0 } '
        'lines { id: 2 name: "XLA Ops" timestamp_ns: 0 '
        + " ".join(events) + " } " + " ".join(meta + stat_meta) + " } "
        'planes { id: 3 name: "/host:metadata" event_metadata { key: 1 '
        f'value {{ id: 1 name: "jit_f({PROGRAM_ID})" stats {{ metadata_id: 1 '
        f'bytes_value: "{_escaped(_hlo_proto())}" }} }} }} '
        'stat_metadata { key: 1 value { id: 1 name: "Hlo Proto" } } } '
        'planes { id: 2 name: "/host:CPU" '
        'lines { id: 1 name: "python" timestamp_ns: 0 '
        + " ".join(host_events) + " } " + " ".join(host_meta)
        + ' stat_metadata { key: 1 value { id: 1 name: "req" } } }')


def _escaped(b: bytes) -> str:
    return "".join(f"\\{c:03o}" for c in b)


@pytest.fixture()
def xplanes(tmp_path):
    """The same trace with the program's spans and without them."""
    from jax.profiler import ProfileData
    out = {}
    for name, spans in (("with", BENCH_SPANS + REPRO_SPANS),
                        ("without", BENCH_SPANS)):
        path = tmp_path / f"{name}.xplane.pb"
        path.write_bytes(ProfileData.text_proto_to_serialized_xspace(
            _xplane(spans)))
        out[name] = str(path)
    return out


@pytest.mark.parametrize("op_name,stage", [
    ("jit(<lambda>)/vmap(vmap(coarsen))/while/body/closed_call/add:",
     "coarsen"),
    ("jit(<lambda>)/vmap(jit(partition))/vmap(refine)/jit(lp_refine)/mul",
     "refine"),
    # the outermost stage owns an op: select's edge cut of a refined part
    ("jit(f)/vmap(select)/refine/add", "select"),
    ("jit(run)/level_ops/concatenate:", "level_ops"),
    ("jit(_pad_pe)/evaluate/concatenate", "evaluate"),
    ("jit(coarsen_cascade)/while/body/add", "unscoped"),
    ("jit(concatenate)/concatenate", "unscoped"),
    ("", "unscoped"),
])
def test_stage_of_the_outermost_scope(op_name, stage):
    assert S.stage_of(op_name) == stage


def test_stage_seconds_cover_the_device_self_time(xplanes):
    path = xplanes["with"]
    names = S.read_op_names(path)
    assert list(names) == ["/device:TPU:0"]
    assert [n for n, _ in names["/device:TPU:0"]] == [o[0] for o in OPS]
    assert names["/device:TPU:0"][1][1] == OPS[1][1][4:]  # a ref_value
    # the copy without an op_name takes its loop's, from the program
    assert names["/device:TPU:0"][2][1] == OPS[0][1].rstrip(":")
    assert names["/device:TPU:0"][3][1] == ""
    tr = T.read_xplane(path, 100)
    secs = S.stage_seconds(tr, names)
    # the while's 50 ns less its body's 33, and the copy's 3 inside it;
    # the fusion outside any scope, 10
    assert secs == {"coarsen": pytest.approx(20e-9),
                    "initial": pytest.approx(5e-9),
                    "refine": pytest.approx(30e-9),
                    "select": 0.0, "level_ops": pytest.approx(5e-9),
                    "evaluate": 0.0, "unscoped": pytest.approx(10e-9)}
    assert sum(secs.values()) == pytest.approx(tr.busy_s)
    assert sum(S.shares(secs).values()) == pytest.approx(100.0)
    assert S.shares(secs)["unscoped"] == pytest.approx(100 * 10 / 70)
    with pytest.raises(ValueError):
        S.stage_seconds(tr, {"/device:TPU:0": names["/device:TPU:0"][1:]})


def test_gaps_named_by_the_innermost_span(xplanes):
    tr = T.read_xplane(xplanes["with"], 100)
    spans = S.read_spans(xplanes["with"])
    assert sorted(s.name for s in spans) == sorted(
        n for n, _, _ in BENCH_SPANS + REPRO_SPANS)
    # gaps [50, 60], [70, 80] and [90, 100] (mid 95: repro.map has closed)
    assert S.idle_gaps(tr, spans) == [
        ["repro.advance", pytest.approx(10e-9)],
        ["repro.fetch", pytest.approx(10e-9)],
        ["bench.map", pytest.approx(10e-9)]]


def test_existing_readings_are_unchanged_by_the_program_spans(xplanes):
    """``trace.py`` keeps ``bench.*`` spans only: the accepted metrics and
    the breakdown read the same trace the same with ``repro.*`` spans in
    it."""
    cell = Cell(ROOT, CELL["name"])
    read = {tr: T.read_xplane(xplanes[tr], 100) for tr in xplanes}
    a, b = read["with"], read["without"]
    assert [s.name for s in a.host_spans] == ["bench.map"]
    assert a.busy_s == b.busy_s == pytest.approx(70e-9)
    assert a.top_ops() == b.top_ops()
    assert a.idle_gaps() == b.idle_gaps() == [
        ["bench.map", pytest.approx(10e-9)]] * 3
    for metric in ("device_idle_share", "lp_gain_roofline"):
        got = [cell.reader(metric)({"trace": r, "device_kind": "TPU v5 lite"})
               for r in (a, b)]
        assert got[0] is not None and got[0] == got[1], metric
    assert S.idle_gaps(b, S.read_spans(xplanes["without"])) == b.idle_gaps()


def test_the_command_line_prints_one_json_line(xplanes, capsys):
    assert S.main([xplanes["with"], "--window-s", "1e-7"]) == 0
    line = json.loads(capsys.readouterr().out)
    assert line["stages"]["refine"] == pytest.approx(30e-9)
    assert line["busy_s"] == pytest.approx(70e-9)
    assert line["idle_gaps"][0][0] == "repro.advance"


class _Answer:
    def __init__(self, t_done, stats, error=None):
        self.t_done, self.stats, self.error = t_done, stats, error


def test_vcycle_useful_share_reader():
    read = Cell(ROOT, CELL["name"]).reader("vcycle_useful_share")
    work = {"vcycle_real_vertex_work": 30, "vcycle_padded_vertex_work": 100}
    rec = {"close": 10.0,
           "answers": [_Answer(1.0, work), _Answer(2.0, dict(
               work, vcycle_real_vertex_work=50)),
               _Answer(11.0, dict(work, vcycle_real_vertex_work=100))]}
    assert read(rec) == pytest.approx(80 / 200)
    # a program without the counter: nothing to read
    assert read({"close": 10.0, "answers": [_Answer(1.0, {
        "padded_vertex_work": 4, "real_vertex_work": 3})]}) is None
