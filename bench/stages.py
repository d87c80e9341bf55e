#!/usr/bin/env python3
"""Device time per stage of the mapper, and idle gaps named by the
program's own spans, from one JAX profiler trace (``.xplane.pb``).

    python3 bench/stages.py <trace.xplane.pb> --window-s <seconds>

prints one JSON line: ``stages`` (seconds of device self time under each
stage scope, and ``unscoped``), ``shares`` (each in percent of all device
self time) and ``idle_gaps`` (the longest, named by the innermost
``bench.*`` or ``repro.*`` host span open at their middle).

The program runs its device programs under ``jax.named_scope``s (see
``STAGES``), so each HLO op's ``op_name`` metadata names its stage, as in
``jit(<lambda>)/vmap(vmap(refine))/jit(rebalance)/while/body/add``. A TPU
trace keeps that path in the ``tf_op`` stat of each op's event metadata,
which ``jax.profiler.ProfileData`` does not expose; :func:`read_op_names`
reads it from the file's protobuf wire format directly. XLA gives many
ops it creates itself (copies, loop plumbing, fusions of expanded ops) no
``op_name``; such an op takes the one of the instruction it runs inside
(the fusion or the loop whose body holds it), from the program's HLO,
which the trace keeps in its ``/host:metadata`` plane. An op's stage is
the outermost stage name in its path; the stages' self times and
``unscoped`` sum to the device self time of the window (``trace.py``'s
``self_times``, as ``top_ops`` takes it).

``bench/run.py`` deletes its trace before the metric readers run, so the
result line carries none of this yet (PERF.md, Open questions).
"""
from __future__ import annotations

import argparse
import json
import re
import sys
from pathlib import Path

if __name__ == "__main__":
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import trace as T  # noqa: E402

STAGES = ("coarsen", "initial", "refine", "select", "level_ops", "evaluate")
UNSCOPED = "unscoped"
SPAN_PREFIXES = ("bench.", "repro.")
TF_OP = "tf_op"
HOST_METADATA = "/host:metadata"
HLO_PROTO = "Hlo Proto"
_WRAPPER = re.compile(r"^(?:[\w.<>-]+\()+")


def stage_of(op_name: str) -> str:
    """The outermost stage in an ``op_name`` path, or ``unscoped``. A
    scope inside a transformation reads ``vmap(vmap(refine))``; a TPU
    trace ends the path with ``:``."""
    for part in op_name.rstrip(":").split("/"):
        name = _WRAPPER.sub("", part).rstrip(")")
        if name in STAGES:
            return name
    return UNSCOPED


# --- the protobuf wire format of XSpace (tsl/profiler/protobuf/xplane.proto)

def _varint(buf, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """(field number, value) of one message: ints for varints, memoryview
    slices for length-delimited fields, fixed-width fields skipped."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            v, i = _varint(buf, i)
        elif kind == 2:
            size, i = _varint(buf, i)
            v, i = buf[i:i + size], i + size
        elif kind in (1, 5):
            i += 8 if kind == 1 else 4
            continue
        else:
            raise ValueError(f"wire type {kind} is not in an XSpace")
        yield key >> 3, v


def _text(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def _ints(v) -> list[int]:
    """A repeated integer field's values: one varint, or a packed run."""
    if isinstance(v, int):
        return [v]
    out, i = [], 0
    while i < len(v):
        x, i = _varint(v, i)
        out.append(x)
    return out


def _plane(plane) -> tuple[str, list, dict, dict]:
    """(name, lines, stat names by id, event metadata by id) of an XPlane."""
    name, lines, stat_names, meta = "", [], {}, {}
    for f, v in _fields(plane):
        if f == 2:                              # XPlane.name
            name = _text(v)
        elif f == 3:                            # XPlane.lines
            lines.append(v)
        elif f in (4, 5):                       # event / stat metadata maps
            entry = dict(_fields(v))
            key, value = entry.get(1, 0), entry.get(2, b"")
            if f == 5:
                stat_names[key] = _text(dict(_fields(value)).get(2, b""))
            else:
                meta[key] = value
    return name, lines, stat_names, meta


def _stats(md, stat_names: dict) -> tuple[str, dict]:
    """(name, {stat name: value}) of an XEventMetadata; a value is an int,
    a string or bytes (``ref_value`` resolved to the name it points to)."""
    name, stats = "", {}
    for f, v in _fields(md):
        if f == 2:                              # XEventMetadata.name
            name = _text(v)
        elif f == 5:                            # XEventMetadata.stats
            key, value = None, None
            for sf, sv in _fields(v):
                if sf == 1:                     # XStat.metadata_id
                    key = stat_names.get(sv)
                elif sf in (3, 4):              # uint64 / int64
                    value = sv
                elif sf == 5:                   # str_value
                    value = _text(sv)
                elif sf == 6:                   # bytes_value
                    value = sv
                elif sf == 7:                   # ref_value
                    value = stat_names.get(sv, "")
            stats[key] = value
    return name, stats


def _program_op_names(hlo_proto) -> dict[str, str]:
    """Each instruction's op_name in one program (an ``HloProto``): its own
    or, where XLA added the instruction without one, that of the
    instruction whose called computation holds it (the fusion or the
    loop it runs in)."""
    module = dict(_fields(hlo_proto)).get(1, b"")  # HloProto.hlo_module
    own, comp_of, caller = {}, {}, {}
    for f, comp in _fields(module):
        if f != 3:                              # HloModuleProto.computations
            continue
        fields = list(_fields(comp))
        cid = dict(fields).get(5, 0)            # HloComputationProto.id
        for cf, ins in fields:
            if cf != 2:                         # .instructions
                continue
            name, op = "", ""
            for jf, v in _fields(ins):
                if jf == 1:                     # HloInstructionProto.name
                    name = _text(v)
                elif jf == 7:                   # .metadata: OpMetadata
                    op = _text(dict(_fields(v)).get(2, b""))
                elif jf == 38:                  # .called_computation_ids
                    for c in _ints(v):
                        caller.setdefault(c, name)
            own[name], comp_of[name] = op, cid
    resolved: dict[str, str] = {}

    def resolve(name: str) -> str:
        if name not in resolved:
            up = caller.get(comp_of[name])
            resolved[name] = own[name] or (resolve(up) if up else "")
        return resolved[name]

    return {name: resolve(name) for name in own}


def read_op_names(path: str) -> dict[str, list[tuple[str, str]]]:
    """For each device plane, ``(name, op_name)`` of every event of its
    ``XLA Ops`` line, in the line's order: the order in which
    ``trace.read_xplane`` lists them. An op without an ``op_name`` of its
    own takes the one its program (kept in the ``/host:metadata`` plane)
    gives it through the instruction it runs inside."""
    buf = memoryview(Path(path).read_bytes())
    devices, programs = [], {}
    for f, plane in _fields(buf):
        if f != 1:                              # XSpace.planes
            continue
        name, lines, stat_names, meta = _plane(plane)
        if name.startswith(T.DEVICE_PREFIX):
            devices.append((name, lines, stat_names, meta))
        elif name == HOST_METADATA:
            for md in meta.values():
                label, stats = _stats(md, stat_names)
                pid = re.search(r"\((\d+)\)$", label)
                if pid and HLO_PROTO in stats:
                    programs[int(pid.group(1))] = _program_op_names(
                        stats[HLO_PROTO])
    out = {}
    for name, lines, stat_names, meta in devices:
        cache: dict[int, tuple[str, str]] = {}
        events = []
        for line in lines:
            fields = list(_fields(line))
            if _text(dict(fields).get(2, b"")) != T.OPS_LINE:
                continue
            for f, ev in fields:
                if f != 4:                      # XLine.events
                    continue
                mid = dict(_fields(ev)).get(1, 0)  # XEvent.metadata_id
                if mid not in cache:
                    op_text, stats = _stats(meta.get(mid, b""), stat_names)
                    op = stats.get(TF_OP) or programs.get(
                        stats.get("program_id"), {}).get(
                            T.Event(op_text, 0, 0).op, "")
                    cache[mid] = (op_text, op)
                events.append(cache[mid])
        out[name] = events
    return out


def stage_seconds(tr: T.Reduced, op_names: dict) -> dict[str, float]:
    """Device self time in the window under each stage, and ``unscoped``,
    averaged over the devices; the values sum to the window's self time."""
    out = dict.fromkeys(STAGES + (UNSCOPED,), 0.0)
    for dev, evs in tr.device_ops.items():
        names = op_names.get(dev, [])
        if [n for n, _ in names] != [e.name for e in evs]:
            raise ValueError(f"{dev}: the op names do not match the trace's "
                             f"{len(evs)} ops")
        for (_, op), t in zip(names, T.self_times(evs, tr.lo, tr.hi)):
            out[stage_of(op)] += t * 1e-9
    share = 1.0 / max(len(tr.device_ops), 1)
    return {k: v * share for k, v in out.items()}


def shares(seconds: dict[str, float]) -> dict[str, float]:
    """Each stage's percent of the device self time."""
    total = sum(seconds.values())
    return {k: 100.0 * v / total for k, v in seconds.items()} if total else {}


def read_spans(path: str, prefixes=SPAN_PREFIXES) -> list[T.Event]:
    """The host spans whose names start with one of ``prefixes``."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [T.Event(e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events if e.name.startswith(prefixes)]
    return out


def idle_gaps(tr: T.Reduced, spans: list[T.Event], n: int = 10) -> list[list]:
    """``Reduced.idle_gaps`` with the gaps named from ``spans``."""
    return T.Reduced(tr.device_ops, spans, tr.lo, tr.hi).idle_gaps(n)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("xplane")
    ap.add_argument("--window-s", type=float, required=True,
                    help="the traced window, from the profiler's start")
    args = ap.parse_args(argv)
    tr = T.read_xplane(args.xplane, args.window_s * 1e9)
    secs = stage_seconds(tr, read_op_names(args.xplane))
    print(json.dumps({"stages": secs, "shares": shares(secs),
                      "busy_s": tr.busy_s, "window_s": tr.window_s,
                      "idle_gaps": idle_gaps(tr, read_spans(args.xplane))}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
