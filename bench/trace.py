"""Reduction of a JAX profiler trace to device busy time, idle gaps and
kernel events.

Busy is the union of the intervals in which an operation ran on a device
(the ``XLA Ops`` line of each ``/device:TPU:*`` plane), averaged over the
devices; the window is the traced interval; the idle gaps are the parts of
the window in which no operation ran, each named by the benchmark's own
host span (``bench.*``, see ``common.py``) that was open at its middle.
"""
from __future__ import annotations

import glob
from pathlib import Path

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_SPAN_PREFIX = "bench."


class Event:
    __slots__ = ("name", "start", "end")

    def __init__(self, name: str, start: float, end: float):
        self.name, self.start, self.end = name, start, end

    @property
    def op(self) -> str:
        """The HLO instruction's name: a TPU trace names an op event by its
        whole HLO text (``%fusion.12 = f32[8]{0} fusion(...), ...``)."""
        head = self.name.split(" = ", 1)[0]
        return head.lstrip("%")

    @property
    def opcode(self) -> str:
        """``fusion``, ``while``, ``custom-call``, ... (``""`` when the name
        is not HLO text)."""
        if " = " not in self.name:
            return ""
        rhs = self.name.split(" = ", 1)[1]
        # the result type comes first; the opcode precedes the operands
        depth, i = 0, 0
        for i, ch in enumerate(rhs):
            if ch in "([{":
                depth += 1
            elif ch in ")]}":
                depth -= 1
            elif ch == " " and depth == 0:
                break
        rest = rhs[i + 1:]
        return rest.split("(", 1)[0].strip()


def self_times(events: list[Event], lo: float, hi: float) -> list[float]:
    """Nanoseconds of each event inside [lo, hi] not covered by an event
    nested in it (a ``while`` op encloses the ops of its body)."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i].start, -events[i].end))
    own = [0.0] * len(events)
    stack: list[int] = []
    clip = lambda a, b: max(0.0, min(b, hi) - max(a, lo))
    for i in order:
        e = events[i]
        while stack and events[stack[-1]].end <= e.start:
            stack.pop()
        own[i] += clip(e.start, e.end)
        if stack:
            own[stack[-1]] -= clip(e.start, min(e.end, events[stack[-1]].end))
        stack.append(i)
    return own


def union_length(intervals, lo: float, hi: float) -> float:
    """Length of the union of ``intervals`` clipped to [lo, hi]."""
    total, end = 0.0, lo
    for a, b in sorted((max(a, lo), min(b, hi)) for a, b in intervals
                       if b > lo and a < hi):
        if b > end:
            total += b - max(a, end)
            end = b
    return total


def gaps(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """The parts of [lo, hi] that no interval covers."""
    out, end = [], lo
    for a, b in sorted((max(a, lo), min(b, hi)) for a, b in intervals
                       if b > lo and a < hi):
        if a > end:
            out.append((end, a))
        end = max(end, b)
    if hi > end:
        out.append((end, hi))
    return out


def span_at(spans: list[Event], t: float) -> str:
    """The innermost (shortest) host span open at ``t``, or ``"none"``."""
    open_ = [s for s in spans if s.start <= t <= s.end]
    return min(open_, key=lambda s: s.end - s.start).name if open_ else "none"


class Reduced:
    """What the metric readers and the result line take from one trace."""

    def __init__(self, device_ops: dict[str, list[Event]],
                 host_spans: list[Event], lo: float, hi: float):
        self.device_ops = device_ops      # device -> its op events
        self.host_spans = host_spans
        self.lo, self.hi = lo, hi

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) * 1e-9

    @property
    def busy_s(self) -> float:
        """Seconds in which an op ran, averaged over the devices."""
        if not self.device_ops:
            return 0.0
        return sum(union_length([(e.start, e.end) for e in evs], self.lo,
                                self.hi)
                   for evs in self.device_ops.values()) \
            / len(self.device_ops) * 1e-9

    def ops(self):
        for evs in self.device_ops.values():
            for e in evs:
                if e.end > self.lo and e.start < self.hi:
                    yield e

    def top_ops(self, n: int = 10) -> list[list]:
        """The device ops that took most time, by self time (what ops
        nested in them took is theirs): ``[op opcode, seconds]``."""
        tot: dict[str, float] = {}
        for evs in self.device_ops.values():
            for e, t in zip(evs, self_times(evs, self.lo, self.hi)):
                key = f"{e.op} {e.opcode}".strip()
                tot[key] = tot.get(key, 0.0) + t * 1e-9
        share = 1.0 / max(len(self.device_ops), 1)
        return [[k, v * share] for k, v in
                sorted(tot.items(), key=lambda kv: -kv[1])[:n] if v > 0]

    def idle_gaps(self, n: int = 10) -> list[list]:
        """The longest idle gaps of the first device, each named by the
        benchmark's host span open at its middle: ``[span, seconds]``."""
        if not self.device_ops:
            return []
        evs = next(iter(self.device_ops.values()))
        gs = gaps([(e.start, e.end) for e in evs], self.lo, self.hi)
        gs.sort(key=lambda g: g[0] - g[1])
        return [[span_at(self.host_spans, (a + b) / 2), (b - a) * 1e-9]
                for a, b in gs[:n]]


def read_xplane(path: str, window_ns: float) -> Reduced:
    """Reduce one ``.xplane.pb``. Its timestamps start at the profiler's
    start, so the window is [0, window_ns]."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    device_ops: dict[str, list[Event]] = {}
    host: list[Event] = []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    device_ops[plane.name] = [
                        Event(e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(HOST_SPAN_PREFIX):
                        host.append(Event(e.name, e.start_ns,
                                          e.start_ns + e.duration_ns))
    return Reduced(device_ops, host, 0.0, float(window_ns))


def find_xplane(log_dir: str) -> str | None:
    found = sorted(glob.glob(str(Path(log_dir) / "plugins" / "profile" / "*"
                                 / "*.xplane.pb")))
    return found[-1] if found else None
