"""End to end: the mapping's quality. For each instance of the pool, the
mean over its answers in the window of float64 J over the expected J of a
uniform random placement (``check.py`` sets each answer's ratio); then the
mean over the instances. Each instance weighs the same however often the
window happened to draw it. A faster mapper with a worse J is not
faster."""
from bench.check import in_window


def read(rec: dict):
    per: dict[str, list[float]] = {}
    for a in in_window(rec):
        if a.ratio is None:
            return None
        per.setdefault(a.req.inst.name, []).append(a.ratio)
    if not per:
        return None
    return sum(sum(v) / len(v) for v in per.values()) / len(per)
