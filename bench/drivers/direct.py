"""One caller maps the pool with ``shared_map`` (no service): each request
once in set-up, then requests drawn by the mix's ``order`` until the
window closes."""
from __future__ import annotations

from bench import common as C


def run(cell, seed: int, window: C.Window, hooks) -> dict:
    if int(cell.traffic.get("clients", 1)) != 1:
        raise ValueError("the direct driver has one caller")
    h = C.hierarchy(cell)
    with C.span("bench.inputs"):
        reqs = C.requests(cell, seed)
    cfgs = {r.key: C.program_config(cell, r.config_seed) for r in reqs}
    with C.span("bench.warmup"):
        warm = [C.map_direct(r, h, cfgs[r.key]) for r in reqs]
    C.log(f"set-up: {len(reqs)} requests warmed, "
          f"{[round(a.t_done - a.t_submit, 3) for a in warm]} s each")
    draw = C.Order(reqs, seed, cell.traffic.get("order", "shuffle"))
    hooks.setup_done()
    answers = []
    t0 = window.open()
    while window.is_open():
        r = draw()
        with C.span("bench.map"):
            answers.append(C.map_direct(r, h, cfgs[r.key]))
    return {"t0": t0, "answers": answers, "warmup": warm, "requests": reqs,
            "hierarchy": h, "counters": {}}
