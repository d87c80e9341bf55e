"""The comparison that decides ``correct``, and the window's answers.

Every answer the run produced (set-up, window and late) is compared with
the plain reference of ``yardstick.py``:

- ``failed``: requests that raised; limit 0.
- ``pe_bad``: answers whose mapping is not one PE id in [0, k) per vertex;
  limit 0.
- ``load``: the largest PE load over the load the instance admits there
  (``yardstick.load_over_limit``); the configuration's eps sets the limit 1.
- ``J_gap``: the largest relative gap between the J the program reports
  (float32, the ``mapcost`` kernel) and float64 ``numpy_J``.
- ``J_over_random_max``: the largest J over the expected J of a uniform
  random placement (``yardstick.random_J``).
- ``direct_mismatch`` (service cells): answers whose mapping differs from
  the direct path's for the same request; limit 0.

The limits of ``J_gap`` and ``J_over_random_max`` are the configuration's
``check_limits``; ``PERF.md`` gives the readings each was set from.
"""
from __future__ import annotations

import numpy as np

from bench import yardstick as Y

EXACT = {"failed": 0, "pe_bad": 0, "direct_mismatch": 0}


def in_window(rec: dict) -> list:
    """The answers the end-to-end metrics count: completed, without error,
    by the window's close."""
    return [a for a in rec["answers"]
            if a.error is None and a.t_done <= rec["close"]]


def window_bounds(rec: dict) -> tuple[float, float]:
    done = in_window(rec)
    return rec["t0"], max((a.t_done for a in done), default=rec["t0"])


def check_run(rec: dict) -> dict:
    cell = rec["cell"]
    h = rec["hierarchy"]
    a_, d_, k = tuple(h.a), tuple(h.d), int(np.prod(h.a))
    eps = float(cell.config["eps"])
    limits = dict(EXACT, load=1.0, **cell.config["check_limits"])
    every = rec["warmup"] + rec["answers"]
    rand_J = {a.req.inst.name: Y.random_J(a.req.inst, a_, d_) for a in every}
    nums = {"failed": sum(a.error is not None for a in every),
            "pe_bad": 0, "load": 0.0, "J_gap": 0.0, "J_over_random_max": 0.0}
    for a in every:
        if a.error is not None:
            continue
        inst, pe = a.req.inst, np.asarray(a.pe_of)
        if pe.shape != (inst.n,) or pe.min() < 0 or pe.max() >= k:
            nums["pe_bad"] += 1
            continue
        J64 = Y.numpy_J(inst, pe, a_, d_)
        nums["load"] = max(nums["load"], Y.load_over_limit(inst, pe, k, eps))
        nums["J_gap"] = max(nums["J_gap"],
                            abs(a.J - J64) / max(abs(J64), 1e-300))
        a.ratio = J64 / rand_J[inst.name]
        nums["J_over_random_max"] = max(nums["J_over_random_max"], a.ratio)
    if "direct" in rec:
        nums["direct_mismatch"] = sum(
            1 for a in every if a.error is None
            and not np.array_equal(np.asarray(a.pe_of),
                                   rec["direct"].get(a.req.key)))
    correct = all(nums[n] <= limits[n] for n in nums)
    if not in_window(rec):
        correct = False  # no answer in the window: nothing was served
    started = [a for a in rec["answers"] if a.t_submit < rec["close"]]
    return {"correct": bool(correct), "attempted": len(started),
            "failed": sum(a.error is not None for a in started),
            "numbers": {n: {"value": nums[n], "limit": limits[n]}
                        for n in nums}}
