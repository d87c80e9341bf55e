"""Closed-loop callers send the pool's requests through ``MappingService``.

Besides the keys of ``common.py``, the mix gives ``workers`` (the
service's worker processes; 0 maps in this process) and ``service``, the
other options of ``MappingService``. Each of ``clients`` callers has one
request outstanding: it sends the next one drawn by ``order`` as soon as
its answer is back. Set-up sends every request alone and then runs the
callers for ``warmup_rounds`` passes over the pool (default 2 where there
is more than one caller), so the merged batches that callers meet are
compiled there; those that set-up did not meet compile in the window,
where ``window_compiles`` counts them. After the window, every request is
mapped by the direct path, for the check that the service changes no
answer.
"""
from __future__ import annotations

import threading
import time

import numpy as np

from bench import common as C


def run(cell, seed: int, window: C.Window, hooks) -> dict:
    from repro.serve.mapper import MappingService
    tr = cell.traffic
    h = C.hierarchy(cell)
    with C.span("bench.inputs"):
        reqs = C.requests(cell, seed)
    cfgs = {r.key: C.program_config(cell, r.config_seed) for r in reqs}
    clients = int(tr.get("clients", 1))
    draw = C.Order(reqs, seed, tr.get("order", "shuffle"))
    svc = MappingService(workers=int(tr.get("workers", 0)),
                         **tr.get("service", {}))

    def ask(r) -> C.Answer:
        t = time.time()
        try:
            with C.span("bench.request"):
                res = svc.submit(r.graph, h, cfgs[r.key]).result()
        except Exception as e:  # noqa: BLE001 - a failed request counts
            return C.Answer(r, t, time.time(),
                            error=f"{type(e).__name__}: {e}")
        return C.Answer(r, t, time.time(), np.asarray(res.pe_of),
                        float(res.J), res.stats)

    def callers(go) -> list[C.Answer]:
        """``clients`` closed loops, each sending while ``go()`` holds."""
        out: list[C.Answer] = []
        lock = threading.Lock()

        def loop():
            while go():
                a = ask(draw())
                with lock:
                    out.append(a)
        threads = [threading.Thread(target=loop) for _ in range(clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        return out

    try:
        with C.span("bench.warmup"):
            warm = [ask(r) for r in reqs]
            if clients > 1:
                left = iter(range(int(tr.get("warmup_rounds", 2))
                                  * len(reqs)))
                lock = threading.Lock()

                def budget() -> bool:
                    with lock:
                        return next(left, None) is not None
                warm += callers(budget)
        C.log(f"set-up: {len(warm)} answers warmed, "
              f"{[round(a.t_done - a.t_submit, 3) for a in warm]} s each")
        hooks.setup_done()
        before = svc.stats()["coalesce"]
        t0 = window.open()
        answers = callers(window.is_open)
        after = svc.stats()["coalesce"]
    finally:
        svc.close()

    def direct_answers() -> dict:
        """The direct path's answer to each request of the pool."""
        with C.span("bench.check"):
            return {"direct": {r.key: C.map_direct(r, h, cfgs[r.key]).pe_of
                               for r in reqs}}
    return {"t0": t0, "answers": answers, "warmup": warm, "requests": reqs,
            "hierarchy": h, "after_window": direct_answers,
            "counters": {"coalesce_before": before, "coalesce_after": after}}
