"""What every driver shares: requests, answers, the window and spans.

A traffic mix (``traffic/<name>.json``) is read by its driver; the keys
every driver reads are:

- ``driver``: ``drivers/<driver>.py``;
- ``clients``: closed-loop callers, each with one request outstanding;
- ``order``: how the callers draw the pool's requests: ``shuffle``, passes
  over the pool each in an order drawn from the run's seed, or ``cycle``,
  the pool's own order over and over;
- ``config_seeds``: the ``SharedMapConfig.seed`` values 0 .. n-1 that each
  instance is requested with (default: the configuration's
  ``config_seed``);
- ``trace_seconds``: how much of the window ``--trace 1`` profiles.

A driver builds the program's inputs, warms every shape its window uses
(set-up), measures for the given seconds, and returns the run's record:
the answers with their times, the counters read around the window, and
what the correctness check needs. Every call into the program is wrapped
in a ``jax.profiler.TraceAnnotation`` named ``bench.*``, so that a
trace's idle gaps can be named by what the benchmark was doing.
"""
from __future__ import annotations

import sys
import threading
import time

import numpy as np

from bench import yardstick as Y


class Answer:
    __slots__ = ("req", "t_submit", "t_done", "pe_of", "J", "stats", "error",
                 "ratio")

    def __init__(self, req, t_submit, t_done, pe_of=None, J=None, stats=None,
                 error=None):
        self.req, self.t_submit, self.t_done = req, t_submit, t_done
        self.pe_of, self.J, self.stats, self.error = pe_of, J, stats, error
        self.ratio = None  # float64 J over random J, set by the check


class Request:
    """One mapping request: an instance and the SharedMapConfig seed."""

    def __init__(self, inst: Y.Instance, graph, config_seed: int):
        self.inst, self.graph, self.config_seed = inst, graph, config_seed
        self.key = f"{inst.name}/seed{config_seed}"


def requests(cell, seed: int) -> list[Request]:
    """The pool: each instance of the configuration's family, as a program
    input, once for each configuration seed of the mix."""
    from repro.core.taskgraph import TaskGraph
    insts = cell.family()(cell.config, seed)
    seeds = range(int(cell.traffic.get("config_seeds", 0))) \
        or [cell.config.get("config_seed", 0)]
    out = []
    for inst in insts:
        tg = TaskGraph.from_edges(inst.n, inst.u, inst.v, inst.w,
                                  vwgt=inst.vwgt)
        out += [Request(inst, tg, s) for s in seeds]
    return out


class Order:
    """The requests the callers send, drawn by the mix's ``order``; safe
    to share between callers."""

    def __init__(self, pool: list, seed: int, policy: str = "shuffle"):
        if policy not in ("shuffle", "cycle"):
            raise ValueError(f"unknown order {policy!r}")
        self.pool, self.policy = pool, policy
        self.rng = np.random.default_rng([seed, 1])
        self._next: list = []
        self._lock = threading.Lock()

    def __call__(self):
        with self._lock:
            if not self._next:
                idx = (self.rng.permutation(len(self.pool))
                       if self.policy == "shuffle" else range(len(self.pool)))
                self._next = [self.pool[i] for i in reversed(list(idx))]
            return self._next.pop()


def program_config(cell, config_seed: int):
    from repro.core.api import SharedMapConfig
    c = cell.config
    return SharedMapConfig(eps=c["eps"], preset=c["preset"],
                           strategy=c["strategy"], seed=int(config_seed))


def hierarchy(cell):
    from repro.core.hierarchy import Hierarchy
    h = cell.config["hierarchy"]
    return Hierarchy(a=tuple(h["a"]), d=tuple(float(x) for x in h["d"]))


def log(msg: str) -> None:
    """Progress on standard error, before the check's lines."""
    print(f"bench: {msg}", file=sys.stderr, flush=True)


def span(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


class Window:
    """The measured window: [t0, close]; answers done after close are
    late and are checked, not counted."""

    def __init__(self, seconds: float, on_open=None):
        self.seconds = seconds
        self.on_open = on_open
        self.t0 = self.close = None

    def open(self):
        if self.on_open is not None:
            self.on_open()
        self.t0 = time.time()
        self.close = self.t0 + self.seconds
        return self.t0

    def is_open(self) -> bool:
        return time.time() < self.close


def map_direct(req: Request, h, cfg) -> Answer:
    """One mapping through ``shared_map``, complete on the host."""
    from repro.core.api import shared_map
    t = time.time()
    res = shared_map(req.graph, h, cfg)
    pe = np.asarray(res.pe_of)  # host array: the mapping is complete
    return Answer(req, t, time.time(), pe, float(res.J), res.stats)
