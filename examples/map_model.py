"""Map a real model's communication graph onto the chip hierarchy.

The PR 10 closed loop, end to end: compile one tiny train cell of a model
from ``configs/`` (abstract params — no weights materialize), extract its
per-op HLO communication graph as a :class:`TaskGraph`, run SharedMap on
the physical 16x16 chip hierarchy, and compare the communication cost J
against a launcher that ignores the communication pattern entirely.

    PYTHONPATH=src python examples/map_model.py [arch]

Any ``configs/`` arch works; the default (whisper-tiny) finishes in about
a minute on one CPU core.
"""
import sys
import time

from repro.compile_cache import use_compile_cache
from repro.core.api import SharedMapConfig, shared_map
from repro.core.mapping import evaluate_J
from repro.launch.comm_graph import default_placement, model_comm_graph
from repro.launch.mesh import physical_hierarchy


def main(arch: str = "whisper-tiny"):
    use_compile_cache()
    h = physical_hierarchy(False)  # 16 chips/rack x 16 racks, D = 1:10
    print(f"hierarchy {h} -> k={h.k} chips")

    # 1. compile + extract (min_tasks=2k auto-expands fusion groups until
    #    the graph is fine-grained enough to spread over k chips)
    t0 = time.time()
    tg = model_comm_graph(arch, min_tasks=2 * h.k)
    print(f"extracted {tg!r} in {time.time() - t0:.1f}s "
          f"(granularity={tg.meta['granularity']}, "
          f"while_trips={tg.meta['while_trips']})")

    # 2. map
    t0 = time.time()
    res = shared_map(tg, h, SharedMapConfig(preset="fast"))
    print(f"mapped in {time.time() - t0:.1f}s "
          f"({res.stats['partition_calls']} partition calls)")

    # 3. score against program-order chunking onto the default chip order
    g = tg.to_graph()
    j_def = evaluate_J(g, h, default_placement(tg.n, h.k))
    print(f"J(sharedmap) = {res.J:12.4g}")
    print(f"J(default)   = {j_def:12.4g}   "
          f"-> {j_def / res.J:.2f}x less cross-hierarchy traffic")


if __name__ == "__main__":
    main(*sys.argv[1:2])
