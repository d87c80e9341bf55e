"""Random geometric graphs (the paper's rgg family), made by the
benchmark's copy of the generator (``yardstick.gen_rgg_edges``).

The configuration gives ``n``, ``radius_scale`` and ``instance_seeds``:
a fixed set of graphs, whatever the run's seed. The program compiles
again for every new graph (its shapes follow the graph's edge count and
partition sizes), so a set drawn from the run's seed would put
compilation into set-up and vary it from run to run.
"""
from __future__ import annotations

from bench import yardstick as Y


def instances(config: dict, seed: int) -> list[Y.Instance]:
    n = int(config["n"])
    return [Y.Instance(f"rgg{n}-s{s}", n,
                       *Y.gen_rgg_edges(n, s, config["radius_scale"]))
            for s in config["instance_seeds"]]
