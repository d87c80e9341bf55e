"""Finds a cell's configuration, traffic mix and code by name.

``BENCHMARK.json`` at the checkout's root names each cell's configuration
(``configs[].file``) and traffic mix. Under ``<bench>``, the first of
``paths``:

- a traffic mix is ``traffic/<name>.json``; its ``driver`` key names
  ``drivers/<driver>.py``, which exports ``run(cell, seed, window, hooks)``;
- a configuration's ``family`` key names ``families/<family>.py``, which
  exports ``instances(config, seed)``;
- a metric, end-to-end or per-layer, is ``metrics/<name>.py``, which
  exports ``read(record)``.

Adding a cell, a configuration, a mix, a driver, a family or a metric is
adding files and entries: nothing here changes.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

HERE = Path(__file__).resolve().parent


class Cell:
    def __init__(self, root: Path, name: str):
        self.root = Path(root)
        bench = json.loads((self.root / "BENCHMARK.json").read_text())
        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                           f"(has {sorted(cells)})")
        self.workload = cells[name]
        self.name = name
        self.chips = int(self.workload["chips"])
        self.bench_dir = self.root / bench["paths"][0]
        configs = {c["name"]: c for c in bench["configs"]}
        self.config_entry = configs[self.workload["config"]]
        self.config = json.loads(
            (self.root / self.config_entry["file"]).read_text())
        self.traffic = json.loads(
            (self.bench_dir / "traffic" / f"{self.workload['traffic']}.json")
            .read_text())
        self.end_to_end = [m for m in bench["end_to_end"] if self._has(m)]
        self.per_layer = [m for m in bench["per_layer"] if self._has(m)]

    def _has(self, metric: dict) -> bool:
        return "workloads" not in metric or self.name in metric["workloads"]

    def module(self, kind: str, name: str):
        """``<kind>/<name>.py``: the cell's own bench directory first, then
        this one's."""
        for d in (self.bench_dir / kind, HERE / kind):
            path = d / f"{name}.py"
            if path.is_file():
                spec = importlib.util.spec_from_file_location(
                    f"bench_{kind}_{name}", path)
                mod = importlib.util.module_from_spec(spec)
                spec.loader.exec_module(mod)
                return mod
        raise KeyError(f"no {kind}/{name}.py")

    def driver(self):
        """``run(cell, seed, window, hooks) -> record`` of the mix's driver."""
        return self.module("drivers", self.traffic["driver"]).run

    def family(self):
        """``instances(config, seed) -> [Instance]`` of the configuration's
        instance family."""
        return self.module("families", self.config["family"]).instances

    def reader(self, metric: str):
        """``read(record) -> float | None`` of ``metrics/<metric>.py``."""
        return self.module("metrics", metric).read
