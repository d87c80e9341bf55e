"""Tiny cells through the harness's own run, on the CPU with the look for
a chip stubbed: the result line as the contract has it, a driver and an
instance family added as files alone, and ``correct`` false for the
control and for each fault planted under the timed path."""
from __future__ import annotations

import json
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from bench import run as R  # noqa: E402

TINY = {"family": "rgg", "n": 300, "radius_scale": 0.55,
        "instance_seeds": [1, 2],
        "hierarchy": {"a": [4, 8, 3], "d": [1.0, 10.0, 100.0]},
        "eps": 0.03, "preset": "fast", "strategy": "bucket",
        "config_seed": 0,
        "check_limits": {"J_gap": 1e-5, "J_over_random_max": 0.3}}
MIXES = {"direct": {"driver": "direct", "clients": 1, "order": "shuffle",
                    "trace_seconds": 1},
         "serial": {"driver": "service", "clients": 1, "order": "shuffle",
                    "config_seeds": 2, "workers": 0,
                    "service": {"cache_entries": 0,
                                "degrade_on_failure": False},
                    "trace_seconds": 1},
         # four callers at once, each with one request outstanding; the
         # service merges no batches across requests, since every new
         # combination of merged shapes compiles, and a compile on a
         # loaded host outlasts the 1 s window
         "burst": {"driver": "service", "clients": 4, "order": "cycle",
                   "config_seeds": 2, "workers": 0,
                   "service": {"cache_entries": 0,
                               "degrade_on_failure": False,
                               "merge_across_requests": False,
                               "batch_window_s": 0.005},
                   "warmup_rounds": 1, "trace_seconds": 1}}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = tmp_path_factory.mktemp("benchroot")
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    (root / "bench" / "configs").mkdir(parents=True)
    (root / "bench" / "traffic").mkdir()
    (root / "bench" / "configs" / "tiny.json").write_text(json.dumps(TINY))
    for name, mix in MIXES.items():
        (root / "bench" / "traffic" / f"{name}.json").write_text(
            json.dumps(mix))
    bench["configs"] = [{"name": "tiny", "source": "x", "reduced": [],
                         "file": "bench/configs/tiny.json", "why": "x"}]
    bench["workloads"] = [{"name": f"tiny-{m}", "config": "tiny",
                           "traffic": m, "chips": 1, "why": "x"}
                          for m in MIXES]
    for m in bench["end_to_end"] + bench["per_layer"]:
        m.pop("workloads", None)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def cpu(chips):
    import jax
    return jax.devices()[:chips]


def run(root, cell, trace=0, seed=2**31 + 12345, control=False):
    argv = ["--workload", cell, "--seed", str(seed), "--seconds", "1",
            "--trace", str(trace)] + (["--control"] if control else [])
    return R.run(R.parse(argv), root=root, devices=cpu, use_cache=False)


@pytest.mark.parametrize("mix", list(MIXES))
def test_tiny_cell_end_to_end(root, mix):
    line = run(root, f"tiny-{mix}")
    assert list(line)[:3] == ["correct", "attempted", "failed"]
    assert list(line)[-1] == "check"
    assert line["correct"] is True, line["check"]
    assert line["attempted"] >= 1 and line["failed"] == 0
    m = line["metrics"]
    assert {"maps_per_s", "J_over_random", "setup_s"} <= set(m)
    assert 0 < m["J_over_random"]["value"] < 1
    assert line["device"]["platform"] == "cpu"
    assert line["device"]["count"] == 1
    assert "memory_peak_bytes" in line["device"]
    if mix != "direct":
        assert "direct_mismatch" in line["check"]
    json.dumps(line)  # one JSON line


def test_tiny_cell_traced(root):
    line = run(root, "tiny-direct", trace=1)
    assert not (root / R.TRACE_DIR).exists()
    m = line["metrics"]
    assert m["padded_work_ratio"]["value"] >= 1.0
    assert m["window_compiles"]["value"] == 0.0
    assert "window_s" in line["device"] and "breakdown" in line
    assert line["correct"] is True


FAMILY = """
import numpy as np
from bench import yardstick as Y


def instances(config, seed):
    n = int(config["n"])
    u = np.arange(n)
    v = (u + 1) % n
    return [Y.Instance(f"ring{n}", n, np.minimum(u, v), np.maximum(u, v))]
"""
DRIVER = """
from bench import common as C


def run(cell, seed, window, hooks):
    h = C.hierarchy(cell)
    reqs = C.requests(cell, seed)
    cfg = C.program_config(cell, 0)
    warm = [C.map_direct(r, h, cfg) for r in reqs]
    hooks.setup_done()
    t0 = window.open()
    answers = []
    while window.is_open():
        answers += [C.map_direct(r, h, cfg) for r in reqs]
    return {"t0": t0, "answers": answers, "warmup": warm,
            "requests": reqs, "hierarchy": h, "counters": {}}
"""


def test_a_new_family_and_driver_run_with_no_code_change(root, tmp_path):
    """An instance family and a driver added as files under a new checkout's
    bench directory, found by the names a configuration and a mix give."""
    new = tmp_path / "checkout"
    for d in ("configs", "traffic", "families", "drivers"):
        (new / "bench" / d).mkdir(parents=True)
    (new / "bench" / "families" / "ring.py").write_text(FAMILY)
    (new / "bench" / "drivers" / "once.py").write_text(DRIVER)
    (new / "bench" / "configs" / "ring.json").write_text(json.dumps(
        dict(TINY, family="ring", n=256,
             hierarchy={"a": [2, 2], "d": [1.0, 10.0]})))
    (new / "bench" / "traffic" / "once.json").write_text(json.dumps(
        {"driver": "once", "clients": 1, "trace_seconds": 1}))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"] = [{"name": "ring", "source": "x", "reduced": [],
                         "file": "bench/configs/ring.json", "why": "x"}]
    bench["workloads"] = [{"name": "ring-once", "config": "ring",
                           "traffic": "once", "chips": 1, "why": "x"}]
    (new / "BENCHMARK.json").write_text(json.dumps(bench))
    line = run(new, "ring-once")
    assert line["correct"] is True, line["check"]
    assert {"maps_per_s", "J_over_random", "setup_s"} <= set(line["metrics"])


@pytest.mark.parametrize("mix", ["direct", "burst"])
def test_control_is_not_correct(root, mix):
    line = run(root, f"tiny-{mix}", control=True)
    assert line["correct"] is False
    gap = line["check"]["J_gap"]
    assert gap["value"] > gap["limit"]


def _zeros(batch, eps, salts):
    import jax.numpy as jnp
    return jnp.zeros(batch.vwgt.shape, jnp.int32)


def _half(fn):
    def half(batch, eps, salts):
        parts = fn(batch, eps, salts)
        return parts.at[(parts.shape[0] + 1) // 2:].set(0)
    return half


def _shuffled(hm):
    def shuffled(g, h, **kw):
        res = hm(g, h, **kw)
        res.pe_of = np.random.default_rng(0).permutation(
            np.asarray(res.pe_of))
        return res
    return shuffled


def _service_altered(finalize):
    def altered(self, req, ms_result):
        pe = np.array(ms_result.pe_of)
        pe[0] = (pe[0] + 1) % req.h.k
        ms_result.pe_of = pe
        return finalize(self, req, ms_result)
    return altered


FAULTS = {
    # a partition step that returns its state unchanged: every lane's
    # vertices left in block 0
    "state_unchanged": ("repro.core.multisection", "batched_partition",
                        lambda orig: lambda *a, **k: _zeros),
    # half of each batched dispatch's lanes left unpartitioned
    "half_batch": ("repro.core.multisection", "batched_partition",
                   lambda orig: lambda *a, **k: _half(orig(*a, **k))),
    # the answer altered where it is produced
    "answer_altered": ("repro.core.api", "hierarchical_multisection",
                       _shuffled),
}


@pytest.mark.parametrize("fault", list(FAULTS))
def test_fault_under_the_direct_path_is_not_correct(root, monkeypatch,
                                                    fault):
    import importlib
    mod, attr, make = FAULTS[fault]
    module = importlib.import_module(mod)
    monkeypatch.setattr(module, attr, make(getattr(module, attr)))
    line = run(root, "tiny-direct")
    assert line["correct"] is False, (fault, line["check"])


@pytest.mark.parametrize("mix", ["serial", "burst"])
def test_answer_altered_in_the_service_is_not_correct(root, monkeypatch,
                                                      mix):
    from repro.serve.mapper import MappingService
    monkeypatch.setattr(MappingService, "_finalize",
                        _service_altered(MappingService._finalize))
    line = run(root, f"tiny-{mix}")
    assert line["correct"] is False
    assert line["check"]["direct_mismatch"]["value"] > 0
