"""The Graph500 family (``bench/families/kron.py``) and the readers of the
coarsening counters (``bench/metrics/twohop_merge_share.py``,
``coarsest_over_target.py``). No chip and no program run here."""
from __future__ import annotations

import json
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from bench.families import kron  # noqa: E402
from bench.spec import Cell  # noqa: E402

CELL = "kron-4x8x3-direct"
CONFIG = {"scale": 10, "edgefactor": 16,
          "initiator": {"A": 0.57, "B": 0.19, "C": 0.19},
          "instance_seeds": [1, 2]}


def test_generator_is_deterministic_per_seed():
    a = kron.kronecker_edges(9, 16, 5)
    b = kron.kronecker_edges(9, 16, 5)
    c = kron.kronecker_edges(9, 16, 6)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert not np.array_equal(a[0], c[0])
    assert a[0].shape == (16 << 9,)
    assert a[0].min() >= 0 and max(a[0].max(), a[1].max()) < 1 << 9


def test_quadrant_frequencies_match_the_initiator():
    """Each bit of each draw falls in quadrant (0,0), (0,1), (1,0), (1,1)
    with probability A, B, C, D. 163,840 bit draws: one standard error is
    at most 0.0013, so 0.01 is about 8 of them."""
    ii, jj = kron.initiator_draws(np.random.default_rng(3), 10, 16 << 10,
                                  0.57, 0.19, 0.19)
    freq = [np.mean(~ii & ~jj), np.mean(~ii & jj), np.mean(ii & ~jj),
            np.mean(ii & jj)]
    np.testing.assert_allclose(freq, [0.57, 0.19, 0.19, 0.05], atol=0.01)


def test_labels_are_permuted():
    """Unpermuted, R-MAT piles the edges on ids with few one bits (the A
    quadrant is the heaviest); the permutation spreads them."""
    scale = 10
    ii, jj = kron.initiator_draws(np.random.default_rng(4), scale,
                                  16 << scale, 0.57, 0.19, 0.19)
    raw = (ii * (1 << np.arange(scale))[:, None]).sum(axis=0)
    u, _ = kron.kronecker_edges(scale, 16, 4)
    low = np.array([bin(x).count("1") <= 3 for x in range(1 << scale)])

    def share_on_low(ids):
        return np.mean(low[ids])

    assert share_on_low(raw) > 3 * low.mean()
    assert share_on_low(u) < 1.5 * low.mean()


def test_instances_keep_duplicates_and_isolated_vertices():
    insts = kron.instances(CONFIG, seed=123)
    assert [i.name for i in insts] == ["kron10e16-s1", "kron10e16-s2"]
    again = kron.instances(CONFIG, seed=999)  # the run's seed orders only
    for a, b in zip(insts, again):
        assert np.array_equal(a.u, b.u) and np.array_equal(a.v, b.v)
    inst = insts[0]
    assert inst.n == 1 << 10
    assert not np.any(inst.u == inst.v)
    u, v = kron.kronecker_edges(10, 16, 1)
    assert inst.u.size == np.count_nonzero(u != v)
    pairs = np.minimum(inst.u, inst.v) * inst.n + np.maximum(inst.u, inst.v)
    assert np.unique(pairs).size < pairs.size  # duplicates as drawn
    deg = np.bincount(np.concatenate([inst.u, inst.v]), minlength=inst.n)
    assert np.count_nonzero(deg == 0) > 0  # isolated vertices stay
    assert deg.max() > 20 * deg.mean()  # a power-law hub


def test_family_imports_nothing_of_the_program():
    code = ("import sys; sys.path.insert(0, sys.argv[1]); "
            "import bench.families.kron as k; "
            "k.instances({'scale': 6, 'edgefactor': 4, 'instance_seeds': [1], "
            "'initiator': {'A': 0.57, 'B': 0.19, 'C': 0.19}}, 0); "
            "bad = [m for m in sys.modules if m.split('.')[0] in "
            "('repro', 'jax')]; assert not bad, bad")
    subprocess.run([sys.executable, "-c", code, str(ROOT)], check=True,
                   timeout=120)


def test_the_cell_names_the_family():
    cell = Cell(ROOT, CELL)
    assert cell.config["family"] == "kron"
    assert cell.family() is not None
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cfg = {c["name"]: c for c in bench["configs"]}[cell.workload["config"]]
    assert cfg["reduced"] == ["scale"] and len(cfg["source"]) <= 200


class _Answer:
    def __init__(self, t_done, stats, error=None):
        self.t_done, self.stats, self.error = t_done, stats, error


def _read(metric, answers):
    return Cell(ROOT, CELL).reader(metric)({"close": 10.0,
                                            "answers": answers})


def test_twohop_merge_share_reader():
    a = {"coarsen_twohop_merges": 30, "coarsen_merges": 40}
    b = {"coarsen_twohop_merges": 10, "coarsen_merges": 60}
    assert _read("twohop_merge_share", [
        _Answer(1.0, a), _Answer(2.0, b),
        _Answer(11.0, a),                # after the window's close
        _Answer(3.0, b, error="boom"),   # failed
    ]) == pytest.approx(40 / 100)


def test_coarsest_over_target_reader():
    a = {"coarsest_real_vertices": 300, "coarsening_target": 200}
    b = {"coarsest_real_vertices": 100, "coarsening_target": 200}
    assert _read("coarsest_over_target", [
        _Answer(1.0, a), _Answer(2.0, b), _Answer(12.0, b),
    ]) == pytest.approx(400 / 400)


@pytest.mark.parametrize("metric,stats", [
    # a program without the counters (the parent's)
    ("twohop_merge_share", {"initial_compact_dispatches": 1,
                            "initial_dispatches": 1}),
    ("coarsest_over_target", {"vcycle_real_vertex_work": 3,
                              "vcycle_padded_vertex_work": 4}),
    # nothing merged, no lane coarsened
    ("twohop_merge_share", {"coarsen_twohop_merges": 0,
                            "coarsen_merges": 0}),
    ("coarsest_over_target", {"coarsest_real_vertices": 0,
                              "coarsening_target": 0}),
])
def test_coarsening_readers_read_nothing(metric, stats):
    assert _read(metric, [_Answer(1.0, stats)]) is None
