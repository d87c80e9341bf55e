"""The reader of the compact-initial counter
(``bench/metrics/initial_compact_share.py``), on hand-built records. No
chip and no program run here."""
from __future__ import annotations

import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from bench.spec import Cell  # noqa: E402

CELL = json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"][0]


class _Answer:
    def __init__(self, t_done, stats, error=None):
        self.t_done, self.stats, self.error = t_done, stats, error


def _read(answers):
    read = Cell(ROOT, CELL["name"]).reader("initial_compact_share")
    return read({"close": 10.0, "answers": answers})


def test_initial_compact_share_reader():
    both = {"initial_compact_dispatches": 2, "initial_dispatches": 2}
    assert _read([_Answer(1.0, both),
                  _Answer(2.0, dict(both, initial_compact_dispatches=1)),
                  # after the window's close, or failed: not counted
                  _Answer(11.0, dict(both, initial_compact_dispatches=0)),
                  _Answer(3.0, dict(both, initial_compact_dispatches=0),
                          error="boom")]) == pytest.approx(3 / 4)


@pytest.mark.parametrize("stats", [
    # a program without the counter
    {"vcycle_real_vertex_work": 3, "vcycle_padded_vertex_work": 4},
    # no dispatch whose initial partition could shrink
    {"initial_compact_dispatches": 0, "initial_dispatches": 0},
])
def test_initial_compact_share_reads_nothing(stats):
    assert _read([_Answer(1.0, stats)]) is None
