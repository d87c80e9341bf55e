"""The benchmark's harness on the CPU: cells found by name, the yardstick
and the trace reduction. No chip and no program run here."""
from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from bench import trace as T  # noqa: E402
from bench import yardstick as Y  # noqa: E402
from bench.spec import Cell  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())


@pytest.mark.parametrize("name", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_resolves(name):
    cell = Cell(ROOT, name)
    assert callable(cell.driver())
    assert callable(cell.family())
    for m in cell.per_layer + cell.end_to_end:
        assert callable(cell.reader(m["name"]))
    names = {m["name"] for m in cell.end_to_end}
    assert {"setup_s", "maps_per_s", "J_over_random"} <= names
    assert cell.chips == 1


def test_a_new_cell_is_found_by_name_with_no_code_change(tmp_path):
    """A configuration, a traffic mix and a per-layer metric added as files
    and entries only."""
    bench = dict(BENCH)
    (tmp_path / "bench" / "configs").mkdir(parents=True)
    (tmp_path / "bench" / "traffic").mkdir()
    (tmp_path / "bench" / "metrics").mkdir()
    (tmp_path / "bench" / "configs" / "new.json").write_text(json.dumps(
        {"family": "rgg", "n": 300, "instance_seeds": [5]}))
    (tmp_path / "bench" / "traffic" / "newmix.json").write_text(json.dumps(
        {"driver": "direct", "clients": 1}))
    (tmp_path / "bench" / "metrics" / "new_metric.py").write_text(
        "def read(rec):\n    return 42.0\n")
    bench["configs"] = BENCH["configs"] + [
        {"name": "new", "source": "x", "file": "bench/configs/new.json",
         "reduced": [], "why": "x"}]
    bench["workloads"] = BENCH["workloads"] + [
        {"name": "new-cell", "config": "new", "traffic": "newmix",
         "chips": 1, "why": "x"}]
    bench["per_layer"] = BENCH["per_layer"] + [
        {"name": "new_metric", "unit": "x", "better": "lower",
         "source": "program_counter", "layer": "planner",
         "moves": "maps_per_s", "workloads": ["new-cell"]}]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = Cell(tmp_path, "new-cell")
    assert cell.config["n"] == 300 and cell.traffic["clients"] == 1
    assert [m["name"] for m in cell.per_layer][-1] == "new_metric"
    assert cell.reader("new_metric")({}) == 42.0
    # the harness's own readers are found from a new root as well
    assert callable(cell.reader("padded_work_ratio"))
    with pytest.raises(KeyError):
        Cell(tmp_path, "no-such-cell")


def test_numpy_J_equals_evaluate_J():
    from repro.core import graph as G
    from repro.core.hierarchy import Hierarchy
    from repro.core.mapping import evaluate_J
    rng = np.random.default_rng(7)
    for seed, (a, d) in enumerate([((4, 8, 3), (1.0, 10.0, 100.0)),
                                   ((16, 16), (1.0, 10.0)),
                                   ((2, 3), (1.0, 5.0))]):
        h = Hierarchy(a=a, d=d)
        u, v = Y.gen_rgg_edges(500, seed + 1)
        w = rng.integers(1, 5, u.size).astype(np.float64)
        inst = Y.Instance("g", 500, u, v, w)
        pe = rng.integers(0, h.k, 500)
        g = G.from_edges(500, u, v, w)
        ref = Y.numpy_J(inst, pe, a, d)
        assert ref > 0
        assert evaluate_J(g, h, pe) == pytest.approx(ref, rel=1e-6)


def test_rgg_copy_is_deterministic_and_matches_the_program():
    from repro.core import graph as G
    a = Y.gen_rgg_edges(2000, 11)
    b = Y.gen_rgg_edges(2000, 11)
    c = Y.gen_rgg_edges(2000, 12)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert not np.array_equal(a[0], c[0])
    g = G.gen_rgg(2000, seed=11, host=True)
    m = int(g.m)
    ours = G.from_edges(2000, *a, host=True)
    assert int(ours.m) == m
    assert np.array_equal(np.asarray(ours.rows)[:m], np.asarray(g.rows)[:m])
    assert np.array_equal(np.asarray(ours.cols)[:m], np.asarray(g.cols)[:m])


def test_load_over_limit_admits_a_heavy_task():
    # 4 PEs, W = 16, ceil(W/k) = 4: a task of 10 gets a PE of its own
    inst = Y.Instance("h", 4, [0], [1], vwgt=[10.0, 2.0, 2.0, 2.0])
    assert Y.load_over_limit(inst, [0, 1, 2, 3], 4, 0.03) == \
        pytest.approx(10 / (1.03 * 10))
    # two light tasks on one PE: 4 <= 1.03 * 4
    assert Y.load_over_limit(inst, [0, 1, 1, 3], 4, 0.03) == \
        pytest.approx(4 / (1.03 * 4))
    # the heavy task and a light one: 12 > 1.03 * 10
    assert Y.load_over_limit(inst, [0, 0, 2, 3], 4, 0.03) > 1.0


def test_random_J_is_the_mean_over_random_placements():
    u, v = Y.gen_rgg_edges(3000, 4)
    inst = Y.Instance("g", 3000, u, v, np.random.default_rng(0).random(u.size))
    rng = np.random.default_rng(1)
    for a, d in [((4, 8, 3), (1.0, 10.0, 100.0)), ((16, 16), (1.0, 10.0))]:
        k = int(np.prod(a))
        sampled = np.mean([Y.numpy_J(inst, rng.integers(0, k, 3000), a, d)
                           for _ in range(200)])
        assert Y.random_J(inst, a, d) == pytest.approx(sampled, rel=0.01)


def test_bf16_control_departs_from_float64():
    u, v = Y.gen_rgg_edges(4000, 3)
    inst = Y.Instance("g", 4000, u, v)
    pe = np.random.default_rng(1).integers(0, 96, 4000)
    a, d = (4, 8, 3), (1.0, 10.0, 100.0)
    J = Y.numpy_J(inst, pe, a, d)
    assert abs(Y.bf16_J(inst, pe, a, d) - J) / J > 1e-4


def test_trace_reduction_on_a_hand_built_trace():
    ops = [T.Event("a", 0, 10), T.Event("b", 10, 25), T.Event("a", 30, 40),
           T.Event("c", 90, 120)]
    host = [T.Event("bench.map", 0, 60), T.Event("bench.check", 60, 100),
            T.Event("bench.inputs", 45, 55)]
    r = T.Reduced({"/device:TPU:0": ops}, host, 0.0, 100.0)
    assert r.window_s == pytest.approx(100e-9)
    # busy: [0, 25] + [30, 40] + [90, 100] = 45 ns of 100
    assert r.busy_s == pytest.approx(45e-9)
    assert r.top_ops(2) == [["a", pytest.approx(20e-9)],
                            ["b", pytest.approx(15e-9)]]
    # gaps: [25, 30] in bench.map, [40, 90] mid 65 in bench.check
    assert r.idle_gaps() == [["bench.check", pytest.approx(50e-9)],
                             ["bench.map", pytest.approx(5e-9)]]
    assert T.union_length([(0, 10), (5, 20)], 0, 100) == 20
    assert T.gaps([(10, 20)], 0, 30) == [(0, 10), (20, 30)]
    # a while op encloses its body's ops: top ops count self time
    body = [T.Event("%while.3 = (s32[]) while(s32[] %t), body=%b", 0, 50),
            T.Event("%fusion.7 = f32[8]{0} fusion(f32[8]{0} %x), kind=kLoop",
                    10, 40)]
    rw = T.Reduced({"d": body}, [], 0.0, 100.0)
    assert rw.top_ops() == [["fusion.7 fusion", pytest.approx(30e-9)],
                            ["while.3 while", pytest.approx(20e-9)]]
    assert rw.busy_s == pytest.approx(50e-9)
    # two devices: busy is their mean
    r2 = T.Reduced({"0": ops, "1": [T.Event("x", 0, 100)]}, host, 0.0, 100.0)
    assert r2.busy_s == pytest.approx(72.5e-9)


def test_run_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    name = BENCH["workloads"][0]["name"]
    p = subprocess.run([sys.executable, str(ROOT / "bench" / "run.py"),
                        "--workload", name, "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert p.stdout.strip() == ""


# as XLA prints a vmapped call of the kernel for a v5e
LP = ('%vmap_jit_lp_gain_pallas___.1 = f32[4,8,1024]{2,1,0:T(8,128)S(1)} '
      'custom-call(%select_bitcast_fusion, %bitcast.24), '
      'custom_call_target="tpu_custom_call", operand_layout_constraints={'
      's32[4,16,1024]{2,1,0}, f32[4,16,1024]{2,1,0}}, backend_config={"x":1}')
LP_TYPED = ('%jit_lp_gain_pallas___.7 = f32[4,8,1024]{2,1,0} custom-call('
            's32[4,16,1024]{2,1,0:T(8,128)} %a, '
            'f32[4,16,1024]{2,1,0:T(8,128)} %b), '
            'custom_call_target="tpu_custom_call"')
# as a v5e trace names a call whose lanes share the weights
LP_SHARED = ('%lp_gain_pallas.2 = f32[2,3,8192]{2,1,0:T(4,128)S(1)} '
             'custom-call(s32[2,16,8192]{2,1,0:T(8,128)S(1)} %fusion.1487, '
             'f32[16,8192]{1,0:T(8,128)S(1)} %bitcast.2270), '
             'custom_call_target="tpu_custom_call", '
             'operand_layout_constraints={s32[2,16,8192]{2,1,0}, '
             'f32[16,8192]{1,0}}, frontend_attributes={kernel_metadata={}}')
CONTRACT = ('%jit_contract_edges_pallas___.6 = (s32[16,1024]{1,0}, '
            'f32[16,1024]{1,0}) custom-call(s32[16,1024]{1,0} %c, '
            'f32[16,1024]{1,0} %d), custom_call_target="tpu_custom_call"')


def test_lp_gain_cost_from_the_hlo_text_of_a_call():
    from bench import kernels as K
    ops, nbytes = K.lp_gain_cost(LP)
    assert ops == 3 * 8 * 16 * 4 * 1024
    assert nbytes == 4 * 4 * 1024 * (8 + 16 + 16)
    assert K.lp_gain_cost(LP_TYPED) == (ops, nbytes)
    assert K.lp_gain_cost(LP_SHARED) == (
        3 * 3 * 16 * 2 * 8192, 4 * 8192 * (2 * 3 + 2 * 16 + 16))
    assert K.lp_gain_cost(CONTRACT) is None
    assert K.lp_gain_cost("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %x)") \
        is None
    assert K.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        K.peaks("cpu")


def test_lp_gain_roofline_reader():
    from bench import kernels as K
    spec = Cell(ROOT, BENCH["workloads"][0]["name"])
    read = spec.reader("lp_gain_roofline")
    _, nbytes = K.lp_gain_cost(LP)
    least_ns = nbytes / 819e9 * 1e9
    ops = [T.Event(LP, 100, 100 + 4 * least_ns),   # at a quarter of its bound
           T.Event(CONTRACT, 0, 50),
           T.Event(LP, 0, 10 ** 7)]                # cut by the window's end
    tr = T.Reduced({"/device:TPU:0": ops}, [], 0.0, 10 ** 6)
    assert read({"trace": tr, "device_kind": "TPU v5 lite"}) == \
        pytest.approx(25.0)
    empty = T.Reduced({"/device:TPU:0": ops[1:2]}, [], 0.0, 10 ** 6)
    assert read({"trace": empty, "device_kind": "TPU v5 lite"}) is None


def test_run_exits_nonzero_without_the_program(tmp_path):
    """A checkout that holds only BENCHMARK.json and the benchmark's files:
    past the look for a chip (stubbed), the program is missing."""
    import shutil
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in BENCH["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    stub = ("import sys, jax; sys.path.insert(0, sys.argv[1]); "
            "jax.default_backend = lambda: 'tpu'; "
            "from bench import run as R; sys.exit(R.main(sys.argv[2:]))")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run([sys.executable, "-c", stub, str(tmp_path),
                        "--workload", BENCH["workloads"][0]["name"],
                        "--seed", "1", "--seconds", "1", "--trace", "0"],
                       cwd=tmp_path, env=env, capture_output=True, text=True,
                       timeout=120)
    assert p.returncode != 0
    assert "program was not found" in p.stderr
    assert p.stdout.strip() == ""


def test_order_draws_passes_over_the_pool():
    from bench.common import Order
    pool = list("abcde")
    draw = Order(pool, 2**31 + 7)
    passes = [[draw() for _ in pool] for _ in range(4)]
    assert all(sorted(p) == pool for p in passes)
    assert len({"".join(p) for p in passes}) > 1
    again = Order(pool, 2**31 + 7)
    assert [again() for _ in range(20)] == [x for p in passes for x in p]
    cyc = Order(pool, 1, "cycle")
    assert "".join(cyc() for _ in range(7)) == "abcdeab"
    with pytest.raises(ValueError):
        Order(pool, 1, "zipf")


class _A:
    def __init__(self, name, ratio, t_done, error=None):
        self.req = type("R", (), {"inst": type("I", (), {"name": name})})
        self.ratio, self.t_done, self.t_submit = ratio, t_done, 0.0
        self.error = error


def test_end_to_end_readers_count_the_window():
    spec = Cell(ROOT, BENCH["workloads"][0]["name"])
    rec = {"t0": 0.0, "close": 10.0, "setup_s": 42.0,
           "answers": [_A("a", 0.01, 2.0), _A("a", 0.01, 4.0),
                       _A("a", 0.01, 6.0), _A("b", 0.04, 8.0),
                       _A("b", 9.0, 11.0),            # late: not counted
                       _A("b", None, 9.0, "boom")]}   # failed: not counted
    assert spec.reader("maps_per_s")(rec) == pytest.approx(4 / 8.0)
    # each instance weighs the same: (0.01 + 0.04) / 2
    assert spec.reader("J_over_random")(rec) == pytest.approx(0.025)
    assert spec.reader("setup_s")(rec) == 42.0
    empty = dict(rec, answers=[])
    assert spec.reader("maps_per_s")(empty) is None
    assert spec.reader("J_over_random")(empty) is None
