"""Stage scopes in the device programs, ``repro.*`` host spans, and the
v-cycle counter (core/multisection.py, Tracing)."""
import glob
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import graph as G
from repro.core.api import SharedMapConfig, shared_map
from repro.core.coarsen import coarsen_cascade
from repro.core.hierarchy import Hierarchy
from repro.core.multisection import hierarchical_multisection
from repro.core.partition import Preset, batched_partition, num_levels

H = Hierarchy(a=(4, 2, 3), d=(1.0, 10.0, 100.0))


@pytest.fixture(scope="module")
def g():
    return G.gen_rgg(300, seed=1)


def _stack(g, B):
    return jax.tree_util.tree_map(lambda a: jnp.stack([a] * B), g)


def _op_names(compiled) -> list[str]:
    return re.findall(r'op_name="([^"]*)"', compiled.as_text())


def test_batched_partition_ops_carry_their_stage(g):
    """Every stage of the fused v-cycle names its ops in the compiled
    program's metadata, through the vmaps of lanes and restarts."""
    fn = batched_partition(4, num_levels(g.N, 4), "eco", "auto", None)
    names = _op_names(jax.jit(fn.with_counters).lower(
        _stack(g, 2), jnp.full((2,), 0.03, jnp.float32),
        jnp.arange(2, dtype=jnp.int32)).compile())
    for stage in ("coarsen", "initial", "refine", "select"):
        assert any(f"({stage})" in n or f"/{stage}/" in n for n in names), \
            stage


def test_level_ops_and_evaluate_scopes(g):
    from repro.core.multisection import _scatter_op
    from repro.kernels import ops as kops
    B, N = 2, 16
    scatter = _scatter_op(B, N)
    names = _op_names(scatter.lower(
        jnp.zeros(B * N + 1, jnp.int32), jnp.zeros((B, N), jnp.int32),
        jnp.zeros((B, N), jnp.int32), jnp.zeros((B,), jnp.int32)).compile())
    assert names and all("level_ops" in n for n in names
                         if n.startswith("jit("))
    part = kops._mapcost_partials_ref.lower(
        g.rows, g.cols, g.ewgt, jnp.zeros(g.N, jnp.int32),
        jnp.asarray([1, 4, 8], jnp.int32),
        jnp.asarray([1.0, 10.0, 100.0], jnp.float32)).compile()
    assert any("/evaluate/" in n for n in _op_names(part))


def test_level_sizes_equal_the_coarsening_cascade(g):
    """One lane's per-level coarse vertex counts are the sizes
    ``coarsen_cascade`` gives for the same graph, ELL degree and salts."""
    levels = num_levels(g.N, 4)
    assert levels >= 2
    deg = G.default_ell_deg(g.N, g.M)
    fn = batched_partition(4, levels, "fast", "auto", deg)
    parts, sizes, _ = fn.with_counters(
        _stack(g, 2), jnp.full((2,), 0.03, jnp.float32),
        jnp.asarray([3, 5], jnp.int32))
    ns, _ = coarsen_cascade(g, levels, ell_deg=deg)
    assert sizes.shape == (2, levels) and sizes.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(sizes[0]), np.asarray(ns))
    np.testing.assert_array_equal(np.asarray(sizes[1]), np.asarray(ns))
    # the parts alone, as before
    np.testing.assert_array_equal(
        np.asarray(fn(_stack(g, 2), jnp.full((2,), 0.03, jnp.float32),
                      jnp.asarray([3, 5], jnp.int32))), np.asarray(parts))


@pytest.mark.parametrize("strategy,resident", [("bucket", None),
                                               ("bucket", False),
                                               ("device", None)])
def test_vcycle_counter_on_one_level(g, strategy, resident):
    """One hierarchy level is one lane, the root graph at (N0, M0): the
    counter reads its n, its coarse sizes and its v-cycles against N0 per
    pass, whichever path ran it."""
    h = Hierarchy(a=(4,), d=(1.0,))
    res = hierarchical_multisection(g, h, preset="eco", strategy=strategy,
                                    resident=resident)
    n, m = int(g.n), int(g.m)
    N0, M0 = 1 << (n - 1).bit_length(), 1 << (m - 1).bit_length()
    levels = num_levels(N0, 4)
    lane = G.repad_device(g, N0, M0)
    ns, _ = coarsen_cascade(lane, levels,
                            ell_deg=G.default_ell_deg(N0, M0))
    passes = 1 + Preset.get("eco").vcycles
    assert res.stats["vcycle_real_vertex_work"] == \
        n * passes + int(np.asarray(ns).sum())
    assert res.stats["vcycle_padded_vertex_work"] == N0 * (passes + levels)


def test_vcycle_counter_on_the_paper_hierarchy(g):
    res = shared_map(g, H, SharedMapConfig(preset="fast"))
    real = res.stats["vcycle_real_vertex_work"]
    padded = res.stats["vcycle_padded_vertex_work"]
    assert 0 < real <= padded
    assert "seconds" not in res.stats
    assert all(set(lv) == {"graphs"} for lv in res.stats["levels"])


def test_vcycle_counter_through_the_service(g):
    """The service's planner path reads the same counter as the direct
    path, through the same LevelPlanner."""
    from repro.core.api import shared_map_direct
    from repro.serve.mapper import MappingService
    cfg = SharedMapConfig(preset="fast")
    svc = MappingService(cache_entries=0)
    try:
        served = svc.map(g, H, cfg).stats
    finally:
        svc.close()
    direct = shared_map_direct(g, H, cfg).stats
    for key in ("vcycle_real_vertex_work", "vcycle_padded_vertex_work"):
        assert served[key] == direct[key] > 0


def test_counter_fetch_is_one_transfer_after_the_mapping(g):
    """The counter adds one fetch, after the pe_of fetch, and leaves the
    device strategy's one array fetch and its metadata reads as they were."""
    from repro.core.multisection import reset_transfer_stats, transfer_stats
    hierarchical_multisection(g, H, preset="fast", strategy="device")
    reset_transfer_stats()
    hierarchical_multisection(g, H, preset="fast", strategy="device")
    xf = transfer_stats()
    assert xf["d2h_array_fetches"] == 1, xf
    assert xf["d2h_meta_fetches"] <= 1, xf
    assert xf["d2h_counter_fetches"] == 1, xf


def test_shared_map_writes_repro_spans_of_one_request(g, tmp_path):
    from jax.profiler import ProfileData
    cfg = SharedMapConfig(preset="fast")
    shared_map(g, H, cfg)  # compiles outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        shared_map(g, H, cfg)
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                          / "*.xplane.pb"))
    spans = [e for plane in ProfileData.from_file(path).planes
             for line in plane.lines for e in line.events
             if e.name.startswith("repro.")]
    names = {e.name for e in spans}
    assert names == {"repro.map", "repro.plan", "repro.dispatch",
                     "repro.advance", "repro.fetch", "repro.finalize"}
    reqs = {str(dict(e.stats)["req"]) for e in spans}
    assert len(reqs) == 1, reqs
    plans = [dict(e.stats) for e in spans if e.name == "repro.plan"]
    assert [p["depth"] for p in plans] == [3, 2, 1]
    assert [p["lanes"] for p in plans] == [1, 3, 6]
