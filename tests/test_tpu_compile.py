"""Compile the mapper's Pallas kernels for a described TPU v5e chip.

Nothing runs: each kernel is lowered with ``interpret=False`` at the widths
the main path uses and compiled by the TPU compiler against a v5e topology
that is described, not attached. The compiler refuses what the chip would
refuse (unaligned blocks, 1-D gathers, layouts Mosaic cannot match), so
these tests guard the compiled path without a chip.

The topology is described inside a module fixture: only one process at a
time may load the TPU library, and each pytest-xdist worker imports every
test module, so describing it at import would break the other workers.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.coarsen_kernels import (contract_edges_pallas,
                                           hem_propose_pallas)
from repro.kernels.lp_gain import lp_gain_pallas
from repro.kernels.mapcost import mapcost_pallas

N = 100_000    # rows of a 10^5-vertex instance (rgg100k, grid 317^2)
M = 1_600_000  # directed edges at mean degree 16


@pytest.fixture(scope="module")
def topo():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def _no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep the cache off here."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("deg", [16, 32])
@pytest.mark.parametrize("k", [4, 8, 16])
def test_lp_gain_compiles(one_chip, deg, k):
    s = lambda shape, dt: _spec(one_chip, shape, dt)
    fn = jax.jit(lambda a, w, p: lp_gain_pallas(a, w, p, k, interpret=False))
    _assert_kernel(fn.lower(s((N, deg), jnp.int32), s((N, deg), jnp.float32),
                            s((N,), jnp.int32)).compile())


@pytest.mark.parametrize("deg", [16, 32])
def test_hem_propose_compiles(one_chip, deg):
    s = lambda shape, dt: _spec(one_chip, shape, dt)
    fn = jax.jit(lambda a, w, j, m: hem_propose_pallas(a, w, j, m,
                                                       interpret=False))
    _assert_kernel(fn.lower(s((N, deg), jnp.int32), s((N, deg), jnp.float32),
                            s((N, deg), jnp.float32),
                            s((N,), jnp.int32)).compile())


@pytest.mark.parametrize("deg", [16, 32])
def test_contract_edges_compiles(one_chip, deg):
    s = lambda shape, dt: _spec(one_chip, shape, dt)
    fn = jax.jit(lambda c, w: contract_edges_pallas(c, w, interpret=False))
    _assert_kernel(fn.lower(s((N, 2 * deg), jnp.int32),
                            s((N, 2 * deg), jnp.float32)).compile())


@pytest.mark.parametrize("levels", [3, 2], ids=["k96", "k256"])
def test_mapcost_compiles(one_chip, levels):
    s = lambda shape, dt: _spec(one_chip, shape, dt)
    fn = jax.jit(lambda r, c, w, pe, gb, dv: mapcost_pallas(
        r, c, w, pe, gb, dv, interpret=False))
    _assert_kernel(fn.lower(s((M,), jnp.int32), s((M,), jnp.int32),
                            s((M,), jnp.float32), s((N,), jnp.int32),
                            s((levels,), jnp.int32),
                            s((levels,), jnp.float32)).compile())


@pytest.mark.parametrize("kernel", ["lp_gain", "hem_propose",
                                    "contract_edges", "mapcost"])
def test_kernels_compile_under_vmap(one_chip, kernel):
    """The main path calls each kernel under two vmaps (lanes of a
    ``batched_partition`` dispatch, restarts inside ``partition``), which
    add grid dimensions to every ``pallas_call``."""
    s = lambda shape, dt: _spec(one_chip, (2, 2) + shape, dt)
    deg, k = 16, 16
    fns = {
        "lp_gain": (lambda a, w, p: lp_gain_pallas(a, w, p, k,
                                                   interpret=False),
                    [((N, deg), jnp.int32), ((N, deg), jnp.float32),
                     ((N,), jnp.int32)]),
        "hem_propose": (lambda a, w, j, m: hem_propose_pallas(
            a, w, j, m, interpret=False),
            [((N, deg), jnp.int32), ((N, deg), jnp.float32),
             ((N, deg), jnp.float32), ((N,), jnp.int32)]),
        "contract_edges": (lambda c, w: contract_edges_pallas(
            c, w, interpret=False),
            [((N, 2 * deg), jnp.int32), ((N, 2 * deg), jnp.float32)]),
        "mapcost": (lambda r, c, w, pe: mapcost_pallas(
            r, c, w, pe, jnp.asarray([1, 4, 32], jnp.int32),
            jnp.asarray([1.0, 10.0, 100.0], jnp.float32), interpret=False),
            [((M,), jnp.int32), ((M,), jnp.int32), ((M,), jnp.float32),
             ((N,), jnp.int32)]),
    }
    fn, shapes = fns[kernel]
    lowered = jax.jit(jax.vmap(jax.vmap(fn))).lower(
        *(s(shape, dt) for shape, dt in shapes))
    _assert_kernel(lowered.compile())
