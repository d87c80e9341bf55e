import numpy as np
import pytest

# NOTE: no XLA_FLAGS here on purpose — smoke tests and benches must see ONE
# device; only launch/dryrun.py forces 512 host devices (see system design).


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)


@pytest.fixture(autouse=True, scope="module")
def _release_programs():
    """Drop every compiled program once a test module is done. XLA's CPU
    backend maps each executable's code into memory regions of its own,
    and a process that keeps a few hundred mapping programs alive passes
    the kernel's limit on mappings (``vm.max_map_count``, 65,530 by
    default) and aborts inside the compiler."""
    yield
    release_programs()


@pytest.fixture
def fresh_programs():
    """For a test that compiles many programs of its own: it starts and
    ends with none held."""
    release_programs()
    yield
    release_programs()


def release_programs() -> None:
    """Drop the memoized partition callables, JAX's compiled programs and
    their code mappings."""
    import gc

    import jax

    from repro.core.multisection import clear_compile_cache
    clear_compile_cache()
    jax.clear_caches()
    gc.collect()


def pytest_configure(config):
    config.addinivalue_line("markers", "slow: long-running integration tests")
