"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret mode)."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from hypcompat import given, settings, st

from repro.core import graph as G
from repro.core.hierarchy import Hierarchy
from repro.core.refine import connectivity
from repro.kernels import ops, ref
from repro.kernels.lp_gain import lp_gain_pallas
from repro.kernels.mapcost import mapcost_pallas


def _edge_arrays(n, m, k, seed, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    rows = jnp.asarray(rng.integers(0, n, m), jnp.int32)
    cols = jnp.asarray(rng.integers(0, n, m), jnp.int32)
    w = jnp.asarray(rng.random(m), dtype)
    pe = jnp.asarray(rng.integers(0, k, n), jnp.int32)
    return rows, cols, w, pe


@pytest.mark.parametrize("n,m", [(64, 128), (257, 1000), (1000, 5000), (4096, 2048)])
@pytest.mark.parametrize("hier", [(4, 2), (4, 2, 3), (16, 16)])
def test_mapcost_shapes(n, m, hier):
    h = Hierarchy(a=hier, d=tuple(10.0 ** i for i in range(len(hier))))
    rows, cols, w, pe = _edge_arrays(n, m, h.k, seed=n + m)
    gb = jnp.asarray((1,) + h.strides[:-1], jnp.int32)
    dv = jnp.asarray(h.d, jnp.float32)
    a = ref.mapcost_partials_ref(rows, cols, w, pe, gb, dv)
    b = mapcost_pallas(rows, cols, w, pe, gb, dv, interpret=True)
    assert np.array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_allclose(float(ref.mapcost_ref(rows, cols, w, pe, gb, dv)),
                               float(ref.fold_partials(b)), rtol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_mapcost_dtypes(dtype):
    h = Hierarchy(a=(4, 4), d=(1.0, 10.0))
    rows, cols, w, pe = _edge_arrays(300, 900, h.k, seed=1, dtype=dtype)
    gb = jnp.asarray((1,) + h.strides[:-1], jnp.int32)
    dv = jnp.asarray(h.d, jnp.float32)
    a = ref.mapcost_ref(rows, cols, w.astype(jnp.float32), pe, gb, dv)
    b = mapcost_pallas(rows, cols, w.astype(jnp.float32), pe, gb, dv, interpret=True)
    np.testing.assert_allclose(float(a), float(ref.fold_partials(b)), rtol=2e-3)


@pytest.mark.parametrize("n,deg,k", [(128, 8, 4), (300, 16, 8), (1024, 32, 16), (77, 128, 3)])
def test_lp_gain_shapes(n, deg, k):
    rng = np.random.default_rng(n * k)
    adj = jnp.asarray(rng.integers(0, n + 1, (n, deg)), jnp.int32)  # n == pad
    adw = jnp.asarray(rng.random((n, deg)) * (np.asarray(adj) < n), jnp.float32)
    part = jnp.asarray(rng.integers(0, k, n), jnp.int32)
    c1, b1, g1 = ref.lp_gain_ref(adj, adw, part, k)
    c2, b2, g2 = lp_gain_pallas(adj, adw, part, k, interpret=True)
    np.testing.assert_allclose(np.asarray(c1), np.asarray(c2), atol=1e-4)
    assert np.array_equal(np.asarray(b1), np.asarray(b2))
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), atol=1e-4)


@given(st.integers(0, 10_000))
@settings(max_examples=20, deadline=None)
def test_csr_to_ell_roundtrip(seed):
    """ELL conversion preserves per-(row, block) connectivity."""
    rng = np.random.default_rng(seed)
    n = int(rng.integers(10, 200))
    g = G.gen_rgg(n, seed=seed)
    k = int(rng.integers(2, 6))
    part = jnp.asarray(rng.integers(0, k, g.N), jnp.int32)
    deg = int(max(np.asarray(G.degrees(g)).max(), 1))
    adj, adw = ref.csr_to_ell(g.rows, g.cols, g.ewgt, g.N, deg)
    conn_ell, _, _ = ref.lp_gain_ref(adj, adw, part, k)
    conn_csr = connectivity(g, part, k)
    np.testing.assert_allclose(np.asarray(conn_ell), np.asarray(conn_csr), atol=1e-4)


def test_ops_dispatch(monkeypatch):
    """ops.py returns identical numbers through either backend flag."""
    monkeypatch.setenv("REPRO_KERNEL_BACKEND", "interpret")
    h = Hierarchy(a=(4, 2), d=(1.0, 10.0))
    rows, cols, w, pe = _edge_arrays(200, 600, h.k, seed=3)
    gb = jnp.asarray((1,) + h.strides[:-1], jnp.int32)
    dv = jnp.asarray(h.d, jnp.float32)
    a = ops.mapcost(rows, cols, w, pe, gb, dv, use_pallas=False)
    b = ops.mapcost(rows, cols, w, pe, gb, dv, use_pallas=True)
    assert np.asarray(a).tobytes() == np.asarray(b).tobytes()


# --- PR3: randomized kernel parity (property-style, seeded loops) -------------

def _rand_hier(rng):
    l = int(rng.integers(2, 4))
    a = tuple(int(rng.integers(2, 5)) for _ in range(l))
    d = tuple(float(10.0 ** i) for i in range(l))
    return Hierarchy(a=a, d=d)


@pytest.mark.parametrize("seed", range(8))
def test_mapcost_parity_random(seed):
    """mapcost_pallas (interpret) == jnp oracle on random edge arrays with
    zero-weight padding tails (the padded-edge case)."""
    rng = np.random.default_rng(seed)
    h = _rand_hier(rng)
    n = int(rng.integers(16, 400))
    m = int(rng.integers(1, 2000))
    rows = rng.integers(0, n, m)
    cols = rng.integers(0, n, m)
    w = rng.random(m).astype(np.float32)
    cut = int(rng.integers(0, m))  # zero-weight tail == padding slots
    w[cut:] = 0.0
    pe = jnp.asarray(rng.integers(0, h.k, n), jnp.int32)
    gb = jnp.asarray((1,) + h.strides[:-1], jnp.int32)
    dv = jnp.asarray(h.d, jnp.float32)
    args = (jnp.asarray(rows, jnp.int32), jnp.asarray(cols, jnp.int32),
            jnp.asarray(w), pe, gb, dv)
    a = ref.mapcost_partials_ref(*args)
    b = mapcost_pallas(*args, interpret=True)
    assert np.array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("seed", range(8))
def test_lp_gain_parity_random(seed):
    """lp_gain_pallas (interpret) == jnp oracle on random ELL matrices with
    zero-degree vertices and padded neighbour slots."""
    rng = np.random.default_rng(1000 + seed)
    n = int(rng.integers(8, 300))
    deg = int(rng.integers(1, 24))
    k = int(rng.integers(2, 9))
    adj = rng.integers(0, n + 1, (n, deg))          # n == pad id
    zero_rows = rng.random(n) < 0.2                 # zero-degree vertices
    adj[zero_rows] = n
    adw = rng.random((n, deg)).astype(np.float32) * (adj < n)
    part = jnp.asarray(rng.integers(0, k, n), jnp.int32)
    adj = jnp.asarray(adj, jnp.int32)
    adw = jnp.asarray(adw)
    c1, b1, g1 = ref.lp_gain_ref(adj, adw, part, k)
    c2, b2, g2 = lp_gain_pallas(adj, adw, part, k, interpret=True)
    np.testing.assert_allclose(np.asarray(c1), np.asarray(c2), atol=1e-4)
    assert np.array_equal(np.asarray(b1), np.asarray(b2))
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), atol=1e-4)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_ell_adjacency_parity(seed):
    """graph.ell_adjacency == connectivity on non-overflow rows, and the
    overflow mask flags exactly the rows whose degree exceeds the cap."""
    rng = np.random.default_rng(seed)
    g = G.gen_rgg(int(rng.integers(100, 500)), seed=seed)
    k = int(rng.integers(2, 6))
    part = jnp.asarray(rng.integers(0, k, g.N), jnp.int32)
    degs = np.asarray(G.degrees(g))[: int(g.n)]
    for cap in (8, int(max(degs.max(), 1) + 7) // 8 * 8):
        adj, adw, ovf = G.ell_adjacency(g, cap)
        ovf_np = np.asarray(ovf)
        assert np.array_equal(ovf_np[: int(g.n)], degs > cap)
        conn_e, _, _ = ref.lp_gain_ref(adj, adw, part, k)
        conn_c = connectivity(g, part, k)
        keep = ~ovf_np
        np.testing.assert_allclose(np.asarray(conn_e)[keep],
                                   np.asarray(conn_c)[keep], atol=1e-4)


def test_ops_lp_gain_dispatch(monkeypatch):
    """ops.lp_gain returns identical numbers through either backend flag."""
    monkeypatch.setenv("REPRO_KERNEL_BACKEND", "interpret")
    rng = np.random.default_rng(7)
    n, deg, k = 200, 12, 5
    adj = jnp.asarray(rng.integers(0, n + 1, (n, deg)), jnp.int32)
    adw = jnp.asarray(rng.random((n, deg)) * (np.asarray(adj) < n), jnp.float32)
    part = jnp.asarray(rng.integers(0, k, n), jnp.int32)
    c1, b1, g1 = ops.lp_gain(adj, adw, part, k, use_pallas=False)
    c2, b2, g2 = ops.lp_gain(adj, adw, part, k, use_pallas=True)
    np.testing.assert_allclose(np.asarray(c1), np.asarray(c2), atol=1e-4)
    assert np.array_equal(np.asarray(b1), np.asarray(b2))


def test_split_blocks_backend_invariant(monkeypatch):
    """The on-device split must produce identical children whatever kernel
    backend is selected — it is pure data movement end to end."""
    from repro.core import multisection as M
    from repro.core.graph import split_blocks

    g = G.gen_rgg(200, seed=17)
    rng = np.random.default_rng(1)
    k = 2
    part = jnp.asarray(
        np.where(np.arange(g.N) < int(g.n),
                 rng.integers(0, k, g.N), k).astype(np.int32))
    orig = jnp.asarray(
        np.where(np.arange(g.N) < int(g.n),
                 np.arange(g.N), int(g.n)).astype(np.int32))
    outs = {}
    for be in ("xla", "interpret"):
        monkeypatch.setenv("REPRO_KERNEL_BACKEND", be)
        ch, co, ws = split_blocks(g, part, orig, k, jnp.int32(int(g.n)))
        outs[be] = jax.tree_util.tree_map(np.asarray, (ch, co, ws))
    for a, b in zip(jax.tree_util.tree_leaves(outs["xla"]),
                    jax.tree_util.tree_leaves(outs["interpret"])):
        assert np.array_equal(a, b)


def test_kernel_backend_env(monkeypatch):
    monkeypatch.setenv("REPRO_KERNEL_BACKEND", "interpret")
    assert ops.kernel_backend() == "interpret"
    assert ops.dispatch() == (True, True)
    monkeypatch.setenv("REPRO_KERNEL_BACKEND", "xla")
    assert ops.dispatch() == (False, False)
    monkeypatch.delenv("REPRO_KERNEL_BACKEND")
    assert ops.kernel_backend() in ("pallas", "xla")


@pytest.mark.parametrize("value", ["tpu", "PALLAS", "compiled"])
def test_kernel_backend_rejects_unknown(monkeypatch, value):
    """A misspelt backend raises instead of falling back to the default."""
    monkeypatch.setenv("REPRO_KERNEL_BACKEND", value)
    with pytest.raises(ValueError, match="REPRO_KERNEL_BACKEND"):
        ops.kernel_backend()


def test_kernel_backend_follows_platform(monkeypatch):
    monkeypatch.delenv("REPRO_KERNEL_BACKEND", raising=False)
    assert ops.kernel_backend("tpu") == "pallas"
    assert ops.kernel_backend("cpu") == "xla"
    monkeypatch.setenv("REPRO_KERNEL_BACKEND", "xla")
    assert ops.kernel_backend("tpu") == "xla"


def test_explicit_use_pallas_never_interprets(monkeypatch):
    """use_pallas=True picks the kernel; only the environment picks
    interpret mode. Off a TPU a compiled kernel is refused, loudly."""
    monkeypatch.setenv("REPRO_KERNEL_BACKEND", "xla")
    assert ops.dispatch(True) == (True, False)
    monkeypatch.setenv("REPRO_KERNEL_BACKEND", "interpret")
    assert ops.dispatch(True) == (True, True)
    assert ops.dispatch(False) == (False, True)
    monkeypatch.setenv("REPRO_KERNEL_BACKEND", "xla")
    h = Hierarchy(a=(4, 2), d=(1.0, 10.0))
    rows, cols, w, pe = _edge_arrays(64, 128, h.k, seed=5)
    gb = jnp.asarray((1,) + h.strides[:-1], jnp.int32)
    dv = jnp.asarray(h.d, jnp.float32)
    if jax.default_backend() != "tpu":
        with pytest.raises(Exception):
            ops.mapcost(rows, cols, w, pe, gb, dv, use_pallas=True)


@pytest.mark.parametrize("wrapper", [
    "lp_gain.lp_gain_pallas", "mapcost.mapcost_pallas",
    "coarsen_kernels.hem_propose_pallas",
    "coarsen_kernels.contract_edges_pallas",
    "flashattn.flash_attention_pallas"])
def test_pallas_wrappers_require_interpret(wrapper):
    """No wrapper defaults to interpret mode: the flag is a required
    keyword, so every call site states which mode it runs."""
    import importlib
    import inspect
    mod, name = wrapper.split(".")
    fn = getattr(importlib.import_module(f"repro.kernels.{mod}"), name)
    p = inspect.signature(fn).parameters["interpret"]
    assert p.kind is inspect.Parameter.KEYWORD_ONLY
    assert p.default is inspect.Parameter.empty
