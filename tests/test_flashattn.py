"""Flash-attention Pallas kernel vs oracle: shape/dtype/mask sweeps."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.kernels import ops, ref
from repro.kernels.flashattn import flash_attention_pallas
from repro.models.attention import _sdpa


def _qkv(bh, s, d, dt, seed=0):
    rng = np.random.default_rng(seed)
    mk = lambda: jnp.asarray(rng.standard_normal((bh, s, d)), dt)
    return mk(), mk(), mk()


@pytest.mark.parametrize("s", [128, 256, 300, 384])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_matches_oracle(s, causal):
    q, k, v = _qkv(2, s, 64, jnp.float32, seed=s)
    a = ref.flash_ref(q, k, v, causal)
    b = flash_attention_pallas(q, k, v, causal, interpret=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-3)


@pytest.mark.parametrize("window", [64, 128])
def test_flash_sliding_window(window):
    q, k, v = _qkv(2, 256, 64, jnp.float32, seed=window)
    a = ref.flash_ref(q, k, v, True, window)
    b = flash_attention_pallas(q, k, v, True, window, interpret=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-3)


@pytest.mark.parametrize("dt", [jnp.float32, jnp.bfloat16])
def test_flash_dtypes(dt):
    q, k, v = _qkv(2, 256, 128, dt, seed=7)
    a = ref.flash_ref(q, k, v, True)
    b = flash_attention_pallas(q, k, v, True, interpret=True)
    atol = 0.06 if dt == jnp.bfloat16 else 2e-3
    np.testing.assert_allclose(np.asarray(a, np.float32),
                               np.asarray(b, np.float32), atol=atol)


def test_flash_gqa_wrapper_matches_sdpa(monkeypatch):
    """ops.flash_attention (GQA layout) vs the model's _sdpa path."""
    monkeypatch.setenv("REPRO_KERNEL_BACKEND", "interpret")
    rng = np.random.default_rng(3)
    B, S, H, Hkv, D = 2, 128, 8, 2, 64
    q = jnp.asarray(rng.standard_normal((B, S, H, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, S, Hkv, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, S, Hkv, D)), jnp.float32)
    kx = jnp.repeat(k, H // Hkv, axis=2)
    vx = jnp.repeat(v, H // Hkv, axis=2)
    mask = (jnp.arange(S)[None, :] <= jnp.arange(S)[:, None])[None]
    a = _sdpa(q, kx, vx, mask)
    b = ops.flash_attention(q, k, v, causal=True, use_pallas=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-3)


def test_flash_in_model_path():
    """self_attention(ctx.use_flash) == dense-mask path (smoke arch)."""
    from repro.configs.registry import get_smoke_config
    from repro.models import attention as A
    from repro.models.sharding import ShardCtx
    import dataclasses

    cfg = get_smoke_config("llama3.2-3b")
    key = jax.random.PRNGKey(0)
    p = A.attn_params(cfg, key)
    x = jnp.asarray(np.random.default_rng(0).standard_normal((2, 32, cfg.d_model)) * 0.1,
                    jnp.float32)

    class _Ctx:  # minimal stand-in (mesh-free)
        use_flash = True
        attn_seq_shard = False

    a, _ = A.self_attention(cfg, p, x, causal=True)
    b, _ = A.self_attention(cfg, p, x, causal=True, ctx=_Ctx())
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-3)
