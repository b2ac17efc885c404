"""Pallas kernels vs ref.py oracles — shape/dtype sweeps (interpret
mode on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops
from repro.kernels import ref as kref
from repro.kernels.flash_attention import flash_attention as flash_pallas
from repro.kernels.moe_gmm import moe_gmm as gmm_pallas


FLASH_CASES = [
    # (b, hq, hkv, sq, sk, dk, dv, causal, dtype)
    (2, 4, 2, 128, 128, 64, 64, True, jnp.float32),
    (1, 8, 8, 256, 256, 128, 128, True, jnp.float32),
    (2, 4, 2, 64, 192, 32, 32, False, jnp.float32),
    (1, 6, 2, 96, 96, 64, 32, True, jnp.float32),
    (1, 4, 4, 128, 128, 64, 64, True, jnp.bfloat16),
    (2, 2, 1, 64, 64, 16, 16, False, jnp.bfloat16),
]


@pytest.mark.parametrize("case", FLASH_CASES)
def test_flash_attention_kernel(case):
    b, hq, hkv, sq, sk, dk, dv, causal, dtype = case
    q = jax.random.normal(jax.random.PRNGKey(0), (b, hq, sq, dk), dtype)
    k = jax.random.normal(jax.random.PRNGKey(1), (b, hkv, sk, dk), dtype)
    v = jax.random.normal(jax.random.PRNGKey(2), (b, hkv, sk, dv), dtype)
    out = flash_pallas(q, k, v, causal=causal, block_q=64, block_k=64,
                       interpret=True)
    ref = kref.flash_attention_ref(q, k, v, causal=causal)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol)


SSD_CASES = [
    # (b, s, h, p, n, chunk, dtype)
    (2, 64, 3, 16, 8, 16, jnp.float32),
    (1, 128, 2, 32, 16, 32, jnp.float32),
    (1, 32, 4, 8, 4, 8, jnp.float32),
    (2, 64, 2, 16, 8, 16, jnp.bfloat16),
]


@pytest.mark.parametrize("case", SSD_CASES)
def test_ssd_scan_kernel(case):
    b, s, h, p, n, chunk, dtype = case
    key = jax.random.PRNGKey(3)
    x = jax.random.normal(key, (b, s, h, p), dtype)
    dt = jax.nn.softplus(jax.random.normal(
        jax.random.fold_in(key, 1), (b, s, h))).astype(jnp.float32)
    A = -jnp.exp(jax.random.normal(jax.random.fold_in(key, 2), (h,)))
    Bm = jax.random.normal(jax.random.fold_in(key, 3), (b, s, h, n), dtype)
    Cm = jax.random.normal(jax.random.fold_in(key, 4), (b, s, h, n), dtype)
    y, hf = ops.ssd_scan(x, dt, A, Bm, Cm, chunk=chunk,
                         backend="interpret")
    y_ref, h_ref = kref.ssd_scan_ref(x, dt, A, Bm, Cm, chunk)
    tol = 1e-4 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(y_ref, np.float32), atol=tol)
    np.testing.assert_allclose(np.asarray(hf), np.asarray(h_ref),
                               atol=tol)


GMM_CASES = [
    (4, 64, 96, 80, jnp.float32),
    (2, 128, 64, 64, jnp.float32),
    (8, 32, 48, 32, jnp.bfloat16),
    (1, 256, 128, 256, jnp.float32),
]


@pytest.mark.parametrize("case", GMM_CASES)
def test_moe_gmm_kernel(case):
    e, c, d, f, dtype = case
    xb = jax.random.normal(jax.random.PRNGKey(0), (e, c, d), dtype)
    w = jax.random.normal(jax.random.PRNGKey(1), (e, d, f), dtype)
    out = gmm_pallas(xb, w, block_c=32, block_f=32, block_d=32,
                     interpret=True)
    ref = kref.moe_gmm_ref(xb, w)
    tol = 1e-4 if dtype == jnp.float32 else 1e-1
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol,
                               rtol=1e-2)


def test_backend_auto_resolves_to_xla_on_cpu():
    assert not ops.on_tpu()
    q = jax.random.normal(jax.random.PRNGKey(0), (1, 2, 16, 8))
    out = ops.flash_attention(q, q, q, causal=True)    # backend=None
    ref = kref.flash_attention_ref(q, q, q, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_model_kernels_hooks_match_model_layout():
    from repro.configs.base import ModelConfig
    cfg = ModelConfig(n_layers=1, d_model=32, n_heads=4, n_kv_heads=2,
                      d_ff=64, vocab=64, dtype=jnp.float32,
                      param_dtype=jnp.float32, q_block=16)
    ks = ops.model_kernels(cfg, backend="interpret")
    b, s = 2, 64
    q = jax.random.normal(jax.random.PRNGKey(0), (b, s, 4, 16))
    k = jax.random.normal(jax.random.PRNGKey(1), (b, s, 2, 16))
    v = jax.random.normal(jax.random.PRNGKey(2), (b, s, 2, 16))
    out = ks["flash_attention"](q, k, v, causal=True, scale=0.25)
    ref = kref.flash_attention_ref(
        jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2),
        jnp.swapaxes(v, 1, 2), causal=True, scale=0.25)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(jnp.swapaxes(ref, 1, 2)),
                               atol=2e-5)


def test_pallas_backend_refuses_cpu():
    # compiled Mosaic needs the TPU; interpret mode is asked for by name
    q = jnp.zeros((1, 2, 16, 8))
    with pytest.raises(RuntimeError, match="backend='interpret'"):
        ops.flash_attention(q, q, q, backend="pallas")
    with pytest.raises(RuntimeError, match="backend='interpret'"):
        ops.model_kernels(None, backend="pallas")
