"""Pallas kernels vs ref.py oracles — shape/dtype sweeps (interpret
mode on CPU)."""
import functools

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


# (W, dtype): rows of one and of two lane widths; R = 16 rows a tile for
# bfloat16, 8 for float32
ROW_WRITE_CASES = [(128, jnp.bfloat16), (256, jnp.bfloat16),
                   (128, jnp.float32), (256, jnp.float32)]


def _row_write_case(w, dtype, seed=0):
    """A pool [L=3, B=6, S=256, W] of stale rows, one new row per slot,
    and lengths: a free slot (0), R-1 and R on both sides of a tile
    edge, 127 and 128 on both sides of a 128-position block, and
    max_seq - 1."""
    from repro.kernels.kv_row_write import row_tile
    r, s = row_tile(dtype), 256
    ks = jax.random.split(jax.random.PRNGKey(seed), 2)
    pool = jax.random.normal(ks[0], (3, 6, s, w), dtype)
    rows = jax.random.normal(ks[1], (3, 6, w), dtype)
    lengths = jnp.array([0, r - 1, r, 127, 128, s - 1], jnp.int32)
    return pool, rows, lengths


@pytest.mark.parametrize("case", ROW_WRITE_CASES)
def test_kv_row_write_kernel_matches_block_write(case):
    """Bit for bit what the model's block write gives, on a stacked leaf
    [L, B, S, W] and on a prefix leaf [B, S, W], and what the oracle
    gives."""
    from repro.models.model import _write_rows
    pool, rows, lengths = _row_write_case(*case)
    hook = functools.partial(ops.kv_row_write, backend="interpret")
    caches = {"prefix_0": {"k": pool[0]}, "stack": {"l0": {"k": pool}}}
    new = {"prefix_0": {"k": rows[0]}, "stack": {"l0": {"k": rows}}}
    counts = {}
    got = _write_rows(None, caches, new, lengths, hook, counts)
    want = _write_rows(None, caches, new, lengths)
    assert counts == {"kernel": 2}
    jax.tree.map(np.testing.assert_array_equal, got, want)
    np.testing.assert_array_equal(
        ops.kv_row_write(pool, rows, lengths, backend="interpret"),
        kref.kv_row_write_ref(pool, rows, lengths))
    # every other row is the pool's own
    hit = np.zeros(pool.shape[1:3], bool)
    hit[np.arange(6), np.asarray(lengths)] = True
    np.testing.assert_array_equal(np.asarray(got["stack"]["l0"]["k"])
                                  [:, ~hit], np.asarray(pool)[:, ~hit])


def test_kv_row_write_aliases_the_pool_to_its_output():
    pool, rows, lengths = _row_write_case(128, jnp.bfloat16)
    jaxpr = jax.make_jaxpr(functools.partial(
        ops.kv_row_write, backend="interpret"))(pool, rows, lengths)

    def calls(jx):
        for e in jx.eqns:
            if e.primitive.name == "pallas_call":
                yield e
            for v in e.params.values():
                sub = getattr(v, "jaxpr", v)
                if hasattr(sub, "eqns"):
                    yield from calls(sub)

    (call,) = calls(jaxpr.jaxpr)
    # operands: lengths (scalar prefetch), rows, pool -> output 0
    assert call.params["input_output_aliases"] == ((2, 0),)


def test_kv_row_write_engages_only_where_it_fits():
    """The model hands the kernel a leaf whose rows are whole lanes and
    whose positions are whole tiles of rows; any other leaf keeps the
    block write, and the kernel itself refuses it."""
    from repro.models.model import _write_rows
    hook = functools.partial(ops.kv_row_write, backend="interpret")
    lengths = jnp.array([0, 5], jnp.int32)
    leaves = {"lanes": (3, 2, 32, 64), "rows": (3, 2, 24, 128),
              "fits": (3, 2, 32, 128)}
    caches = {"stack": {k: jnp.zeros(v, jnp.bfloat16)
                        for k, v in leaves.items()}}
    new = {"stack": {k: jnp.ones(v[:2] + v[3:], jnp.bfloat16)
                     for k, v in leaves.items()}}
    counts = {}
    got = _write_rows(None, caches, new, lengths, hook, counts)
    assert counts == {"kernel": 1, "block": 2}
    jax.tree.map(np.testing.assert_array_equal, got,
                 _write_rows(None, caches, new, lengths))
    with pytest.raises(ValueError, match="S of 16"):
        hook(caches["stack"]["rows"], new["stack"]["rows"], lengths)


def test_kv_row_write_pallas_refuses_cpu():
    pool, rows, lengths = _row_write_case(128, jnp.bfloat16)
    with pytest.raises(RuntimeError, match="backend='interpret'"):
        ops.kv_row_write(pool, rows, lengths, backend="pallas")
