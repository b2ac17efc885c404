"""The main path's Pallas kernels compile for a described TPU v5e.

Nothing runs: each test lowers a kernel at a published width for one
chip of a ``v5e:2x2`` topology that the TPU compiler describes without
hardware, compiles it, and checks that the Mosaic kernel is in the HLO
(``tpu_custom_call``), so an interpreter or XLA stand-in cannot pass.
The topology is described inside a fixture, never at import, because
only one process at a time may load the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention import flash_attention
from repro.kernels.moe_gmm import moe_gmm
from repro.kernels.ssd_scan import ssd_scan_chunked


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_hlo(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def test_flash_attention_compiles_at_qwen2_0_5b_prefill(one_chip):
    # qwen2-0.5b: 14 query heads, 2 kv heads, head_dim 64; 2048 tokens
    b, hq, hkv, s, d = 1, 14, 2, 2048, 64
    q = _spec((b, hq, s, d), jnp.bfloat16, one_chip)
    kv = _spec((b, hkv, s, d), jnp.bfloat16, one_chip)
    hlo = _compiled_hlo(
        lambda q_, k_, v_: flash_attention(q_, k_, v_, causal=True),
        q, kv, kv)
    assert "tpu_custom_call" in hlo


def test_ssd_scan_compiles_at_mamba2_130m(one_chip):
    # mamba2-130m: d_inner 1536 / head_dim 64 = 24 heads, state 128,
    # chunk 256; 2048 tokens = 8 chunks
    b, h, nc, cs, p, n = 1, 24, 8, 256, 64, 128
    x = _spec((b, h, nc, cs, p), jnp.bfloat16, one_chip)
    dt = _spec((b, h, nc, cs, 1), jnp.float32, one_chip)
    a = _spec((h,), jnp.float32, one_chip)
    bc = _spec((b, h, nc, cs, n), jnp.bfloat16, one_chip)
    hlo = _compiled_hlo(ssd_scan_chunked, x, dt, a, bc, bc)
    assert "tpu_custom_call" in hlo


def test_moe_gmm_compiles_at_qwen3_moe_expert_share(one_chip):
    # qwen3-moe-30b-a3b: d_model 2048, expert d_ff 768; 8 experts with a
    # capacity of 256 tokens each
    e, c, d, f = 8, 256, 2048, 768
    xb = _spec((e, c, d), jnp.bfloat16, one_chip)
    w = _spec((e, d, f), jnp.bfloat16, one_chip)
    hlo = _compiled_hlo(moe_gmm, xb, w)
    assert "tpu_custom_call" in hlo
