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


def test_decode_slots_writes_rows_in_place_at_qwen2_0_5b_chat(one_chip,
                                                               monkeypatch):
    """The serving engine's decode at chat's sizes (qwen2-0.5b, 64 slots
    × 2048 positions, the pool donated) with the kernels a TPU gets
    holds the row-write kernel and lays no part of the pool out anew:
    no copy or transpose as large as one layer's slice of a leaf (a
    relayout reads 1.6 GB of temporaries; the block write 387,072 B)."""
    import re

    import numpy as np

    from repro.configs.base import get_config
    from repro.kernels import ops
    from repro.models import init_cache
    from repro.models.model import abstract_init
    from repro.serving import ServeConfig, ServingEngine
    # the described chip is not the backend JAX runs on: ask for the
    # compiled kernels as the engine gets them on a TPU
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    cfg = get_config("qwen2-0.5b")
    b, s = 64, 2048

    def on_chip(tree):
        return jax.tree.map(lambda x: _spec(x.shape, x.dtype, one_chip),
                            tree)

    params = on_chip(abstract_init(cfg)[0])
    caches = on_chip(jax.eval_shape(lambda: init_cache(cfg, b, s)))
    eng = ServingEngine(cfg, params, ServeConfig(n_slots=1, max_seq=16),
                        kernels=ops.model_kernels(cfg), use_executor=False)
    compiled = eng._decode.lower(
        params, _spec((b, 1), jnp.int32, one_chip), caches,
        _spec((b,), jnp.int32, one_chip)).compile()
    hlo = compiled.as_text()
    assert eng.stats["kv_row_write"] == {"kernel": 2, "block": 0}
    assert "tpu_custom_call" in hlo
    layer = b * s * cfg.n_kv_heads * cfg.head_dim
    moved = [m.group(0) for m in re.finditer(
        r"= \w+\[([\d,]+)\]\S* (?:copy|transpose)\(", hlo)
        if np.prod([int(d) for d in m.group(1).split(",")]) >= layer]
    assert moved == []
    assert compiled.memory_analysis().temp_size_in_bytes < 64 * 2 ** 20
