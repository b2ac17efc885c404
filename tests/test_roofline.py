"""HLO walker + roofline term derivation against analytically-known
programs."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.hlo_walk import parse_computations, walk
from repro.analysis.roofline import model_flops, active_params


def test_walk_plain_matmul():
    @jax.jit
    def mm(a, b):
        return a @ b

    a = jax.ShapeDtypeStruct((512, 512), jnp.float32)
    hlo = mm.lower(a, a).compile().as_text()
    t = walk(hlo)
    assert t.flops == pytest.approx(2 * 512 ** 3, rel=0.01)


def test_walk_scan_multiplies_trip_count():
    @jax.jit
    def scanned(a, ws):
        def body(x, w):
            return x @ w, None
        out, _ = jax.lax.scan(body, a, ws)
        return out

    a = jax.ShapeDtypeStruct((256, 256), jnp.float32)
    ws = jax.ShapeDtypeStruct((7, 256, 256), jnp.float32)
    hlo = scanned.lower(a, ws).compile().as_text()
    t = walk(hlo)
    assert t.flops == pytest.approx(7 * 2 * 256 ** 3, rel=0.02)


def test_walk_nested_scan():
    @jax.jit
    def nested(a, ws):
        def outer(x, w):
            def inner(y, _):
                return y @ w, None
            y, _ = jax.lax.scan(inner, x, None, length=3)
            return y, None
        out, _ = jax.lax.scan(outer, a, ws)
        return out

    a = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    ws = jax.ShapeDtypeStruct((5, 128, 128), jnp.float32)
    hlo = nested.lower(a, ws).compile().as_text()
    t = walk(hlo)
    assert t.flops == pytest.approx(5 * 3 * 2 * 128 ** 3, rel=0.05)


def test_collective_parse_synthetic():
    hlo = """
HloModule m

ENTRY %main (p0: f32[1024,1024]) -> f32[1024,1024] {
  %p0 = f32[1024,1024]{1,0} parameter(0)
  %ar = f32[1024,1024]{1,0} all-reduce(%p0), replica_groups=[16,16]<=[256], to_apply=%add
  ROOT %ag = f32[1024,1024]{1,0} all-gather(%ar), replica_groups=[32,8]<=[256], dimensions={0}
}
"""
    t = walk(hlo)
    nbytes = 1024 * 1024 * 4
    expect = 2 * nbytes * 15 / 16 + nbytes * 7 / 8
    assert t.coll_wire == pytest.approx(expect, rel=0.01)
    assert t.coll_by_kind["all-reduce"] == pytest.approx(
        2 * nbytes * 15 / 16)


def test_collective_brace_groups():
    hlo = """
ENTRY %main (p0: f32[64]) -> f32[64] {
  %p0 = f32[64]{0} parameter(0)
  ROOT %ar = f32[64]{0} all-reduce(%p0), replica_groups={{0,1,2,3}}, to_apply=%add
}
"""
    t = walk(hlo)
    assert t.coll_wire == pytest.approx(2 * 64 * 4 * 3 / 4)


def test_model_flops_conventions():
    from repro.configs.base import ModelConfig
    from repro.models import abstract_init
    cfg = ModelConfig(n_layers=2, d_model=64, n_heads=4, n_kv_heads=4,
                      d_ff=128, vocab=100)
    proto, _ = abstract_init(cfg)
    total, act = active_params(cfg, proto)
    assert act < total           # embeddings excluded
    mf_train = model_flops(cfg, proto, "train", 128, 4)
    mf_dec = model_flops(cfg, proto, "decode", 128, 4)
    assert mf_train == pytest.approx(6 * act * 128 * 4)
    assert mf_dec == pytest.approx(2 * act * 4)


def test_moe_active_params_scaled():
    from repro.configs.base import ModelConfig
    from repro.models import abstract_init
    cfg = ModelConfig(family="moe", n_layers=2, d_model=64, n_heads=4,
                      n_kv_heads=4, d_ff=128, vocab=100, n_experts=8,
                      n_experts_per_tok=2, moe_d_ff=64,
                      moe_backend="sort")
    proto, _ = abstract_init(cfg)
    total, act = active_params(cfg, proto)
    # routed experts contribute k/E of their params
    expert_params = 3 * 8 * 64 * 64 * 2   # gate/up/down x E x d x f x 2 layers
    assert act < total - expert_params * 0.5


def test_peaks_refuse_an_unknown_device_kind():
    from repro.launch.mesh import DRYRUN_DEVICE_KIND, peaks
    assert peaks(DRYRUN_DEVICE_KIND)["flops_bf16"] == 197e12
    with pytest.raises(KeyError, match="no published peaks"):
        peaks("cpu")
