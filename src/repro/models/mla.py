"""Multi-head Latent Attention (DeepSeek-V2/V3).

Queries and keys/values are projected through low-rank latents:

  q:   x -> w_dq [d, q_lora] -> rmsnorm -> w_uq [q_lora, H*(nope+rope)]
  kv:  x -> w_dkv [d, kv_lora + rope]   (k_rope is *shared* across heads)
       c_kv -> rmsnorm -> w_ukv [kv_lora, H*(nope+v)]

RoPE is applied only to the rope sub-dimensions.  The decode path uses
the **absorbed** formulation: ``w_uk`` is folded into the query and
``w_uv`` into the output so attention runs directly against the cached
latent ``c_kv`` — the cache is [B, S, kv_lora + rope] instead of
[B, S, H, 2·hd] (the paper-V2 memory saving, 576 vs 32768 per token for
V3's 128 heads).
"""
from __future__ import annotations

import math
from typing import Any, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from .common import (PyTree, dense, dense_init, merge, norm, norm_init,
                     rope_cos_sin)
from .attention import NEG_INF


def _rope_interleaved(x: jax.Array, cos: jax.Array, sin: jax.Array
                      ) -> jax.Array:
    """x [..., S, H, D] (D even), cos/sin [S, D/2]."""
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    c = cos[..., :, None, :]
    s = sin[..., :, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c],
                           axis=-1).astype(x.dtype)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------
def mla_init(key: jax.Array, cfg: Any) -> Tuple[PyTree, PyTree]:
    H = cfg.n_heads
    qk = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
    ks = jax.random.split(key, 8)
    parts = [
        ("w_dq", dense_init(ks[0], cfg.d_model, cfg.q_lora_rank,
                            dims=("embed", "q_lora"),
                            dtype=cfg.param_dtype)),
        ("qnorm", norm_init("rms", cfg.q_lora_rank, cfg.param_dtype)),
        ("w_uq", dense_init(ks[1], cfg.q_lora_rank, H * qk,
                            dims=("q_lora", "q_proj"),
                            dtype=cfg.param_dtype)),
        ("w_dkv", dense_init(ks[2], cfg.d_model,
                             cfg.kv_lora_rank + cfg.qk_rope_head_dim,
                             dims=("embed", "kv_lora"),
                             dtype=cfg.param_dtype)),
        ("kvnorm", norm_init("rms", cfg.kv_lora_rank, cfg.param_dtype)),
        ("w_uk", dense_init(ks[3], cfg.kv_lora_rank,
                            H * cfg.qk_nope_head_dim,
                            dims=("kv_lora", "q_proj"),
                            dtype=cfg.param_dtype)),
        ("w_uv", dense_init(ks[4], cfg.kv_lora_rank, H * cfg.v_head_dim,
                            dims=("kv_lora", "q_proj"),
                            dtype=cfg.param_dtype)),
        ("wo", dense_init(ks[5], H * cfg.v_head_dim, cfg.d_model,
                          dims=("q_proj", "embed"),
                          scale=1.0 / math.sqrt(H * cfg.v_head_dim),
                          dtype=cfg.param_dtype)),
    ]
    return merge(*parts)


def _queries(cfg: Any, p: PyTree, x: jax.Array, positions: jax.Array
             ) -> Tuple[jax.Array, jax.Array]:
    """-> (q_nope [B,S,H,nope], q_rope [B,S,H,rope])."""
    b, s, _ = x.shape
    H = cfg.n_heads
    qk = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
    cq = norm("rms", p["qnorm"], dense(p["w_dq"], x), cfg.norm_eps)
    q = dense(p["w_uq"], cq).reshape(b, s, H, qk)
    q_nope = q[..., : cfg.qk_nope_head_dim]
    q_rope = q[..., cfg.qk_nope_head_dim:]
    cos, sin = rope_cos_sin(positions, cfg.qk_rope_head_dim, cfg.rope_theta)
    q_rope = _rope_interleaved(q_rope, cos, sin)
    return q_nope, q_rope


def _latents(cfg: Any, p: PyTree, x: jax.Array, positions: jax.Array
             ) -> Tuple[jax.Array, jax.Array]:
    """-> (c_kv [B,S,kv_lora] normed, k_rope [B,S,rope] roped)."""
    ckv_full = dense(p["w_dkv"], x)
    c_kv = norm("rms", p["kvnorm"], ckv_full[..., : cfg.kv_lora_rank],
                cfg.norm_eps)
    k_rope = ckv_full[..., cfg.kv_lora_rank:]
    cos, sin = rope_cos_sin(positions, cfg.qk_rope_head_dim, cfg.rope_theta)
    k_rope = _rope_interleaved(k_rope[..., None, :], cos, sin)[..., 0, :]
    return c_kv, k_rope


# ---------------------------------------------------------------------------
# full-sequence (train / prefill): up-project then standard attention
# ---------------------------------------------------------------------------
def mla_apply(cfg: Any, p: PyTree, x: jax.Array, *,
              positions: jax.Array, impl: str = "chunked") -> jax.Array:
    b, s, _ = x.shape
    H = cfg.n_heads
    q_nope, q_rope = _queries(cfg, p, x, positions)
    c_kv, k_rope = _latents(cfg, p, x, positions)
    k_nope = dense(p["w_uk"], c_kv).reshape(b, s, H, cfg.qk_nope_head_dim)
    v = dense(p["w_uv"], c_kv).reshape(b, s, H, cfg.v_head_dim)
    scale = 1.0 / math.sqrt(cfg.qk_nope_head_dim + cfg.qk_rope_head_dim)

    # flash attention over KV blocks (scores = nope + shared rope)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[..., None, :],
                                  (b, s, H, cfg.qk_rope_head_dim))],
        axis=-1)
    from .attention import (attention_chunked, attention_full, _constrain,
                            _tp_size)
    if _tp_size() > 1 and H % _tp_size() == 0:
        # head-TP (the MLA case: 128 heads): q/k/v head-sharded straight
        # out of the column-parallel up-projections
        q = _constrain(q, ("batch", None, "kv_heads", None))
        k = _constrain(k, ("batch", None, "kv_heads", None))
        v = _constrain(v, ("batch", None, "kv_heads", None))
    if impl == "full" or s <= cfg.q_block:
        out = attention_full(q, k, v, scale=scale, causal=cfg.causal,
                             window=None, q_pos=positions, k_pos=positions)
    else:
        out = attention_chunked(
            q, k, v, scale=scale, causal=cfg.causal, window=None,
            q_block=cfg.q_block, k_block=cfg.q_block,
            causal_skip=(impl == "chunked_causal_skip"))
    if _tp_size() > 1 and H % _tp_size() == 0:
        out = _constrain(out, ("batch", None, "kv_heads", None))
    elif s > 1:
        out = _constrain(out, ("batch", "seq", None, None))
    return dense(p["wo"], out.reshape(b, s, H * cfg.v_head_dim))


# ---------------------------------------------------------------------------
# decode: absorbed matmuls against the latent cache
# ---------------------------------------------------------------------------
def mla_cache_init(cfg: Any, batch: int, max_seq: int,
                   dtype: Any = None) -> PyTree:
    dtype = dtype or cfg.dtype
    return {"ckv": jnp.zeros((batch, max_seq, cfg.kv_lora_rank), dtype),
            "krope": jnp.zeros((batch, max_seq, cfg.qk_rope_head_dim),
                               dtype)}


def mla_cache_dims() -> PyTree:
    return {"ckv": ("cache_batch", "cache_seq", "kv_lora"),
            "krope": ("cache_batch", "cache_seq", "head")}


def mla_decode(cfg: Any, p: PyTree, x: jax.Array, cache: PyTree,
               length: jax.Array) -> Tuple[jax.Array, PyTree]:
    """One decode step with the absorbed formulation.

    scores = q_nope @ w_uk^T @ ckv  +  q_rope @ k_rope
    out    = (attn @ ckv) @ w_uv
    """
    b = x.shape[0]
    H = cfg.n_heads
    positions = jnp.full((1,), length, jnp.int32)
    q_nope, q_rope = _queries(cfg, p, x, positions)   # [B,1,H,*]
    c_new, kr_new = _latents(cfg, p, x, positions)    # [B,1,kv_lora/rope]
    from .attention import seq_sharded_decode
    if seq_sharded_decode(cache["ckv"].shape[1]):
        return _mla_decode_sharded(cfg, p, x, q_nope, q_rope, c_new,
                                   kr_new, cache, length)
    ckv = lax.dynamic_update_slice(
        cache["ckv"], c_new.astype(cache["ckv"].dtype), (0, length, 0))
    krope = lax.dynamic_update_slice(
        cache["krope"], kr_new.astype(cache["krope"].dtype), (0, length, 0))
    smax = ckv.shape[1]

    # absorb w_uk into the query: q_lat [B,1,H,kv_lora]
    wuk = p["w_uk"]["w"].reshape(cfg.kv_lora_rank, H, cfg.qk_nope_head_dim)
    q_lat = jnp.einsum("bqhd,lhd->bqhl", q_nope, wuk.astype(x.dtype))
    s_nope = jnp.einsum("bqhl,bkl->bhqk", q_lat, ckv.astype(x.dtype),
                        preferred_element_type=jnp.float32)
    s_rope = jnp.einsum("bqhd,bkd->bhqk", q_rope, krope.astype(x.dtype),
                        preferred_element_type=jnp.float32)
    scale = 1.0 / math.sqrt(cfg.qk_nope_head_dim + cfg.qk_rope_head_dim)
    s = (s_nope + s_rope) * scale
    k_valid = jnp.arange(smax) <= length
    s = jnp.where(k_valid[None, None, None, :], s, NEG_INF)
    pattn = jax.nn.softmax(s, axis=-1).astype(x.dtype)
    o_lat = jnp.einsum("bhqk,bkl->bqhl", pattn, ckv.astype(x.dtype))
    wuv = p["w_uv"]["w"].reshape(cfg.kv_lora_rank, H, cfg.v_head_dim)
    out = jnp.einsum("bqhl,lhd->bqhd", o_lat, wuv.astype(x.dtype))
    y = dense(p["wo"], out.reshape(b, 1, H * cfg.v_head_dim))
    return y, {"ckv": ckv, "krope": krope}


def _mla_decode_sharded(cfg: Any, p: PyTree, x: jax.Array,
                        q_nope: jax.Array, q_rope: jax.Array,
                        c_new: jax.Array, kr_new: jax.Array,
                        cache: PyTree, length: jax.Array
                        ) -> Tuple[jax.Array, PyTree]:
    """Context-parallel absorbed decode: the latent cache stays sharded
    along seq over ``model``; partial softmax combined flash-decoding
    style (see attention.attn_decode_sharded)."""
    from jax.sharding import PartitionSpec as P
    from repro.compat import shard_map
    from repro.parallel.sharding import active_mesh
    from .attention import (_dp_prefix, _flash_decode_combine,
                            _local_row_update)
    mesh = active_mesh()
    b = x.shape[0]
    H = cfg.n_heads
    wuk = p["w_uk"]["w"].reshape(cfg.kv_lora_rank, H, cfg.qk_nope_head_dim)
    q_lat = jnp.einsum("bqhd,lhd->bqhl", q_nope, wuk.astype(x.dtype))
    scale = 1.0 / math.sqrt(cfg.qk_nope_head_dim + cfg.qk_rope_head_dim)
    bspec = _dp_prefix(mesh, b)
    c3 = P(bspec, "model", None)

    def body(ql, qr, cn, kn, ckv, krope, ln):
        rank = lax.axis_index("model")
        s_loc = ckv.shape[1]
        start = rank * s_loc
        off = ln - start
        in_range = (off >= 0) & (off < s_loc)
        ckv = _local_row_update(ckv, cn, off, in_range)
        krope = _local_row_update(krope, kn, off, in_range)
        s_nope = jnp.einsum("bqhl,bkl->bhqk", ql, ckv.astype(ql.dtype),
                            preferred_element_type=jnp.float32)
        s_rope = jnp.einsum("bqhd,bkd->bhqk", qr, krope.astype(qr.dtype),
                            preferred_element_type=jnp.float32)
        s = (s_nope + s_rope) * scale               # [B,H,1,Sl]
        pos = start + jnp.arange(s_loc)
        s = jnp.where((pos <= ln)[None, None, None, :], s, NEG_INF)
        m = s.max(axis=-1)
        pr = jnp.exp(s - m[..., None])
        l = pr.sum(axis=-1)
        acc = jnp.einsum("bhqk,bkl->bhql", pr.astype(ckv.dtype),
                         ckv).astype(jnp.float32)
        o = _flash_decode_combine(acc, m, l, "model")
        return o.astype(ql.dtype), ckv, krope

    o_lat, ckv, krope = shard_map(
        body, mesh,
        in_specs=(P(bspec, None, None, None), P(bspec, None, None, None),
                  P(bspec, None, None), P(bspec, None, None), c3, c3, P()),
        out_specs=(P(bspec, None, None, None), c3, c3))(
            q_lat, q_rope, c_new, kr_new, cache["ckv"], cache["krope"],
            length)
    o_lat = jnp.moveaxis(o_lat, 1, 2)               # [B,1,H,kv_lora]
    wuv = p["w_uv"]["w"].reshape(cfg.kv_lora_rank, H, cfg.v_head_dim)
    out = jnp.einsum("bqhl,lhd->bqhd", o_lat, wuv.astype(x.dtype))
    y = dense(p["wo"], out.reshape(b, 1, H * cfg.v_head_dim))
    return y, {"ckv": ckv, "krope": krope}
