"""Pure-jnp oracles for every Pallas kernel (the contract each kernel is
validated against, shape/dtype-swept in tests/test_kernels_*.py)."""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax


def flash_attention_ref(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        causal: bool = True,
                        scale: Optional[float] = None) -> jax.Array:
    """q [B,Hq,Sq,Dk], k [B,Hkv,Sk,Dk], v [B,Hkv,Sk,Dv] -> [B,Hq,Sq,Dv].
    GQA via head grouping (Hq % Hkv == 0)."""
    b, hq, sq, dk = q.shape
    hkv, sk = k.shape[1], k.shape[2]
    g = hq // hkv
    scale = (dk ** -0.5) if scale is None else scale
    qg = q.reshape(b, hkv, g, sq, dk)
    s = jnp.einsum("bhgqd,bhkd->bhgqk", qg.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        mask = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        s = jnp.where(mask[None, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgqk,bhkd->bhgqd", p, v.astype(jnp.float32))
    return o.reshape(b, hq, sq, v.shape[-1]).astype(v.dtype)


def ssd_scan_ref(x: jax.Array, dt: jax.Array, A: jax.Array, Bm: jax.Array,
                 Cm: jax.Array, chunk: int
                 ) -> Tuple[jax.Array, jax.Array]:
    """Sequential (non-chunked) SSD recurrence — the gold reference.

    x [B,S,H,P], dt [B,S,H] (>=0), A [H] (<0), B/C [B,S,H,N].
    Returns y [B,S,H,P], h_final [B,H,N,P]."""
    b, s, h, p = x.shape
    n = Bm.shape[-1]
    f32 = jnp.float32

    def step(hstate, inp):
        xt, dtt, bt, ct = inp                     # [b,h,*]
        decay = jnp.exp(dtt.astype(f32) * A)      # [b,h]
        hstate = hstate * decay[..., None, None] \
            + jnp.einsum("bh,bhn,bhp->bhnp", dtt.astype(f32),
                         bt.astype(f32), xt.astype(f32))
        y = jnp.einsum("bhn,bhnp->bhp", ct.astype(f32), hstate)
        return hstate, y

    h0 = jnp.zeros((b, h, n, p), f32)
    hf, ys = lax.scan(step, h0,
                      (x.swapaxes(0, 1), dt.swapaxes(0, 1),
                       Bm.swapaxes(0, 1), Cm.swapaxes(0, 1)))
    return ys.swapaxes(0, 1).astype(x.dtype), hf


def moe_gmm_ref(xb: jax.Array, w: jax.Array) -> jax.Array:
    """Grouped (expert-batched) matmul: [E,C,d] @ [E,d,f] -> [E,C,f]."""
    return jnp.einsum("ecd,edf->ecf", xb.astype(jnp.float32),
                      w.astype(jnp.float32)).astype(xb.dtype)


def ring_allgather_ref(x: jax.Array, axis: str) -> jax.Array:
    """Under shard_map: x [1, ...] per device -> [n, ...]."""
    return lax.all_gather(x[0], axis, tiled=False)


def kv_row_write_ref(pool: jax.Array, rows: jax.Array,
                     lengths: jax.Array) -> jax.Array:
    """pool [L,B,S,W] with ``pool[:, b, lengths[b]] = rows[:, b]``; a
    length outside [0, S) writes nothing."""
    b = jnp.arange(pool.shape[1])
    return pool.at[:, b, lengths].set(rows.astype(pool.dtype), mode="drop")
