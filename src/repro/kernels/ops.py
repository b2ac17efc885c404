"""Public kernel entry points with backend selection.

``backend`` resolution per call:
- ``"pallas"``    — the compiled Mosaic kernel; needs the TPU backend
  and raises anywhere else;
- ``"interpret"`` — the same kernel in Pallas interpret mode, which the
  CPU tests ask for by name;
- ``"xla"``       — the ref.py oracle (pure jnp, what the dry-run lowers);
- ``None``        — by the backend JAX runs on: ``pallas`` on TPU,
  ``xla`` elsewhere (XLA's CPU backend cannot compile Mosaic kernels).

``model_kernels(cfg)`` builds the kernels dict consumed by
`repro.models` (signatures match ``attn_apply``/``ssm_apply`` hooks).
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from . import ref as _ref
from .flash_attention import flash_attention as _flash_pallas
from .kv_row_write import kv_row_write as _row_write_pallas
from .moe_gmm import moe_gmm as _gmm_pallas
from .ring_allgather import ring_all_gather as _ring_pallas

BACKENDS = ("pallas", "interpret", "xla")


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _resolve(backend: Optional[str]) -> str:
    if backend is None:
        return "pallas" if on_tpu() else "xla"
    if backend not in BACKENDS:
        raise ValueError(f"unknown kernel backend {backend!r}; "
                         f"expected one of {BACKENDS}")
    if backend == "pallas" and not on_tpu():
        raise RuntimeError(
            f"backend='pallas' compiles Mosaic kernels for the TPU, but "
            f"JAX runs on {jax.default_backend()!r}; ask for "
            f"backend='interpret' to run the kernel in interpret mode")
    return backend


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, scale: Optional[float] = None,
                    block_q: int = 256, block_k: int = 256,
                    backend: Optional[str] = None) -> jax.Array:
    """[B,Hq,Sq,Dk] x [B,Hkv,Sk,Dk] x [B,Hkv,Sk,Dv] -> [B,Hq,Sq,Dv]."""
    be = _resolve(backend)
    if be == "xla":
        return _ref.flash_attention_ref(q, k, v, causal=causal, scale=scale)
    return _flash_pallas(q, k, v, causal=causal,
                         scale=(q.shape[-1] ** -0.5 if scale is None
                                else scale),
                         block_q=block_q, block_k=block_k,
                         interpret=(be == "interpret"))


# ---------------------------------------------------------------------------
# SSD scan
# ---------------------------------------------------------------------------
def ssd_scan(x: jax.Array, dt: jax.Array, A: jax.Array, Bm: jax.Array,
             Cm: jax.Array, *, chunk: int = 256,
             backend: Optional[str] = None
             ) -> Tuple[jax.Array, jax.Array]:
    """Model-layout SSD.  x [B,S,H,P], dt [B,S,H], A [H],
    B/C [B,S,H,N] -> (y [B,S,H,P], h_final [B,H,N,P])."""
    be = _resolve(backend)
    if be == "xla":
        from repro.models.ssm import ssd_chunked
        return ssd_chunked(x, dt, A, Bm, Cm, chunk)
    from .ssd_scan import ssd_scan_chunked
    b, s, h, p = x.shape
    n = Bm.shape[-1]
    cs = min(chunk, s)
    while s % cs:
        cs //= 2
    nc = s // cs

    def chunked(t):  # [B,S,H,*] -> [B,H,nc,cs,*]
        t = jnp.moveaxis(t, 2, 1)
        return t.reshape((b, h, nc, cs) + t.shape[3:])

    y, hf = ssd_scan_chunked(
        chunked(x), chunked(dt[..., None]),
        A.astype(jnp.float32), chunked(Bm), chunked(Cm),
        interpret=(be == "interpret"))
    y = jnp.moveaxis(y.reshape(b, h, s, p), 1, 2)
    return y, hf


# ---------------------------------------------------------------------------
# grouped matmul
# ---------------------------------------------------------------------------
def moe_gmm(xb: jax.Array, w: jax.Array, *,
            backend: Optional[str] = None) -> jax.Array:
    be = _resolve(backend)
    if be == "xla":
        return _ref.moe_gmm_ref(xb, w)
    return _gmm_pallas(xb, w, interpret=(be == "interpret"))


# ---------------------------------------------------------------------------
# ring all-gather (LCX put-with-signal ring)
# ---------------------------------------------------------------------------
def ring_all_gather(x: jax.Array, axis: str, *, axis_size: int,
                    backend: Optional[str] = None) -> jax.Array:
    be = _resolve(backend)
    if be == "xla":
        return _ref.ring_allgather_ref(x, axis)
    return _ring_pallas(x, axis, axis_size=axis_size,
                        interpret=(be == "interpret"))


# ---------------------------------------------------------------------------
# KV row write (one new row per slot, in place)
# ---------------------------------------------------------------------------
def kv_row_write(pool: jax.Array, rows: jax.Array, lengths: jax.Array, *,
                 backend: Optional[str] = None) -> jax.Array:
    """pool [L,B,S,W], rows [L,B,W], lengths [B] -> pool with
    ``pool[:, b, lengths[b]] = rows[:, b]``."""
    be = _resolve(backend)
    if be == "xla":
        return _ref.kv_row_write_ref(pool, rows, lengths)
    return _row_write_pallas(pool, rows, lengths,
                             interpret=(be == "interpret"))


# ---------------------------------------------------------------------------
# model hook adapters
# ---------------------------------------------------------------------------
def model_kernels(cfg: Any, backend: Optional[str] = None
                  ) -> Dict[str, Any]:
    """Kernels dict for `repro.models` hooks.

    - flash_attention hook signature: (q,k,v [B,S,H,D], causal, scale)
      -> [B,S,Hq,Dv]   (model layout: seq-major).  Left out for
      sliding-window configs: the kernel has no window, and the model's
      own attention applies it.
    - ssd_scan hook signature: (x,dt,A,B,C, chunk) -> (y, h_final)
    - kv_row_write hook signature: (pool [L,B,S,W], rows [L,B,W],
      lengths [B]) -> pool; the per-row decode hands it each cache leaf
      that fits it.  Left out on the ``xla`` backend: there the model's
      own block write stays, since XLA lays a TPU pool out anew around a
      scatter into it.
    """
    backend = _resolve(backend)

    def attn_hook(q, k, v, *, causal, scale):
        qT = jnp.swapaxes(q, 1, 2)
        kT = jnp.swapaxes(k, 1, 2)
        vT = jnp.swapaxes(v, 1, 2)
        o = flash_attention(qT, kT, vT, causal=causal, scale=scale,
                            block_q=cfg.q_block, block_k=cfg.q_block,
                            backend=backend)
        return jnp.swapaxes(o, 1, 2)

    def ssd_hook(x, dt, A, Bm, Cm, *, chunk):
        return ssd_scan(x, dt, A, Bm, Cm, chunk=chunk, backend=backend)

    def row_write_hook(pool, rows, lengths):
        return kv_row_write(pool, rows, lengths, backend=backend)

    kernels: Dict[str, Any] = {"ssd_scan": ssd_hook}
    if cfg.sliding_window is None:
        kernels["flash_attention"] = attn_hook
    if backend != "xla":
        kernels["kv_row_write"] = row_write_hook
    return kernels
