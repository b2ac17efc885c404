"""Pallas TPU kernel for the Mamba-2 SSD chunked scan.

Grid = (B, H, nc) with the chunk index innermost, so the inter-chunk
state h [N, P] lives in VMEM scratch and carries across the sequential
chunk sweep (the TPU grid is executed in order) — the recurrence never
round-trips to HBM.  Within a chunk the quadratic term uses two MXU
matmuls ([cs,N]@[N,cs] and [cs,cs]@[cs,P]); cs defaults to 128/256 so
every matmul dim is MXU-aligned.

All decay arithmetic in f32; the decays are exp of non-positive sums.

Layout: the wrapper (`repro.kernels.ops.ssd_scan`) reshapes the model's
[B,S,H,*] tensors to chunked head-major [B,H,nc,cs,*] so blocks are
contiguous along the trailing two dims.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, hout_ref,
                h_ref, *, cs: int, nc: int):
    hi = pl.program_id(1)
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    x = x_ref[0, 0, 0].astype(jnp.float32)           # [cs, P]
    dt = dt_ref[0, 0, 0].astype(jnp.float32)         # [cs, 1]
    A = a_ref[hi]                                    # scalar f32 (SMEM)
    Bm = b_ref[0, 0, 0].astype(jnp.float32)          # [cs, N]
    Cm = c_ref[0, 0, 0].astype(jnp.float32)          # [cs, N]

    # Prefix sums and row forms come from masked reductions over
    # [cs, cs] tiles: Mosaic lowers neither cumsum nor a [cs, 1]
    # transpose.  rows = i (query), cols = j (key).
    rows = jax.lax.broadcasted_iota(jnp.int32, (cs, cs), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (cs, cs), 1)
    causal = rows >= cols
    dA = dt * A                                      # [cs,1] (<= 0)
    dt_row = jnp.sum(jnp.where(rows == cols, dt, 0.0), axis=0,
                     keepdims=True)                  # [1,cs]
    cum = jnp.sum(jnp.where(causal, dt_row * A, 0.0), axis=1,
                  keepdims=True)                     # [cs,1] sum_{j<=i}
    cum_row = jnp.sum(jnp.where(rows <= cols, dA, 0.0), axis=0,
                      keepdims=True)                 # [1,cs] sum_{i<=j}
    cum_last = jnp.sum(dA, axis=0, keepdims=True)    # [1,1]

    # within-chunk quadratic term
    scores = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    seg = jnp.where(causal, cum - cum_row, NEG_INF)  # [cs, cs]
    w = scores * jnp.exp(seg) * dt_row
    y = jax.lax.dot_general(w, x, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)

    # carried-state contribution: C_i · h * exp(cum_i)
    h = h_ref[...]                                   # [N, P]
    y = y + jax.lax.dot_general(Cm, h, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32) \
        * jnp.exp(cum)

    # state update: h' = h*exp(sum dA) + sum_j decay_j dt_j B_j x_j^T
    decay_end = jnp.exp(cum_last - cum)              # [cs,1]
    bw = Bm * (decay_end * dt)                       # [cs, N]
    Sc = jax.lax.dot_general(bw, x, (((0,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    h_ref[...] = h * jnp.exp(cum_last) + Sc

    y_ref[0, 0, 0] = y.astype(y_ref.dtype)

    @pl.when(ci == nc - 1)
    def _fin():
        hout_ref[0, 0] = h_ref[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssd_scan_chunked(x: jax.Array, dt: jax.Array, A: jax.Array,
                     Bm: jax.Array, Cm: jax.Array, *,
                     interpret: bool = False
                     ) -> Tuple[jax.Array, jax.Array]:
    """Chunk-major SSD.  x [B,H,nc,cs,P], dt [B,H,nc,cs,1], A [H] f32
    (whole, in SMEM), B/C [B,H,nc,cs,N].  Returns (y like x, h_final [B,H,N,P] f32)."""
    b, h, nc, cs, p = x.shape
    n = Bm.shape[-1]
    kernel = functools.partial(_ssd_kernel, cs=cs, nc=nc)
    y, hout = pl.pallas_call(
        kernel,
        grid=(b, h, nc),
        in_specs=[
            pl.BlockSpec((1, 1, 1, cs, p),
                         lambda bi, hi, ci: (bi, hi, ci, 0, 0)),
            pl.BlockSpec((1, 1, 1, cs, 1),
                         lambda bi, hi, ci: (bi, hi, ci, 0, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, 1, cs, n),
                         lambda bi, hi, ci: (bi, hi, ci, 0, 0)),
            pl.BlockSpec((1, 1, 1, cs, n),
                         lambda bi, hi, ci: (bi, hi, ci, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, cs, p),
                         lambda bi, hi, ci: (bi, hi, ci, 0, 0)),
            pl.BlockSpec((1, 1, n, p), lambda bi, hi, ci: (bi, hi, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, nc, cs, p), x.dtype),
            jax.ShapeDtypeStruct((b, h, n, p), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((n, p), jnp.float32)],
        interpret=interpret,
    )(x, dt, A, Bm, Cm)
    return y, hout
