"""Pallas TPU kernel: write each slot's new KV row into the pool in place.

pool [L, B, S, W] holds L layers of B slots of S positions, W =
n_kv_heads · head_dim wide; rows [L, B, W] is the row each slot adds at
its own position ``lengths[b]``.  Grid = (B,): slot b reads the one
(L, 1, R, W) block of R positions that holds ``lengths[b]`` (R is the
dtype's sublane tile, so the block is whole tiles of the pool's own
row-major layout), selects its row into it, and writes the block back.
The pool is aliased to the output, so under a donated pool nothing
else of it moves: per slot 2 · L · R · W elements, read and written.

``lengths`` reaches the index maps by scalar prefetch.  A length
outside [0, S) writes nothing (its block index is clamped and no
position of the block matches), as an out-of-range scatter drops.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128


def row_tile(dtype) -> int:
    """Rows of one (sublane, lane) tile of ``dtype``: 8 for float32, 16
    for bfloat16, 32 for 8-bit types."""
    return 32 // jnp.dtype(dtype).itemsize


def _block(n, r: int, nb: int):
    return jnp.clip(n // r, 0, nb - 1)


def _row_write_kernel(lengths_ref, rows_ref, pool_ref, out_ref, *, r: int,
                      nb: int):
    n = lengths_ref[pl.program_id(0)]
    at = n - _block(n, r, nb) * r
    hit = lax.broadcasted_iota(jnp.int32, out_ref.shape, 2) == at
    out_ref[...] = jnp.where(hit, jnp.broadcast_to(rows_ref[...],
                                                   out_ref.shape),
                             pool_ref[...])


@functools.partial(jax.jit, static_argnames=("interpret",))
def kv_row_write(pool: jax.Array, rows: jax.Array, lengths: jax.Array, *,
                 interpret: bool = False) -> jax.Array:
    """pool [L,B,S,W], rows [L,B,W], lengths [B] -> pool with
    ``pool[:, b, lengths[b]] = rows[:, b]`` for every slot b."""
    r = row_tile(pool.dtype)
    if pool.ndim != 4 or pool.shape[-1] % LANES or pool.shape[-2] % r:
        raise ValueError(
            f"kv_row_write needs a pool [L, B, S, W] with W a multiple of "
            f"{LANES} and S of {r}; got {pool.shape} {pool.dtype}")
    n_layers, b, s, w = pool.shape
    nb = s // r
    rows = rows.astype(pool.dtype).reshape(n_layers, b, 1, w)
    block = (n_layers, 1, r, w)
    pool_spec = pl.BlockSpec(
        block, lambda i, lens: (0, i, _block(lens[i], r, nb), 0))
    return pl.pallas_call(
        functools.partial(_row_write_kernel, r=r, nb=nb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b,),
            in_specs=[pl.BlockSpec((n_layers, 1, 1, w),
                                   lambda i, lens: (0, i, 0, 0)),
                      pool_spec],
            out_specs=pool_spec),
        out_shape=jax.ShapeDtypeStruct(pool.shape, pool.dtype),
        # operands: lengths 0, rows 1, pool 2
        input_output_aliases={2: 0},
        interpret=interpret,
        name="kv_row_write",
    )(lengths.astype(jnp.int32), rows, pool)
