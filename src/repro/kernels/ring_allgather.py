"""Pallas TPU ring all-gather — LCX ``put`` with remote signal at the
metal: ``pltpu.make_async_remote_copy`` is RDMA-write-with-signal (the
paper §2.2's put + remote completion object), and the DMA semaphores are
the completion objects.

Each device forwards the slot it received on the previous step to its
right neighbour; after n-1 steps every device holds every shard.  One
DMA in flight per step per device, send/recv semaphores as completion.

Validated on CPU with the TPU interpret machinery
(``pltpu.InterpretParams(dma_execution_mode="eager")`` — eager matches
real hardware, where the DMA read engine snapshots the source at
``start()``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ring_kernel(x_ref, o_ref, send_sem, recv_sem, *, axis: str, n: int):
    my_id = lax.axis_index(axis)
    # local shard into my slot (LCX loopback put)
    pltpu.sync_copy(x_ref, o_ref.at[pl.ds(my_id, 1)])
    for step in range(n - 1):
        slot = (my_id - step) % n
        rdc = pltpu.make_async_remote_copy(
            src_ref=o_ref.at[pl.ds(slot, 1)],
            dst_ref=o_ref.at[pl.ds(slot, 1)],
            send_sem=send_sem,
            recv_sem=recv_sem,
            device_id=(my_id + 1) % n,
            device_id_type=pltpu.DeviceIdType.LOGICAL,
        )
        rdc.start()           # post the LCX put
        rdc.wait()            # completion: send drained + slot arrived


def ring_all_gather(x: jax.Array, axis: str, *, axis_size: int,
                    interpret: bool = False) -> jax.Array:
    """Under shard_map: x [1, ...] (this device's shard, leading axis 1)
    -> [axis_size, ...] (all shards).  Compiled for the TPU;
    ``interpret=True`` simulates the DMAs on CPU."""
    n = axis_size
    kernel = functools.partial(_ring_kernel, axis=axis, n=n)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((n,) + x.shape[1:], x.dtype),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[pltpu.SemaphoreType.DMA, pltpu.SemaphoreType.DMA],
        interpret=(pltpu.InterpretParams(dma_execution_mode="eager")
                   if interpret else False),
        compiler_params=(None if interpret
                         else pltpu.CompilerParams(collective_id=7)),
    )(x)
