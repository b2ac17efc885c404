"""Pipeline parallelism as a first-class model execution mode.

The scan-over-layers stack is split across a ``pipe`` mesh axis (each
rank owns ``n_periods / pipe`` periods) and executed with the GPipe
microbatch schedule built on LCX send/recv
(`repro.parallel.pipeline.gpipe`).  The shard_map is manual on every
mesh axis: inside a stage, activations are replicated across the
non-pipe axes and the logical sharding constraints resolve to no-ops.

Autodiff through the GPipe schedule IS GPipe training (forward all
microbatches, backward in reverse — the ppermute transposes to the
opposite shift), so ``jax.grad`` of :func:`pp_loss` gives a
pipeline-parallel train step with no extra machinery.

Restrictions (asserted): no prefix layers, n_periods % pipe == 0, and
no shard_map-based MoE inside a stage (nested manual regions — use
``moe_backend="sort"`` configs for PP).
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.models.common import softmax_xent
from repro.models.model import (_embed_in, _head_out, layer_apply)
from repro.parallel.pipeline import gpipe

PyTree = Any


def pp_apply_model(cfg: Any, params: PyTree, tokens: jax.Array, *,
                   mesh: Any, n_micro: int = 8,
                   impl: Optional[str] = None) -> jax.Array:
    """Pipeline-parallel forward.  tokens [B, S] -> logits [B, S, V]."""
    prefix, period, n_periods = cfg.scan_plan()
    assert not prefix, "PP demo requires a prefix-free layer plan"
    pipe = mesh.shape["pipe"]
    assert n_periods % pipe == 0, (n_periods, pipe)
    assert cfg.n_experts == 0 or cfg.moe_backend != "lcx", \
        "PP stages cannot nest the shard_map MoE; use moe_backend='sort'"

    x = _embed_in(cfg, params, tokens, None)
    b, s, d = x.shape
    assert b % n_micro == 0, (b, n_micro)
    positions = jnp.arange(s, dtype=jnp.int32)
    micro = x.reshape(n_micro, b // n_micro, s, d)

    def stage_fn(stack_local, xm):
        # stack_local leaves: [n_periods/pipe, ...] — this rank's periods
        def body(x_, p_period):
            for j, spec in enumerate(period):
                x_, _, _ = layer_apply(cfg, spec, p_period[f"l{j}"], x_,
                                       positions=positions, mode="train",
                                       impl=impl)
            return x_, None

        out, _ = lax.scan(body, xm, stack_local)
        return out

    def region(stack, micro_, rank_arr):
        # gpipe owns a private LCX runtime; no global init needed.
        # rank arrives as sharded data (each rank holds its own index)
        # because lax.axis_index lowers to PartitionId, which XLA CPU
        # SPMD partitioning rejects under partial-manual shard_map.
        # The region is fully manual; logical-axis constraints inside it
        # resolve to no-ops (sharding.py skips bound axes).
        return gpipe(stage_fn, stack, micro_, axis="pipe",
                     rank=rank_arr[0])

    from repro.compat import shard_map
    stack_spec = jax.tree.map(lambda _: P("pipe"), params["stack"])
    ranks = jnp.arange(pipe, dtype=jnp.int32)
    # Fully-manual shard_map: the region is manual on every mesh axis,
    # with activations replicated across the non-pipe axes.
    out_micro = shard_map(
        region, mesh=mesh, in_specs=(stack_spec, P(), P("pipe")),
        out_specs=P(), check=False)(params["stack"], micro, ranks)
    x = out_micro.reshape(b, s, d)
    return _head_out(cfg, params, x)


def pp_loss(cfg: Any, params: PyTree, batch: Dict[str, jax.Array], *,
            mesh: Any, n_micro: int = 8) -> jax.Array:
    logits = pp_apply_model(cfg, params, batch["tokens"], mesh=mesh,
                            n_micro=n_micro)
    return softmax_xent(logits, batch["labels"])
