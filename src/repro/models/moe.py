"""Mixture-of-Experts with expert-parallel dispatch over LCX.

Three backends (``cfg.moe_backend``):

- ``dense`` — loop-over-experts masked reference (exact, O(E·T·d·f)
  compute; smoke tests / correctness oracle only).
- ``sort``  — single-device sort-based capacity dispatch (argsort by
  expert id, position-in-expert from group offsets, capacity drop),
  the local building block of the EP path.
- ``lcx``   — expert parallelism: tokens are sharded over the ``model``
  mesh axis (sequence-parallel when S divides, token-sliced otherwise),
  dispatched to experts with an **LCX all-to-all** (`repro.core`
  collectives — the paper's fine-grained async a2a is exactly the MoE
  dispatch pattern), expert FFN computed on the local expert shard, and
  combined with a second a2a.  Runs inside ``shard_map`` over the active
  mesh (see `repro.parallel.sharding.active_mesh`).

Routers: ``softmax`` (standard top-k) and ``sigmoid`` (DeepSeek-V3 style
with top-k normalization).  Aux loss is the Switch load-balancing loss.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from .common import PyTree, dense_init, merge, swiglu


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------
def _expert_stack(key: jax.Array, E: int, d_in: int, d_out: int,
                  dims: Tuple[str, ...], dtype: Any) -> Tuple[PyTree, PyTree]:
    scale = 1.0 / math.sqrt(d_in)
    w = (jax.random.normal(key, (E, d_in, d_out), jnp.float32)
         * scale).astype(dtype)
    return {"w": w}, {"w": ("experts",) + dims}


def moe_init(key: jax.Array, cfg: Any) -> Tuple[PyTree, PyTree]:
    E, d, f = cfg.n_experts, cfg.d_model, cfg.moe_d_ff
    ks = jax.random.split(key, 6)
    parts = [
        ("router", dense_init(ks[0], d, E, dims=("embed", "router"),
                              dtype=jnp.float32)),
        ("w_gate", _expert_stack(ks[1], E, d, f, ("embed", "moe_mlp"),
                                 cfg.param_dtype)),
        ("w_up", _expert_stack(ks[2], E, d, f, ("embed", "moe_mlp"),
                               cfg.param_dtype)),
        ("w_down", _expert_stack(ks[3], E, f, d, ("moe_mlp", "embed"),
                                 cfg.param_dtype)),
    ]
    if cfg.n_shared_experts:
        fs = cfg.n_shared_experts * cfg.moe_d_ff
        parts.append(("shared_gate", dense_init(
            ks[4], d, fs, dims=("embed", "mlp"), dtype=cfg.param_dtype)))
        parts.append(("shared_up", dense_init(
            jax.random.fold_in(ks[4], 1), d, fs, dims=("embed", "mlp"),
            dtype=cfg.param_dtype)))
        parts.append(("shared_down", dense_init(
            ks[5], fs, d, dims=("mlp", "embed"), dtype=cfg.param_dtype)))
    return merge(*parts)


# ---------------------------------------------------------------------------
# routing
# ---------------------------------------------------------------------------
def route(cfg: Any, router_p: PyTree, x: jax.Array
          ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """x [T, d] -> (ids [T, k], weights [T, k] f32, aux_loss scalar)."""
    logits = (x.astype(jnp.float32)
              @ router_p["w"].astype(jnp.float32))          # [T, E]
    if cfg.router_type == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    else:
        scores = jax.nn.softmax(logits, axis=-1)
    w, ids = lax.top_k(scores, cfg.n_experts_per_tok)
    if cfg.router_norm_topk:
        w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)
    # Switch load-balance aux: E * sum_e f_e * P_e
    E = cfg.n_experts
    probs = (scores if cfg.router_type != "sigmoid"
             else jax.nn.softmax(logits, axis=-1))
    f = jnp.zeros((E,), jnp.float32).at[ids.reshape(-1)].add(1.0)
    f = f / jnp.maximum(ids.size, 1)
    aux = E * jnp.sum(f * probs.mean(0))
    return ids, w, aux


# ---------------------------------------------------------------------------
# expert FFN on a capacity buffer  xb [E_loc, Cb, d]
# ---------------------------------------------------------------------------
def _expert_ffn(p: PyTree, xb: jax.Array, e_start: int, e_count: int
                ) -> jax.Array:
    wg = lax.dynamic_slice_in_dim(p["w_gate"]["w"], e_start, e_count, 0)
    wu = lax.dynamic_slice_in_dim(p["w_up"]["w"], e_start, e_count, 0)
    wd = lax.dynamic_slice_in_dim(p["w_down"]["w"], e_start, e_count, 0)
    g = jnp.einsum("ecd,edf->ecf", xb, wg.astype(xb.dtype))
    u = jnp.einsum("ecd,edf->ecf", xb, wu.astype(xb.dtype))
    return jnp.einsum("ecf,efd->ecd", swiglu(g, u), wd.astype(xb.dtype))


# ---------------------------------------------------------------------------
# sort-based capacity dispatch (local)
# ---------------------------------------------------------------------------
def capacity(cfg: Any, n_tokens: int) -> int:
    c = math.ceil(n_tokens * cfg.n_experts_per_tok / cfg.n_experts
                  * cfg.capacity_factor)
    return max(8, -(-c // 8) * 8)        # multiple of 8 for TPU alignment


def dispatch(x_flat: jax.Array, ids: jax.Array, w: jax.Array, E: int,
             C: int) -> Tuple[jax.Array, PyTree]:
    """x_flat [T, d]; ids/w [T, k] -> (buf [E, C, d], combine info).

    Stable-sort by expert id; position within expert from group offsets;
    tokens beyond capacity are dropped (scatter mode='drop')."""
    T, k = ids.shape
    d = x_flat.shape[-1]
    flat_ids = ids.reshape(-1)                       # [T*k]
    order = jnp.argsort(flat_ids, stable=True)
    ids_s = flat_ids[order]
    tok_s = order // k
    sizes = jnp.bincount(flat_ids, length=E)
    starts = jnp.cumsum(sizes) - sizes
    pos = jnp.arange(T * k) - starts[ids_s]
    keep = pos < C
    slot = jnp.where(keep, ids_s * C + pos, E * C)   # E*C = drop bucket
    buf = jnp.zeros((E * C, d), x_flat.dtype)
    buf = buf.at[slot].set(x_flat[tok_s], mode="drop")
    info = {"slot": slot, "tok": tok_s,
            "w": w.reshape(-1)[order].astype(jnp.float32), "T": T}
    return buf.reshape(E, C, d), info


def combine(yb: jax.Array, info: PyTree, d: int) -> jax.Array:
    """yb [E, C, d] -> y [T, d] weighted scatter-add."""
    yb_flat = yb.reshape(-1, d)
    gathered = jnp.take(yb_flat, jnp.minimum(info["slot"],
                                             yb_flat.shape[0] - 1), axis=0)
    gathered = jnp.where((info["slot"] < yb_flat.shape[0])[:, None],
                         gathered, 0)
    y = jnp.zeros((info["T"], d), yb.dtype)
    return y.at[info["tok"]].add(gathered
                                 * info["w"][:, None].astype(yb.dtype))


# ---------------------------------------------------------------------------
# backends
# ---------------------------------------------------------------------------
def _moe_dense(cfg: Any, p: PyTree, x_flat: jax.Array
               ) -> Tuple[jax.Array, jax.Array]:
    """Masked loop-over-experts reference."""
    ids, w, aux = route(cfg, p["router"], x_flat)
    y = jnp.zeros_like(x_flat)
    for e in range(cfg.n_experts):
        mask = (ids == e).astype(jnp.float32) * w          # [T, k]
        gate = mask.sum(-1).astype(x_flat.dtype)           # [T]
        he = _expert_ffn(p, x_flat[None], e, 1)[0]
        y = y + he * gate[:, None]
    return y, aux


def _moe_sort_local(cfg: Any, p: PyTree, x_flat: jax.Array,
                    stream_chunks: int = 0) -> Tuple[jax.Array, jax.Array]:
    ids, w, aux = route(cfg, p["router"], x_flat)
    C = capacity(cfg, x_flat.shape[0])
    buf, info = dispatch(x_flat, ids, w, cfg.n_experts, C)
    if stream_chunks > 1 and cfg.n_experts % stream_chunks == 0:
        # decode path: stream FSDP-sharded expert weights in chunks (a
        # scan with dynamic slices bounds the gathered weight slab to
        # E/stream_chunks experts at a time instead of all E)
        E, ck = cfg.n_experts, cfg.n_experts // stream_chunks
        bufc = buf.reshape(stream_chunks, ck, C, -1)

        def body(_, args):
            i, xb = args
            return None, _expert_ffn(p, xb, i * ck, ck)

        _, ybs = lax.scan(body, None,
                          (jnp.arange(stream_chunks) , bufc))
        yb = ybs.reshape(E, C, -1)
    else:
        yb = _expert_ffn(p, buf, 0, cfg.n_experts)
    return combine(yb, info, x_flat.shape[-1]), aux


def _moe_ep_shard(cfg: Any, p: PyTree, x_flat: jax.Array, ep_axis: str,
                  a2a_backend: str) -> Tuple[jax.Array, jax.Array]:
    """Body under shard_map: x_flat [T_loc, d] tokens of THIS rank;
    expert weights in ``p`` are the full stacks (sliced locally)."""
    import repro.core as lcx
    from repro.compat import axis_size
    ep = axis_size(ep_axis)
    rank = lax.axis_index(ep_axis)
    E = cfg.n_experts
    E_loc = E // ep
    d = x_flat.shape[-1]
    ids, w, aux = route(cfg, p["router"], x_flat)
    C = capacity(cfg, x_flat.shape[0])
    buf, info = dispatch(x_flat, ids, w, E, C)             # [E, C, d]

    # Private runtime + isolated device per a2a region: the MoE layer's
    # traffic never touches (or requires) the global default runtime.
    rt = lcx.Runtime(name="moe-ep")
    dev = rt.device(axis=ep_axis)
    a2a = lcx.all_to_all_x(buf.reshape(E * C, d)).device(dev) \
        .backend(a2a_backend)()
    # rows grouped by source rank: [ep, E_loc, C, d] -> [E_loc, ep*C, d]
    xb = a2a.reshape(ep, E_loc, C, d).transpose(1, 0, 2, 3) \
        .reshape(E_loc, ep * C, d)
    # expert weights arrive pre-sharded over the EP axis ([E_loc, ...])
    yb = _expert_ffn(p, xb, 0, E_loc)
    back = yb.reshape(E_loc, ep, C, d).transpose(1, 0, 2, 3) \
        .reshape(E * C, d)
    y_all = lcx.all_to_all_x(back).device(dev).backend(a2a_backend)()
    y = combine(y_all.reshape(E, C, d), info, d)
    return y, aux


def _resident_ok(cfg: Any, mesh: Any) -> bool:
    """Resident-expert decode needs (i) the experts rule to actually
    shard over the joint axes (set by launch.steps.decode_rules), (ii)
    the resident slab to fit the HBM budget."""
    from repro.parallel.sharding import active_rules
    axes = resident_plan(cfg, mesh)
    return axes is not None \
        and tuple(active_rules().get("experts", ())) == axes


def moe_apply(cfg: Any, p: PyTree, x: jax.Array) -> Tuple[jax.Array,
                                                          jax.Array]:
    """x [B, S, d] -> (y [B, S, d], aux loss scalar)."""
    from repro.parallel.sharding import active_mesh, dp_axes, ep_axis_name
    b, s, d = x.shape
    x_flat = x.reshape(-1, d)
    backend = cfg.moe_backend
    mesh = active_mesh()
    if s == 1 and backend == "lcx" and mesh is not None \
            and _resident_ok(cfg, mesh):
        # decode with RESIDENT experts (sharded over data x model): no
        # weight streaming at all — §Perf iteration 6
        y, aux = _moe_resident_decode(cfg, p, x_flat, mesh)
    elif s == 1 and backend == "lcx" and mesh is not None:
        # decode fallback: weight-streamed local compute with chunked
        # expert gathers (bounds the FSDP slab)
        y, aux = _moe_sort_local(cfg, p, x_flat,
                                 stream_chunks=min(16, cfg.n_experts))
    elif backend == "lcx" and mesh is not None \
            and ep_axis_name() in mesh.axis_names \
            and mesh.shape[ep_axis_name()] > 1 \
            and cfg.n_experts % mesh.shape[ep_axis_name()] == 0:
        y, aux = _moe_ep(cfg, p, x, mesh)
    elif backend == "dense":
        y, aux = _moe_dense(cfg, p, x_flat)
    else:
        y, aux = _moe_sort_local(cfg, p, x_flat)
    y = y.reshape(b, s, d)
    if cfg.n_shared_experts:
        from .common import dense
        g = dense(p["shared_gate"], x)
        u = dense(p["shared_up"], x)
        y = y + dense(p["shared_down"], swiglu(g, u))
    return y, aux


def _moe_ep(cfg: Any, p: PyTree, x: jax.Array, mesh: Any
            ) -> Tuple[jax.Array, jax.Array]:
    """shard_map wrapper: tokens sequence-sharded over the EP axis when
    S divides, token-sliced inside the region otherwise (decode)."""
    from jax.sharding import PartitionSpec as P
    from repro.compat import shard_map
    from repro.parallel.sharding import dp_axes, ep_axis_name
    ep_ax = ep_axis_name()
    ep = mesh.shape[ep_ax]
    b, s, d = x.shape
    # batch spec: largest prefix of the dp axes that divides b (decode
    # at global_batch=1 keeps the batch replicated)
    dp_list = []
    prod = 1
    for a in dp_axes(mesh):
        if b % (prod * mesh.shape[a]) == 0:
            dp_list.append(a)
            prod *= mesh.shape[a]
        else:
            break
    dp = tuple(dp_list) if dp_list else None
    expert_spec = {"w": P("model", None, None)}
    p_specs = {
        "router": {"w": P(None, None)},
        "w_gate": expert_spec, "w_up": expert_spec, "w_down": expert_spec,
    }
    p_ep = {k: p[k] for k in p_specs}

    if s % ep == 0:
        x_spec = P(dp, ep_ax, None)

        def body(p_, x_):
            xf = x_.reshape(-1, d)
            y, aux = _moe_ep_shard(cfg, p_, xf, ep_ax,
                                   cfg_a2a_backend(cfg))
            return y.reshape(x_.shape), lax.pmean(aux, ep_ax)

        y, aux = shard_map(
            body, mesh, in_specs=(p_specs, x_spec),
            out_specs=(x_spec, P()))(p_ep, x)
        return y.reshape(-1, d), aux

    # decode / non-divisible: tokens replicated over EP axis; each rank
    # takes a padded slice, computes, and the results are summed back.
    x_spec = P(dp, None, None)

    def body(p_, x_):
        xf = x_.reshape(-1, d)
        T = xf.shape[0]
        Tp = -(-T // ep) * ep
        xp = jnp.pad(xf, ((0, Tp - T), (0, 0)))
        rank = lax.axis_index(ep_ax)
        mine = lax.dynamic_slice_in_dim(xp, rank * (Tp // ep), Tp // ep, 0)
        y_loc, aux = _moe_ep_shard(cfg, p_, mine, ep_ax,
                                   cfg_a2a_backend(cfg))
        # place local slice into the padded buffer, sum over ranks
        yp = jnp.zeros((Tp, d), y_loc.dtype)
        yp = lax.dynamic_update_slice_in_dim(yp, y_loc, rank * (Tp // ep), 0)
        yp = lax.psum(yp, ep_ax)
        return yp[:T].reshape(x_.shape), lax.pmean(aux, ep_ax)

    y, aux = shard_map(
        body, mesh, in_specs=(p_specs, x_spec),
        out_specs=(x_spec, P()))(p_ep, x)
    return y.reshape(-1, d), aux


def cfg_a2a_backend(cfg: Any) -> str:
    """LCX a2a lowering: 'native' (lax.all_to_all HLO) or 'pairwise'
    (ring of LCX puts).  Tunable per config for the perf loop."""
    return getattr(cfg, "moe_a2a", "native")


# ---------------------------------------------------------------------------
# resident-expert decode (beyond-paper, EXPERIMENTS.md §Perf iteration 6)
# ---------------------------------------------------------------------------
RESIDENT_BUDGET_BYTES = 6 * 1024 ** 3     # HBM share for resident experts


def resident_axes(mesh: Any, E: int) -> Tuple[Tuple[str, ...], int]:
    """Longest (dp..., model) prefix whose product divides E — the joint
    axis set expert weights can shard over so they stay RESIDENT on
    device for decode (no FSDP weight streaming).  dsv3: 256 experts /
    256 chips = 1 resident expert per device."""
    from repro.parallel.sharding import dp_axes
    axes = []
    prod = 1
    # model-first, then data, then pod: on the multi-pod mesh dsv3's 256
    # experts land on (model, data) = 256 and stay replicated across
    # pods (pod-local expert routing, no inter-pod dispatch)
    for a in ("model", *reversed(dp_axes(mesh))):
        if a in mesh.shape and E % (prod * mesh.shape[a]) == 0:
            axes.append(a)
            prod *= mesh.shape[a]
        else:
            break
    return tuple(axes), prod


def resident_plan(cfg: Any, mesh: Any) -> Optional[Tuple[str, ...]]:
    """Axes for resident-expert decode, or None when the per-device
    resident slab would not fit the HBM budget (e.g. jamba's 16 fat
    experts across 256 chips -> 1.2 GiB x 36 layers: stream instead)."""
    if not cfg.n_experts:
        return None
    axes, n = resident_axes(mesh, cfg.n_experts)
    if n <= 1:
        return None
    n_moe_layers = sum(1 for spec in cfg.layer_plan()
                       if spec.ffn == "moe")
    per_dev = (cfg.n_experts // n) * 3 * cfg.d_model * cfg.moe_d_ff \
        * jnp.dtype(cfg.param_dtype).itemsize * n_moe_layers
    if per_dev > RESIDENT_BUDGET_BYTES:
        return None
    return axes


def _moe_resident_decode(cfg: Any, p: PyTree, x_flat: jax.Array,
                         mesh: Any) -> Tuple[jax.Array, jax.Array]:
    """Decode MoE with resident experts: tokens are replicated (tiny at
    decode), every rank routes identically, slices the capacity buffer
    rows of ITS resident experts, runs the FFN with fully local weights
    (zero weight movement), and the combined output is one small psum
    over the expert-owner axes."""
    from jax.sharding import PartitionSpec as P
    from repro.compat import shard_map
    E = cfg.n_experts
    d = x_flat.shape[-1]
    axes, n_owner = resident_axes(mesh, E)
    E_loc = E // n_owner
    wspec = {"w": P(axes if len(axes) > 1 else axes[0], None, None)}
    p_specs = {"router": {"w": P(None, None)},
               "w_gate": wspec, "w_up": wspec, "w_down": wspec}
    p_ep = {k: p[k] for k in p_specs}

    def body(p_, xf):
        rank = jnp.int32(0)
        for a in axes:
            rank = rank * mesh.shape[a] + lax.axis_index(a)
        ids, w, aux = route(cfg, p_["router"], xf)
        C = capacity(cfg, xf.shape[0])
        buf, info = dispatch(xf, ids, w, E, C)          # [E, C, d] (repl.)
        mine = lax.dynamic_slice_in_dim(buf, rank * E_loc, E_loc, 0)
        yb_loc = _expert_ffn(p_, mine, 0, E_loc)        # resident weights
        yb = jnp.zeros((E, C, d), yb_loc.dtype)
        yb = lax.dynamic_update_slice_in_dim(yb, yb_loc, rank * E_loc, 0)
        yb = lax.psum(yb, axes)                         # small at decode
        return combine(yb, info, d), aux

    y, aux = shard_map(
        body, mesh, in_specs=(p_specs, P(None, None)),
        out_specs=(P(None, None), P()))(p_ep, x_flat)
    return y, aux
