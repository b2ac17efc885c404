"""Model builder: embed → (prefix layers + scanned periodic stack) → head.

Layer plans come from ``ModelConfig.layer_plan()`` (dense / MoE / SSM /
hybrid / MLA / encoder-only).  The periodic part of the stack is executed
with ``lax.scan`` over stacked parameters (compact HLO, one compiled body
per period) and rematerialized according to ``cfg.remat``.

Three entry points per model:
- :func:`apply_model` — full-sequence forward (train / eval / prefill
  logits), returns ``(logits, aux)``.
- :func:`loss_fn` — next-token cross entropy + MoE aux + optional MTP.
- :func:`init_cache` / :func:`prefill` / :func:`decode_step` — serving.

Activation sharding constraints are applied at layer boundaries via
`repro.parallel.sharding.constrain` (logical names → mesh axes).
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from .common import (PyTree, dense, dense_init, embed, embed_init, gelu,
                     merge, norm, norm_init, softmax_xent, swiglu)
from . import attention as attn_mod
from . import mla as mla_mod
from . import moe as moe_mod
from . import ssm as ssm_mod
from .attention import (attn_apply, attn_cache_init, attn_decode, attn_init)
from .mla import mla_apply, mla_cache_init, mla_decode, mla_init
from .moe import moe_apply, moe_init
from .ssm import ssm_apply, ssm_cache_init, ssm_decode, ssm_init


def _constrain(x: jax.Array, dims: Tuple[Optional[str], ...]) -> jax.Array:
    from repro.parallel.sharding import constrain
    return constrain(x, dims)


# ---------------------------------------------------------------------------
# dense FFN
# ---------------------------------------------------------------------------
def ffn_init(key: jax.Array, cfg: Any) -> Tuple[PyTree, PyTree]:
    ks = jax.random.split(key, 3)
    if cfg.act == "swiglu":
        return merge(
            ("gate", dense_init(ks[0], cfg.d_model, cfg.d_ff,
                                dims=("embed", "mlp"),
                                dtype=cfg.param_dtype)),
            ("up", dense_init(ks[1], cfg.d_model, cfg.d_ff,
                              dims=("embed", "mlp"),
                              dtype=cfg.param_dtype)),
            ("down", dense_init(ks[2], cfg.d_ff, cfg.d_model,
                                dims=("mlp", "embed"),
                                dtype=cfg.param_dtype)),
        )
    return merge(
        ("fc1", dense_init(ks[0], cfg.d_model, cfg.d_ff,
                           dims=("embed", "mlp"), bias=True,
                           dtype=cfg.param_dtype)),
        ("fc2", dense_init(ks[1], cfg.d_ff, cfg.d_model,
                           dims=("mlp", "embed"), bias=True,
                           dtype=cfg.param_dtype)),
    )


def ffn_apply(cfg: Any, p: PyTree, x: jax.Array) -> jax.Array:
    if cfg.act == "swiglu":
        h = swiglu(dense(p["gate"], x), dense(p["up"], x))
        h = _constrain(h, ("batch", None, "mlp"))
        return dense(p["down"], h)
    h = gelu(dense(p["fc1"], x))
    h = _constrain(h, ("batch", None, "mlp"))
    return dense(p["fc2"], h)


# ---------------------------------------------------------------------------
# one layer
# ---------------------------------------------------------------------------
def layer_init(key: jax.Array, cfg: Any, spec: Any) -> Tuple[PyTree, PyTree]:
    k1, k2, k3, k4 = jax.random.split(key, 4)
    parts = [("norm1", norm_init(cfg.norm, cfg.d_model, cfg.param_dtype))]
    if spec.mixer == "attn":
        parts.append(("mixer", attn_init(k1, cfg)))
    elif spec.mixer == "mla":
        parts.append(("mixer", mla_init(k1, cfg)))
    else:
        parts.append(("mixer", ssm_init(k1, cfg)))
    if spec.ffn is not None:
        parts.append(("norm2", norm_init(cfg.norm, cfg.d_model,
                                         cfg.param_dtype)))
        if spec.ffn == "moe":
            parts.append(("ffn", moe_init(k2, cfg)))
        else:
            parts.append(("ffn", ffn_init(k2, cfg)))
    return merge(*parts)


def layer_cache_init(cfg: Any, spec: Any, batch: int, max_seq: int) -> PyTree:
    if spec.mixer == "attn":
        return attn_cache_init(cfg, batch, max_seq)
    if spec.mixer == "mla":
        return mla_cache_init(cfg, batch, max_seq)
    return ssm_cache_init(cfg, batch)


def layer_apply(cfg: Any, spec: Any, p: PyTree, x: jax.Array, *,
                positions: jax.Array, mode: str = "train",
                cache: Optional[PyTree] = None,
                length: Optional[jax.Array] = None,
                impl: Optional[str] = None,
                kernels: Optional[Dict[str, Any]] = None
                ) -> Tuple[jax.Array, Optional[PyTree], jax.Array]:
    """-> (x_out, new_cache | None, aux_loss)."""
    impl = impl or getattr(cfg, "attn_impl", "chunked")
    kernels = kernels or {}
    aux = jnp.zeros((), jnp.float32)
    new_cache = None
    h = norm(cfg.norm, p["norm1"], x, cfg.norm_eps)

    if spec.mixer == "attn":
        if mode == "decode":
            y, new_cache = attn_decode(cfg, p["mixer"], h, cache, length)
        else:
            y = attn_apply(cfg, p["mixer"], h, positions=positions,
                           impl=impl,
                           kernel_fn=kernels.get("flash_attention"))
            if mode == "prefill":
                new_cache = _attn_fill_cache(cfg, p["mixer"], h, positions,
                                             cache)
    elif spec.mixer == "mla":
        if mode == "decode":
            y, new_cache = mla_decode(cfg, p["mixer"], h, cache, length)
        else:
            y = mla_apply(cfg, p["mixer"], h, positions=positions, impl=impl)
            if mode == "prefill":
                new_cache = _mla_fill_cache(cfg, p["mixer"], h, positions,
                                            cache)
    else:  # mamba
        if mode == "decode":
            y, new_cache = ssm_decode(cfg, p["mixer"], h, cache)
        else:
            y, state = ssm_apply(cfg, p["mixer"], h,
                                 return_cache=(mode == "prefill"),
                                 kernel_fn=kernels.get("ssd_scan"))
            if mode == "prefill":
                new_cache = state
    if mode != "decode":
        # pin the row-parallel partial-sum output to the seq-sharded
        # layout BEFORE the residual add: GSPMD then lowers the psum as
        # a reduce-scatter instead of all-reduce+slice (§Perf iter. 4)
        y = _constrain(y, ("batch", "seq", "embed"))
    x = x + y
    x = _constrain(x, ("batch", "seq", "embed"))

    if spec.ffn is not None:
        h = norm(cfg.norm, p["norm2"], x, cfg.norm_eps)
        if spec.ffn == "moe":
            y, aux = moe_apply(cfg, p["ffn"], h)
        else:
            y = ffn_apply(cfg, p["ffn"], h)
        if mode != "decode":
            y = _constrain(y, ("batch", "seq", "embed"))
        x = x + y
        x = _constrain(x, ("batch", "seq", "embed"))
    return x, new_cache, aux


def _attn_fill_cache(cfg: Any, p: PyTree, h: jax.Array,
                     positions: jax.Array, cache: PyTree) -> PyTree:
    k = dense(p["wk"], h).reshape(h.shape[0], h.shape[1], cfg.n_kv_heads,
                                  cfg.head_dim)
    v = dense(p["wv"], h).reshape(h.shape[0], h.shape[1], cfg.n_kv_heads,
                                  cfg.head_dim)
    if cfg.qk_norm:
        k = norm("rms", p["knorm"], k, cfg.norm_eps)
    from .common import rope_cos_sin, apply_rope
    cos, sin = rope_cos_sin(positions, cfg.head_dim, cfg.rope_theta)
    k = apply_rope(k, cos, sin)
    s = h.shape[1]
    return {
        "k": lax.dynamic_update_slice(
            cache["k"], k.astype(cache["k"].dtype), (0, 0, 0, 0)),
        "v": lax.dynamic_update_slice(
            cache["v"], v.astype(cache["v"].dtype), (0, 0, 0, 0)),
    }


def _mla_fill_cache(cfg: Any, p: PyTree, h: jax.Array,
                    positions: jax.Array, cache: PyTree) -> PyTree:
    c_kv, k_rope = mla_mod._latents(cfg, p, h, positions)
    return {
        "ckv": lax.dynamic_update_slice(
            cache["ckv"], c_kv.astype(cache["ckv"].dtype), (0, 0, 0)),
        "krope": lax.dynamic_update_slice(
            cache["krope"], k_rope.astype(cache["krope"].dtype), (0, 0, 0)),
    }


# ---------------------------------------------------------------------------
# whole model
# ---------------------------------------------------------------------------
def init_model(key: jax.Array, cfg: Any) -> Tuple[PyTree, PyTree]:
    prefix, period, n_periods = cfg.scan_plan()
    keys = jax.random.split(key, 8)
    params: Dict[str, Any] = {}
    dims: Dict[str, Any] = {}

    if cfg.frontend is None or cfg.family != "audio":
        p, d = embed_init(keys[0], cfg.vocab, cfg.d_model,
                          dtype=cfg.param_dtype)
        params["embed"], dims["embed"] = p, d

    # prefix layers (individual)
    for i, spec in enumerate(prefix):
        p, d = layer_init(jax.random.fold_in(keys[1], i), cfg, spec)
        params[f"prefix_{i}"], dims[f"prefix_{i}"] = p, d

    # scanned periodic body: stack n_periods copies
    def init_period(k):
        ps, ds = {}, {}
        for j, spec in enumerate(period):
            p, d = layer_init(jax.random.fold_in(k, j), cfg, spec)
            ps[f"l{j}"], ds[f"l{j}"] = p, d
        return ps, ds

    period_keys = jax.random.split(keys[2], n_periods)
    stacked = jax.vmap(lambda k: init_period(k)[0])(period_keys)
    _, period_dims = init_period(period_keys[0])
    params["stack"] = stacked
    dims["stack"] = jax.tree.map(
        lambda t: ("layers",) + t if isinstance(t, tuple) else t,
        period_dims, is_leaf=lambda t: isinstance(t, tuple))

    p, d = norm_init(cfg.norm, cfg.d_model, cfg.param_dtype)
    params["final_norm"], dims["final_norm"] = p, d

    if not cfg.tie_embeddings:
        p, d = dense_init(keys[3], cfg.d_model, cfg.vocab,
                          dims=("embed", "vocab"), dtype=cfg.param_dtype)
        params["head"], dims["head"] = p, d

    if cfg.mtp_depth:
        from repro.configs.base import LayerSpec
        p, d = layer_init(keys[4], cfg,
                          LayerSpec("attn" if cfg.family != "ssm"
                                    else "mamba", "dense"))
        params["mtp_layer"], dims["mtp_layer"] = p, d
        p, d = dense_init(keys[5], 2 * cfg.d_model, cfg.d_model,
                          dims=("embed", "embed_out"),
                          dtype=cfg.param_dtype)
        params["mtp_proj"], dims["mtp_proj"] = p, d
        p, d = norm_init(cfg.norm, cfg.d_model, cfg.param_dtype)
        params["mtp_norm"], dims["mtp_norm"] = p, d
    return params, dims


def abstract_init(cfg: Any, key: Optional[jax.Array] = None
                  ) -> Tuple[PyTree, PyTree]:
    """(ShapeDtypeStruct params, dims) without allocating anything —
    the dry-run / trainer-construction path for huge configs."""
    key = jax.random.PRNGKey(0) if key is None else key
    captured: Dict[str, Any] = {}

    def f(k):
        p, d = init_model(k, cfg)
        captured["dims"] = d
        return p

    params_proto = jax.eval_shape(f, key)
    return params_proto, captured["dims"]


def _embed_in(cfg: Any, params: PyTree, tokens: jax.Array,
              frontend_embeds: Optional[jax.Array]) -> jax.Array:
    if cfg.family == "audio":
        # encoder stub: inputs ARE frame embeddings [B, S, D]
        return frontend_embeds.astype(cfg.dtype)
    x = embed(params["embed"], tokens, cfg.dtype)
    if frontend_embeds is not None:       # VLM: prepend patch embeddings
        x = jnp.concatenate([frontend_embeds.astype(cfg.dtype), x], axis=1)
    return x


def _head_out(cfg: Any, params: PyTree, x: jax.Array) -> jax.Array:
    x = norm(cfg.norm, params["final_norm"], x, cfg.norm_eps)
    if cfg.tie_embeddings:
        logits = x @ params["embed"]["emb"].T.astype(x.dtype)
    else:
        logits = dense(params["head"], x)
    return _constrain(logits, ("batch", "seq", "vocab"))


def _stack_sweep(cfg: Any, params: PyTree, x: jax.Array, *,
                 positions: jax.Array, mode: str,
                 caches: Optional[PyTree] = None,
                 length: Optional[jax.Array] = None,
                 impl: Optional[str] = None,
                 kernels: Optional[Dict[str, Any]] = None
                 ) -> Tuple[jax.Array, jax.Array, Optional[PyTree]]:
    """Run prefix + scanned stack.  Returns (x, aux, new_caches)."""
    prefix, period, n_periods = cfg.scan_plan()
    aux_total = jnp.zeros((), jnp.float32)
    new_caches: Dict[str, Any] = {}

    for i, spec in enumerate(prefix):
        c = None if caches is None else caches[f"prefix_{i}"]
        x, nc, aux = layer_apply(cfg, spec, params[f"prefix_{i}"], x,
                                 positions=positions, mode=mode, cache=c,
                                 length=length, impl=impl, kernels=kernels)
        aux_total = aux_total + aux
        if nc is not None:
            new_caches[f"prefix_{i}"] = nc

    def period_body(carry, inp):
        x_, aux_ = carry
        p_stack = inp["params"]
        c_stack = inp.get("cache")
        ncs: Dict[str, Any] = {}
        for j, spec in enumerate(period):
            c = None if c_stack is None else c_stack[f"l{j}"]
            x_, nc, a = layer_apply(cfg, spec, p_stack[f"l{j}"], x_,
                                    positions=positions, mode=mode,
                                    cache=c, length=length, impl=impl,
                                    kernels=kernels)
            aux_ = aux_ + a
            if nc is not None:
                ncs[f"l{j}"] = nc
        return (x_, aux_), (ncs if ncs else 0)

    body = period_body
    if mode == "train" and cfg.remat != "none":
        policy = (jax.checkpoint_policies.nothing_saveable
                  if cfg.remat == "full"
                  else jax.checkpoint_policies.checkpoint_dots)
        body = jax.checkpoint(period_body, policy=policy,
                              prevent_cse=False)

    xs: Dict[str, Any] = {"params": params["stack"]}
    if caches is not None:
        xs["cache"] = caches["stack"]
    (x, aux_total), stack_caches = lax.scan(body, (x, aux_total), xs)
    if mode in ("prefill", "decode"):
        new_caches["stack"] = stack_caches
        return x, aux_total, new_caches
    return x, aux_total, None


def apply_model(cfg: Any, params: PyTree, tokens: jax.Array, *,
                frontend_embeds: Optional[jax.Array] = None,
                impl: Optional[str] = None,
                kernels: Optional[Dict[str, Any]] = None
                ) -> Tuple[jax.Array, jax.Array]:
    """Full-sequence forward.  tokens [B, S] -> (logits [B, S', V], aux)."""
    x = _embed_in(cfg, params, tokens, frontend_embeds)
    x = _constrain(x, ("batch", "seq", "embed"))
    positions = jnp.arange(x.shape[1], dtype=jnp.int32)
    x, aux, _ = _stack_sweep(cfg, params, x, positions=positions,
                             mode="train", impl=impl, kernels=kernels)
    return _head_out(cfg, params, x), aux


def loss_fn(cfg: Any, params: PyTree, batch: Dict[str, jax.Array], *,
            impl: Optional[str] = None,
            kernels: Optional[Dict[str, Any]] = None
            ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    logits, aux = apply_model(cfg, params, batch["tokens"],
                              frontend_embeds=batch.get("frontend"),
                              impl=impl, kernels=kernels)
    labels = batch["labels"]
    if cfg.family == "vlm" and "frontend" in batch:
        logits = logits[:, batch["frontend"].shape[1]:, :]
    xent = softmax_xent(logits, labels, batch.get("mask"))
    loss = xent + cfg.aux_loss_coef * aux
    metrics = {"xent": xent, "aux": aux}
    if cfg.mtp_depth:
        mtp = _mtp_loss(cfg, params, batch, logits)
        loss = loss + cfg.mtp_loss_coef * mtp
        metrics["mtp"] = mtp
    metrics["loss"] = loss
    return loss, metrics


def _mtp_loss(cfg: Any, params: PyTree, batch: Dict[str, jax.Array],
              logits: jax.Array) -> jax.Array:
    """DeepSeek-V3 multi-token prediction (depth 1, simplified): combine
    hidden-ish signal (re-embedded argmax-free: use token embeddings) with
    the next token's embedding, one extra layer, predict t+2."""
    tokens = batch["tokens"]
    x = embed(params["embed"], tokens, cfg.dtype)
    nxt = jnp.roll(x, -1, axis=1)
    h = dense(params["mtp_proj"], jnp.concatenate([x, nxt], axis=-1))
    positions = jnp.arange(h.shape[1], dtype=jnp.int32)
    from repro.configs.base import LayerSpec
    spec = LayerSpec("attn" if cfg.family != "ssm" else "mamba", "dense")
    h, _, _ = layer_apply(cfg, spec, params["mtp_layer"], h,
                          positions=positions, mode="train")
    h = norm(cfg.norm, params["mtp_norm"], h, cfg.norm_eps)
    mtp_logits = _head_out(cfg, params, h)
    labels2 = jnp.roll(batch["labels"], -1, axis=1)
    mask = jnp.ones_like(labels2, jnp.float32).at[:, -2:].set(0.0)
    return softmax_xent(mtp_logits, labels2, mask)


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------
def init_cache(cfg: Any, batch: int, max_seq: int) -> PyTree:
    prefix, period, n_periods = cfg.scan_plan()
    caches: Dict[str, Any] = {}
    for i, spec in enumerate(prefix):
        caches[f"prefix_{i}"] = layer_cache_init(cfg, spec, batch, max_seq)

    def one_period(_):
        return {f"l{j}": layer_cache_init(cfg, spec, batch, max_seq)
                for j, spec in enumerate(period)}

    caches["stack"] = jax.vmap(one_period)(jnp.arange(n_periods))
    return caches


def cache_batch_axes(cfg: Any, caches: PyTree) -> PyTree:
    """Pytree (matching ``caches``) of the batch-dim index per leaf:
    0 for prefix-layer caches, 1 for scan-stacked caches (dim 0 is the
    period index there).  Used by the serving engine for slot indexing
    and by the per-row decode's cache update."""
    return {k: jax.tree.map(lambda _: 1 if k == "stack" else 0, v)
            for k, v in caches.items()}


def prefill(cfg: Any, params: PyTree, tokens: jax.Array, caches: PyTree, *,
            frontend_embeds: Optional[jax.Array] = None,
            impl: Optional[str] = None,
            kernels: Optional[Dict[str, Any]] = None
            ) -> Tuple[jax.Array, PyTree]:
    """Fill the cache for the prompt; return (last-position logits, cache)."""
    x = _embed_in(cfg, params, tokens, frontend_embeds)
    positions = jnp.arange(x.shape[1], dtype=jnp.int32)
    x, _, new_caches = _stack_sweep(cfg, params, x, positions=positions,
                                    mode="prefill", caches=caches,
                                    impl=impl, kernels=kernels)
    logits = _head_out(cfg, params, x[:, -1:, :])
    return logits, new_caches


def decode_step(cfg: Any, params: PyTree, tokens: jax.Array, caches: PyTree,
                length: jax.Array, *,
                kernels: Optional[Dict[str, Any]] = None
                ) -> Tuple[jax.Array, PyTree]:
    """One token for every sequence.  tokens [B, 1]; length [] = the
    cache fill of every row, or [B] = each row's own fill.  Returns
    (logits [B, 1, V], new caches).

    With a scalar length each layer writes its new K/V row into the
    cache inside the layer scan.  With a vector length the cache is
    read-only inside the scan: each attention layer attends its old
    rows plus its own new row and returns only that row, and after the
    scan one pass over the rows writes every layer's row at ``[layer, b,
    length[b]]`` (in place when the caller donates the cache)."""
    x = _embed_in(cfg, params, tokens, None)
    per_row = jnp.ndim(length) == 1
    positions = length[:, None] if per_row \
        else jnp.full((1,), length, jnp.int32)
    x, _, new_caches = _stack_sweep(cfg, params, x, positions=positions,
                                    mode="decode", caches=caches,
                                    length=length, kernels=kernels)
    if per_row:
        new_caches = _write_rows(cfg, caches, new_caches, length)
    return _head_out(cfg, params, x), new_caches


# On a TPU the KV pool's sequence axis is its lane (minor) axis; a row
# written at a dynamic position there makes XLA relayout the whole pool
# around the write, a read-modify-write of the aligned block of positions
# that holds it does not.
ROW_BLOCK = 128


def _write_rows(cfg: Any, caches: PyTree, new: PyTree,
                length: jax.Array) -> PyTree:
    """The caches after a per-row decode: each attention leaf gets its
    new row ``new`` (the leaf less its sequence axis) written at
    ``[..., b, length[b]]``, slot by slot in place; a recurrent state
    (same shape as its leaf, no sequence axis) replaces the old one."""
    def put(buf, row, axis):
        if row.ndim == buf.ndim:
            return row
        blk = math.gcd(buf.shape[axis + 1], ROW_BLOCK)
        shape = buf.shape[:axis] + (1, blk) + buf.shape[axis + 2:]
        rest = (1,) * (row.ndim - axis - 1)

        def one(b, buf):
            s0 = length[b] // blk * blk
            start = (0,) * axis + (b, s0) + (0,) * len(rest)
            old = lax.dynamic_slice(buf, start, shape)
            upd = jnp.expand_dims(lax.dynamic_slice_in_dim(row, b, 1, axis),
                                  axis + 1).astype(buf.dtype)
            hit = (jnp.arange(blk) == length[b] - s0).reshape((blk,) + rest)
            return lax.dynamic_update_slice(buf, jnp.where(hit, upd, old),
                                            start)

        return lax.fori_loop(0, length.shape[0], one, buf)

    return jax.tree.map(put, caches, new, cache_batch_axes(cfg, caches))
