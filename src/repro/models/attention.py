"""Grouped-query attention (GQA) with RoPE — train/prefill/decode paths.

Execution strategies (``cfg.attn_impl`` / ``impl=``):

- ``full``    — one [S, S] score matrix (reference; small shapes only).
- ``chunked`` — pure-JAX flash attention with a **custom VJP**: the
  forward runs online-softmax over KV blocks and saves only
  ``(q, k, v, out, lse)``; the backward recomputes block scores — O(S)
  residual memory instead of the O(S²) block-score stacks that plain
  autodiff-through-scan materializes.  This is the train/prefill
  baseline for the dry-run.
- ``chunked_causal_skip`` — unrolled lower-triangular block schedule:
  causal upper blocks are *omitted from the HLO entirely*, halving
  attention FLOPs (hillclimb step; see EXPERIMENTS.md §Perf).

Sharding: q/k/v are constrained per the logical rules — head dims shard
over ``model`` when divisible (Megatron-style TP attention, row-parallel
all-reduce after ``wo``), and drop to replicated otherwise instead of
letting GSPMD split the contraction (which inserts per-block score
all-reduces — see EXPERIMENTS.md §Perf iteration log).

The Pallas flash kernel (`repro.kernels.flash_attention`) replaces the
inner loop on real TPUs via ``kernels={"flash_attention": ...}``; the
dry-run uses this pure-XLA path (CPU placeholder devices cannot compile
Mosaic kernels).

Decode uses a pre-allocated KV cache ``{k, v: [B, S_max, n_kv · hd]}``:
each position's heads sit side by side in one row, so a row is whole
lanes on a TPU and the pool keeps its row-major layout through a
per-row write.  The decode reads it in that layout, each row whole
(``_row_scores``, ``_row_values``).
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from .common import (PyTree, apply_rope, dense, dense_init, merge, norm,
                     norm_init, rope_cos_sin)

NEG_INF = -1e30


def _constrain(x: jax.Array, dims) -> jax.Array:
    from repro.parallel.sharding import constrain
    return constrain(x, dims)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------
def attn_init(key: jax.Array, cfg: Any) -> Tuple[PyTree, PyTree]:
    hd = cfg.head_dim
    ks = jax.random.split(key, 6)
    parts = [
        ("wq", dense_init(ks[0], cfg.d_model, cfg.n_heads * hd,
                          dims=("embed", "q_proj"), bias=cfg.qkv_bias,
                          dtype=cfg.param_dtype)),
        ("wk", dense_init(ks[1], cfg.d_model, cfg.n_kv_heads * hd,
                          dims=("embed", "kv_proj"), bias=cfg.qkv_bias,
                          dtype=cfg.param_dtype)),
        ("wv", dense_init(ks[2], cfg.d_model, cfg.n_kv_heads * hd,
                          dims=("embed", "kv_proj"), bias=cfg.qkv_bias,
                          dtype=cfg.param_dtype)),
        ("wo", dense_init(ks[3], cfg.n_heads * hd, cfg.d_model,
                          dims=("q_proj", "embed"), bias=False,
                          scale=1.0 / math.sqrt(cfg.n_heads * hd),
                          dtype=cfg.param_dtype)),
    ]
    if cfg.qk_norm:
        parts.append(("qnorm", norm_init("rms", hd, cfg.param_dtype)))
        parts.append(("knorm", norm_init("rms", hd, cfg.param_dtype)))
    return merge(*parts)


# ---------------------------------------------------------------------------
# masking
# ---------------------------------------------------------------------------
def _mask_bias(q_pos: jax.Array, k_pos: jax.Array, causal: bool,
               window: Optional[int], k_valid: Optional[jax.Array] = None
               ) -> jax.Array:
    """[..., Q, K] additive bias in f32."""
    ok = jnp.ones((q_pos.shape[-1], k_pos.shape[-1]), bool)
    if causal:
        ok &= q_pos[:, None] >= k_pos[None, :]
    if window is not None:
        ok &= q_pos[:, None] - k_pos[None, :] < window
    if k_valid is not None:
        ok &= k_valid[None, :]
    return jnp.where(ok, 0.0, NEG_INF).astype(jnp.float32)


# ---------------------------------------------------------------------------
# reference full attention (q [B,Q,Hq,Dk], k/v [B,K,Hkv,D*])
# ---------------------------------------------------------------------------
def _gqa_scores(q: jax.Array, k: jax.Array) -> jax.Array:
    """-> [B, Hkv, G, Q, K] grouped scores (f32)."""
    b, qlen, hq, d = q.shape
    hkv = k.shape[2]
    g = hq // hkv
    qg = q.reshape(b, qlen, hkv, g, d)
    return jnp.einsum("bqhgd,bkhd->bhgqk", qg, k,
                      preferred_element_type=jnp.float32)


def _gqa_out(p: jax.Array, v: jax.Array) -> jax.Array:
    """p [B,Hkv,G,Q,K], v [B,K,Hkv,Dv] -> [B,Q,Hq,Dv]."""
    b, hkv, g, qlen, _ = p.shape
    out = jnp.einsum("bhgqk,bkhd->bqhgd", p, v)
    return out.reshape(b, qlen, hkv * g, v.shape[-1])


def _row_scores(q: jax.Array, buf: jax.Array, n_kv: int) -> jax.Array:
    """q [B,1,Hq,hd] against every row of a cache leaf [B,S,Hkv·hd] ->
    grouped scores [B,Hkv,G,1,S] (f32).  Each query head is spread over
    the whole row, zero outside its own kv head's part, so the leaf is
    contracted over its row in its own layout: a [B,S,Hkv,hd] view of it
    would make XLA lay a TPU pool out anew in every layer."""
    b, _, hq, hd = q.shape
    g = hq // n_kv
    eye = jnp.eye(n_kv, dtype=q.dtype)
    qw = (q.reshape(b, n_kv, g, 1, hd) * eye[:, None, :, None]
          ).reshape(b, hq, n_kv * hd)
    with jax.named_scope("kv_read"):
        s = jnp.einsum("bnw,bsw->bns", qw, buf,
                       preferred_element_type=jnp.float32)
    return s.reshape(b, n_kv, g, 1, -1)


def _row_values(p: jax.Array, buf: jax.Array) -> jax.Array:
    """Weights p [B,Hkv,G,1,S] over the rows of a cache leaf [B,S,Hkv·hd]
    -> [B,Hkv,G,1,hd] (f32): each head sums whole rows, of which it keeps
    its own kv head's part (``_row_scores``'s layout)."""
    b, n_kv, g, _, s = p.shape
    with jax.named_scope("kv_read"):
        o = jnp.einsum("bns,bsw->bnw", p.reshape(b, n_kv * g, s), buf,
                       preferred_element_type=jnp.float32)
    o = o.reshape(b, n_kv, g, n_kv, -1)
    eye = jnp.eye(n_kv, dtype=o.dtype)
    return (o * eye[:, None, :, None]).sum(axis=3)[:, :, :, None]


def attention_full(q: jax.Array, k: jax.Array, v: jax.Array, *,
                   scale: float, causal: bool, window: Optional[int],
                   q_pos: jax.Array, k_pos: jax.Array) -> jax.Array:
    s = _gqa_scores(q, k) * scale
    s = s + _mask_bias(q_pos, k_pos, causal, window)
    p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    return _gqa_out(p, v)


# ---------------------------------------------------------------------------
# flash attention (pure JAX, custom VJP).  Grouped layout internally:
# q [B,Hkv,G,S,Dk], k/v [B,Hkv,S,D*].  positions = arange(S).
# ---------------------------------------------------------------------------
def _blocks(x: jax.Array, nb: int, axis: int) -> jax.Array:
    """Split ``axis`` into (nb, block) and move nb to the front."""
    shape = x.shape
    bsz = shape[axis] // nb
    x = x.reshape(shape[:axis] + (nb, bsz) + shape[axis + 1:])
    return jnp.moveaxis(x, axis, 0)


def _cblocks(x, dims):
    """Pin a block-stack sharding via the logical rules."""
    return _constrain(x, dims)


def _tp_size() -> int:
    from repro.parallel.sharding import active_mesh
    mesh = active_mesh()
    if mesh is None or "model" not in mesh.axis_names:
        return 1
    return int(mesh.shape["model"])


def _pick_chunks(s: int, block: int, tp: int) -> Tuple[int, int]:
    """(n_chunks, block) such that n_chunks divides s, is a multiple of
    tp (so the chunk stack shards over ``model``), and the block size is
    closest to the requested one.  Falls back to gcd blocking when no
    tp-aligned divisor exists."""
    best = None
    d = 1
    while d * d <= s:
        if s % d == 0:
            for nq in (d, s // d):
                if nq % tp == 0 and s // nq >= 1:
                    # log-distance: 4 and 16384 are both "far" from 256
                    score = abs(math.log2(s / nq) - math.log2(block))
                    if best is None or score < best[0]:
                        best = (score, nq)
        d += 1
    if best is not None:
        nq = best[1]
        return nq, s // nq
    bq = max(1, math.gcd(s, block))
    return s // bq, bq


def _mode_dims(mode: str):
    """Sharding dims for the q-side 6D stacks / kv-side 5D stacks per
    parallelism mode.

    - ``chunk``: sequence parallelism — chunk dim over model, kv stacks
      replicated (GQA kv is small);
    - ``head``: TP attention — the Hkv dim shards over model (only legal
      when n_kv_heads divides the axis; then *nothing* is replicated and
      attention needs no collectives at all).
    """
    if mode == "head":
        return ((None, "batch", "kv_heads", None, None, None),
                (None, "batch", "kv_heads", None, None, None),
                (None, "batch", "kv_heads", None, None),
                (None, "batch", "kv_heads", None, None))
    return (("attn_chunks", "batch", None, None, None, None),
            (None, "batch", None, None, None, None),
            ("attn_chunks", "batch", None, None, None),
            (None, "batch", None, None, None))


def _flash_fwd_impl(q, k, v, scale, causal, window, bq, bk, mode):
    b, hkv, g, sq, dk = q.shape
    sk, dv = k.shape[2], v.shape[-1]
    nq, nk = sq // bq, sk // bk
    qdims, _, kdims, _ = _mode_dims(mode)
    qb = _cblocks(_blocks(q, nq, 3), qdims)
    # chunk mode: every q-chunk scans the full KV — kv stacks stay
    # replicated over chunks (one all-gather of the small GQA k/v per
    # layer).  head mode: kv sharded by heads, fully local.
    kb = _cblocks(_blocks(k, nk, 2), kdims)
    vb = _cblocks(_blocks(v, nk, 2), kdims)

    def q_chunk(qi, qblk):
        q_pos = qi * bq + jnp.arange(bq)
        acc0 = jnp.zeros((b, hkv, g, bq, dv), jnp.float32)
        m0 = jnp.full((b, hkv, g, bq), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, hkv, g, bq), jnp.float32)

        def kv_step(carry, args2):
            kj, kblk, vblk = args2
            acc, m, l = carry
            k_pos = kj * bk + jnp.arange(bk)
            s = jnp.einsum("bhgqd,bhkd->bhgqk", qblk, kblk,
                           preferred_element_type=jnp.float32) * scale
            s = s + _mask_bias(q_pos, k_pos, causal, window)
            m_new = jnp.maximum(m, s.max(axis=-1))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new[..., None])
            l = l * alpha + p.sum(axis=-1)
            pv = jnp.einsum("bhgqk,bhkd->bhgqd", p.astype(vblk.dtype),
                            vblk)
            acc = acc * alpha[..., None].astype(acc.dtype) + pv
            return (acc, m_new, l), None

        (acc, m, l), _ = lax.scan(
            kv_step, (acc0, m0, l0), (jnp.arange(nk), kb, vb))
        l_safe = jnp.maximum(l, 1e-30)
        out = (acc / l_safe[..., None]).astype(v.dtype)
        lse = m + jnp.log(l_safe)
        return out, lse                           # [B,Hkv,G,bq,dv], [..bq]

    # vmap (not lax.map): the chunk dim stays a *batched* dim, so GSPMD
    # shards the attention compute over it (a sequential loop cannot be
    # sharded)
    outs, lses = jax.vmap(q_chunk)(jnp.arange(nq), qb)
    outs = _cblocks(outs, qdims)
    out = jnp.moveaxis(outs, 0, 3).reshape(b, hkv, g, sq, dv)
    lse = jnp.moveaxis(lses, 0, 3).reshape(b, hkv, g, sq)
    return out, lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash(q, k, v, scale, causal, window, bq, bk, mode):
    out, _ = _flash_fwd_impl(q, k, v, scale, causal, window, bq, bk, mode)
    return out


def _flash_fwd(q, k, v, scale, causal, window, bq, bk, mode):
    out, lse = _flash_fwd_impl(q, k, v, scale, causal, window, bq, bk,
                               mode)
    return out, (q, k, v, out, lse)


def _flash_bwd(scale, causal, window, bq, bk, mode, res, dout):
    """Single-pass flash backward, vmapped over q chunks: each chunk
    computes its dq locally AND emits per-(q,kv)-block dk/dv
    contributions; the sum over the (sharded) chunk dim is the dk/dv
    reduction GSPMD lowers to one reduce over the model axis.

    vs. the classic two-pass form this (i) never replicates the q-side
    stacks across sequence shards (§Perf iteration 3 — the 2-pass dkv
    sweep all-gathered q/do/out per layer), and (ii) computes p/ds once
    per block pair: 5 matmuls instead of 7."""
    q, k, v, out, lse = res
    b, hkv, g, sq, dk = q.shape
    sk, dv = k.shape[2], v.shape[-1]
    nq, nk = sq // bq, sk // bk
    cdims6, rdims6, cdims5, rdims5 = _mode_dims(mode)
    qb = _cblocks(_blocks(q, nq, 3), cdims6)
    dob = _cblocks(_blocks(dout, nq, 3), cdims6)
    outb = _cblocks(_blocks(out, nq, 3), cdims6)
    lseb = _cblocks(_blocks(lse, nq, 3), cdims5)
    kb = _cblocks(_blocks(k, nk, 2), rdims5)
    vb = _cblocks(_blocks(v, nk, 2), rdims5)
    f32 = jnp.float32

    def chunk_bwd(qi, qblk, doblk, oblk, lblk):
        q_pos = qi * bq + jnp.arange(bq)
        Di = jnp.sum(doblk.astype(f32) * oblk.astype(f32), axis=-1)

        def kv_step(dq_i, args2):
            kj, kblk, vblk = args2
            k_pos = kj * bk + jnp.arange(bk)
            s = jnp.einsum("bhgqd,bhkd->bhgqk", qblk, kblk,
                           preferred_element_type=f32) * scale
            s = s + _mask_bias(q_pos, k_pos, causal, window)
            p = jnp.exp(s - lblk[..., None])     # [B,Hkv,G,bq,bk]
            dp = jnp.einsum("bhgqd,bhkd->bhgqk", doblk.astype(f32),
                            vblk.astype(f32))
            ds = p * (dp - Di[..., None]) * scale
            dq_i = dq_i + jnp.einsum("bhgqk,bhkd->bhgqd",
                                     ds.astype(f32), kblk.astype(f32))
            dv_blk = jnp.einsum("bhgqk,bhgqd->bhkd", p.astype(f32),
                                doblk.astype(f32))
            dk_blk = jnp.einsum("bhgqk,bhgqd->bhkd", ds.astype(f32),
                                qblk.astype(f32))
            return dq_i, (dk_blk, dv_blk)

        dq0 = jnp.zeros((b, hkv, g, bq, dk), f32)
        dq_i, (dk_parts, dv_parts) = lax.scan(
            kv_step, dq0, (jnp.arange(nk), kb, vb))
        return dq_i, dk_parts, dv_parts         # parts: [nk,B,Hkv,bk,d]

    dqs, dkp, dvp = jax.vmap(chunk_bwd)(jnp.arange(nq), qb, dob, outb,
                                        lseb)
    dqs = _cblocks(dqs, cdims6)
    # sum per-chunk contributions; the chunk dim is sharded in chunk
    # mode, so this is a cross-shard reduce of the SMALL GQA dk/dv
    dks = dkp.sum(axis=0)
    dvs = dvp.sum(axis=0)
    dks = _cblocks(dks, rdims5)
    dvs = _cblocks(dvs, rdims5)
    dq = jnp.moveaxis(dqs, 0, 3).reshape(b, hkv, g, sq, dk).astype(q.dtype)
    dk_out = jnp.moveaxis(dks, 0, 2).reshape(b, hkv, sk, dk).astype(k.dtype)
    dv_out = jnp.moveaxis(dvs, 0, 2).reshape(b, hkv, sk, dv).astype(v.dtype)
    return dq, dk_out, dv_out


_flash.defvjp(_flash_fwd, _flash_bwd)


def attention_chunked(q: jax.Array, k: jax.Array, v: jax.Array, *,
                      scale: float, causal: bool, window: Optional[int],
                      q_block: int, k_block: int,
                      causal_skip: bool = False) -> jax.Array:
    """Model-layout wrapper.  q [B,S,Hq,Dk], k/v [B,S,Hkv,D*] (positions
    are arange(S)) -> [B,S,Hq,Dv]."""
    b, s, hq, d = q.shape
    hkv = k.shape[2]
    g = hq // hkv
    tp = _tp_size()
    # parallelism mode: TP by kv heads when they divide the model axis
    # (collective-free), sequence/chunk parallelism otherwise
    mode = "head" if (tp > 1 and hkv % tp == 0) else "chunk"
    if mode == "chunk" and tp > 1:
        # the chunk count must be a multiple of tp or the chunk sharding
        # silently drops (e.g. VLM S=4096+576 — §Perf iteration 1)
        _, bq = _pick_chunks(s, q_block, tp)
        bk = max(1, math.gcd(s, k_block))
    else:
        bq = max(1, math.gcd(s, q_block))
        bk = max(1, math.gcd(s, k_block))
    qg = jnp.moveaxis(q.reshape(b, s, hkv, g, d), 1, 3)  # [B,Hkv,G,S,D]
    kg = jnp.moveaxis(k, 1, 2)                           # [B,Hkv,S,D]
    vg = jnp.moveaxis(v, 1, 2)
    if mode == "head":
        qg = _constrain(qg, ("batch", "kv_heads", None, None, None))
        kg = _constrain(kg, ("batch", "kv_heads", None, None))
        vg = _constrain(vg, ("batch", "kv_heads", None, None))
    if causal_skip and causal and window is None:
        out = _flash_causal_skip(qg, kg, vg, scale, bq, bk)
    else:
        out = _flash(qg, kg, vg, scale, causal, window, bq, bk, mode)
    return jnp.moveaxis(out, 3, 1).reshape(b, s, hq, v.shape[-1])


def _flash_causal_skip(q, k, v, scale, bq, bk):
    """Unrolled triangular schedule: upper blocks never emitted.  Memory
    behaviour of autodiff here is the plain-scan one per *diagonal row*,
    acceptable because block count is triangular; used as a §Perf
    iteration, not the default."""
    b, hkv, g, sq, dk = q.shape
    sk, dv = k.shape[2], v.shape[-1]
    nq, nk = sq // bq, sk // bk
    outs = []
    for qi in range(nq):
        qblk = lax.dynamic_slice_in_dim(q, qi * bq, bq, 3)
        q_pos = qi * bq + jnp.arange(bq)
        acc = jnp.zeros((b, hkv, g, bq, dv), jnp.float32)
        m = jnp.full((b, hkv, g, bq), NEG_INF, jnp.float32)
        l = jnp.zeros((b, hkv, g, bq), jnp.float32)
        for kj in range(min(qi + 1, nk)):
            kblk = lax.dynamic_slice_in_dim(k, kj * bk, bk, 2)
            vblk = lax.dynamic_slice_in_dim(v, kj * bk, bk, 2)
            k_pos = kj * bk + jnp.arange(bk)
            s = jnp.einsum("bhgqd,bhkd->bhgqk", qblk, kblk,
                           preferred_element_type=jnp.float32) * scale
            s = s + _mask_bias(q_pos, k_pos, True, None)
            m_new = jnp.maximum(m, s.max(axis=-1))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new[..., None])
            l = l * alpha + p.sum(axis=-1)
            pv = jnp.einsum("bhgqk,bhkd->bhgqd", p.astype(vblk.dtype),
                            vblk)
            acc = acc * alpha[..., None].astype(acc.dtype) + pv
            m = m_new
        out_i = (acc / jnp.maximum(l, 1e-30)[..., None]).astype(v.dtype)
        outs.append(out_i)
    return jnp.concatenate(outs, axis=3)


# ---------------------------------------------------------------------------
# layer application
# ---------------------------------------------------------------------------
def _project_qkv(cfg: Any, p: PyTree, x: jax.Array, positions: jax.Array
                 ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    b, s, _ = x.shape
    hd = cfg.head_dim
    q = dense(p["wq"], x).reshape(b, s, cfg.n_heads, hd)
    k = dense(p["wk"], x).reshape(b, s, cfg.n_kv_heads, hd)
    v = dense(p["wv"], x).reshape(b, s, cfg.n_kv_heads, hd)
    if cfg.qk_norm:
        q = norm("rms", p["qnorm"], q, cfg.norm_eps)
        k = norm("rms", p["knorm"], k, cfg.norm_eps)
    cos, sin = rope_cos_sin(positions, hd, cfg.rope_theta)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    tp = _tp_size()
    if s > 1 and not (tp > 1 and cfg.n_kv_heads % tp == 0):
        # chunk (sequence-parallel) mode: pin projections seq-sharded.
        # head mode leaves them alone — the column-parallel weight
        # sharding already produces head-sharded q/k/v locally.
        q = _constrain(q, ("batch", "seq", None, None))
        k = _constrain(k, ("batch", "seq", None, None))
        v = _constrain(v, ("batch", "seq", None, None))
    return q, k, v


def attn_apply(cfg: Any, p: PyTree, x: jax.Array, *,
               positions: jax.Array,
               impl: str = "chunked",
               kernel_fn: Any = None) -> jax.Array:
    """Full-sequence (train/prefill) attention.  x [B,S,D]."""
    b, s, _ = x.shape
    q, k, v = _project_qkv(cfg, p, x, positions)
    scale = 1.0 / math.sqrt(cfg.head_dim)
    if kernel_fn is not None:
        out = kernel_fn(q, k, v, causal=cfg.causal, scale=scale)
    elif impl == "full" or s <= cfg.q_block:
        out = attention_full(q, k, v, scale=scale, causal=cfg.causal,
                             window=cfg.sliding_window, q_pos=positions,
                             k_pos=positions)
    else:
        out = attention_chunked(
            q, k, v, scale=scale, causal=cfg.causal,
            window=cfg.sliding_window,
            q_block=cfg.q_block, k_block=cfg.q_block,
            causal_skip=(impl == "chunked_causal_skip"))
    tp = _tp_size()
    if tp > 1 and cfg.n_kv_heads % tp == 0 and cfg.n_heads % tp == 0:
        # head-TP: out stays head-sharded into the row-parallel wo
        # ("kv_heads" rule resolves to the model axis w/ divisibility)
        out = _constrain(out, ("batch", None, "kv_heads", None))
    elif s > 1:
        out = _constrain(out, ("batch", "seq", None, None))
    return dense(p["wo"], out.reshape(b, s, cfg.n_heads * cfg.head_dim))


# ---------------------------------------------------------------------------
# decode with KV cache
# ---------------------------------------------------------------------------
def seq_sharded_decode(smax: int) -> bool:
    """True when the decode cells run with the KV cache sharded along
    the sequence dim over ``model`` (context-parallel decode — set by
    launch.steps.decode_rules for archs whose kv-head count cannot shard
    the model axis, and always for MLA's head-less latent cache)."""
    from repro.parallel.sharding import active_mesh, active_rules
    mesh = active_mesh()
    if mesh is None or "model" not in mesh.axis_names:
        return False
    if mesh.shape["model"] <= 1 or smax % mesh.shape["model"]:
        return False
    return "model" in active_rules().get("cache_seq", ())


def _dp_prefix(mesh, b: int):
    axes = []
    prod = 1
    for a in ("pod", "data"):
        if a in mesh.shape and b % (prod * mesh.shape[a]) == 0:
            axes.append(a)
            prod *= mesh.shape[a]
        else:
            break
    return tuple(axes) if axes else None


def _local_row_update(buf: jax.Array, row: jax.Array, off: jax.Array,
                      in_range: jax.Array) -> jax.Array:
    """Write ``row`` at local offset ``off`` iff ``in_range`` — O(1 row)
    (a full-buffer select would rewrite the whole cache every token)."""
    off_c = jnp.clip(off, 0, buf.shape[1] - row.shape[1])
    start = (0, off_c) + (0,) * (buf.ndim - 2)
    cur = lax.dynamic_slice(buf, start, row.shape)
    row = jnp.where(in_range, row.astype(buf.dtype), cur)
    return lax.dynamic_update_slice(buf, row, start)


def _flash_decode_combine(acc, m, l, axis: str):
    """Flash-decoding softmax combine across sequence shards."""
    m_g = lax.pmax(m, axis)
    corr = jnp.exp(m - m_g)
    l_g = lax.psum(l * corr, axis)
    acc_g = lax.psum(acc * corr[..., None], axis)
    return acc_g / jnp.maximum(l_g, 1e-30)[..., None]


def attn_decode_sharded(cfg: Any, q: jax.Array, k_new: jax.Array,
                        v_new: jax.Array, cache: PyTree,
                        length: jax.Array) -> Tuple[jax.Array, PyTree]:
    """Context-parallel decode: the KV cache stays sharded along seq
    over ``model``; each shard updates its local rows and computes a
    partial softmax, combined with pmax/psum (flash-decoding)."""
    from jax.sharding import PartitionSpec as P
    from repro.compat import shard_map
    from repro.parallel.sharding import active_mesh
    mesh = active_mesh()
    b = q.shape[0]
    scale = 1.0 / math.sqrt(cfg.head_dim)
    bspec = _dp_prefix(mesh, b)
    cspec = P(bspec, "model", None)
    qspec = P(bspec, None, None, None)

    def body(q_, kn, vn, ck, cv, ln):
        rank = lax.axis_index("model")
        s_loc = ck.shape[1]
        start = rank * s_loc
        off = ln - start
        in_range = (off >= 0) & (off < s_loc)
        ck = _local_row_update(ck, kn.reshape(ck.shape[0], 1, -1), off,
                               in_range)
        cv = _local_row_update(cv, vn.reshape(cv.shape[0], 1, -1), off,
                               in_range)
        s = _row_scores(q_, ck.astype(q_.dtype), cfg.n_kv_heads) * scale
        pos = start + jnp.arange(s_loc)
        s = jnp.where((pos <= ln)[None, None, None, None, :], s, NEG_INF)
        m = s.max(axis=-1)
        p = jnp.exp(s - m[..., None])
        l = p.sum(axis=-1)
        acc = _row_values(p.astype(cv.dtype), cv.astype(q_.dtype))
        out = _flash_decode_combine(acc, m, l, "model")
        return out.astype(q_.dtype), ck, cv

    out, ck, cv = shard_map(
        body, mesh,
        in_specs=(qspec, qspec, qspec, cspec, cspec, P()),
        out_specs=(P(bspec, None, None, None, None), cspec, cspec))(
            q, k_new, v_new, cache["k"], cache["v"], length)
    # out [B,Hkv,G,1,dv] -> [B,1,Hq,dv]
    b_, hkv, g, _, dv = out.shape
    y = jnp.transpose(out, (0, 3, 1, 2, 4)).reshape(b_, 1, hkv * g, dv)
    return y, {"k": ck, "v": cv}


def attn_cache_init(cfg: Any, batch: int, max_seq: int,
                    dtype: Any = None) -> PyTree:
    """{k, v: [batch, max_seq, n_kv_heads · head_dim]}."""
    dtype = dtype or cfg.dtype
    shape = (batch, max_seq, cfg.n_kv_heads * cfg.head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def attn_cache_dims() -> PyTree:
    # a row holds the kv heads side by side: split over "kv_heads" it
    # keeps whole heads together where the head count divides the axis,
    # and decode_rules replicate it where it does not
    return {"k": ("cache_batch", "cache_seq", "kv_heads"),
            "v": ("cache_batch", "cache_seq", "kv_heads")}


def old_rows_mask(cache_seq: int, length: jax.Array,
                  window: Optional[int] = None) -> jax.Array:
    """[B, cache_seq] bool: the cache rows a decode at per-row lengths
    [B] attends besides its new row, those before ``length[b]`` (and
    inside the sliding ``window``).  Per-row lengths are for the
    unsharded cache of the serving engine."""
    if seq_sharded_decode(cache_seq):
        raise NotImplementedError(
            "decode with per-row lengths [B] needs an unsharded KV cache; "
            "a sequence-sharded cache takes one scalar length")
    dist = length[:, None] - jnp.arange(cache_seq, dtype=jnp.int32)[None, :]
    ok = dist > 0
    if window is not None:
        ok &= dist < window
    return ok


def _attn_decode_rows(cfg: Any, p: PyTree, x: jax.Array, cache: PyTree,
                      length: jax.Array) -> Tuple[jax.Array, PyTree]:
    """``attn_decode`` with a length per row [B].  The cache is only
    read: each row attends its old rows ``< length[b]`` and its new K/V
    row, one extra logit and value term of the same softmax.  Returns
    (y, the new row {k, v: [B, Hkv·hd]})."""
    b = x.shape[0]
    ok = old_rows_mask(cache["k"].shape[1], length, cfg.sliding_window)
    q, k_new, v_new = _project_qkv(cfg, p, x, length[:, None])
    scale = 1.0 / math.sqrt(cfg.head_dim)
    s_old = _row_scores(q, cache["k"].astype(x.dtype), cfg.n_kv_heads) \
        * scale
    s_old = jnp.where(ok[:, None, None, None, :], s_old, NEG_INF)
    s_new = _gqa_scores(q, k_new) * scale               # [B,Hkv,G,1,1]
    m = jnp.maximum(s_old.max(axis=-1, keepdims=True), s_new)
    p_old, p_new = jnp.exp(s_old - m), jnp.exp(s_new - m)
    l = p_old.sum(axis=-1, keepdims=True) + p_new
    out = _row_values((p_old / l).astype(x.dtype),
                      cache["v"].astype(x.dtype)) \
        + jnp.einsum("bhgqk,bkhd->bhgqd", p_new / l,
                     v_new.astype(jnp.float32))
    out = out.astype(x.dtype)
    y = dense(p["wo"], out.reshape(b, 1, cfg.n_heads * cfg.head_dim))
    return y, {"k": k_new.reshape(b, -1), "v": v_new.reshape(b, -1)}


def attn_decode(cfg: Any, p: PyTree, x: jax.Array, cache: PyTree,
                length: jax.Array) -> Tuple[jax.Array, PyTree]:
    """One decode step.  x [B,1,D]; cache k/v [B,Smax,Hkv·hd]; length []
    (tokens already in cache).  Returns (y [B,1,D], new_cache).  A
    length per row [B] returns only the new row instead
    (``_attn_decode_rows``)."""
    if jnp.ndim(length) == 1:
        return _attn_decode_rows(cfg, p, x, cache, length)
    b = x.shape[0]
    positions = jnp.full((1,), length, jnp.int32)
    q, k_new, v_new = _project_qkv(cfg, p, x, positions)
    if seq_sharded_decode(cache["k"].shape[1]):
        out, new_cache = attn_decode_sharded(cfg, q, k_new, v_new, cache,
                                             length)
        y = dense(p["wo"], out.reshape(b, 1, cfg.n_heads * cfg.head_dim))
        return y, new_cache
    k = lax.dynamic_update_slice(
        cache["k"], k_new.reshape(b, 1, -1).astype(cache["k"].dtype),
        (0, length, 0))
    v = lax.dynamic_update_slice(
        cache["v"], v_new.reshape(b, 1, -1).astype(cache["v"].dtype),
        (0, length, 0))
    smax = k.shape[1]
    k_pos = jnp.arange(smax, dtype=jnp.int32)
    k_valid = k_pos <= length
    scale = 1.0 / math.sqrt(cfg.head_dim)
    s = _row_scores(q, k.astype(x.dtype), cfg.n_kv_heads) * scale \
        + _mask_bias(positions, k_pos, False, cfg.sliding_window, k_valid)
    p_ = jax.nn.softmax(s, axis=-1).astype(x.dtype)
    out = _row_values(p_, v.astype(x.dtype)).astype(x.dtype)
    y = dense(p["wo"], out.reshape(b, 1, cfg.n_heads * cfg.head_dim))
    return y, {"k": k, "v": v}
