"""The grouped-query-attention (GQA) dense decoder (qwen2, starcoder2):
weights from a seed, the plain float32 reference, the map into the
program's configuration and parameter tree, the operations and bytes
its steps need, and its cut for CPU tests (``bench/archs/__init__.py``
lists the names).

The reference imports nothing of the system under test; only
``program_config`` and ``program_params`` name the program's types,
to hand it the weights.  The weights are drawn in
one jitted call from the seed, in the type they are served in; the
harness hands them to the program, and the reference draws them again
from the same seed after the program's state is freed.

The reference is a straightforward forward pass in ``jax.numpy``: float32
at "highest" matmul precision, one sequence at a time, the full [S, S]
attention with its causal and window mask, no cache and no kernels.  It
follows the published descriptions (HF ``Qwen2ForCausalLM``,
``Starcoder2ForCausalLM``): RoPE over two halves of each head,
grouped-query heads (query head h reads key/value head h // group),
RMSNorm or LayerNorm, SwiGLU or tanh-GELU MLP.  One departure: the
program has no bias on the attention output projection, which
starcoder2 publishes; the reference leaves it out too (it would be a
weight of zero).

``gaps`` returns, per position, how far below the reference's best
logit a given token's logit lies: the comparison that decides
``correct``.  ``fp8=True`` computes the same forward with every dense
matmul's operands rounded to float8 (e4m3, one scale per row of the
activations and per output column of the weights): the control.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax import lax

from bench.flops import attention_flops

HIGHEST = lax.Precision.HIGHEST
NEG_INF = -1e30


@dataclasses.dataclass(frozen=True)
class Spec:
    """The model's sizes and mechanisms, read from its configuration
    file (HF ``config.json`` key names)."""
    hidden_size: int
    intermediate_size: int
    num_attention_heads: int
    num_key_value_heads: int
    num_hidden_layers: int
    vocab_size: int
    head_dim: int
    norm: str                    # "rms" | "layer"
    norm_eps: float
    gated_mlp: bool              # SwiGLU (silu) else tanh-GELU
    qkv_bias: bool
    mlp_bias: bool
    tie_word_embeddings: bool
    rope_theta: float
    sliding_window: Optional[int]
    dtype: str                   # the type the weights are served in

    @classmethod
    def from_config(cls, c: Dict[str, Any]) -> "Spec":
        act = c["hidden_act"]
        if act not in ("silu", "gelu_pytorch_tanh"):
            raise ValueError(f"no reference for hidden_act {act!r}")
        norm = {"rms_norm": "rms", "layer_norm": "layer"}[c["norm_type"]]
        return cls(
            hidden_size=c["hidden_size"],
            intermediate_size=c["intermediate_size"],
            num_attention_heads=c["num_attention_heads"],
            num_key_value_heads=c["num_key_value_heads"],
            num_hidden_layers=c["num_hidden_layers"],
            vocab_size=c["vocab_size"],
            head_dim=c.get("head_dim") or
            c["hidden_size"] // c["num_attention_heads"],
            norm=norm, norm_eps=float(c["norm_epsilon"]),
            gated_mlp=(act == "silu"),
            qkv_bias=bool(c["qkv_bias"]), mlp_bias=bool(c["mlp_bias"]),
            tie_word_embeddings=bool(c["tie_word_embeddings"]),
            rope_theta=float(c["rope_theta"]),
            sliding_window=(c.get("sliding_window")
                            if c.get("use_sliding_window", True) else None),
            dtype=c["torch_dtype"])


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from a seed of up to 64 bits (``PRNGKey`` alone keeps
    only the low 32 without x64)."""
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def weight_shapes(spec: Spec) -> Dict[str, tuple]:
    d, f, L = spec.hidden_size, spec.intermediate_size, \
        spec.num_hidden_layers
    q, kv = spec.num_attention_heads * spec.head_dim, \
        spec.num_key_value_heads * spec.head_dim
    s = {"emb": (spec.vocab_size, d), "final_g": (d,),
         "ln1_g": (L, d), "ln2_g": (L, d),
         "wq": (L, d, q), "wk": (L, d, kv), "wv": (L, d, kv),
         "wo": (L, q, d)}
    if spec.norm == "layer":
        s.update(final_b=(d,), ln1_b=(L, d), ln2_b=(L, d))
    if spec.qkv_bias:
        s.update(bq=(L, q), bk=(L, kv), bv=(L, kv))
    if spec.gated_mlp:
        s.update(w_gate=(L, d, f), w_up=(L, d, f), w_down=(L, f, d))
    else:
        s.update(w_fc1=(L, d, f), w_fc2=(L, f, d))
    if spec.mlp_bias:
        s.update(b_fc1=(L, f), b_fc2=(L, d))
    if not spec.tie_word_embeddings:
        s["head"] = (d, spec.vocab_size)
    return s


def _init_one(name: str, key: jax.Array, shape: tuple) -> jax.Array:
    z = jax.random.normal(key, shape, jnp.float32)
    if name == "emb":
        return 0.02 * z
    if name.endswith("_g"):                  # norm gains around 1
        return 1.0 + 0.1 * z
    if name.startswith("b") or name.endswith("_b"):
        return 0.1 * z                       # biases, norm shifts
    return z / math.sqrt(shape[-2])          # 1/sqrt(fan-in)


def make_weights(spec: Spec, seed: int) -> Dict[str, jax.Array]:
    """Every weight, drawn from ``seed`` on the device in one program,
    in the served type."""
    shapes = weight_shapes(spec)
    names = sorted(shapes)
    dtype = jnp.dtype(spec.dtype)

    def build(key):
        keys = jax.random.split(key, len(names))
        return {n: _init_one(n, k, shapes[n]).astype(dtype)
                for n, k in zip(names, keys)}

    return jax.jit(build)(seed_key(seed))


# ---------------------------------------------------------------------------
# forward pass
# ---------------------------------------------------------------------------
def _fp8(x: jax.Array, axis: int) -> jax.Array:
    """Round to float8 e4m3 with one scale per slice along ``axis``."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / 448.0, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) \
        * scale


def _mm(x: jax.Array, w: jax.Array, fp8: bool) -> jax.Array:
    if fp8:
        x, w = _fp8(x, -1), _fp8(w, 0)
    return jnp.matmul(x, w, precision=HIGHEST)


def _norm(spec: Spec, x, g, b):
    if spec.norm == "rms":
        return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                             + spec.norm_eps) * g
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * lax.rsqrt(var + spec.norm_eps) * g + b


def _rope(x: jax.Array, theta: float) -> jax.Array:
    """x [S, H, D]; rotate the two halves of each head."""
    s, _, d = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    c, sn = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * c - x2 * sn, x1 * sn + x2 * c], -1)


def logits(spec: Spec, w: Dict[str, jax.Array], tokens: jax.Array,
           fp8: bool = False) -> jax.Array:
    """tokens [S] -> logits [S, V], float32."""
    f32 = jnp.float32
    s = tokens.shape[0]
    hq, hkv, hd = spec.num_attention_heads, spec.num_key_value_heads, \
        spec.head_dim
    g = hq // hkv
    # attention in blocks of query rows, so that the score matrix of a
    # long sequence fits beside the weights
    qblock = math.gcd(s, 512)
    pos = jnp.arange(s)
    ok = pos[:, None] >= pos[None, :]
    if spec.sliding_window is not None:
        ok &= pos[:, None] - pos[None, :] < spec.sliding_window
    stacked = {n: t for n, t in w.items()
               if n not in ("emb", "head", "final_g", "final_b")}

    def layer(x, p):
        p = {n: t.astype(f32) for n, t in p.items()}
        h = _norm(spec, x, p["ln1_g"], p.get("ln1_b"))
        q, k, v = (_mm(h, p["w" + n], fp8) for n in "qkv")
        if spec.qkv_bias:
            q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
        q = _rope(q.reshape(s, hq, hd), spec.rope_theta)
        k = _rope(k.reshape(s, hkv, hd), spec.rope_theta)
        v = v.reshape(s, hkv, hd)

        def rows(args):                  # one block of query rows
            qb, okb = args
            sc = jnp.einsum("qhgd,khd->hgqk", qb, k,
                            precision=HIGHEST) / math.sqrt(hd)
            sc = jnp.where(okb, sc, NEG_INF)
            return jnp.einsum("hgqk,khd->qhgd", jax.nn.softmax(sc, -1), v,
                              precision=HIGHEST)

        nb = s // qblock
        o = lax.map(rows, (q.reshape(nb, qblock, hkv, g, hd),
                           ok.reshape(nb, qblock, s)))
        x = x + _mm(o.reshape(s, hq * hd), p["wo"], fp8)
        h = _norm(spec, x, p["ln2_g"], p.get("ln2_b"))
        if spec.gated_mlp:
            m = jax.nn.silu(_mm(h, p["w_gate"], fp8)) * \
                _mm(h, p["w_up"], fp8)
            return x + _mm(m, p["w_down"], fp8), None
        m = _mm(h, p["w_fc1"], fp8)
        m = jax.nn.gelu(m + p["b_fc1"] if spec.mlp_bias else m,
                        approximate=True)
        y = _mm(m, p["w_fc2"], fp8)
        return x + (y + p["b_fc2"] if spec.mlp_bias else y), None

    x = jnp.take(w["emb"], tokens, axis=0).astype(f32)
    x, _ = lax.scan(layer, x, stacked)
    x = _norm(spec, x, w["final_g"].astype(f32),
              w["final_b"].astype(f32) if "final_b" in w else None)
    head = (w["emb"].T if spec.tie_word_embeddings else w["head"])
    return _mm(x, head.astype(f32), fp8)


def gaps(spec: Spec, w: Dict[str, jax.Array], tokens: jax.Array,
         targets: jax.Array, fp8: bool = False) -> jax.Array:
    """Per position i: best reference logit minus the reference logit of
    ``targets[i]`` (0 where the target is the reference's first choice).
    With ``fp8``, the target at each position is the token that the
    float8 forward puts first instead (``targets`` is then unused)."""
    ref = logits(spec, w, tokens)
    best = ref.max(-1)
    if fp8:
        targets = jnp.argmax(logits(spec, w, tokens, fp8=True), -1)
    got = jnp.take_along_axis(ref, targets[:, None], -1)[:, 0]
    return best - got


def gaps_program(spec: Spec, fp8: bool = False):
    """The jitted ``gaps`` for one spec: (weights, tokens [S],
    targets [S]) -> [S]."""
    return jax.jit(lambda w, t, y: gaps(spec, w, t, y, fp8=fp8))


# ---------------------------------------------------------------------------
# the program's configuration and parameter tree
# ---------------------------------------------------------------------------
def program_config(spec: Spec, serve: Dict[str, Any]):
    """The program's ``ModelConfig`` for the configuration file."""
    from repro.configs.base import ModelConfig
    dtype = jnp.dtype(spec.dtype)
    return ModelConfig(
        name=serve["name"], family="dense",
        n_layers=spec.num_hidden_layers, d_model=spec.hidden_size,
        n_heads=spec.num_attention_heads,
        n_kv_heads=spec.num_key_value_heads, head_dim=spec.head_dim,
        d_ff=spec.intermediate_size, vocab=spec.vocab_size,
        norm=spec.norm, norm_eps=spec.norm_eps,
        act="swiglu" if spec.gated_mlp else "gelu",
        qkv_bias=spec.qkv_bias, rope_theta=spec.rope_theta,
        sliding_window=spec.sliding_window,
        tie_embeddings=spec.tie_word_embeddings,
        max_seq_len=serve["max_seq"], dtype=dtype, param_dtype=dtype)


def program_params(spec: Spec, w: Dict[str, Any]) -> Dict:
    """The weights in the program's parameter tree (one scanned period
    holding every layer)."""
    def lin(name, bias=None):
        p = {"w": w[name]}
        if bias in w:
            p["b"] = w[bias]
        return p

    def nrm(g, b):
        return {"g": w[g], **({"b": w[b]} if b in w else {})}

    ffn = ({"gate": lin("w_gate"), "up": lin("w_up"), "down": lin("w_down")}
           if spec.gated_mlp else
           {"fc1": lin("w_fc1", "b_fc1"), "fc2": lin("w_fc2", "b_fc2")})
    layer = {"norm1": nrm("ln1_g", "ln1_b"),
             "mixer": {"wq": lin("wq", "bq"), "wk": lin("wk", "bk"),
                       "wv": lin("wv", "bv"), "wo": lin("wo")},
             "norm2": nrm("ln2_g", "ln2_b"), "ffn": ffn}
    params = {"embed": {"emb": w["emb"]}, "stack": {"l0": layer},
              "final_norm": nrm("final_g", "final_b")}
    if not spec.tie_word_embeddings:
        params["head"] = {"w": w["head"]}
    return params


# ---------------------------------------------------------------------------
# operations and bytes, from shapes (``bench/flops.py`` says what counts)
# ---------------------------------------------------------------------------
def _dims(spec: Spec) -> Dict[str, int]:
    hd = spec.head_dim
    return dict(d=spec.hidden_size, f=spec.intermediate_size,
                hq=spec.num_attention_heads, hkv=spec.num_key_value_heads,
                hd=hd, L=spec.num_hidden_layers, V=spec.vocab_size)


def layer_matmul_params(spec: Spec) -> int:
    """Weights one token multiplies through in one layer."""
    k = _dims(spec)
    attn = k["d"] * k["hq"] * k["hd"] * 2 + k["d"] * k["hkv"] * k["hd"] * 2
    mlp = (3 if spec.gated_mlp else 2) * k["d"] * k["f"]
    return attn + mlp


def weight_count(spec: Spec) -> int:
    """Every parameter of the served model (norms and biases included)."""
    k = _dims(spec)
    per_layer = layer_matmul_params(spec)
    per_layer += (k["hq"] + 2 * k["hkv"]) * k["hd"] if spec.qkv_bias else 0
    per_layer += (k["f"] + k["d"]) if spec.mlp_bias else 0
    per_layer += 2 * k["d"] * (2 if spec.norm == "layer" else 1)
    total = k["L"] * per_layer + k["V"] * k["d"]
    total += k["d"] * (2 if spec.norm == "layer" else 1)
    if not spec.tie_word_embeddings:
        total += k["d"] * k["V"]
    return total


def kv_bytes_per_token(spec: Spec, itemsize: int = 2) -> int:
    k = _dims(spec)
    return k["L"] * 2 * k["hkv"] * k["hd"] * itemsize


def prefill_flops(spec: Spec, s: int) -> int:
    """One prompt of ``s`` tokens: every layer's matmuls and causal
    attention, and the head for the last position only (the program
    returns only the last position's logits)."""
    k = _dims(spec)
    mm = 2 * s * k["L"] * layer_matmul_params(spec)
    att = k["L"] * attention_flops(k["hq"], k["hd"], s,
                                   spec.sliding_window)
    return mm + att + 2 * k["d"] * k["V"]


def decode_bytes(spec: Spec, kv_rows: int, itemsize: int = 2) -> int:
    """One decode step: every weight once, except the embedding rows
    that an untied model only gathers, plus ``kv_rows`` cache rows
    (summed over the slots it advances)."""
    w = weight_count(spec)
    if not spec.tie_word_embeddings:
        w -= spec.vocab_size * spec.hidden_size
    return w * itemsize + kv_rows * kv_bytes_per_token(spec, itemsize)


# ---------------------------------------------------------------------------
# the cut for CPU tests
# ---------------------------------------------------------------------------
def small(config: Dict[str, Any]) -> Dict[str, Any]:
    """``config`` with every width, the depth and the vocabulary made
    small; every mechanism it states kept."""
    return dict(config, hidden_size=64, intermediate_size=128,
                num_attention_heads=4, num_key_value_heads=2,
                num_hidden_layers=2, vocab_size=256)
