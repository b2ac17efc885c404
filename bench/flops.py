"""Peaks of the chip, and the operations and bytes each measured program
needs, computed from shapes.  Counted is what the algorithm needs, not
what the program happens to do: a decode that reads the whole
pre-allocated cache is charged only for the rows up to each slot's
length, so a roofline share below 100% also shows such waste.
"""
from __future__ import annotations

from typing import Dict

# Published peaks of one chip, keyed by ``jax.Device.device_kind``.
# Source: Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16,
# 16 GB HBM at 819 GB/s).  A kind missing here is an error.
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
}
PEAKS_SOURCE = "Google Cloud documentation, TPU v5e"


def peaks(device_kind: str) -> Dict[str, float]:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}")
    return PEAKS[device_kind]


def _dims(spec) -> Dict[str, int]:
    hd = spec.head_dim
    return dict(d=spec.hidden_size, f=spec.intermediate_size,
                hq=spec.num_attention_heads, hkv=spec.num_key_value_heads,
                hd=hd, L=spec.num_hidden_layers, V=spec.vocab_size)


def layer_matmul_params(spec) -> int:
    """Weights one token multiplies through in one layer."""
    k = _dims(spec)
    attn = k["d"] * k["hq"] * k["hd"] * 2 + k["d"] * k["hkv"] * k["hd"] * 2
    mlp = (3 if spec.gated_mlp else 2) * k["d"] * k["f"]
    return attn + mlp


def weight_count(spec) -> int:
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


def kv_bytes_per_token(spec, itemsize: int = 2) -> int:
    k = _dims(spec)
    return k["L"] * 2 * k["hkv"] * k["hd"] * itemsize


def attention_flops(hq: int, hd: int, s: int, window=None) -> int:
    """Causal self-attention over ``s`` positions: QK^T and PV, each
    2*hd operations per (query, visible key) pair and head."""
    if window is None or window >= s:
        pairs = s * (s + 1) // 2
    else:
        pairs = sum(min(i + 1, window) for i in range(s))
    return 4 * hq * hd * pairs


def prefill_flops(spec, s: int) -> int:
    """One prompt of ``s`` tokens: every layer's matmuls and causal
    attention, and the head for the last position only (the program
    returns only the last position's logits)."""
    k = _dims(spec)
    mm = 2 * s * k["L"] * layer_matmul_params(spec)
    att = k["L"] * attention_flops(k["hq"], k["hd"], s,
                                   spec.sliding_window)
    return mm + att + 2 * k["d"] * k["V"]


def decode_bytes(spec, kv_rows: int, itemsize: int = 2) -> int:
    """One decode step: every weight once, except the embedding rows
    that an untied model only gathers, plus ``kv_rows`` cache rows
    (summed over the slots it advances)."""
    w = weight_count(spec)
    if not spec.tie_word_embeddings:
        w -= spec.vocab_size * spec.hidden_size
    return w * itemsize + kv_rows * kv_bytes_per_token(spec, itemsize)


def flash_cost(hq: int, hkv: int, hd: int, s: int, itemsize: int = 2):
    """(operations, bytes) of one causal flash-attention call over ``s``
    positions: q, k, v read once, the output written once."""
    flops = attention_flops(hq, hd, s)
    nbytes = itemsize * s * hd * (2 * hq + 2 * hkv)
    return flops, nbytes


def roofline_share(flops: float, nbytes: float, seconds: float,
                   device_kind: str):
    """(share of the roofline in %, the bound: "compute" or "memory")."""
    pk = peaks(device_kind)
    t_c = flops / pk["bf16_flops"]
    t_m = nbytes / pk["hbm_bytes_per_s"]
    return 100.0 * max(t_c, t_m) / seconds, \
        ("compute" if t_c >= t_m else "memory")
