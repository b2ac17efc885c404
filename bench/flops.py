"""Peaks of the chip, and the counts of operations and bytes that fit any
architecture; what one architecture's steps need is counted, from its
shapes, in ``bench/archs/<arch>.py``.  Counted is what the algorithm
needs, not
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


def attention_flops(hq: int, hd: int, s: int, window=None) -> int:
    """Causal self-attention over ``s`` positions: QK^T and PV, each
    2*hd operations per (query, visible key) pair and head."""
    if window is None or window >= s:
        pairs = s * (s + 1) // 2
    else:
        pairs = sum(min(i + 1, window) for i in range(s))
    return 4 * hq * hd * pairs


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
