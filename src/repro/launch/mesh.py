"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (never a module-level constant) so
importing this module never touches jax device state.  The dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import to build these meshes on CPU placeholder devices.

Single pod:  (data=16, model=16)            = 256 chips (one v5e pod)
Multi-pod:   (pod=2, data=16, model=16)     = 512 chips

Axis roles (see repro.parallel.sharding.DEFAULT_RULES):
- ``pod``   — data parallelism across pods (gradient reduction crosses
  the inter-pod links; the compressed-allreduce path targets this axis)
- ``data``  — data parallelism + FSDP(ZeRO-3) parameter sharding
- ``model`` — tensor parallelism / expert parallelism / sequence
  parallelism for long-context decode
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
from jax.sharding import Mesh

from repro.compat import AxisType, make_mesh as _compat_make_mesh


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _compat_make_mesh(shape, axes)


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]) -> Mesh:
    return _compat_make_mesh(shape, axes)


def make_host_mesh(model: Optional[int] = None) -> Optional[Mesh]:
    """Best-effort mesh from whatever devices exist (tests/examples)."""
    n = len(jax.devices())
    if n == 1:
        return None
    model = model or (2 if n % 2 == 0 else 1)
    data = n // model
    return make_mesh((data, model), ("data", "model"))


# Published per-chip peaks used by the roofline analysis, keyed by
# ``jax.Device.device_kind`` (Google Cloud documentation, "TPU v5e").
# The dry-run models a v5e pod.  A kind missing here is an error.
PEAKS = {
    "TPU v5 lite": {
        "flops_bf16": 197e12,          # FLOP/s
        "hbm_bw": 819e9,               # B/s
        "ici_link_bw": 50e9,           # B/s per link per direction
    },
}
DRYRUN_DEVICE_KIND = "TPU v5 lite"


def peaks(device_kind: str) -> dict:
    """Published peaks of one ``device_kind`` chip."""
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}")
    return PEAKS[device_kind]
