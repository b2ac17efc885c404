"""Pallas TPU kernels for the framework's compute hot-spots.

Each kernel: <name>.py (pl.pallas_call + BlockSpec), a pure-jnp oracle
in ref.py, and a backend-selecting wrapper in ops.py.  Validated in
interpret mode on CPU (tests/test_kernels_*.py sweeps shapes/dtypes);
compiled Mosaic on real TPUs.  The ring all-gather is the LCX
put-with-remote-signal pattern at the DMA level.
"""
from . import ops, ref
from .ops import (flash_attention, kv_row_write, model_kernels, moe_gmm,
                  on_tpu, ring_all_gather, ssd_scan)

__all__ = ["ops", "ref", "flash_attention", "kv_row_write", "model_kernels",
           "moe_gmm", "on_tpu", "ring_all_gather", "ssd_scan"]
