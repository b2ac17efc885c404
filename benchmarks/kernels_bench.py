"""Per-kernel correctness/latency table: Pallas (interpret on CPU — a
correctness proxy, not TPU timing) vs the pure-XLA oracle.  The TPU
story for each kernel is in EXPERIMENTS.md §Roofline (VMEM working sets
and MXU-aligned block shapes from the BlockSpecs)."""
from __future__ import annotations

import time
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops, ref as kref


def _time(fn, *args, repeat=3):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(repeat):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / repeat, out


def bench_flash() -> Dict[str, float]:
    b, hq, hkv, s, d = 1, 8, 2, 512, 64
    q = jax.random.normal(jax.random.PRNGKey(0), (b, hq, s, d))
    k = jax.random.normal(jax.random.PRNGKey(1), (b, hkv, s, d))
    v = jax.random.normal(jax.random.PRNGKey(2), (b, hkv, s, d))
    t_ref, o_ref = _time(jax.jit(
        lambda *a: kref.flash_attention_ref(*a, causal=True)), q, k, v)
    t_pal, o_pal = _time(jax.jit(
        lambda *a: ops.flash_attention(*a, causal=True,
                                       backend="interpret")), q, k, v)
    err = float(jnp.abs(o_ref - o_pal).max())
    return {"kernel": "flash_attention", "xla_us": t_ref * 1e6,
            "pallas_interp_us": t_pal * 1e6, "max_err": err}


def bench_ssd() -> Dict[str, float]:
    b, s, h, p, n = 1, 512, 4, 32, 16
    key = jax.random.PRNGKey(3)
    x = jax.random.normal(key, (b, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(jax.random.fold_in(key, 1),
                                           (b, s, h)))
    A = -jnp.exp(jax.random.normal(jax.random.fold_in(key, 2), (h,)))
    Bm = jax.random.normal(jax.random.fold_in(key, 3), (b, s, h, n))
    Cm = jax.random.normal(jax.random.fold_in(key, 4), (b, s, h, n))
    t_ref, (y_ref, _) = _time(jax.jit(
        lambda *a: ops.ssd_scan(*a, chunk=128, backend="xla")),
        x, dt, A, Bm, Cm)
    t_pal, (y_pal, _) = _time(jax.jit(
        lambda *a: ops.ssd_scan(*a, chunk=128, backend="interpret")),
        x, dt, A, Bm, Cm)
    err = float(jnp.abs(y_ref - y_pal).max())
    return {"kernel": "ssd_scan", "xla_us": t_ref * 1e6,
            "pallas_interp_us": t_pal * 1e6, "max_err": err}


def bench_gmm() -> Dict[str, float]:
    e, c, d, f = 8, 256, 256, 512
    xb = jax.random.normal(jax.random.PRNGKey(0), (e, c, d))
    w = jax.random.normal(jax.random.PRNGKey(1), (e, d, f))
    t_ref, o_ref = _time(jax.jit(kref.moe_gmm_ref), xb, w)
    t_pal, o_pal = _time(jax.jit(
        lambda *a: ops.moe_gmm(*a, backend="interpret")), xb, w)
    err = float(jnp.abs(o_ref - o_pal).max() / jnp.abs(o_ref).max())
    return {"kernel": "moe_gmm", "xla_us": t_ref * 1e6,
            "pallas_interp_us": t_pal * 1e6, "max_err": err}


def main(out_csv: str = None) -> List[Dict[str, float]]:
    rows = [bench_flash(), bench_ssd(), bench_gmm()]
    print(f"{'kernel':18s} {'xla_us':>10s} {'interp_us':>11s} "
          f"{'max_err':>9s}")
    for r in rows:
        print(f"{r['kernel']:18s} {r['xla_us']:10.1f} "
              f"{r['pallas_interp_us']:11.1f} {r['max_err']:9.2e}")
    if out_csv:
        import csv
        with open(out_csv, "w", newline="") as f:
            w = csv.DictWriter(f, fieldnames=list(rows[0]))
            w.writeheader()
            w.writerows(rows)
    return rows


if __name__ == "__main__":
    main()
