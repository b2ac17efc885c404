"""LCX p2p-built collectives vs native XLA — structural cost table.

For each collective (all-gather / reduce-scatter / all-reduce /
all-to-all) and backend (lcx ring|pairwise vs native), report wall time
(vmap-emulated ranks on CPU) and the LCX device/pool statistics (number
of p2p transfers, bytes moved) — the schedule the ring algorithms post.
"""
from __future__ import annotations

import time
from typing import Dict, List

import jax
import jax.numpy as jnp

import repro.core as lcx

N = 8
SIZE = 1 << 14       # elements per rank
REPEAT = 20


OPS = ("all_gather", "reduce_scatter", "all_reduce", "all_to_all")


def backends(op: str) -> tuple:
    """(LCX backend, native) pair compared for ``op``."""
    return ("pairwise", "native") if op == "all_to_all" \
        else ("ring", "native")


def collective_body(op: str, backend: str, stats: Dict = None):
    """Per-rank body over axis ``x`` (under vmap or shard_map); the
    device's transfer statistics land in ``stats``."""
    fn = {"all_gather": lcx.all_gather,
          "reduce_scatter": lcx.reduce_scatter,
          "all_reduce": lcx.all_reduce, "all_to_all": lcx.all_to_all}[op]

    def body(x):
        lcx.init()
        dev = lcx.Device(axis="x")
        out = fn(x, device=dev, backend=backend)
        if stats is not None:
            stats.update(dev.stats)
        return out

    return body


def bench(op: str, backend: str) -> Dict[str, float]:
    stats = {}
    body = collective_body(op, backend, stats)
    xs = jnp.arange(float(N * SIZE)).reshape(N, SIZE)
    fn = jax.jit(jax.vmap(body, axis_name="x"))
    out = fn(xs)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(REPEAT):
        out = fn(xs)
    jax.block_until_ready(out)
    dt = (time.perf_counter() - t0) / REPEAT
    return {"op": op, "backend": backend, "us_per_call": dt * 1e6,
            "p2p_transfers": stats.get("transfers", 0),
            "bytes_per_rank": stats.get("bytes_moved", 0)}


def main(out_csv: str = None) -> List[Dict[str, float]]:
    rows = []
    print(f"{'op':16s} {'backend':9s} {'us/call':>10s} "
          f"{'p2p':>5s} {'KiB/rank':>9s}")
    for op in OPS:
        for backend in backends(op):
            r = bench(op, backend)
            rows.append(r)
            print(f"{r['op']:16s} {r['backend']:9s} "
                  f"{r['us_per_call']:10.1f} {r['p2p_transfers']:5d} "
                  f"{r['bytes_per_rank']/1024:9.1f}")
    if out_csv:
        import csv
        with open(out_csv, "w", newline="") as f:
            w = csv.DictWriter(f, fieldnames=list(rows[0]))
            w.writeheader()
            w.writerows(rows)
    return rows


if __name__ == "__main__":
    main()
