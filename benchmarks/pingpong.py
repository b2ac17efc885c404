"""Paper Fig. 1 reproduction: multithreaded ping-pong → concurrent-lane
ping-pong on the TPU execution model.

The paper measures aggregated unidirectional 8-byte message rate between
two nodes with 1..128 processes/threads per node.  In SPMD there are no
runtime threads; the analogue of "N threads concurrently posting
fine-grained messages" is N independent in-flight transfer lanes inside
one step (DESIGN.md §2).  We sweep lanes ∈ {1..128} and compare:

- ``mpi-like``  — one matched blocking transfer per message, serialized
  by a data-dependency chain (BSP-style single-threaded rank);
- ``lcx``       — N asynchronous lanes posted independently, one
  explicit progress (per-lane completion objects; the scheduler
  interleaves);
- ``lcx+pool``  — N lanes with packet-pool aggregation: all eager
  messages ride ONE packed transfer (doorbell batching).

Runs under ``shard_map`` over every device of the process, so transfers
lower to real ``collective-permute`` HLO ops.  With ``JAX_PLATFORMS=cpu``
the script decides, before it imports JAX, to re-run itself in a child
with ``--xla_force_host_platform_device_count=2`` fake CPU devices and
``JAX_PLATFORMS=cpu``; every row names the platform and device count it
ran on.

Reported per design: wall-clock msg rate and the number of collective
ops in the compiled HLO (the hardware-independent structural cost; on
Slingshot the paper's LCI2 ≈ LCI1 ≫ MPI ordering tracks this op count
and the serialization between ops).  Every received payload is checked.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time
from typing import Dict, List

LANES = (1, 2, 4, 8, 16, 32, 64, 128)
DESIGNS = ("mpi-like", "lcx", "lcx+pool")
FAKE_DEVICES = 2     # CPU child only; on chips every device is a rank
MSG_WORDS = 2        # 8-byte messages
REPEAT = 50


def _pingpong_body(design: str, lanes: int):
    """Per-rank body: sends ``lanes`` payloads ``x + i`` to the next rank
    and returns what it received, [1, lanes, MSG_WORDS]."""
    import jax.numpy as jnp
    import repro.core as lcx

    def body(x):
        lcx.init()
        pool = lcx.PacketPool(packet_size=1 << 16,
                              aggregate=(design == "lcx+pool"))
        dev = lcx.Device(axis="x")
        peer = lcx.Perm.shift(1)
        x = x[0]
        payloads = [x + i for i in range(lanes)]
        if design == "mpi-like":
            out = []
            carry = jnp.zeros_like(x)
            for i in range(lanes):
                sync = lcx.Synchronizer(threshold=1)
                lcx.put_x(payloads[i] + carry * 0).tag(i) \
                    .perm(peer).remote_comp(sync).device(dev) \
                    .allow_aggregation(False)()
                lcx.progress_x().pool(pool)()
                (ev,) = sync.wait()
                carry = ev.payload          # serializes the next lane
                out.append(ev.payload)
            return jnp.stack(out)[None]
        syncs = [lcx.Synchronizer(threshold=1) for _ in range(lanes)]
        for i in range(lanes):
            lcx.put_x(payloads[i]).tag(i).perm(peer) \
                .remote_comp(syncs[i]).device(dev)()
        lcx.progress_x().pool(pool)()
        return jnp.stack([s.wait()[0].payload for s in syncs])[None]

    return body


def pingpong_program(design: str, lanes: int, mesh):
    """The jitted ping-pong over the 1-D ``x`` mesh, and its input
    [ranks, MSG_WORDS] (rank r sends ``xs[r] + i`` on lane i)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from repro.compat import shard_map

    n = mesh.shape["x"]
    fn = jax.jit(shard_map(_pingpong_body(design, lanes), mesh,
                           in_specs=P("x", None),
                           out_specs=P("x", None, None)))
    xs = jnp.arange(n * MSG_WORDS, dtype=jnp.float32).reshape(n, MSG_WORDS)
    return fn, xs


def received_errors(out, xs, lanes: int) -> int:
    """Payloads that did not arrive intact: rank r must hold
    ``xs[r-1] + i`` on lane i."""
    import numpy as np
    want = np.roll(np.asarray(xs), 1, axis=0)[:, None, :] \
        + np.arange(lanes, dtype=np.float32)[None, :, None]
    return int((np.asarray(out) != want).any(axis=-1).sum())


def _chain_depth(hlo: str) -> int:
    """Longest dependency chain of collective ops in the entry
    computation — the serialization structure the paper's MPI-vs-LCI
    comparison is really about (depth=lanes: blocking/BSP; depth=1:
    fully asynchronous lanes)."""
    import re
    defs = {}
    is_coll = set()
    for line in hlo.splitlines():
        m = re.match(r"\s*(?:ROOT\s+)?%([\w.\-]+)\s*=", line)
        if not m:
            continue
        name = m.group(1)
        defs[name] = re.findall(r"%([\w.\-]+)", line)[1:]
        if re.search(r"\b(collective-permute|all-to-all)(-start)?\(",
                     line):
            is_coll.add(name)
    memo = {}

    def depth(n):
        if n in memo:
            return memo[n]
        memo[n] = 0
        d = max((depth(op) for op in defs.get(n, ())), default=0)
        memo[n] = d + (1 if n in is_coll else 0)
        return memo[n]

    return max((depth(n) for n in is_coll), default=0)


def _run_design_inproc(design: str, lanes: int) -> Dict[str, float]:
    import jax
    from repro.compat import make_mesh

    devices = jax.devices()
    mesh = make_mesh((len(devices),), ("x",))
    fn, xs = pingpong_program(design, lanes, mesh)
    compiled = fn.lower(xs).compile()
    hlo = compiled.as_text()
    n_coll = sum(hlo.count(f" {k}(") + hlo.count(f"{k}-start(")
                 for k in ("collective-permute", "all-to-all",
                           "all-gather", "all-reduce"))
    depth = _chain_depth(hlo)
    out = fn(xs)
    bad = received_errors(out, xs, lanes)
    if bad:
        raise RuntimeError(f"{design} lanes={lanes}: {bad} payloads "
                           f"arrived wrong")
    t0 = time.perf_counter()
    for _ in range(REPEAT):
        out = fn(xs)
    jax.block_until_ready(out)
    dt = (time.perf_counter() - t0) / REPEAT
    return {"design": design, "lanes": lanes,
            "platform": devices[0].platform, "devices": len(devices),
            "us_per_call": dt * 1e6, "msgs_per_s": lanes / dt,
            "hlo_collectives": n_coll, "chain_depth": depth}


def _rows() -> List[Dict[str, float]]:
    import jax
    if len(jax.devices()) < 2:
        raise RuntimeError(
            f"ping-pong needs at least 2 devices, found {jax.devices()}; "
            f"set JAX_PLATFORMS=cpu to run on fake CPU devices")
    return [_run_design_inproc(design, lanes)
            for lanes in LANES for design in DESIGNS]


def _rows_in_cpu_child() -> List[Dict[str, float]]:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        f" --xla_force_host_platform_device_count="
                        f"{FAKE_DEVICES}")
    out = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--child",
         json.dumps({"lanes": LANES, "repeat": REPEAT})],
        capture_output=True, text=True, env=env, timeout=1200)
    if out.returncode != 0:
        raise RuntimeError(out.stderr[-2000:])
    line = [l for l in out.stdout.splitlines()
            if l.startswith("PINGPONG_JSON=")][0]
    return json.loads(line[len("PINGPONG_JSON="):])


def main(out_csv: str = None) -> List[Dict[str, float]]:
    # decided before JAX is imported: a parent that touched JAX would
    # hold the chip its child needs
    if os.environ.get("JAX_PLATFORMS") == "cpu":
        rows = _rows_in_cpu_child()
    else:
        rows = _rows()

    print(f"{'design':10s} {'lanes':>6s} {'platform':>8s} {'devs':>4s} "
          f"{'us/call':>10s} {'Mmsg/s':>8s} {'n_coll':>7s} {'depth':>6s}")
    for r in rows:
        print(f"{r['design']:10s} {r['lanes']:6d} {r['platform']:>8s} "
              f"{r['devices']:4d} {r['us_per_call']:10.1f} "
              f"{r['msgs_per_s']/1e6:8.3f} {r['hlo_collectives']:7d} "
              f"{r['chain_depth']:6d}")
    if out_csv:
        import csv
        with open(out_csv, "w", newline="") as f:
            w = csv.DictWriter(f, fieldnames=list(rows[0]))
            w.writeheader()
            w.writerows(rows)
    return rows


if __name__ == "__main__":
    if sys.argv[1:2] == ["--child"]:
        opts = json.loads(sys.argv[2])
        LANES, REPEAT = tuple(opts["lanes"]), opts["repeat"]
        print("PINGPONG_JSON=" + json.dumps(_rows()))
    else:
        main()
