"""MoE expert-parallel dispatch microbenchmark (the paper's fine-grained
asynchronous a2a pattern, LCX-routed).

Sweeps token counts through the sort-based capacity dispatch + EP
all-to-all over a ``model`` axis of every device in the process, and
reports tokens/s.  With ``JAX_PLATFORMS=cpu`` the script decides, before
it imports JAX, to re-run itself in a child on 2 fake CPU devices
(``JAX_PLATFORMS=cpu`` set explicitly, as in ``pingpong.py``); every row
names the platform and device count it ran on.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time
from typing import Dict, List

TOKENS = (256, 1024, 4096)
FAKE_DEVICES = 2     # CPU child only; on chips every device is a rank


def _run_inproc(n_tokens: int, a2a_backend: str) -> Dict[str, float]:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.configs.base import ModelConfig
    from repro.models.moe import moe_init, moe_apply
    from repro.parallel.sharding import use_mesh, param_shardings
    from repro.compat import make_mesh

    devices = jax.devices()
    mesh = make_mesh((1, len(devices)), ("data", "model"))
    cfg = ModelConfig(name="bench", family="moe", n_layers=1, d_model=128,
                      n_heads=2, n_kv_heads=2, d_ff=256, vocab=64,
                      n_experts=8, n_experts_per_tok=2, moe_d_ff=256,
                      moe_backend="lcx", capacity_factor=1.25,
                      dtype=jnp.float32, param_dtype=jnp.float32)
    cfg.moe_a2a = a2a_backend      # LCX a2a lowering knob
    params, dims = moe_init(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1),
                          (1, n_tokens, 128), jnp.float32)
    with use_mesh(mesh):
        psh = param_shardings(dims, params, mesh)
        params_s = jax.device_put(params, psh)
        x_s = jax.device_put(x, NamedSharding(mesh, P(None, "model",
                                                      None)))
        fn = jax.jit(lambda p, t: moe_apply(cfg, p, t)[0])
        out = fn(params_s, x_s)
        jax.block_until_ready(out)
        t0 = time.perf_counter()
        for _ in range(10):
            out = fn(params_s, x_s)
        jax.block_until_ready(out)
        dt = (time.perf_counter() - t0) / 10
    return {"tokens": n_tokens, "a2a": a2a_backend,
            "platform": devices[0].platform, "devices": len(devices),
            "us_per_call": dt * 1e6, "tokens_per_s": n_tokens / dt}


def _rows() -> List[Dict[str, float]]:
    import jax
    if len(jax.devices()) < 2:
        raise RuntimeError(
            f"EP dispatch needs at least 2 devices, found {jax.devices()}; "
            f"set JAX_PLATFORMS=cpu to run on fake CPU devices")
    return [_run_inproc(t, backend)
            for t in TOKENS for backend in ("native", "pairwise")]


def _rows_in_cpu_child() -> List[Dict[str, float]]:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        f" --xla_force_host_platform_device_count="
                        f"{FAKE_DEVICES}")
    out = subprocess.run([sys.executable, os.path.abspath(__file__),
                          "--child"],
                         capture_output=True, text=True, env=env,
                         timeout=1200)
    if out.returncode != 0:
        raise RuntimeError(out.stderr[-2000:])
    line = [l for l in out.stdout.splitlines()
            if l.startswith("MOEDISPATCH_JSON=")][0]
    return json.loads(line[len("MOEDISPATCH_JSON="):])


def main(out_csv: str = None) -> List[Dict[str, float]]:
    # decided before JAX is imported: a parent that touched JAX would
    # hold the chip its child needs
    if os.environ.get("JAX_PLATFORMS") == "cpu":
        rows = _rows_in_cpu_child()
    else:
        rows = _rows()
    print(f"{'tokens':>7s} {'a2a':9s} {'platform':>8s} {'devs':>4s} "
          f"{'us/call':>10s} {'Mtok/s':>8s}")
    for r in rows:
        print(f"{r['tokens']:7d} {r['a2a']:9s} {r['platform']:>8s} "
              f"{r['devices']:4d} {r['us_per_call']:10.1f} "
              f"{r['tokens_per_s']/1e6:8.3f}")
    if out_csv:
        import csv
        with open(out_csv, "w", newline="") as f:
            w = csv.DictWriter(f, fieldnames=list(rows[0]))
            w.writeheader()
            w.writerows(rows)
    return rows


if __name__ == "__main__":
    if sys.argv[1:2] == ["--child"]:
        print("MOEDISPATCH_JSON=" + json.dumps(_rows()))
    else:
        main()
