"""Where one step of the serving engine's decode goes on the chip.

    python benchmarks/decode_split.py [--arch qwen2-0.5b] [--slots 64] \
        [--max-seq 2048] [--steps 200] [--seed 7] [--out FILE]

Builds ``jit(decode_slots)`` (`repro.serving.engine.make_decode_fn`, the
cache donated, `model_kernels(cfg)` as the engine gets them on a TPU)
at the architecture's published widths with random weights, times
``--steps`` steps on the host clock after a warm-up, then traces 20
more and splits their device time by the named scope each operation
comes from in the compiled program:

- ``row_write``: the ``kv_row_write`` scope of ``decode_step``, the new
  K/V rows written into the pool (the kernel, or the block write);
- ``kv_read``: the ``kv_read`` scope of the attention, the dots that
  read the pool;
- ``rest``: everything else (weights' dots, norms, sampling inputs).

Prints one JSON line: device, sizes, ``step_ms`` (host clock), the
split in ms a step (device trace), the program's temporaries, and the
ten operations that took most time.  Needs a TPU and exits 2 without
one: on another backend the numbers would not be the chip's.
"""
from __future__ import annotations

import argparse
import collections
import glob
import json
import os
import re
import sys
import tempfile
import time
from typing import Dict, Iterable, List, Tuple

TRACED_STEPS = 20


def _inst(event_name: str) -> str:
    """The instruction an operation event names, alone or as the head of
    its HLO text (``%fusion.12 = ...``)."""
    return re.match(r"%?([\w.\-]+)", event_name).group(1)


def scope_of_ops(hlo_text: str) -> Dict[str, str]:
    """Instruction name -> the split class of its ``op_name``, for every
    instruction of a compiled program's HLO text that is not a loop or
    a call (whose time is its body's)."""
    out = {}
    for line in hlo_text.splitlines():
        # the opcode is the first lower-case word before a "(": a TPU
        # layout's tiles, ``{1,0:T(8,128)(2,1)}``, are upper-case
        m = re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = .*?\b([a-z][\w\-]*)\(",
                     line)
        if not m or m.group(2) in ("while", "call", "conditional"):
            continue
        op = re.search(r'op_name="([^"]*)"', line)
        name = op.group(1) if op else ""
        out[m.group(1)] = ("row_write" if "/kv_row_write" in name
                           else "kv_read" if "/kv_read" in name
                           else "rest")
    return out


def split(events: Iterable[Tuple[str, float]],
          classes: Dict[str, str]) -> Dict[str, float]:
    """Seconds by class of (operation event name, seconds) events; the
    events of loops and calls, and of no instruction in ``classes``, are
    left out."""
    out = collections.defaultdict(float)
    for name, secs in events:
        inst = _inst(name)
        if inst in classes:
            out[classes[inst]] += secs
    return dict(out)


def _device_ops(trace_dir: str) -> List[Tuple[str, float]]:
    from jax.profiler import ProfileData
    path = max(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                         recursive=True), key=os.path.getmtime)
    pd = ProfileData.from_file(path)
    evs = []
    for plane in pd.planes:
        if re.match(r"^/device:TPU:0$", plane.name):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    evs += [(e.name, (e.end_ns - e.start_ns) * 1e-9)
                            for e in line.events]
    return evs


def main(argv: List[str]) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--slots", type=int, default=64)
    ap.add_argument("--max-seq", type=int, default=2048)
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np
    if jax.default_backend() != "tpu":
        print(f"decode_split needs a TPU; JAX runs on "
              f"{jax.default_backend()!r}", file=sys.stderr)
        return 2
    from repro.configs.base import get_config
    from repro.kernels import model_kernels
    from repro.models import init_cache, init_model
    from repro.serving.engine import make_decode_fn

    cfg = get_config(args.arch)
    b, s = args.slots, args.max_seq
    key = jax.random.PRNGKey(args.seed & 0x7FFFFFFF)
    params = jax.jit(lambda k: init_model(k, cfg)[0])(key)
    caches = jax.jit(lambda: init_cache(cfg, b, s))()
    rng = np.random.default_rng(args.seed)
    lengths = jnp.asarray(rng.integers(0, s - 1, b), jnp.int32)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab, (b, 1)), jnp.int32)
    stats: Dict[str, object] = {}
    fn = make_decode_fn(cfg, model_kernels(cfg), stats)
    compiled = jax.jit(fn, donate_argnums=(2,)).lower(
        params, tokens, caches, lengths).compile()
    for _ in range(5):
        lg, caches = compiled(params, tokens, caches, lengths)
    lg.block_until_ready()
    t0 = time.perf_counter()
    for _ in range(args.steps):
        lg, caches = compiled(params, tokens, caches, lengths)
    lg.block_until_ready()
    step_ms = (time.perf_counter() - t0) * 1e3 / args.steps

    with tempfile.TemporaryDirectory() as d:
        with jax.profiler.trace(d):
            for _ in range(TRACED_STEPS):
                lg, caches = compiled(params, tokens, caches, lengths)
            lg.block_until_ready()
        ops = _device_ops(d)
    classes = scope_of_ops(compiled.as_text())
    by_class = split(ops, classes)
    per_op = collections.defaultdict(float)
    for name, secs in ops:
        inst = _inst(name)
        per_op[f"{classes.get(inst, 'loop')}:{inst}"] += secs
    top = sorted(per_op.items(), key=lambda kv: -kv[1])[:10]
    dev = jax.devices()[0]
    row = {
        "device": dev.device_kind, "arch": args.arch, "slots": b,
        "max_seq": s, "steps": args.steps, "step_ms": step_ms,
        "split_ms": {k: v * 1e3 / TRACED_STEPS
                     for k, v in sorted(by_class.items())},
        "traced_ms": sum(by_class.values()) * 1e3 / TRACED_STEPS,
        "kv_row_write": stats.get("kv_row_write"),
        "temp_bytes": compiled.memory_analysis().temp_size_in_bytes,
        "top_ops_ms": [[k, v * 1e3 / TRACED_STEPS] for k, v in top],
    }
    line = json.dumps(row)
    print(line)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "a") as f:
            f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src"))
    sys.exit(main(sys.argv[1:]))
