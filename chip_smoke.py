#!/usr/bin/env python3
"""Bring-up check on a TPU: the serving path once, at full width.

    python chip_smoke.py               # one chip: qwen2-0.5b served
    python chip_smoke.py --four-chips  # cross-chip LCX paths on 4 chips

One chip.  Builds qwen2-0.5b at its published width (24 layers,
d_model 896, 14/2 GQA heads, d_ff 4864, vocab 151936) with parameters
drawn from ``--seed`` and serves 8 requests on 4 slots through the code
that ``python -m repro.launch.serve --full`` runs: admission through the
AMT executor, prefill through the Pallas flash kernel, decode through
one batched step with a length per slot.  The 8 prompts are served
twice, cold and then warm.  Checks: every request returned 32 tokens
and none was evicted; the warm pass repeats the cold one token for
token; the prefill program holds a Mosaic kernel
(``tpu_custom_call``); its logits agree with the XLA prefill within a
bf16 tolerance.

Four chips.  The three ping-pong designs with every payload checked,
the LCX ring and pairwise collectives against the native ones bit for
bit, and the expert-parallel MoE layer over LCX against the sort
oracle, each across the 4 chips of one host.  Nothing else runs.

The script needs a TPU and exits non-zero without one, before it builds
anything.  It runs in one process, which holds the chips.  Its last
line is one JSON object naming the device; a failed check exits
non-zero and prints no such line.
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(ROOT, "src"), os.path.join(ROOT, "benchmarks")]

ARCH = "qwen2-0.5b"
SLOTS, MAX_SEQ, MAX_NEW = 4, 1024, 32
# two distinct lengths: the engine compiles one prefill program per length
PROMPT_LENS = (128, 384) * 4
# bf16 logits: the kernel and XLA round attention differently and the
# difference passes through 24 layers.  16 bf16 ulps (2^-8 each) of the
# largest reference logit; a wrong mask or head mapping moves the
# logits by their own size.
LOGIT_RTOL = 16 * 2.0 ** -8
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class Failed(Exception):
    """A check of this script did not hold."""


class CompileLog:
    """Every XLA compile request of the process, from JAX's monitoring
    events: seconds per jitted function name, and persistent-cache hits."""

    def __init__(self) -> None:
        import jax
        self.seconds = {}
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **kw) -> None:
        if event == COMPILE_EVENT:
            self.seconds.setdefault(kw.get("fun_name", "?"), []).append(secs)

    def _event(self, event, **kw) -> None:
        if event == CACHE_HIT_EVENT:
            self.cache_hits += 1

    def requests(self) -> int:
        return sum(len(v) for v in self.seconds.values())


def one_chip(seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.launch import serve
    from repro.models import prefill
    from repro.models.model import cache_batch_axes

    log = CompileLog()
    t0 = time.perf_counter()
    eng = serve.build_engine(ARCH, full=True, slots=SLOTS, max_seq=MAX_SEQ,
                             max_new=MAX_NEW, seed=seed)
    jax.block_until_ready(eng.params)
    cfg = eng.cfg
    n_params = sum(x.size for x in jax.tree.leaves(eng.params))
    print(f"model: {cfg.name} {cfg.n_layers}L d_model {cfg.d_model} heads "
          f"{cfg.n_heads}/{cfg.n_kv_heads} d_ff {cfg.d_ff} vocab "
          f"{cfg.vocab}: {n_params} parameters, built in "
          f"{time.perf_counter() - t0:.3f}s")
    if not eng.kernels or "flash_attention" not in eng.kernels:
        raise Failed("the engine was built without the Pallas flash kernel")

    prompts = serve.random_prompts(np.random.default_rng(seed), cfg.vocab,
                                   PROMPT_LENS)
    outputs = []
    for label in ("cold", "warm"):
        done, secs = serve.serve(eng, prompts)
        problems = serve.shortfalls(eng, done, len(prompts))
        problems += [f"request {r.rid} returned {len(r.output)} tokens"
                     for r in done if len(r.output) != MAX_NEW]
        if problems:
            raise Failed(f"{label} pass: " + "; ".join(problems))
        tokens = sum(len(r.output) for r in done)
        print(f"serve {label}: {len(done)} requests on {SLOTS} slots, "
              f"{tokens} tokens in {secs:.6f}s wall = "
              f"{tokens / secs:.3f} tokens/s")
        outputs.append([r.output for r in sorted(done, key=lambda r: r.rid)])
    if outputs[0] != outputs[1]:
        raise Failed("the warm pass generated other tokens than the cold")
    print(f"compile: {log.requests()} compile requests, {log.cache_hits} "
          f"loaded from the persistent cache, "
          f"{log.requests() - log.cache_hits} programs compiled")
    print(f"compile seconds: prefill {log.seconds.get('jit(prefill_slot)')} "
          f"(lengths {sorted(set(PROMPT_LENS))}), decode "
          f"{log.seconds.get('jit(decode_slots)')}")

    # the engine's Pallas prefill against the XLA prefill, same params
    axes = cache_batch_axes(cfg, eng.caches)
    slot_cache = jax.tree.map(lambda t, a: jnp.take(t, 0, axis=a),
                              eng.caches, axes)

    @jax.jit
    def xla_prefill(params, toks, cache):
        cache_b = jax.tree.map(jnp.expand_dims, cache, axes)
        lg, _ = prefill(cfg, params, toks[None], cache_b, kernels=None)
        return lg[0, -1]

    for prompt in prompts[:2]:
        program = eng.prefill_program(len(prompt))
        toks = jnp.asarray(prompt, jnp.int32)
        if "tpu_custom_call" not in program.lower(
                eng.params, toks, slot_cache).as_text():
            raise Failed(f"prefill of length {len(prompt)} holds no "
                         f"tpu_custom_call")
        got = np.asarray(program(eng.params, toks, slot_cache)[0],
                         np.float32)
        want = np.asarray(xla_prefill(eng.params, toks, slot_cache),
                          np.float32)
        diff = float(np.abs(got - want).max())
        tol = LOGIT_RTOL * float(np.abs(want).max())
        print(f"prefill logits, length {len(prompt)}: Pallas vs XLA max "
              f"|diff| {diff:.6g}, tolerance {tol:.6g}, greedy token "
              f"{int(got.argmax())} vs {int(want.argmax())}; "
              f"tpu_custom_call in HLO")
        if not np.isfinite(got).all() or diff > tol:
            raise Failed(f"prefill logits of length {len(prompt)} differ "
                         f"from XLA by {diff} > {tol}")
    peak = jax.devices()[0].memory_stats()["peak_bytes_in_use"]
    print(f"peak device memory: {peak} bytes")


def four_chips(seed: int) -> None:
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    import lcx_collectives
    import pingpong
    from repro.compat import make_mesh, shard_map
    from repro.configs.base import ModelConfig
    from repro.models import apply_model, init_model
    from repro.parallel.sharding import param_shardings, use_mesh

    n = len(jax.devices())
    if n != 4:
        raise Failed(f"--four-chips needs 4 chips, JAX found {n}")
    ring = make_mesh((n,), ("x",))

    for design in pingpong.DESIGNS:
        for lanes in (1, 8, 64):
            fn, xs = pingpong.pingpong_program(design, lanes, ring)
            bad = pingpong.received_errors(fn(xs), xs, lanes)
            print(f"pingpong {design} lanes {lanes} over {n} devices: "
                  f"{bad} of {n * lanes} payloads wrong")
            if bad:
                raise Failed(f"pingpong {design} lanes {lanes}")

    # integer-valued floats: every sum is exact, so ring and native
    # results must agree bit for bit
    rows = 1024
    x = (jnp.arange(n * rows * 128) % 251).astype(jnp.float32) \
        .reshape(n * rows, 128)
    for op in lcx_collectives.OPS:
        outs = [np.asarray(jax.jit(shard_map(
                    lcx_collectives.collective_body(op, backend), ring,
                    in_specs=P("x", None), out_specs=P("x", None)))(x))
                for backend in lcx_collectives.backends(op)]
        same = outs[0].shape == outs[1].shape and np.array_equal(
            outs[0].view(np.uint32), outs[1].view(np.uint32))
        print(f"{op} {'/'.join(lcx_collectives.backends(op))} over {n} "
              f"devices: {'bit-identical' if same else 'DIFFERENT'}")
        if not same:
            raise Failed(f"LCX {op} differs from the native collective")

    cfg = ModelConfig(name="moe-ep", family="moe", n_layers=2, d_model=64,
                      n_heads=4, n_kv_heads=4, d_ff=128, vocab=97,
                      n_experts=8, n_experts_per_tok=2, moe_d_ff=96,
                      moe_backend="lcx", capacity_factor=16.0,
                      dtype=jnp.float32, param_dtype=jnp.float32, q_block=8)
    ref_cfg = dataclasses.replace(cfg, moe_backend="sort")
    mesh = make_mesh((1, n), ("data", "model"))
    with jax.default_matmul_precision("highest"):
        params, dims = init_model(jax.random.PRNGKey(seed), cfg)
        toks = jax.random.randint(jax.random.PRNGKey(seed + 1), (4, 16),
                                  0, cfg.vocab)
        ref, _ = apply_model(ref_cfg, params, toks)
        with use_mesh(mesh):
            params_s = jax.device_put(params,
                                      param_shardings(dims, params, mesh))
            toks_s = jax.device_put(toks, NamedSharding(mesh, P("data",
                                                                None)))
            out, _ = jax.jit(lambda p, t: apply_model(cfg, p, t))(
                params_s, toks_s)
    err = float(np.abs(np.asarray(out) - np.asarray(ref)).max())
    print(f"MoE lcx EP over a model axis of {n} vs sort oracle: max "
          f"|diff| {err:.6g}, tolerance 5e-05")
    if not err < 5e-5:
        raise Failed(f"MoE EP differs from the sort oracle by {err}")


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--four-chips", action="store_true",
                   help="run only the cross-chip paths, on 4 chips")
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args()

    import jax
    from repro.launch.compile_cache import enable_compilation_cache

    cache_dir = enable_compilation_cache()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r}); "
              f"this check runs only on the chip", file=sys.stderr)
        return 2
    print(f"device: {dev.platform} {dev.device_kind}, "
          f"{len(jax.devices())} in the process; compilation cache "
          f"{cache_dir}")
    try:
        (four_chips if args.four_chips else one_chip)(args.seed)
    except Failed as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
