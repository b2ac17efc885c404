"""Serving driver: continuous-batching engine over a registry model.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-0.5b \
        --requests 16 --max-new 24
    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-0.5b --full

Without ``--full`` the arch's smoke config runs (CPU-sized).  On the
TPU backend prefill runs the Pallas kernels (`repro.kernels.model_kernels`);
elsewhere the model's own XLA attention.  Exits 1 when any request was
evicted or finished short of the tokens it asked for.
"""
import argparse
import sys
import time
from typing import List, Optional, Sequence, Tuple

import jax
import numpy as np

from repro.configs.base import get_config, get_smoke_config
from repro.kernels import model_kernels, on_tpu
from repro.launch.compile_cache import enable_compilation_cache
from repro.models import init_model
from repro.serving import Request, ServeConfig, ServingEngine


def build_engine(arch: str, *, full: bool, slots: int, max_seq: int,
                 max_new: int, temperature: float = 0.0,
                 seed: int = 0) -> ServingEngine:
    """Engine for ``arch`` with parameters initialised from ``seed``."""
    cfg = get_config(arch) if full else get_smoke_config(arch)
    if cfg.family == "audio":
        raise SystemExit("encoder-only architectures have no decode path")
    # one program builds every parameter on the device
    params = jax.jit(lambda key: init_model(key, cfg)[0])(
        jax.random.PRNGKey(seed))
    kernels = model_kernels(cfg) if on_tpu() else None
    return ServingEngine(cfg, params, ServeConfig(
        n_slots=slots, max_seq=max_seq, max_new_tokens=max_new,
        temperature=temperature, seed=seed), kernels=kernels)


def random_prompts(rng: np.random.Generator, vocab: int,
                   lengths: Sequence[int]) -> List[np.ndarray]:
    return [rng.integers(0, vocab, size=n).astype(np.int32)
            for n in lengths]


def serve(eng: ServingEngine, prompts: Sequence[np.ndarray]
          ) -> Tuple[List[Request], float]:
    """Submit ``prompts`` and drain the engine.  Returns the requests
    that finished in this call and its wall seconds."""
    start = len(eng.finished)
    t0 = time.perf_counter()
    for i, prompt in enumerate(prompts):
        eng.submit(Request(rid=start + i, prompt=prompt))
    eng.run_until_drained()
    return eng.finished[start:], time.perf_counter() - t0


def shortfalls(eng: ServingEngine, done: Sequence[Request],
               n_submitted: int) -> List[str]:
    """What went wrong in a :func:`serve` call: evicted requests,
    requests that never finished, outputs short of their token budget."""
    problems = [f"request {r.rid} evicted: {r.error}" for r in eng.failed]
    if len(done) < n_submitted:
        problems.append(f"{n_submitted - len(done)} of {n_submitted} "
                        f"requests never finished")
    eos = eng.scfg.eos_token
    for r in done:
        want = r.max_new_tokens or eng.scfg.max_new_tokens
        stopped = eos is not None and r.output and r.output[-1] == eos
        if r.error is None and len(r.output) < want and not stopped:
            problems.append(f"request {r.rid} returned {len(r.output)} of "
                            f"{want} tokens")
    return problems


def main(argv: Optional[Sequence[str]] = None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--arch", required=True)
    p.add_argument("--full", action="store_true",
                   help="the published config instead of the smoke one "
                        "(one TPU chip holds qwen2-0.5b)")
    p.add_argument("--requests", type=int, default=8)
    p.add_argument("--slots", type=int, default=4)
    p.add_argument("--max-seq", type=int, default=256)
    p.add_argument("--max-new", type=int, default=16)
    p.add_argument("--temperature", type=float, default=0.0)
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args(argv)

    enable_compilation_cache()
    eng = build_engine(args.arch, full=args.full, slots=args.slots,
                       max_seq=args.max_seq, max_new=args.max_new,
                       temperature=args.temperature, seed=args.seed)
    rng = np.random.default_rng(args.seed)
    lengths = [int(rng.integers(4, 12)) for _ in range(args.requests)]
    done, dt = serve(eng, random_prompts(rng, eng.cfg.vocab, lengths))
    total_tokens = sum(len(r.output) for r in done)
    print(f"served {len(done)} requests, {total_tokens} tokens in "
          f"{dt:.2f}s ({total_tokens/dt:.1f} tok/s); stats={eng.stats}")
    for r in done[:4]:
        print(f"  rid={r.rid} prompt={list(r.prompt)[:6]}... "
              f"output={r.output}")
    problems = shortfalls(eng, done, args.requests)
    for line in problems:
        print(f"serve: {line}", file=sys.stderr)
    return 1 if problems else 0


if __name__ == "__main__":
    sys.exit(main())
