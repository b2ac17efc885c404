"""Cells of a served model: the engine of ``repro.serving``, built as
``repro.launch.serve.build_engine`` builds it, with the weights this
benchmark draws from the seed.

Set-up builds the weights in one jitted call, builds the engine, and
serves one short request per prompt length of the cell's traffic, so
every program the window runs is compiled (or loaded from the
persistent cache) before it opens.  The window then drives
``ServingEngine.tick()`` and stamps each request's tokens, from outside,
at the end of the tick that produced them.

``correct`` compares the tokens the window served with the float32
reference of the configuration's architecture (``bench/archs/<arch>.py``,
named by its ``"arch"``): a sample of finished requests, drawn from the
seed and always holding the longest, is run through the reference once
the engine is freed, and the widest gap by which a served token's
reference logit lies below the reference's best must stay under the
cell's limit (``bench/limits/<workload>.json``).  Every request must
also finish with all the tokens it asked for.
"""
from __future__ import annotations

import gc
import importlib
import os
import time
from typing import Any, Dict, List, Tuple

import numpy as np

from bench import archs, harness, loadgen
from bench.harness import Req, Run, Tick, now, span

# weights drawn from the seed get this stream; the sample of requests
# checked gets another
CHECK_STREAM = 0x5EED


def load_arch(config: Dict[str, Any], source: str):
    """(the module ``bench/archs/<arch>.py`` that the configuration's
    ``"arch"`` names, its ``Spec`` of the configuration); ``source`` is
    the configuration's file, named in the error when there is none."""
    name = config.get("arch")
    known = sorted(f[:-3] for f in os.listdir(os.path.dirname(
        archs.__file__)) if f.endswith(".py") and f != "__init__.py")
    if name not in known:
        raise ValueError(f"{source}: \"arch\" is {name!r}; it has to name "
                         f"a module of bench/archs/: {known}")
    arch = importlib.import_module(f"{archs.__name__}.{name}")
    return arch, arch.Spec.from_config(config)


def load_model(config: Dict[str, Any], source: str, seed: int):
    """(arch, spec, the program's ``ModelConfig``, its parameters holding
    the weights drawn from ``seed``) for a served configuration."""
    arch, spec = load_arch(config, source)
    cfg = arch.program_config(spec, config["serve"])
    params = arch.program_params(spec, arch.make_weights(spec, seed))
    check_layout(cfg, params)
    return arch, spec, cfg, params


def check_layout(cfg, params) -> None:
    """The tree must be the one ``init_model`` builds, leaf for leaf."""
    import jax
    from repro.models import init_model
    want = jax.eval_shape(lambda k: init_model(k, cfg)[0],
                          jax.random.PRNGKey(0))
    got = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                       params)
    if jax.tree.structure(want) != jax.tree.structure(got) or \
            jax.tree.leaves(want) != jax.tree.leaves(got):
        raise ValueError(f"weights do not match the program's layout: "
                         f"{got} vs {want}")


def build(cfg, params, serve: Dict[str, Any], max_new: int, seed: int,
          kernels: Any):
    """``serve.build_engine`` with the given weights."""
    from repro.kernels import model_kernels, on_tpu
    from repro.serving import ServeConfig, ServingEngine
    if kernels is None and on_tpu():
        kernels = model_kernels(cfg)
    return ServingEngine(cfg, params, ServeConfig(
        n_slots=serve["n_slots"], max_seq=serve["max_seq"],
        max_new_tokens=max_new, temperature=0.0,
        seed=seed & 0x7FFFFFFF), kernels=kernels)


class Driver:
    """Submits, ticks and stamps; keeps the run's records."""

    def __init__(self, eng, run: Run) -> None:
        self.eng, self.run = eng, run
        self.live: Dict[int, Tuple[Any, Req]] = {}
        self.pairs: List[Tuple[Any, Req]] = []    # every request sent
        self.next_rid = 0
        self.tracing = False

    def submit(self, p: loadgen.Planned, arrival: float) -> None:
        from repro.serving import Request
        rec = Req(arrival, len(p.prompt), p.max_new)
        req = Request(rid=self.next_rid, prompt=p.prompt,
                      max_new_tokens=p.max_new)
        self.next_rid += 1
        self.eng.submit(req)
        self.live[req.rid] = (req, rec)
        self.pairs.append((req, rec))
        self.run.requests.append(rec)

    def busy(self) -> bool:
        return bool(self.live)

    def tick(self) -> float:
        t0 = now()
        with span("tick"):
            self.eng.tick()
        t1 = now()
        tick = Tick(t0, t1, [], 0, 0, self.tracing)
        for rid, (req, rec) in list(self.live.items()):
            n = len(req.output)
            k = n - rec.n_out
            if k:
                rec.token_times += [t1] * k
                decoded = k - 1 if rec.n_out == 0 else k
                if rec.n_out == 0:
                    tick.prefill_lens.append(rec.prompt_len)
                if decoded:
                    tick.decoded += decoded
                    tick.kv_rows += rec.prompt_len + n - 1
                rec.n_out = n
            if req.done:
                rec.done = True
                rec.failed = req.error is not None or n < rec.max_new
                del self.live[rid]
        self.run.ticks.append(tick)
        return t1


def warm(eng, lengths: List[int], vocab: int) -> None:
    """Serve one two-token request per prompt length: compiles (or loads)
    every prefill program, the decode program and the eager slot copies
    the window will run, and no other."""
    from repro.serving import Request
    rng = np.random.default_rng(0)
    for i, n in enumerate(lengths):
        eng.submit(Request(rid=-1 - i, prompt=rng.integers(
            0, vocab, size=n).astype(np.int32), max_new_tokens=2))
    eng.run_until_drained()
    if eng.failed:
        raise RuntimeError(f"warm-up request failed: {eng.failed[0].error}")
    eng.finished.clear()
    eng.stats.update(ticks=0, prefills=0, decoded_tokens=0, evictions=0)


def window(drv: Driver, mix: Dict[str, Any], planned, seconds: float,
           tracer: harness.Tracer) -> None:
    run = drv.run
    t0 = now()
    end = t0 + seconds
    run.window_t0 = t0
    if mix["kind"] == "open_loop":
        arr = [t0 + p.arrival for p in planned]
        i, n = 0, len(planned)
        while True:
            t = now()
            if t >= end:
                break
            drv.tracing = tracer.poll(t - t0)
            if i < n and arr[i] <= t:
                with span("submit"):
                    while i < n and arr[i] <= t:
                        drv.submit(planned[i], arr[i])
                        i += 1
            if drv.busy():
                run.window_t1 = drv.tick()
            else:
                with span("wait_arrival"):
                    time.sleep(max(0.0, min(arr[i] if i < n else end, end)
                                   - now()))
        tracer.stop()
        drv.tracing = False
        for j in range(i, n):             # due in the window, not yet sent
            drv.submit(planned[j], arr[j])
        run.window_t1 = max(run.window_t1, end)
        stop = now() + mix["drain_s"]
        while drv.busy() and now() < stop:
            drv.tick()
        for _, rec in drv.live.values():  # never came
            rec.failed = True
    else:                                  # closed loop
        backlog, pool = mix["backlog"], planned
        k = drv.next_rid
        while True:
            t = now()
            if t >= end:
                break
            drv.tracing = tracer.poll(t - t0)
            with span("submit"):
                while len(drv.eng.queue) < backlog:
                    drv.submit(pool[k % len(pool)], now())
                    k += 1
            run.window_t1 = drv.tick()
        tracer.stop()
        drv.tracing = False


def sample_checked(run: Run, outputs: Dict[int, Tuple[np.ndarray, List[int]]],
                   seed: int, tokens: int) -> List[int]:
    """Request indices to check: the longest finished one, then others
    drawn from the seed until ``tokens`` served tokens are covered."""
    ok = [i for i in outputs
          if run.requests[i].done and not run.requests[i].failed]
    if not ok:
        return []
    longest = max(ok, key=lambda i: run.requests[i].prompt_len
                  + run.requests[i].n_out)
    rng = np.random.default_rng([seed, CHECK_STREAM])
    picked, served = [longest], run.requests[longest].n_out
    for i in rng.permutation([i for i in ok if i != longest]):
        if served >= tokens:
            break
        picked.append(int(i))
        served += run.requests[int(i)].n_out
    return picked


def widest_gap(arch, spec, seed: int, max_seq: int,
               checked: List[Tuple[np.ndarray, List[int]]],
               fp8: bool = False) -> Tuple[float, int]:
    """(widest gap over every served token checked, tokens checked)."""
    import jax.numpy as jnp
    w = arch.make_weights(spec, seed)
    fn = arch.gaps_program(spec, fp8=fp8)
    widest, count = 0.0, 0
    for prompt, out in checked:
        seq = np.concatenate([prompt, np.asarray(out[:-1], np.int32)])
        toks = np.zeros(max_seq, np.int32)
        toks[:len(seq)] = seq
        tgt = np.zeros(max_seq, np.int32)
        lo = len(prompt) - 1
        tgt[lo:lo + len(out)] = out
        g = np.asarray(fn(w, jnp.asarray(toks), jnp.asarray(tgt)))
        widest = max(widest, float(g[lo:lo + len(out)].max()))
        count += len(out)
    return widest, count


def run_cell(cell: harness.Cell, seed: int, seconds: float, trace: bool,
             devs, limits: Dict[str, float], tag: str, t_start: float,
             compiles: harness.CompileLog) -> harness.Outcome:
    """One run of a serving cell: set-up, window, check."""
    serve, mix = cell.config["serve"], cell.mix
    if loadgen.longest(mix) > serve["max_seq"] - 1:
        raise ValueError(f"the mix fills {loadgen.longest(mix)} positions, "
                         f"max_seq is {serve['max_seq']}")
    arch, spec, cfg, params = load_model(cell.config, cell.config_file, seed)
    run = Run(cell.name, cell.config, mix, devs[0].device_kind, len(devs),
              spec=spec, arch=arch)
    if mix["kind"] == "open_loop":
        planned = loadgen.open_loop(mix, seconds, seed, spec.vocab_size)
    else:
        planned = loadgen.closed_loop(mix, seed, spec.vocab_size)
    harness.log(tag, "traffic: " + loadgen.describe(planned))

    drv = Driver(build(cfg, params, serve, max(p.max_new for p in planned),
                       seed, None), run)
    del params
    lengths = sorted({len(p.prompt) for p in planned})
    warm(drv.eng, lengths, spec.vocab_size)
    harness.log(tag, f"warmed {len(lengths)} prompt lengths "
                     f"{lengths[0]}..{lengths[-1]} and the decode program "
                     f"({serve['n_slots']} slots, max_seq {serve['max_seq']})")
    if mix["kind"] == "closed_loop":      # the standing backlog, admitted
        for p in planned[:mix["backlog"]]:
            drv.submit(p, now())
        drv.tick()

    before = compiles.compiles
    tracer = harness.Tracer(trace, mix["trace_lead_s"], mix["trace_s"])
    window(drv, mix, planned, seconds, tracer)
    run.setup_s = run.window_t0 - t_start
    harness.log(tag, f"compiles inside the window: "
                     f"{compiles.compiles - before}")
    late = [rec.token_times[0] - rec.arrival for rec in run.requests
            if rec.token_times]
    harness.log(tag, f"window {run.window_s:.6f}s: {len(run.requests)} "
                     f"requests, {len(run.ticks)} ticks, "
                     f"{sum(len(r.token_times) for r in run.requests)} "
                     f"tokens; first-token wait max "
                     f"{max(late) if late else 0:.6f}s")
    peak = harness.memory_peak(devs)
    run.trace = tracer.read()

    served = [(np.asarray(req.prompt), list(req.output))
              for req, _ in drv.pairs]
    failed = sum(rec.failed for rec in run.requests)
    attempted = sum(rec.done or rec.failed for rec in run.requests)
    drv.eng = drv.live = drv.pairs = None
    gc.collect()
    picked = sample_checked(run, dict(enumerate(served)), seed,
                            mix["check_tokens"])
    t = now()
    checked = [served[i] for i in picked]
    gap, n_tok = widest_gap(arch, spec, seed, serve["max_seq"], checked)
    harness.log(tag, f"reference: {len(picked)} requests, {n_tok} served "
                     f"tokens checked in {now() - t:.3f}s")
    checks = {"logit_gap": {"value": gap, "limit": limits["logit_gap"]},
              "failed_requests": {"value": failed, "limit": 0}}
    return harness.Outcome(run, checks, attempted, failed, peak, checked)
