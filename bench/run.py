#!/usr/bin/env python3
"""Run one cell of the benchmark once, on the chips of this machine.

    python3 bench/run.py --workload qwen2-0.5b.chat --seed 7 \
        --seconds 40 --trace 0

The cell (``BENCHMARK.json``'s ``workloads``) names a configuration
(``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<traffic>.json``); its correctness limits are in
``bench/limits/<workload>.json`` and each metric's reader in
``bench/metrics/<metric>.py``.  With ``--trace 0`` the result holds the
cell's end-to-end metrics; with ``--trace 1`` its per-layer metrics,
read from a profiler trace of part of the window.

The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
``breakdown``, and last the ``checks``: each number compared beside its
limit, which also close standard error).  Without a TPU, or with fewer
chips than the cell asks for, it exits 2 and prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def execute(cell, devs, seed: int, seconds: float, trace: bool,
            limits, t_start: float) -> str:
    """One run of ``cell`` on ``devs``; returns the result line."""
    import jax
    from bench import harness, ring, serving, tracing
    from repro.launch.compile_cache import enable_compilation_cache

    cache_dir = enable_compilation_cache()
    # the decode program compiles in under a second: cache it too
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    compiles = harness.CompileLog()
    tag = harness.device_tag(devs)
    harness.log(tag, f"cell {cell.name}, seed {seed}, {seconds}s, trace "
                     f"{int(trace)}; compilation cache {cache_dir}")
    driver = harness.by_kind(cell, {"causal_lm": serving,
                                    "deployment": ring})
    out = driver.run_cell(cell, seed, seconds, trace, devs, limits, tag,
                          t_start, compiles)
    run, checks = out.run, out.checks
    harness.log(tag, f"set-up {run.setup_s:.6f}s; compiles in the process "
                     f"{compiles.compiles} ({compiles.cache_hits} from the "
                     f"persistent cache)")
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": out.memory_peak}
    breakdown = None
    if trace:
        device["busy_s"] = tracing.mean_busy_s(run.trace)
        device["window_s"] = run.trace.window_s
        breakdown = tracing.breakdown(run.trace)
    harness.print_checks(checks)
    return harness.result_line(
        correct=harness.judge(checks), attempted=out.attempted,
        failed=out.failed, metrics=harness.metric_values(run, cell.metrics), device=device,
        checks=checks, breakdown=breakdown)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)

    from bench import harness
    cell = harness.find_cell(args.workload, bool(args.trace))
    limits = harness.load_json(os.path.join(
        harness.BENCH_DIR, "limits", args.workload + ".json"))
    try:
        devs = harness.chips(cell.chips)
    except harness.NoChip as e:
        print(f"bench/run.py: {e}", file=sys.stderr)
        return 2
    line = execute(cell, devs, args.seed, args.seconds, bool(args.trace),
                   limits, T_START)
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
