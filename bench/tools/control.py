#!/usr/bin/env python3
"""Readings from which a cell's limit is set, in one process: for each
seed, one run of the cell as the benchmark makes it, and its control.

- A served model: on the same requests and served tokens, the reference
  computed with float8 (e4m3) matmul operands, the precision below the
  bfloat16 that the configuration states, read at each position as the
  token it puts first.  It must read far above every sound run's
  ``logit_gap``, and the cell's own comparison (``harness.judge``, with
  the limits of ``bench/limits/<workload>.json``) must find it not
  correct.
- The LCX ring: a second run with the exchange between chips left out
  (every put goes to its own rank), which breaks the delivery guarantee
  the configuration states; its ``wrong_payloads`` must be above 0.

    python3 bench/tools/control.py --workload qwen2-0.5b.chat \
        --seeds 1,2,3 --seconds 10

Prints one JSON line per seed (with ``program_correct`` and
``control_correct``, each as the cell's comparison judges it) and a
summary last.
"""
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def served_row(cell, seed, seconds, devs, limits, tag, compiles):
    from bench import harness, serving
    out = serving.run_cell(cell, seed, seconds, False, devs, limits, tag,
                           time.perf_counter(), compiles)
    ctrl, n = serving.widest_gap(out.run.arch, out.run.spec, seed,
                                 cell.config["serve"]["max_seq"],
                                 out.checked, fp8=True)
    judged = dict(out.checks, logit_gap={"value": ctrl,
                                         "limit": limits["logit_gap"]})
    return {"seed": seed, "program": out.checks["logit_gap"]["value"],
            "control": ctrl, "tokens": n, "failed": out.failed,
            "program_correct": harness.judge(out.checks),
            "control_correct": harness.judge(judged)}


def ring_row(cell, seed, seconds, devs, limits, tag, compiles):
    from bench import harness, ring
    import repro.core as lcx
    sound = ring.run_cell(cell, seed, seconds, False, devs, limits, tag,
                          time.perf_counter(), compiles)
    shift = lcx.Perm.shift
    lcx.Perm.shift = staticmethod(lambda k: shift(0))
    try:
        broken = ring.run_cell(cell, seed, seconds, False, devs, limits, tag,
                               time.perf_counter(), compiles)
    finally:
        lcx.Perm.shift = staticmethod(shift)
    return {"seed": seed,
            "program": sound.checks["wrong_payloads"]["value"],
            "control": broken.checks["wrong_payloads"]["value"],
            "payloads": sound.attempted,
            "program_correct": harness.judge(sound.checks),
            "control_correct": harness.judge(broken.checks)}


def main() -> int:
    import argparse
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--seconds", type=float, default=10.0)
    args = p.parse_args()

    import jax
    from bench import harness
    from repro.launch.compile_cache import enable_compilation_cache
    cell = harness.find_cell(args.workload, False)
    devs = harness.chips(cell.chips)
    enable_compilation_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    compiles = harness.CompileLog()
    tag = harness.device_tag(devs)
    limits = harness.load_json(os.path.join(harness.BENCH_DIR, "limits",
                                            cell.name + ".json"))
    row_of = harness.by_kind(cell, {"causal_lm": served_row,
                                    "deployment": ring_row})
    rows = []
    for seed in [int(s) for s in args.seeds.split(",")]:
        row = row_of(cell, seed, args.seconds, devs, limits, tag, compiles)
        rows.append(row)
        harness.log(tag, json.dumps(row))
    print(json.dumps({
        "workload": cell.name, "program_max": max(r["program"] for r in rows),
        "control_min": min(r["control"] for r in rows),
        "controls_judged_correct": sum(r["control_correct"] for r in rows),
        "device": {"platform": devs[0].platform, "kind": devs[0].device_kind,
                   "count": len(devs)}, "rows": rows}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
