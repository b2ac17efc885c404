#!/usr/bin/env python3
"""Find the knee of an open-loop serving cell on the chip: one process,
one engine, one window at each offered rate.

    python3 bench/tools/sweep.py --workload qwen2-0.5b.chat \
        --rates 10,20,30,40 --seconds 15 --seed 3

For each rate it prints, as a JSON line: the requests offered, the
requests that finished in the second half of the window over those that
arrived in it (``finish_rate_share``: at a rate the system sustains,
as many finish as arrive), the requests in flight at the middle and at
the close of the window, and the tails of the cell's own readers.  The
knee is the highest rate at which requests finish at 97% of the rate
they arrive or more, none fails, and the in-flight count does not grow
from the middle to the close beyond a Poisson count's swing
(``sustained``); the last line names it.
"""
import json
import os
import sys
import time

T_START = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def in_flight(run, t: float) -> int:
    return sum(1 for r in run.requests if r.arrival <= t and
               not (r.token_times and r.token_times[-1] <= t and r.done))


def sustained(row) -> bool:
    mid = row["in_flight_mid"]
    return (row["finish_rate_share"] >= 0.97 and row["failed"] == 0
            and row["in_flight_close"] <= 1.5 * mid + 2)


def knee(rows):
    """The highest rate of the ascending sweep up to which every rate is
    sustained; ``None`` when the lowest is not."""
    best = None
    for row in sorted(rows, key=lambda r: r["rate_per_s"]):
        if not sustained(row):
            break
        best = row["rate_per_s"]
    return best


def main() -> int:
    import argparse
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--rates", required=True)
    p.add_argument("--seconds", type=float, default=15.0)
    p.add_argument("--seed", type=int, default=1)
    p.add_argument("--drain", type=float, default=20.0)
    args = p.parse_args()

    import jax
    from bench import harness, loadgen, serving
    from repro.launch.compile_cache import enable_compilation_cache
    cell = harness.find_cell(args.workload, False)
    devs = harness.chips(cell.chips)
    enable_compilation_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    tag = harness.device_tag(devs)
    arch, spec, cfg, params = serving.load_model(
        cell.config, cell.config_file, args.seed)
    serve, mix = cell.config["serve"], dict(cell.mix, drain_s=args.drain)
    eng = serving.build(cfg, params, serve, mix["output"]["max"], args.seed,
                        None)
    pr = mix["prompt"]
    serving.warm(eng, list(range(pr["min"], pr["max"] + 1, pr["grid"])),
                 spec.vocab_size)
    harness.log(tag, f"set-up {time.perf_counter() - T_START:.3f}s")
    metrics = {m["name"]: harness.load_reader(m["name"])
               for m in cell.metrics if m["name"] != "setup_s"}
    rows = []
    for rate in [float(r) for r in args.rates.split(",")]:
        mx = dict(mix, rate_per_s=rate)
        planned = loadgen.open_loop(mx, args.seconds, args.seed,
                                    spec.vocab_size)
        run = harness.Run(cell.name, cell.config, mx, devs[0].device_kind,
                          len(devs), spec=spec, arch=arch)
        drv = serving.Driver(eng, run)
        serving.window(drv, mx, planned, args.seconds,
                       harness.Tracer(False, 0, 0))
        t0, t1 = run.window_t0, run.window_t0 + args.seconds
        tm = (t0 + t1) / 2
        done = sum(1 for r in run.requests if r.done and not r.failed
                   and tm < r.token_times[-1] <= t1)
        came = sum(1 for r in run.requests if tm < r.arrival <= t1)
        row = {"rate_per_s": rate, "offered": len(planned),
               "finish_rate_share": done / max(came, 1),
               "in_flight_mid": in_flight(run, (t0 + t1) / 2),
               "in_flight_close": in_flight(run, t1),
               "failed": sum(r.failed for r in run.requests),
               **{k: f(run) for k, f in metrics.items()}}
        rows.append(row)
        harness.log(tag, json.dumps(row))
    print(json.dumps({"workload": cell.name, "seconds": args.seconds,
                      "knee_per_s": knee(rows),
                      "device": {"platform": devs[0].platform,
                                 "kind": devs[0].device_kind,
                                 "count": len(devs)}, "rows": rows}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
