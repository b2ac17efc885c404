#!/usr/bin/env python3
"""Where the host's time goes inside ``ServingEngine.tick()``: one traced
run of a serving cell, reduced by the serving engine's and the
executor's own spans (``serve.*``, ``amt.*``; ``docs/amt.md``,
"Tracing") on the device's clock.

    python3 bench/tools/spans.py --workload qwen2-0.5b.chat --seed 7 \
        --seconds 51 [--lead 45]

``bench/tracing.read`` keeps the harness's ``bench.*`` spans only; for
its run this tool keeps the program's spans too, and labels each idle
gap of the device by the innermost span open at its middle, found
exactly however many short spans closed before the gap.  ``--lead``
moves the traced part of the window (``trace_lead_s``).  It prints one
JSON line: ``correct``, the cell's per-layer ``metrics``, and

- ``sched_ms``: the executor's own time a tick: the self time of every
  ``amt.run`` (less its ``amt.task`` children), summed, over the number
  of ``serve.tick``;
- ``admit_ms``: the mean ``serve.admit``;
- ``tick_host_ms``: the mean ``serve.tick`` less the union of its
  ``serve.sync``, the host's time not spent waiting on the device;
- ``serve_tick_ms``: the mean ``serve.tick``, to hold beside
  ``tick_ms``, which the harness times around the same call;
- ``idle_gaps``: the device's idle seconds by innermost span.

A number whose spans the trace lacks is null.
"""
import bisect
import collections
import contextlib
import heapq
import json
import os
import sys
import time

T_START = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import harness, tracing  # noqa: E402

PROGRAM_SPANS = ("serve.", "amt.")


@contextlib.contextmanager
def program_spans():
    """``tracing.read`` keeps the program's spans too, meanwhile: the
    harness reads its trace inside ``serving.run_cell`` and takes no list
    of names."""
    kept = tracing.SPAN_PREFIX
    tracing.SPAN_PREFIX = (kept,) + PROGRAM_SPANS
    try:
        yield
    finally:
        tracing.SPAN_PREFIX = kept


def innermost(spans, points):
    """For each point, the name of the shortest span open there (start
    <= point < end), or "none".  A sweep in time order: a heap holds the
    spans started so far by length, and the ended ones leave it when
    they reach its top."""
    spans = sorted(spans, key=lambda s: s[1])
    out = ["none"] * len(points)
    heap, i = [], 0
    for k in sorted(range(len(points)), key=points.__getitem__):
        p = points[k]
        while i < len(spans) and spans[i][1] <= p:
            name, s, e = spans[i]
            heapq.heappush(heap, (e - s, i, e, name))
            i += 1
        while heap and heap[0][2] <= p:
            heapq.heappop(heap)
        if heap:
            out[k] = heap[0][3]
    return out


def idle_gaps(trace, dev):
    """Seconds the device sat idle, by the innermost span open on the
    host at the middle of each gap."""
    busy = tracing.merged(tracing.busy_events(trace.devices[dev]))
    edges = [trace.start_ns] + [t for iv in busy for t in iv] + \
        [trace.end_ns]
    gaps = [(lo, hi) for lo, hi in zip(edges[::2], edges[1::2]) if hi > lo]
    out = collections.defaultdict(float)
    for (lo, hi), label in zip(gaps, innermost(
            trace.spans, [(lo + hi) / 2 for lo, hi in gaps])):
        out[label] += (hi - lo) * 1e-9
    return dict(out)


def named(trace, name):
    return [s for s in trace.spans if s[0] == name]


def self_ns(parents, children):
    """Each parent's length less the union of the children that start
    inside it."""
    children = sorted(children, key=lambda c: c[1])
    starts = [c[1] for c in children]
    out = []
    for _, s, e in parents:
        inner = children[bisect.bisect_left(starts, s):
                         bisect.bisect_left(starts, e)]
        covered = tracing.merged([(n, a, min(b, e)) for n, a, b in inner])
        out.append(e - s - sum(b - a for a, b in covered))
    return out


def program_numbers(trace):
    """``sched_ms``, ``admit_ms``, ``tick_host_ms`` and ``serve_tick_ms``
    of a trace that kept the program's spans (see the module's doc)."""
    ticks, runs = named(trace, "serve.tick"), named(trace, "amt.run")
    admits = named(trace, "serve.admit")

    def mean_ms(ns):
        return sum(ns) / len(ns) * 1e-6 if ns else None

    return {
        "serve_ticks": len(ticks),
        "sched_ms": sum(self_ns(runs, named(trace, "amt.task"))) * 1e-6
        / len(ticks) if ticks and runs else None,
        "admit_ms": mean_ms([e - s for _, s, e in admits]),
        "tick_host_ms": mean_ms(self_ns(ticks, named(trace, "serve.sync"))),
        "serve_tick_ms": mean_ms([e - s for _, s, e in ticks]),
    }


def measure(cell, seed, seconds, devs, limits, t_start):
    """One traced run of a serving cell, as ``bench/run.py`` makes it,
    with the program's spans kept; returns the JSON line's object."""
    from bench import serving
    compiles = harness.CompileLog()
    with program_spans():
        out = serving.run_cell(cell, seed, seconds, True, devs, limits,
                               harness.device_tag(devs), t_start, compiles)
    run = out.run
    dev = sorted(run.trace.devices)[0] if run.trace.devices else None
    return {"workload": cell.name, "seed": seed,
            "correct": harness.judge(out.checks),
            "metrics": {k: v["value"] for k, v in
                        harness.metric_values(run, cell.metrics).items()},
            **program_numbers(run.trace),
            "idle_gaps": tracing.top(idle_gaps(run.trace, dev), 20)
            if dev else None,
            "device": {"kind": devs[0].device_kind, "count": len(devs),
                       "busy_s": tracing.mean_busy_s(run.trace),
                       "window_s": run.trace.window_s}}


def main() -> int:
    import argparse
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--lead", type=float, default=None)
    args = p.parse_args()

    import jax
    from repro.launch.compile_cache import enable_compilation_cache
    cell = harness.find_cell(args.workload, True)
    if args.lead is not None:
        cell.mix["trace_lead_s"] = args.lead
    devs = harness.chips(cell.chips)
    enable_compilation_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    limits = harness.load_json(os.path.join(harness.BENCH_DIR, "limits",
                                            cell.name + ".json"))
    print(json.dumps(measure(cell, args.seed, args.seconds, devs, limits,
                             T_START)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
