"""Benchmark driver — one section per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--fast]

Sections:
1. matchbench        — progress-engine post+match throughput (keyed vs
                       legacy scan), emits BENCH_progress.json
2. pingpong          — paper Fig. 1 (lanes sweep × 3 designs)
3. lcx_collectives   — LCX ring/pairwise vs native XLA collectives
4. moe_dispatch      — EP a2a dispatch throughput (LCX a2a backends)
5. kernels_bench     — Pallas kernels vs oracles
6. chaosbench        — seeded fault-injection sweep (convergence),
                       emits BENCH_chaos.json at repo root
7. failoverbench     — kill-every-N chaos soak (recovery latency,
                       goodput), emits BENCH_failover.json at repo root
8. isolationbench    — per-device throughput isolation (resource
                       hierarchy), emits BENCH_isolation.json
CSV outputs land in results/.
"""
import argparse
import os
import sys

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, os.path.dirname(__file__))


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--fast", action="store_true",
                   help="trim the lane sweep for CI")
    args = p.parse_args()

    from repro.launch.compile_cache import enable_compilation_cache
    enable_compilation_cache()
    os.makedirs("results", exist_ok=True)

    print("=" * 72)
    print("0. matching/progress fast path (keyed engine vs legacy scan)")
    print("=" * 72)
    import matchbench
    mb_args = ["--out", "results/BENCH_progress.json"]
    if args.fast:
        mb_args.append("--smoke")
    matchbench.main(mb_args)

    print("=" * 72)
    print("1. ping-pong (paper Fig. 1: message rate vs concurrent lanes)")
    print("=" * 72)
    import pingpong
    if args.fast:
        pingpong.LANES = (1, 8, 64)
        pingpong.REPEAT = 10
    pingpong.main(out_csv="results/pingpong.csv")

    print("=" * 72)
    print("2. LCX collectives vs native")
    print("=" * 72)
    import lcx_collectives
    lcx_collectives.main(out_csv="results/lcx_collectives.csv")

    print("=" * 72)
    print("3. MoE EP dispatch (LCX a2a)")
    print("=" * 72)
    import moe_dispatch
    moe_dispatch.main(out_csv="results/moe_dispatch.csv")

    print("=" * 72)
    print("4. Pallas kernels vs oracles")
    print("=" * 72)
    import kernels_bench
    kernels_bench.main(out_csv="results/kernels.csv")

    print("=" * 72)
    print("5. chaos sweep (seeded fault injection must converge)")
    print("=" * 72)
    import chaosbench
    cb_args = ["--out", os.path.join(ROOT, "BENCH_chaos.json")]
    if args.fast:
        cb_args.append("--smoke")
    chaosbench.main(cb_args)

    print("=" * 72)
    print("6. failover soak (kill-every-N: recovery latency + goodput)")
    print("=" * 72)
    import failoverbench
    fb_args = ["--out", os.path.join(ROOT, "BENCH_failover.json")]
    if args.fast:
        fb_args.append("--smoke")
    failoverbench.main(fb_args)

    print("=" * 72)
    print("7. device isolation (busy neighbor must not steal throughput)")
    print("=" * 72)
    import isolationbench
    ib_args = ["--out", "results/BENCH_isolation.json"]
    if args.fast:
        ib_args.append("--smoke")
    isolationbench.main(ib_args)

    print("benchmarks complete; CSVs in results/")


if __name__ == "__main__":
    main()
