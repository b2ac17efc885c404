"""End-to-end training driver.

    PYTHONPATH=src python -m repro.launch.train --arch qwen2-0.5b \
        --smoke --steps 200 --seq-len 256 --batch 16 --ckpt-dir /tmp/ck

``--smoke`` uses the arch's reduced config (CPU-runnable); without it
the full config is built (requires a real cluster).  The trainer wires
checkpoint/restart, failure recovery, straggler monitoring and elastic
remesh (see repro.runtime).
"""
import argparse
import json

import jax

from repro.configs.base import get_config, get_smoke_config
from repro.launch.compile_cache import enable_compilation_cache
from repro.launch.mesh import make_host_mesh
from repro.runtime import FailureInjector, TrainConfig, Trainer


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--arch", required=True)
    p.add_argument("--smoke", action="store_true")
    p.add_argument("--steps", type=int, default=100)
    p.add_argument("--seq-len", type=int, default=256)
    p.add_argument("--batch", type=int, default=16)
    p.add_argument("--lr", type=float, default=3e-4)
    p.add_argument("--warmup", type=int, default=20)
    p.add_argument("--grad-accum", type=int, default=1)
    p.add_argument("--compressed-accum", action="store_true")
    p.add_argument("--ckpt-dir", default=None)
    p.add_argument("--ckpt-every", type=int, default=50)
    p.add_argument("--resume", action="store_true")
    p.add_argument("--inject-failure-at", type=int, action="append",
                   default=[])
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args()

    enable_compilation_cache()
    cfg = get_smoke_config(args.arch) if args.smoke \
        else get_config(args.arch)
    tcfg = TrainConfig(
        lr=args.lr, warmup=args.warmup, total_steps=args.steps,
        seq_len=args.seq_len, global_batch=args.batch,
        grad_accum=args.grad_accum,
        compressed_accum=args.compressed_accum,
        ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
        seed=args.seed,
    )
    mesh = make_host_mesh()
    injector = FailureInjector(fail_at=args.inject_failure_at) \
        if args.inject_failure_at else None
    trainer = Trainer(cfg, tcfg, mesh=mesh, failure_injector=injector)
    if args.resume:
        restored = trainer.restore()
        print(f"resume: {'ok, step ' + str(trainer.step_count) if restored else 'no checkpoint found'}")
    result = trainer.run(args.steps)
    print(json.dumps(result, indent=2, default=str))
    for m in trainer.metrics_log:
        print(f"step {m['step']:5d} loss={m['loss']:.4f} "
              f"lr={m['lr']:.2e} dt={m['dt']*1e3:.0f}ms {m['straggler']}")


if __name__ == "__main__":
    main()
