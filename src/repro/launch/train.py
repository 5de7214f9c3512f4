"""Training launcher.

Production: ``--arch gemma2-27b --shape train_4k --strategy ramora`` on a real
pod (the dry-run proves the mesh/sharding; see launch/dryrun.py).
CPU bring-up: ``--reduced`` shrinks the arch to its smoke-size family twin and
runs real steps on host devices, exercising the identical code path
(trainer, checkpoints, straggler watch, data pipeline).

  PYTHONPATH=src python -m repro.launch.train --arch qwen3-0.6b --reduced \
      --steps 50 --ckpt-dir /tmp/ckpt --mesh 1x1
"""
from __future__ import annotations

import argparse
import json
import time

import jax

from repro.configs import get_arch, get_shape, reduced, strategy
from repro.configs.base import ShapeConfig
from repro.launch.compile_cache import enable_compile_cache
from repro.optim.optimizers import get_optimizer
from repro.optim.schedules import get_schedule
from repro.train.trainer import FaultInjector, Trainer, TrainerConfig


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-0.6b")
    ap.add_argument("--shape", default="train_4k")
    ap.add_argument("--strategy", default="ramora",
                    choices=["occamy", "ramora", "ogopogo"])
    ap.add_argument("--reduced", action="store_true",
                    help="CPU-size family twin of the arch")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--global-batch", type=int, default=0)
    ap.add_argument("--seq-len", type=int, default=0)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--schedule", default="cosine",
                    choices=["cosine", "wsd", "constant"])
    ap.add_argument("--optimizer", default="adamw",
                    choices=["adamw", "sgdm", "adafactor"])
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--mesh", default="1x1",
                    help="DxM data x model mesh for CPU runs, e.g. 2x2")
    ap.add_argument("--inject-fault-at", type=int, default=-1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    # minicpm trains with its WSD schedule per the assignment
    sched_name = "wsd" if args.arch == "minicpm-2b" else args.schedule
    shape = get_shape(args.shape)
    if args.global_batch or args.seq_len:
        shape = ShapeConfig(shape.name, shape.kind,
                            args.seq_len or shape.seq_len,
                            args.global_batch or shape.global_batch)
    if args.reduced and not (args.global_batch or args.seq_len):
        shape = ShapeConfig(shape.name, shape.kind, seq_len=128, global_batch=8)

    d, m = (int(x) for x in args.mesh.split("x"))
    mesh = None
    if d * m > 1:
        mesh = jax.make_mesh((d, m), ("data", "model"))
    strat = strategy(args.strategy, multi_pod=False)

    sched = get_schedule(sched_name, args.lr, args.steps)
    opt = get_optimizer(args.optimizer, sched)
    tcfg = TrainerConfig(steps=args.steps, ckpt_dir=args.ckpt_dir,
                         ckpt_every=args.ckpt_every, seed=args.seed)
    fault = (FaultInjector(at_step=args.inject_fault_at)
             if args.inject_fault_at >= 0 else None)
    trainer = Trainer(cfg, shape, strat, opt, tcfg, mesh=mesh, fault=fault)

    t0 = time.time()
    out = trainer.run_with_restarts()
    dt = time.time() - t0
    losses = out["losses"]
    print(json.dumps({
        "arch": cfg.name, "steps": out["stopped_at"], "wall_s": round(dt, 1),
        "loss_first": round(losses[0], 4) if losses else None,
        "loss_last": round(losses[-1], 4) if losses else None,
        "restarts": out["restarts"], "n_stragglers": out["n_stragglers"],
        "tokens_per_s": round(out["stopped_at"] * shape.global_batch
                              * shape.seq_len / dt, 1),
    }, indent=1))


if __name__ == "__main__":
    main()
