"""Training launcher.

Smoke-scale on CPU:
  PYTHONPATH=src python -m repro.launch.train --arch st-100m --smoke \
      --steps 20 --batch 4 --seq 64

The Trainer runs on the default device (one chip, or the CPU with
JAX_PLATFORMS=cpu); it builds no mesh, and the traced shards are
emulated on that one device.
"""
from __future__ import annotations

import argparse
import json
import sys

import numpy as np

import jax

from repro.configs import get_arch
from repro.data import DataConfig
from repro.optim import AdamWConfig
from repro.train import Trainer, TrainerConfig


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="st-100m")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced smoke config")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=5)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    entry = get_arch(args.arch)
    cfg = entry.smoke if args.smoke else entry.full
    trainer = Trainer(
        cfg,
        AdamWConfig(lr=args.lr, warmup_steps=max(args.steps // 10, 1),
                    total_steps=args.steps),
        DataConfig(seq_len=args.seq, global_batch=args.batch,
                   vocab=cfg.vocab),
        TrainerConfig(steps=args.steps, ckpt_dir=args.ckpt_dir,
                      ckpt_every=args.ckpt_every, seed=args.seed),
    )
    resumed = trainer.maybe_resume()
    if resumed:
        print(f"resumed from step {trainer.step}")
    hist = trainer.run()
    for h in hist:
        if h["step"] % args.log_every == 0 or h["step"] == hist[-1]["step"]:
            print(f"step {h['step']:6d} loss {h['loss']:.4f} "
                  f"({h['seconds']*1e3:.1f} ms)")
    print(json.dumps({"final_loss": hist[-1]["loss"],
                      "steps": trainer.step,
                      "straggler_events": len(trainer.monitor.events)}))
    return 0


if __name__ == "__main__":
    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    sys.exit(main())
