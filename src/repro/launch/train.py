"""Training launcher CLI (one device: the trainer builds no mesh).

    PYTHONPATH=src python -m repro.launch.train --arch llsc-100m \
        --steps 200 --batch 8 --seq 256 [--reduced] [--ckpt-dir ckpts/run1]

``--reduced`` runs the same family and layer pattern at tiny widths, for the
CPU.  On a device that ``repro.roofline.hw`` lists, the duty proxy takes the
published peak; elsewhere (the CPU) pass ``--peak-flops``.
"""
from __future__ import annotations

import argparse

from repro.configs import get_config, reduced_config
from repro.launch.compile_cache import use_compile_cache
from repro.launch.fault import CrashInjector
from repro.train.trainer import Trainer, TrainerConfig


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llsc-100m")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--reduced", action="store_true",
                    help="use the reduced (CPU smoke) config of the arch")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--crash-at", type=int, default=None,
                    help="inject a failure at this step (restart demo)")
    ap.add_argument("--no-resume", action="store_true")
    ap.add_argument("--peak-flops", type=float, default=None,
                    help="device peak FLOP/s for the duty proxy (needed "
                         "where roofline.hw has no entry, e.g. the CPU)")
    args = ap.parse_args(argv)

    use_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced_config(cfg)
    tcfg = TrainerConfig(steps=args.steps, batch_size=args.batch,
                         seq_len=args.seq, ckpt_dir=args.ckpt_dir,
                         ckpt_every=args.ckpt_every,
                         peak_flops=args.peak_flops,
                         job_name=f"train:{cfg.name}")
    crash = CrashInjector(args.crash_at) if args.crash_at else None
    trainer = Trainer(cfg, tcfg, crash=crash)
    out = trainer.run(resume=not args.no_resume)
    print(f"[launch.train] done: start_step={out['start_step']} "
          f"final_loss={out['final_loss']:.4f}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
