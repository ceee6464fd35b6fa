"""End-to-end driver: train the ~110M `llsc-100m` model for a few hundred
steps WITH LLload self-reporting and checkpoint/restart.

    PYTHONPATH=src python examples/train_with_monitoring.py \
        [--steps 240] [--quick] [--crash-at N] [--peak-flops F]

``--quick`` uses the reduced config (CI-speed); the default trains the full
110M model on CPU (batch 4 x seq 64; a few seconds per step).  While
training, the job is visible to LLload exactly like a user job at LLSC:
its duty cycle, memory and step times flow through the collector registry.
The duty cycle divides by the device's published peak (``roofline.hw``); the
CPU has none, so pass one there (``--peak-flops 5e10``).
"""
import argparse

from repro.configs import get_config, reduced_config
from repro.core.collector import JaxJobRegistry, LocalHostCollector
from repro.launch.fault import CrashInjector
from repro.train.trainer import Trainer, TrainerConfig


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=240)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--crash-at", type=int, default=None)
    ap.add_argument("--ckpt-dir", default="/tmp/llsc100m-ckpt")
    ap.add_argument("--peak-flops", type=float, default=None,
                    help="device peak FLOP/s for the duty cycle (needed "
                         "where roofline.hw has no entry, e.g. the CPU)")
    args = ap.parse_args()

    cfg = get_config("llsc-100m")
    if args.quick:
        cfg = reduced_config(cfg)
    tcfg = TrainerConfig(steps=args.steps, batch_size=args.batch,
                         seq_len=args.seq, ckpt_dir=args.ckpt_dir,
                         ckpt_every=40, log_every=10,
                         peak_flops=args.peak_flops,
                         job_name=f"train:{cfg.name}")
    crash = CrashInjector(args.crash_at) if args.crash_at else None
    trainer = Trainer(cfg, tcfg, crash=crash)

    try:
        out = trainer.run(resume=True)
    except RuntimeError as e:
        print(f"!! {e} — restart this script to resume from the last "
              f"checkpoint in {args.ckpt_dir}")
        raise SystemExit(1)

    print(f"\nfinal loss: {out['final_loss']:.4f} "
          f"(resumed from step {out['start_step']})")

    # What LLload sees about this job (the paper's per-user view):
    agg = JaxJobRegistry.global_registry().aggregate()
    print("\nLLload view of this job:")
    print(f"  devices:    {agg.n_devices}")
    print(f"  duty cycle: {agg.duty_cycle:.3f}  (achieved/peak FLOP/s)")
    print(f"  step time:  {agg.step_time_s * 1e3:.0f} ms")
    snap = LocalHostCollector(username="demo").snapshot()
    node = list(snap.nodes.values())[0]
    print(f"  host load:  {node.load:.2f} on {node.cores_total} cores "
          f"(norm {node.norm_load:.2f})")
    if agg.duty_cycle < 0.45:
        print("  -> LLload weekly analysis would flag this job LOW-GPULOAD;"
              " the advisor would suggest overloading (see "
              "examples/overloading_throughput.py)")


if __name__ == "__main__":
    main()
