"""Serving launcher CLI: batched requests against any arch with LLload
monitoring and overload-aware admission.

    PYTHONPATH=src python -m repro.launch.serve --arch llsc-100m --reduced \
        --requests 16 --slots 4 [--max-new 16]

The engine publishes per-step duty cycle into the LLload registry; at the
end it prints the LLload view of itself plus the controller's NPPN verdict
(the paper's overloading loop applied to this very job).  On a device that
``repro.roofline.hw`` lists the duty uses the published peak; elsewhere (the
CPU) pass ``--peak-flops``.
"""
from __future__ import annotations

import argparse

import jax
import numpy as np

from repro.configs import get_config, reduced_config
from repro.core.collector import JaxJobRegistry
from repro.launch.compile_cache import use_compile_cache
from repro.models import init_params
from repro.serve.engine import EngineConfig, Request, ServeEngine


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llsc-100m")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-seq", type=int, default=128)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--peak-flops", type=float, default=None,
                    help="device peak FLOP/s for the duty cycle (needed "
                         "where roofline.hw has no entry, e.g. the CPU)")
    args = ap.parse_args(argv)

    use_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced_config(cfg)
    params = init_params(cfg, jax.random.PRNGKey(args.seed))
    eng = ServeEngine(cfg, params, EngineConfig(
        slots=args.slots, max_seq_len=args.max_seq,
        peak_flops=args.peak_flops, job_name=f"serve:{cfg.name}"))

    rng = np.random.default_rng(args.seed)
    for i in range(args.requests):
        eng.submit(Request(i, rng.integers(0, cfg.vocab_size,
                                           args.prompt_len).astype(np.int32),
                           max_new_tokens=args.max_new))
    stats = eng.run()
    print(f"[serve:{cfg.name}] {stats['requests']} requests, "
          f"{stats['tokens']} tokens in {stats['wall_s']:.2f}s "
          f"({stats['tokens_per_s']:.1f} tok/s, {stats['steps']} steps)")
    agg = JaxJobRegistry.global_registry().aggregate()
    print(f"LLload view: duty={agg.duty_cycle:.3f} "
          f"step={agg.step_time_s * 1e3:.1f}ms")
    d = stats["decision"]
    print(f"Overload controller: slots {args.slots} -> {d.nppn} ({d.reason})")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
