"""Bring-up smoke run of llsc-100m on one TPU chip, at its published widths.

    python3 chip_smoke.py [--seed N]

One process runs every phase through the system's own entry points, with
random weights made from ``--seed``:

  kernel  ``kernels.ops.flash_attention`` at [8,12,1024,64] bf16 against the
          float32 ``attention_ref``; the compiled program must hold the
          Mosaic kernel (``tpu_custom_call``).
  serve   ``ServeEngine`` with 8 slots x 2048 positions answers 16 requests
          (prompt 128, 32 new tokens); then decode at the last prompt
          position must agree with a full-sequence prefill.
  train   ``Trainer`` takes 10 steps at batch 8 x seq 1024; every loss is
          finite and the first agrees with a float32 ``lm_loss``.

Each phase prints its checks, its XLA compile seconds, steady step times
(each timed after the result is ready) and the device's peak memory so far.
These are smoke-run numbers, not a benchmark.  The last line of standard
output is one JSON object naming the device.  Without a TPU the script exits
non-zero and prints no result.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import statistics
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.kernels.ref import attention_ref  # noqa: E402
from repro.launch.compile_cache import use_compile_cache  # noqa: E402
from repro.models import model as model_lib  # noqa: E402
from repro.serve.engine import EngineConfig, Request, ServeEngine  # noqa: E402
from repro.train.train_step import default_opt_cfg, init_train_state  # noqa: E402
from repro.train.trainer import Trainer, TrainerConfig  # noqa: E402

ARCH = "llsc-100m"
# bf16 flash kernel vs float32 reference: the kernel's output is rounded to
# bf16 (2^-9 relative) and its p.v product may run in bf16 passes, so allow a
# few bf16 ulps at |o| <= 4.
FLASH_ATOL = FLASH_RTOL = 2e-2
# bf16 decode vs bf16 prefill of the same tokens: two orders of the same bf16
# arithmetic through 12 layers.  A wrong position or mask moves logits by
# O(1) of their range; rounding moves them by a few 2^-8 steps.
DECODE_REL_TOL = 2e-2
# bf16 training loss vs a float32 evaluation of the same parameters and batch.
LOSS_REL_TOL = 1e-2


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, what: str):
    if not ok:
        raise SmokeFailure(what)


@dataclasses.dataclass(frozen=True)
class Sizes:
    attn: tuple = (8, 12, 1024, 64)     # flash kernel [B, H, S, D]
    slots: int = 8
    max_seq: int = 2048
    requests: int = 16
    prompt: int = 128
    max_new: int = 32
    train_batch: int = 8
    train_seq: int = 1024
    train_steps: int = 10


class CompileClock:
    """Sums XLA backend compile seconds while the block runs."""

    def __enter__(self):
        self.seconds, self.programs = 0.0, 0

        def listen(event, secs, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.seconds += secs
                self.programs += 1

        self._listen = listen
        jax.monitoring.register_event_duration_secs_listener(listen)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._listen)

    def __str__(self):
        return f"xla_compile_s={self.seconds:.2f} ({self.programs} programs)"


def peak_memory() -> str:
    stats = jax.devices()[0].memory_stats()
    if not stats or "peak_bytes_in_use" not in stats:
        return "peak_bytes_in_use=not reported"
    return f"peak_bytes_in_use={stats['peak_bytes_in_use']} (process so far)"


def timed_ms(fn, n: int) -> list:
    out = []
    for _ in range(n):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        out.append((time.perf_counter() - t0) * 1e3)
    return out


def ms_summary(ms: list) -> str:
    return (f"median {statistics.median(ms):.3f} ms, min {min(ms):.3f}, "
            f"max {max(ms):.3f} over {len(ms)}")


def phase_kernel(sizes: Sizes, seed: int) -> dict:
    B, H, S, D = sizes.attn
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(seed), 3)
    q, k, v = (jax.random.normal(key, (B, H, S, D), jnp.bfloat16)
               for key in (kq, kk, kv))
    with CompileClock() as clock:
        compiled = ops.flash_attention.lower(q, k, v).compile()
        out = jax.block_until_ready(compiled(q, k, v))
    mosaic = "tpu_custom_call" in compiled.as_text()
    with jax.default_matmul_precision("highest"):
        ref = attention_ref(q.astype(jnp.float32), k.astype(jnp.float32),
                            v.astype(jnp.float32))
    out, ref = np.asarray(out, np.float32), np.asarray(ref)
    err = float(np.max(np.abs(out - ref)))
    check(np.all(np.isfinite(out)), "flash kernel: non-finite output")
    check(np.allclose(out, ref, atol=FLASH_ATOL, rtol=FLASH_RTOL),
          f"flash kernel: max |err| {err:.3e} beyond atol=rtol={FLASH_ATOL}")
    ms = timed_ms(lambda: compiled(q, k, v), 20)
    print(f"[kernel] flash_attention {list(sizes.attn)} bf16 vs float32 ref: "
          f"max_abs_err={err:.3e} (atol=rtol={FLASH_ATOL}) "
          f"tpu_custom_call={mosaic}")
    print(f"[kernel] {clock}; step {ms_summary(ms)}; {peak_memory()}")
    return {"max_abs_err": err, "mosaic": mosaic}


def _decode_matches_prefill(cfg, params, prompts, T: int):
    """(max |decode - prefill| logit at the last prompt position over the
    largest prefill logit, steady decode step times in ms)."""
    B, S = prompts.shape
    prefill = jax.jit(lambda p, t: model_lib.prefill(p, cfg, t))
    logits_full, _ = prefill(params, prompts)
    _, caches = prefill(params, prompts[:, :-1])
    full = model_lib.cache_struct(cfg, B, T)
    caches = jax.tree.map(
        lambda c, f: jnp.pad(c, [(0, b - a) for a, b in zip(c.shape, f.shape)]),
        caches, full)
    decode = jax.jit(lambda p, t, c, l: model_lib.decode_step(p, cfg, t, c, l))
    lens = jnp.full((B,), S - 1, jnp.int32)
    logits_dec, _, _ = decode(params, prompts[:, -1:], caches, lens)
    full_np = np.asarray(logits_full)
    dec_np = np.asarray(logits_dec)
    check(np.all(np.isfinite(dec_np)), "decode: non-finite logits")
    rel = float(np.max(np.abs(dec_np - full_np)) / np.max(np.abs(full_np)))
    ms = timed_ms(lambda: decode(params, prompts[:, -1:], caches, lens)[0], 20)
    return rel, ms


def phase_serve(cfg, sizes: Sizes, seed: int, peak_flops) -> dict:
    params = model_lib.init_params(cfg, jax.random.PRNGKey(seed))
    rng = np.random.default_rng(seed)
    prompts = rng.integers(0, cfg.vocab_size, (sizes.requests, sizes.prompt),
                           dtype=np.int32)
    with CompileClock() as clock:
        eng = ServeEngine(cfg, params, EngineConfig(
            slots=sizes.slots, max_seq_len=sizes.max_seq,
            peak_flops=peak_flops, job_name=f"smoke:serve:{cfg.name}"))
        for i, prompt in enumerate(prompts):
            eng.submit(Request(i, prompt, max_new_tokens=sizes.max_new))
        stats = eng.run()
    done = sorted(c.request_id for c in eng.completions)
    check(done == list(range(sizes.requests)),
          f"serve: {len(done)} of {sizes.requests} requests completed")
    for c in eng.completions:
        check(len(c.tokens) == sizes.max_new,
              f"serve: request {c.request_id} gave {len(c.tokens)} tokens")
        check(all(0 <= t < cfg.vocab_size for t in c.tokens),
              f"serve: request {c.request_id} has a token outside the vocab")
    print(f"[serve] {stats['requests']}/{sizes.requests} requests completed, "
          f"{stats['tokens']} tokens in range [0, {cfg.vocab_size}), "
          f"{stats['steps']} decode steps, wall {stats['wall_s']:.2f} s "
          f"(compile included); {clock}")

    with CompileClock() as clock:
        rel, ms = _decode_matches_prefill(
            cfg, params, jnp.asarray(prompts[:sizes.slots]), sizes.max_seq)
    check(rel <= DECODE_REL_TOL,
          f"serve: decode vs prefill logits differ by {rel:.3e} of their "
          f"range (tol {DECODE_REL_TOL})")
    print(f"[serve] decode vs prefill at position {sizes.prompt - 1}: "
          f"max|diff|/max|logit|={rel:.3e} (tol {DECODE_REL_TOL}); {clock}")
    print(f"[serve] decode step {sizes.slots} slots x {sizes.max_seq}: "
          f"{ms_summary(ms)}; {peak_memory()}")
    return {"requests": stats["requests"], "decode_rel_err": rel}


def phase_train(cfg, sizes: Sizes, seed: int, peak_flops) -> dict:
    tcfg = TrainerConfig(steps=sizes.train_steps, batch_size=sizes.train_batch,
                         seq_len=sizes.train_seq, seed=seed, log_every=0,
                         peak_flops=peak_flops,
                         job_name=f"smoke:train:{cfg.name}")
    trainer = Trainer(cfg, tcfg)

    # float32 reference: the trainer's initial parameters on its first batch
    batch = trainer.data.batch(0)
    params = init_train_state(cfg, jax.random.PRNGKey(seed),
                              default_opt_cfg(cfg, sizes.train_steps)).params
    cfg32 = dataclasses.replace(cfg, dtype="float32")
    with jax.default_matmul_precision("highest"):
        ref = float(jax.jit(lambda p, t, l: model_lib.lm_loss(p, cfg32, t, l))(
            params, batch["tokens"], batch["labels"]))
    del params

    with CompileClock() as clock:
        out = trainer.run(resume=False)
    losses = out["losses"]
    check(len(losses) == sizes.train_steps,
          f"train: {len(losses)} of {sizes.train_steps} steps ran")
    check(all(np.isfinite(losses)), f"train: non-finite loss in {losses}")
    rel = abs(losses[0] - ref) / abs(ref)
    check(rel <= LOSS_REL_TOL,
          f"train: first loss {losses[0]:.5f} vs float32 {ref:.5f} "
          f"(rel {rel:.2e} > {LOSS_REL_TOL})")
    times = [h["time_s"] * 1e3 for h in trainer.history]
    print(f"[train] {len(losses)} steps, batch {sizes.train_batch} x seq "
          f"{sizes.train_seq}: losses {' '.join(f'{x:.4f}' for x in losses)}; "
          f"all finite")
    print(f"[train] first loss {losses[0]:.5f} vs float32 lm_loss {ref:.5f}: "
          f"rel {rel:.2e} (tol {LOSS_REL_TOL})")
    print(f"[train] first step {times[0]:.1f} ms (compile included); {clock}; "
          f"steady step {ms_summary(times[1:])}; {peak_memory()}")
    return {"losses": losses, "ref_loss": ref}


def run_phases(cfg, sizes: Sizes, seed: int, peak_flops=None) -> dict:
    return {"kernel": phase_kernel(sizes, seed),
            "serve": phase_serve(cfg, sizes, seed, peak_flops),
            "train": phase_train(cfg, sizes, seed, peak_flops)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, but JAX found platform "
              f"{dev.platform!r} ({dev.device_kind}); not running on it",
              file=sys.stderr)
        return 2
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    cache = use_compile_cache()
    print(f"[device] {device['kind']} x{device['count']}; compile cache {cache}")
    print("[smoke] timings below are a smoke run, not a benchmark")
    report = run_phases(get_config(ARCH), Sizes(), args.seed)
    check(report["kernel"]["mosaic"],
          "flash kernel: compiled program holds no tpu_custom_call")
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
