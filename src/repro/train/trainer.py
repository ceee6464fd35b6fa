"""Training loop with LLload self-reporting and checkpoint/restart — the
"user job" side of the paper's pipeline.

Every ``monitor_every`` steps the trainer publishes its measured utilization
(achieved model-FLOP/s over peak => the paper's "GPU load" analog, plus HBM
use) into the in-process LLload registry; an optional PeriodicArchiver
captures snapshots on the 15-minute cadence.  The weekly analysis then sees
this job exactly as LLSC sees a user's GPU job.  Each step opens the
``llload.train.*`` profiler spans (``repro.monitor.SPAN_NAMES``).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Optional

import jax
import numpy as np
from jax.profiler import StepTraceAnnotation, TraceAnnotation

from repro.launch.fault import CrashInjector
from repro.monitor import publish_step_utilization
from repro.models import model as model_lib
from repro.roofline import hw
from repro.train import checkpoint as ckpt_lib
from repro.train.data import DataConfig, SyntheticLM
from repro.train.train_step import (TrainState, default_opt_cfg,
                                    init_train_state, make_train_step)


@dataclasses.dataclass
class TrainerConfig:
    steps: int = 100
    batch_size: int = 8
    seq_len: int = 128
    ckpt_dir: Optional[str] = None
    ckpt_every: int = 50
    async_ckpt: bool = False      # overlap checkpoint I/O with training
    monitor_every: int = 1
    log_every: int = 10
    seed: int = 0
    job_name: str = "train"
    # peak FLOP/s of the local device, for the duty-cycle proxy.  None takes
    # it from roofline.hw's table, which raises for a device it does not list.
    peak_flops: Optional[float] = None


class Trainer:
    def __init__(self, cfg, tcfg: TrainerConfig, *,
                 crash: Optional[CrashInjector] = None):
        self.cfg = cfg
        self.tcfg = tcfg
        self.opt_cfg = default_opt_cfg(cfg, total_steps=tcfg.steps)
        self.data = SyntheticLM(DataConfig(cfg.vocab_size, tcfg.seq_len,
                                           tcfg.batch_size, tcfg.seed))
        self.step_fn = jax.jit(make_train_step(cfg, self.opt_cfg),
                               donate_argnums=(0,))
        self.crash = crash
        self.peak_flops = (hw.resolve_peak_flops(tcfg.peak_flops)
                           if tcfg.monitor_every else None)
        self.history: list = []
        # model flops per step (6 N D) for the duty-cycle report
        self._flops_per_step = model_lib.model_flops(
            cfg, tcfg.batch_size * tcfg.seq_len, training=True)

    # ------------------------------------------------------------------
    def _init_state(self) -> TrainState:
        return init_train_state(self.cfg, jax.random.PRNGKey(self.tcfg.seed),
                                self.opt_cfg)

    def _batch(self, step: int) -> dict:
        b = self.data.batch(step)
        fe = self.data.frontend(step, self.cfg)
        if fe is not None:
            b["frontend"] = fe
        return b

    # ------------------------------------------------------------------
    def run(self, resume: bool = True) -> dict:
        tc = self.tcfg
        start_step = 0
        state = None
        with TraceAnnotation("llload.train.init"):
            if tc.ckpt_dir and resume:
                template = jax.eval_shape(self._init_state)
                from repro.launch.fault import resume_latest

                state, start_step = resume_latest(tc.ckpt_dir, template)
            if state is None:
                state = self._init_state()

        params_bytes = sum(np.prod(x.shape) * x.dtype.itemsize
                           for x in jax.tree.leaves(state))
        hbm_total_gb = hw.HBM_BYTES * jax.device_count() / 1e9
        losses = []
        for step in range(start_step, tc.steps):
            if self.crash is not None:
                self.crash.maybe_crash(step)
            with StepTraceAnnotation("llload.train.step", step_num=step):
                t0 = time.perf_counter()
                with TraceAnnotation("llload.train.feed"):
                    batch = self._batch(step)
                with TraceAnnotation("llload.train.dispatch"):
                    state, metrics = self.step_fn(state, batch)
                with TraceAnnotation("llload.train.sync"):
                    # blocks until the step completes
                    loss = float(metrics["loss"])
                dt = time.perf_counter() - t0
                losses.append(loss)
                entry = {"step": step, "loss": loss, "time_s": dt,
                         "duty": None}
                self.history.append(entry)

                if tc.monitor_every and step % tc.monitor_every == 0:
                    with TraceAnnotation("llload.monitor.publish"):
                        util = publish_step_utilization(
                            tc.job_name,
                            model_flops_per_step=self._flops_per_step,
                            step_time_s=dt, peak_flops=self.peak_flops,
                            n_devices=jax.device_count(),
                            hbm_used_gb=params_bytes / 1e9,
                            hbm_total_gb=hbm_total_gb)
                    entry["duty"] = util.duty_cycle
                if tc.log_every and step % tc.log_every == 0:
                    print(f"[train:{self.cfg.name}] step {step} "
                          f"loss {loss:.4f} ({dt * 1e3:.0f} ms)")
                if tc.ckpt_dir and tc.ckpt_every and \
                        (step + 1) % tc.ckpt_every == 0:
                    with TraceAnnotation("llload.train.checkpoint"):
                        if tc.async_ckpt:
                            ckpt_lib.save_checkpoint_async(
                                tc.ckpt_dir, step + 1, state)
                        else:
                            ckpt_lib.save_checkpoint(tc.ckpt_dir, step + 1,
                                                     state)
        if tc.ckpt_dir:
            with TraceAnnotation("llload.train.checkpoint"):
                ckpt_lib.wait_pending_checkpoints()
                ckpt_lib.save_checkpoint(tc.ckpt_dir, tc.steps, state)
        return {"final_loss": losses[-1] if losses else float("nan"),
                "losses": losses, "start_step": start_step,
                "state": state}
