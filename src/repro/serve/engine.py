"""Batched serving engine with overload-aware admission.

The engine runs fixed-capacity decode *slots* (continuous batching: each
slot has its own cache length; finished slots are refilled from the queue
between steps).  The paper tie-in: slot capacity is the NPPN analog —
the :class:`OverloadController` watches the measured device duty cycle and
steps the number of concurrent streams 1 -> 2 -> 4 -> 8 exactly like LLSC
steps tasks-per-GPU, saturating the device with co-resident low-duty work.
Each step opens the ``llload.serve.*`` profiler spans
(``repro.monitor.SPAN_NAMES``); the device programs are ``jit_serve_prefill``
and ``jit_serve_decode``.  For a model with expert layers the decode program
also sums, on the device, the live tokens routed to each held expert; ``run``
reads the sum once, when it returns.
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import StepTraceAnnotation, TraceAnnotation

from repro.core.overload import (DeviceObservation, OverloadController,
                                 OverloadDecision)
from repro.monitor import publish_step_utilization
from repro.models import model as model_lib
from repro.roofline import hw


@dataclasses.dataclass
class Request:
    request_id: int
    prompt: np.ndarray            # [S] int32
    max_new_tokens: int = 16
    submitted_s: float = 0.0


@dataclasses.dataclass
class Completion:
    request_id: int
    tokens: List[int]
    prompt_len: int
    latency_s: float = 0.0


@dataclasses.dataclass
class EngineConfig:
    slots: int = 4                # concurrent decode streams (NPPN analog)
    max_seq_len: int = 256
    greedy: bool = True           # False: temperature/top-k sampling
    temperature: float = 1.0
    top_k: int = 0                # 0 = full distribution
    seed: int = 0
    job_name: str = "serve"
    # peak FLOP/s of the local device, for the duty cycle.  None takes it
    # from roofline.hw's table, which raises for a device it does not list.
    peak_flops: Optional[float] = None
    monitor: bool = True


class ServeEngine:
    """Single-host engine; slots decode in lockstep with per-slot lengths."""

    def __init__(self, cfg, params, ecfg: EngineConfig):
        self.cfg = cfg
        self.params = params
        self.ecfg = ecfg
        self.queue: deque = deque()
        self.completions: List[Completion] = []
        self.controller = OverloadController()
        self.peak_flops = (hw.resolve_peak_flops(ecfg.peak_flops)
                           if ecfg.monitor else None)

        def serve_decode(params, tokens, caches, lens, live, routed):
            """``live`` [B] (1.0 for a slot that holds a request) and
            ``routed``, the running sum [n_held], are None for a model
            without expert layers; else ``routed`` comes back with this
            step's live tokens added."""
            logits, caches, step = model_lib.decode_step(
                params, cfg, tokens, caches, lens)
            if step is not None:
                routed = routed + jnp.einsum("b,be->e", live, step)
            return logits, caches, routed

        def serve_prefill(params, tokens):
            return model_lib.prefill(params, cfg, tokens)

        self._decode = jax.jit(serve_decode, donate_argnums=(2,))
        self._prefill = jax.jit(serve_prefill)
        self._flops_per_token = model_lib.model_flops(cfg, 1, training=False)

    def submit(self, req: Request):
        req.submitted_s = time.perf_counter()
        self.queue.append(req)

    def _select(self, logits, step: int):
        """Greedy argmax or temperature/top-k sampling. logits [B, V]."""
        ecfg = self.ecfg
        if ecfg.greedy:
            return jnp.argmax(logits, axis=-1)
        key = jax.random.fold_in(jax.random.PRNGKey(ecfg.seed), step)
        scaled = logits / max(ecfg.temperature, 1e-6)
        if ecfg.top_k > 0:
            vals, idx = jax.lax.top_k(scaled, ecfg.top_k)
            choice = jax.random.categorical(key, vals, axis=-1)
            return jnp.take_along_axis(idx, choice[:, None], axis=1)[:, 0]
        return jax.random.categorical(key, scaled, axis=-1)

    # ------------------------------------------------------------------
    def _prefill_one(self, req: Request, caches, slot: int, T: int):
        """Prefill one request, splice its cache rows into slot `slot`.

        Returns (caches, prompt_len, first_token) — the first generated
        token comes from the prefill logits (re-feeding the last prompt
        token through decode would double-update SSM states).
        """
        with TraceAnnotation("llload.serve.prefill"):
            tokens = jnp.asarray(req.prompt, jnp.int32)[None]
            logits, new = self._prefill(self.params, tokens)
        with TraceAnnotation("llload.serve.first_token"):
            first_tok = int(self._select(logits,
                                         10_000_000 + req.request_id)[0])
        S = tokens.shape[1]

        def splice(path, dst, src):
            keys = [str(getattr(p, "key", p)) for p in path]
            name = keys[-1]
            b_ax = 1 if "blocks" in keys[:-1] else 0
            if name in ("k", "v", "ckv", "krope"):
                t_ax = b_ax + 1
                if src.shape[t_ax] < dst.shape[t_ax]:
                    pad = [(0, 0)] * src.ndim
                    pad[t_ax] = (0, dst.shape[t_ax] - src.shape[t_ax])
                    src = jnp.pad(src, pad)
            idx = [slice(None)] * dst.ndim
            idx[b_ax] = slot
            src_idx = [slice(None)] * src.ndim
            src_idx[b_ax] = 0
            return dst.at[tuple(idx)].set(
                src[tuple(src_idx)].astype(dst.dtype))

        with TraceAnnotation("llload.serve.splice"):
            caches = jax.tree_util.tree_map_with_path(splice, caches, new)
        return caches, S, first_tok

    # ------------------------------------------------------------------
    def run(self, *, max_steps: int = 10_000) -> dict:
        """Drain the queue.  Returns throughput stats, with this run's
        admissions and prompt tokens prefilled, and the mean duty cycle the
        monitor hook published (None with monitoring off).  For a model
        with expert layers, also the decode steps' live tokens routed to
        each held expert, summed over the layers (``routed_per_expert``),
        their mean and their max over the mean."""
        cfg, ecfg = self.cfg, self.ecfg
        B, T = ecfg.slots, ecfg.max_seq_len
        with TraceAnnotation("llload.serve.init"):
            caches = model_lib.init_cache(cfg, B, T)
        lens = np.zeros(B, np.int32)
        active: List[Optional[Request]] = [None] * B
        outputs: List[List[int]] = [[] for _ in range(B)]
        last = np.zeros(B, np.int32)
        routed = (None if cfg.moe is None
                  else jnp.zeros((cfg.moe.n_held,), jnp.float32))

        t_start = time.perf_counter()
        tokens_out = 0
        steps = 0
        admitted = prefill_tokens = 0
        duties = []
        while (self.queue or any(a is not None for a in active)) \
                and steps < max_steps:
            with StepTraceAnnotation("llload.serve.step", step_num=steps):
                # refill free slots
                for s in range(B):
                    if active[s] is None and self.queue:
                        req = self.queue.popleft()
                        with TraceAnnotation("llload.serve.admit",
                                             request_id=req.request_id,
                                             prompt_len=len(req.prompt)):
                            caches, S, first = self._prefill_one(req, caches,
                                                                 s, T)
                        admitted += 1
                        prefill_tokens += S
                        active[s] = req
                        lens[s] = S
                        outputs[s] = [first]
                        last[s] = first
                        tokens_out += 1
                        if len(outputs[s]) >= req.max_new_tokens:
                            self.completions.append(Completion(
                                req.request_id, outputs[s], len(req.prompt),
                                time.perf_counter() - req.submitted_s))
                            active[s] = None
                if not any(a is not None for a in active):
                    break

                t0 = time.perf_counter()
                with TraceAnnotation("llload.serve.decode"):
                    # each slot writes its new token at position lens[s]
                    live = (None if routed is None else jnp.asarray(
                        [a is not None for a in active], jnp.float32))
                    logits, caches, routed = self._decode(
                        self.params, jnp.asarray(last[:, None]), caches,
                        jnp.asarray(lens), live, routed)
                with TraceAnnotation("llload.serve.sample"):
                    nxt = np.asarray(self._select(logits, steps), np.int32)
                dt = time.perf_counter() - t0
                steps += 1

                n_active = sum(a is not None for a in active)
                with TraceAnnotation("llload.serve.bookkeep"):
                    for s in range(B):
                        if active[s] is None:
                            continue
                        outputs[s].append(int(nxt[s]))
                        last[s] = nxt[s]
                        lens[s] += 1
                        tokens_out += 1
                        req = active[s]
                        if (len(outputs[s]) >= req.max_new_tokens
                                or lens[s] >= T):
                            self.completions.append(Completion(
                                req.request_id, outputs[s], len(req.prompt),
                                time.perf_counter() - req.submitted_s))
                            active[s] = None

                if ecfg.monitor:
                    with TraceAnnotation("llload.monitor.publish"):
                        achieved = self._flops_per_token * n_active
                        util = publish_step_utilization(
                            ecfg.job_name, model_flops_per_step=achieved,
                            step_time_s=dt, peak_flops=self.peak_flops,
                            n_devices=jax.device_count(),
                            hbm_total_gb=hw.HBM_BYTES / 1e9)
                        self.controller.observe(DeviceObservation(
                            duty_cycle=min(1.0, achieved
                                           / (dt * self.peak_flops)),
                            mem_used_gb=0.1 * n_active, mem_total_gb=16.0))
                    duties.append(util.duty_cycle)

        wall = time.perf_counter() - t_start
        moe = {}
        if routed is not None:
            per = np.asarray(routed, np.float64)
            mean = float(per.mean())
            moe = {"routed_per_expert": per.tolist(), "routed_mean": mean,
                   "routed_max_over_mean": (float(per.max()) / mean
                                            if mean > 0 else None)}
        return {
            "requests": len(self.completions),
            "tokens": tokens_out,
            "steps": steps,
            "admitted": admitted,
            "prefill_tokens": prefill_tokens,
            "wall_s": wall,
            "tokens_per_s": tokens_out / wall if wall > 0 else 0.0,
            "duty_mean": float(np.mean(duties)) if duties else None,
            "decision": self.controller.decide(ecfg.slots),
            **moe,
        }


def overload_decision(engine: ServeEngine) -> OverloadDecision:
    return engine.controller.decide(engine.ecfg.slots)
