"""A batch-serving cell: the program's ``ServeEngine`` draining a queue
filled in advance, timed over one ``ServeEngine.run(max_steps=K)``.

Set-up makes the weights from the seed and builds one engine.  It pushes
one request of every prompt length on the mix's grid, and one into every
slot, through that engine, so that every prefill, splice and decode program
is compiled.  A calibration run of the same mix then times the opening
burst (every slot prefilled), a decode step and an admission, and K is
chosen so that the window lasts about ``--seconds``.  The window's queue holds more
requests than it can drain.

``check`` takes a sample of the window's finished requests, drawn from the
seed and with the longest among them, and runs the reference once over
each prompt with its served tokens: the number compared is the widest gap
by which a served token's logit lies below the reference's best.
"""
from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import traffic


class Cell:
    kind = "serve"

    def __init__(self, ctx):
        self.ctx = ctx
        self.mix = ctx.mix
        self.V = ctx.dims.vocab_size

    def _submit(self, reqs, first_id: int) -> list:
        from repro.serve.engine import Request

        out = [Request(first_id + i, p, max_new_tokens=o)
               for i, (p, o) in enumerate(reqs)]
        for r in out:
            self.engine.submit(r)
        return out

    def setup(self, seconds: float):
        from repro.serve.engine import EngineConfig, ServeEngine

        ctx, mix = self.ctx, self.mix
        self.params = jax.jit(functools.partial(
            ctx.ref.make_params, ctx.dims,
            dtype=jnp.dtype(ctx.dims.dtype)))(ctx.key)
        self.engine = e = ServeEngine(ctx.cfg, self.params, EngineConfig(
            slots=mix["slots"], max_seq_len=mix["max_seq_len"], greedy=True,
            seed=ctx.program_seed, job_name=f"chipbench:{ctx.workload['name']}",
            peak_flops=ctx.program_peak))
        rng = np.random.default_rng([ctx.seed, 1])
        self._submit(traffic.warmup_requests(mix, rng, self.V), 0)
        e.run()

        self.steps = self._calibrate(seconds)
        e.queue.clear()
        e.completions.clear()

        # most requests a window of K steps can admit
        n = mix["slots"] * (1 + self.steps // max(mix["output"]["min"] - 1, 1))
        self.queued = self._submit(
            traffic.requests(mix, np.random.default_rng([ctx.seed, 2]), n,
                             self.V), 2_000_000)

    def _calibrate(self, seconds: float) -> int:
        """K, the window's decode steps, from a short run on the same
        requests whatever the seed: the opening burst (the cache made, every
        slot admitted), a decode step alone, and an admission's cost as a
        line in its prompt length.  Past the burst, a window admits as many
        requests a step as the deck's answers free slots, slots over their
        mean length in decode steps."""
        e, mix = self.engine, self.mix
        self._submit(traffic.requests(mix, np.random.default_rng(0),
                                      mix["slots"] * 8, self.V), 1_000_000)
        decodes, admits = [], []
        decode, admit = e._decode, e._prefill_one

        def timed_admit(req, *a):
            t = time.perf_counter()
            out = admit(req, *a)
            admits.append((len(req.prompt), time.perf_counter() - t))
            return out

        e._decode = lambda *a: decodes.append(time.perf_counter()) or decode(*a)
        e._prefill_one = timed_admit
        t0 = time.perf_counter()
        e.run(max_steps=mix["calibration_steps"])
        wall = time.perf_counter() - t0
        e._decode, e._prefill_one = decode, admit

        slots = mix["slots"]
        burst = decodes[0] - t0
        step = (wall - burst - sum(s for _, s in admits[slots:])) / len(decodes)
        slope, base = np.polyfit(*zip(*admits), 1)
        d = traffic.deck(mix)
        admit_s = base + slope * np.mean([p for p, _ in d])
        per_step = step + admit_s * slots / np.mean([o - 1 for _, o in d])
        steps = max(2, round((seconds - burst) / per_step))
        self.ctx.log(f"set-up: opening burst {burst:.3f} s, decode step "
                     f"{step * 1e3:.3f} ms, admission {admit_s * 1e3:.3f} ms, "
                     f"{per_step * 1e3:.3f} ms a step in all: window of "
                     f"{steps} steps")
        return steps

    def spans(self, spans):
        import repro.serve.engine as engine_mod

        e = self.engine
        return [spans.wrap(engine_mod, "publish_step_utilization",
                           "monitor_hook"),
                spans.wrap(e, "_prefill_one", "prefill_splice"),
                spans.wrap(e, "_decode", "decode"),
                spans.wrap(e, "_select", "select")]

    def window(self) -> dict:
        e, mix = self.engine, self.mix
        t0 = time.perf_counter()
        stats = e.run(max_steps=self.steps)
        wall = time.perf_counter() - t0
        admitted = self.queued[:len(self.queued) - len(e.queue)]
        done = {c.request_id: c for c in e.completions}
        failed = sum(1 for r in admitted if r.request_id in done
                     and not self._valid(r, done[r.request_id].tokens))
        d, arch = self.ctx.dims, self.ctx.arch
        work = sum(arch.prefill_flops(d, len(r.prompt)) for r in admitted)
        decoded = 0
        for c in done.values():
            work += arch.decode_flops(d, c.prompt_len, len(c.tokens) - 1)
            decoded += len(c.tokens) - 1
        # requests still in their slots: their decoded tokens, shared evenly
        live = [r for r in admitted if r.request_id not in done]
        rest = stats["tokens"] - len(admitted) - decoded
        for r in live:
            work += arch.decode_flops(d, len(r.prompt), rest // len(live))
        self.done = list(done.values())
        self.prompts = {r.request_id: r.prompt for r in admitted}
        return {"window_s": wall, "steps": stats["steps"],
                "tokens": stats["tokens"], "attempted": len(admitted),
                "failed": failed, "flops": work,
                "decoded": stats["tokens"] - len(admitted),
                "slots": mix["slots"]}

    def _valid(self, req, tokens) -> bool:
        """As many tokens as asked, or as many as the slot held, and every
        one in the vocabulary."""
        room = 1 + self.mix["max_seq_len"] - len(req.prompt)
        return (len(tokens) in (req.max_new_tokens, room)
                and all(0 <= t < self.V for t in tokens))

    def release(self):
        self.engine.queue.clear()
        self.engine.completions.clear()

    def _sample(self) -> list:
        rng = np.random.default_rng([self.ctx.seed, 3])
        done = sorted(self.done, key=lambda c: c.request_id)
        longest = max(range(len(done)), key=lambda i: len(done[i].tokens))
        rest = [i for i in range(len(done)) if i != longest]
        k = min(self.mix["check_requests"] - 1, len(rest))
        pick = [longest] + list(rng.choice(rest, k, replace=False))
        return [done[i] for i in pick]

    def _rows(self, sample):
        """Each sampled prompt with its served tokens, padded to the slot
        capacity, and the served token at each position (-1: none)."""
        T = self.mix["max_seq_len"]
        toks = np.zeros((len(sample), T), np.int32)
        tgts = np.full((len(sample), T), -1, np.int32)
        for i, c in enumerate(sample):
            p = self.prompts[c.request_id]
            seq = np.concatenate([p, np.asarray(c.tokens[:-1], np.int32)])
            toks[i, :len(seq)] = seq
            tgts[i, len(p) - 1:len(p) - 1 + len(c.tokens)] = c.tokens
        return toks, tgts

    def _gaps(self, fn) -> float:
        if not self.done:
            return float("inf")
        sample = self._sample()
        toks, tgts = self._rows(sample)
        b = self.mix["reference_rows"]
        pad = (-len(sample)) % b
        toks = np.concatenate([toks, np.zeros((pad, toks.shape[1]), np.int32)])
        tgts = np.concatenate([tgts, np.full((pad, tgts.shape[1]), -1,
                                             np.int32)])
        gaps = []
        with jax.default_matmul_precision("highest"):
            for r in range(0, len(toks), b):
                gaps.append(np.asarray(fn(self.params, toks[r:r + b],
                                          tgts[r:r + b])))
        self.ctx.log(f"check: {len(sample)} requests, "
                     f"{int((tgts >= 0).sum())} served tokens compared")
        return float(np.max(np.concatenate(gaps)))

    def readings(self) -> dict:
        dims, ref = self.ctx.dims, self.ctx.ref
        fn = jax.jit(lambda p, t, g: ref.served_gaps(p, t, g, dims))
        return {"served_logit_gap": self._gaps(fn)}

    def control_readings(self) -> dict:
        """The same number with the reference computed in fp8 put in the
        program's place: the gap of the token it puts first."""
        dims, ref = self.ctx.dims, self.ctx.ref
        fn = jax.jit(lambda p, t, g: ref.control_gaps(p, t, g, dims, ref.fp8))
        return {"control_fp8": {"served_logit_gap": self._gaps(fn)}}
