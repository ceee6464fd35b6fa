"""A training cell: the program's ``Trainer`` as a user runs it, monitoring
on, timed over whole ``Trainer.run`` calls.

Set-up builds one ``Trainer`` and drives it from the seed: ``run`` for one
step and then for three, each from the same initial state, on the trainer's
own feed.  From those come the readings that ``check`` holds against the
reference: each of the three steps' loss, the first gradient as the
optimizer got it (its first moment after one step, over 1 - beta1), and the
parameters' change after three steps.  The window is one more ``run`` of
the same object, as many steps as fill ``--seconds`` at the set-up's step
time.
"""
from __future__ import annotations

import functools
import statistics
import time

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def _leaf_norms(tree) -> np.ndarray:
    return np.asarray(jax.device_get(
        [jnp.linalg.norm(x.astype(F32).ravel()) for x in jax.tree.leaves(tree)]),
        np.float64)


@jax.jit
def _change_norms(after, before):
    """Per leaf, the norm of after - before, with no tree of differences
    held."""
    return [jnp.linalg.norm((a.astype(F32) - b.astype(F32)).ravel())
            for a, b in zip(jax.tree.leaves(after), jax.tree.leaves(before))]


def norm_gap(prog: np.ndarray, refn: np.ndarray, keep=None) -> float:
    """Worst leaf: |prog norm - reference norm| over the larger of the
    reference norm and the median leaf's."""
    keep = np.ones(len(refn), bool) if keep is None else keep
    floor = np.median(refn[keep])
    gaps = np.abs(prog - refn) / np.maximum(refn, floor)
    return float(np.max(gaps[keep]))


def reference_steps(ref, params0, batches, dims, opt, quant=None, rows=4,
                    keep_rows=None):
    """Three AdamW steps of the reference module ``ref`` on the trainer's
    batches, its operands rounded by ``quant`` (none by default).

    ``params0()`` makes the initial weights; they are made again at the
    end rather than held, and the gradient and the update run in place, so
    that weights, moments and gradient fit one chip together.  Returns
    (losses, per-leaf norms of the first clipped gradient, per-leaf norms of
    the change after the steps).  ``keep_rows`` takes the first so many rows
    of each batch only (a fault: half the batch left out)."""
    quant = quant or ref.identity

    def accumulate(grads, params, tok, lab):
        s, g = jax.value_and_grad(
            lambda p: ref.loss_sum(p, tok, lab, dims, quant))(params)
        return s, jax.tree.map(jnp.add, grads, g)

    B, S = batches[0]["tokens"].shape
    n = (keep_rows or B) * S

    def update(params, grads, m, v, step):
        return ref.adamw(params, jax.tree.map(lambda g: g / n, grads), m, v,
                         step, opt)

    accumulate = jax.jit(accumulate, donate_argnums=0)
    update = jax.jit(update, static_argnums=4, donate_argnums=(0, 1, 2, 3))
    params = params0()
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    losses, g1 = [], None
    for i, b in enumerate(batches):
        tok, lab = b["tokens"], b["labels"]
        if keep_rows:
            tok, lab = tok[:keep_rows], lab[:keep_rows]
        total, grads = 0.0, jax.tree.map(jnp.zeros_like, params)
        for r in range(0, tok.shape[0], rows):
            s, grads = accumulate(grads, params, tok[r:r + rows],
                                  lab[r:r + rows])
            total += float(s)
        losses.append(total / n)
        params, m, v, clipped = update(params, grads, m, v, i + 1)
        if i == 0:
            g1 = _leaf_norms(clipped)
        del clipped
    del m, v
    dp = np.asarray(jax.device_get(_change_norms(params, params0())),
                    np.float64)
    return losses, g1, dp


class Cell:
    kind = "train"

    def __init__(self, ctx):
        self.ctx = ctx
        self.B, self.S = ctx.mix["batch"], ctx.mix["seq_len"]
        self._make = jax.jit(functools.partial(ctx.ref.make_params, ctx.dims,
                                               dtype=F32))

    # -- the program's state, made from the seed by the benchmark ---------
    def _params0(self):
        return self._make(self.ctx.key)

    def _init_state(self):
        """The trainer's initial state, in one jitted call: the benchmark's
        weights, the optimizer's own zero state."""
        return self._init(self.ctx.key)

    def setup(self, seconds: float):
        from repro.train.trainer import Trainer, TrainerConfig

        ctx = self.ctx
        self.trainer = t = Trainer(ctx.cfg, TrainerConfig(
            steps=ctx.mix["schedule_steps"], batch_size=self.B,
            seq_len=self.S, seed=ctx.program_seed, log_every=0,
            monitor_every=1, job_name=f"chipbench:{ctx.workload['name']}",
            peak_flops=ctx.program_peak))
        stated = ctx.conf["optimizer"]
        have = {k: getattr(t.opt_cfg, k) for k in stated}
        if have != stated:
            raise SystemExit(f"chipbench: the trainer's optimizer {have} is "
                             f"not the one the configuration states {stated}")
        from repro.train.optimizer import init_opt_state
        from repro.train.train_step import TrainState

        def init(key):
            p = ctx.ref.make_params(ctx.dims, key, F32)
            return TrainState(p, init_opt_state(p, t.opt_cfg))

        self._init = jax.jit(init)
        t._init_state = self._init_state

        t.tcfg.steps = 1
        out = t.run(resume=False)
        self.grad1 = _leaf_norms(out["state"].opt.m) / (1 - stated["b1"])
        del out
        t.tcfg.steps = 3
        out = t.run(resume=False)
        self.losses3 = out["losses"]
        self.delta3 = np.asarray(jax.device_get(_change_norms(
            out["state"].params, self._params0())), np.float64)
        del out
        step_s = statistics.median(h["time_s"] for h in t.history[-2:])
        self.steps = max(2, round(seconds / step_s))
        t.history.clear()
        ctx.log(f"set-up: step {step_s * 1e3:.3f} ms, window of "
                f"{self.steps} steps")

    def spans(self, spans):
        import repro.train.trainer as trainer_mod

        t = self.trainer
        return [spans.wrap(trainer_mod, "publish_step_utilization",
                           "monitor_hook"),
                spans.wrap(t, "_batch", "data"),
                spans.wrap(t, "step_fn", "train_step")]

    def window(self) -> dict:
        t = self.trainer
        t.tcfg.steps = self.steps
        t0 = time.perf_counter()
        out = t.run(resume=False)
        wall = time.perf_counter() - t0
        losses = out["losses"]
        del out
        step_ms = [h["time_s"] * 1e3 for h in t.history]
        slowest = int(np.argmax(step_ms))
        self.ctx.log(f"window steps: median {statistics.median(step_ms):.3f} "
                     f"ms, slowest {step_ms[slowest]:.3f} ms (step {slowest})")
        tokens = self.steps * self.B * self.S
        return {"window_s": wall, "steps": self.steps, "tokens": tokens,
                "attempted": self.steps,
                "failed": int(sum(not np.isfinite(x) for x in losses)),
                "flops": tokens * self.ctx.arch.train_flops_per_token(
                    self.ctx.dims, self.S)}

    def release(self):
        self.trainer._init_state = None

    def _reference(self, **kw):
        """Three reference steps on the trainer's own first three batches
        (its feed's inputs; nothing else of the program)."""
        ctx = self.ctx
        batches = [self.trainer.data.batch(s) for s in range(3)]
        with jax.default_matmul_precision("highest"):
            return reference_steps(ctx.ref, self._params0, batches, ctx.dims,
                                   ctx.conf["optimizer"],
                                   rows=ctx.mix["reference_rows"], **kw)

    def readings(self) -> dict:
        """The numbers compared, for the program's three set-up steps."""
        self._ref = self._reference()
        return self._compare((self.losses3, self.grad1, self.delta3))

    def control_readings(self) -> dict:
        """The same numbers for the control (the reference in fp8) and for
        a planted fault (half of each batch left out, the mean taken over
        the rest), each put in the program's place.  A step that returns
        its state unchanged reads 1 on ``update_norm_gap`` by definition."""
        return {"control_fp8": self._compare(
                    self._reference(quant=self.ctx.ref.fp8)),
                "fault_half_batch": self._compare(
                    self._reference(keep_rows=self.B // 2))}

    def _compare(self, program) -> dict:
        r_loss, r_g1, r_dp = self._ref
        p_loss, p_g1, p_dp = program
        # leaves whose reference gradient is nought to rounding move under
        # Adam by round-off alone: they are left out of the change
        moving = r_g1 >= 1e-3 * np.median(r_g1)
        return {
            "loss_rel_gap": max(abs(a - b) / abs(b)
                                for a, b in zip(p_loss, r_loss)),
            "grad_norm_gap": norm_gap(p_g1, r_g1),
            "update_norm_gap": norm_gap(p_dp, r_dp, moving),
        }
