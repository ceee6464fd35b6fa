"""The program's own profiler spans and named device programs: a tiny
``Trainer`` and a tiny ``ServeEngine`` run under ``jax.profiler.trace``,
and the ``.xplane.pb`` it writes read back with ``jax.profiler.ProfileData``.
"""
import glob
import os

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs import reduced_config
from repro.models import init_params
from repro.monitor import SPAN_NAMES
from repro.serve.engine import EngineConfig, Request, ServeEngine
from repro.train.trainer import Trainer, TrainerConfig

# The CPU has no published peak, so monitored jobs are given one.
CPU_PEAK_FLOPS = 5e10
PROMPT_LENS = (5, 9, 7)


def _train(ckpt_dir):
    t = Trainer(reduced_config("llsc-100m"), TrainerConfig(
        steps=2, batch_size=2, seq_len=16, ckpt_dir=str(ckpt_dir),
        ckpt_every=1, log_every=0, job_name="trace-train",
        peak_flops=CPU_PEAK_FLOPS))
    return t, t.run(resume=False)


def _serve():
    cfg = reduced_config("llsc-100m")
    eng = ServeEngine(cfg, init_params(cfg, jax.random.PRNGKey(0)),
                      EngineConfig(slots=2, max_seq_len=32,
                                   job_name="trace-serve",
                                   peak_flops=CPU_PEAK_FLOPS))
    for i, n in enumerate(PROMPT_LENS):
        prompt = np.random.default_rng(i).integers(0, cfg.vocab_size, n)
        eng.submit(Request(100 + i, prompt.astype(np.int32),
                           max_new_tokens=4))
    return eng, eng.run()


def _events(logdir):
    """(host spans named llload.*, hlo modules of the executed ops): each
    span as (line, name, start_ns, end_ns, stats)."""
    path, = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    spans, modules = [], set()
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                stats = dict(e.stats)
                if e.name.startswith("llload."):
                    spans.append((line.name, e.name, e.start_ns, e.end_ns,
                                  stats))
                if "hlo_module" in stats:
                    modules.add(stats["hlo_module"])
    return spans, modules


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    root = tmp_path_factory.mktemp("trace")
    with jax.profiler.trace(str(root / "trace")):
        trainer, train_out = _train(root / "ck")
        engine, serve_out = _serve()
    spans, modules = _events(str(root / "trace"))
    return dict(trainer=trainer, train_out=train_out, engine=engine,
                serve_out=serve_out, spans=spans, modules=modules)


def test_every_span_name_appears(traced):
    seen = {name for _, name, *_ in traced["spans"]}
    assert set(SPAN_NAMES) <= seen


def test_admit_holds_prefill_first_token_and_splice(traced):
    spans = traced["spans"]
    admits = [s for s in spans if s[1] == "llload.serve.admit"]
    assert sorted(a[4]["request_id"] for a in admits) == [100, 101, 102]
    assert sorted(a[4]["prompt_len"] for a in admits) == sorted(PROMPT_LENS)
    for line, _, start, end, _ in admits:
        inside = {name for ln, name, s, e, _ in spans
                  if ln == line and start <= s and e <= end}
        assert {"llload.serve.prefill", "llload.serve.first_token",
                "llload.serve.splice"} <= inside


def test_step_spans_carry_the_step_number(traced):
    spans = traced["spans"]
    train = sorted(s[4]["step_num"] for s in spans
                   if s[1] == "llload.train.step")
    assert train == [0, 1]
    serve = sorted(s[4]["step_num"] for s in spans
                   if s[1] == "llload.serve.step")
    assert serve[:3] == [0, 1, 2]
    assert serve[-1] <= traced["serve_out"]["steps"]


def test_device_programs_are_named(traced):
    modules = traced["modules"]
    assert {"jit_serve_prefill", "jit_serve_decode",
            "jit_train_step"} <= modules
    assert not any("lambda" in m for m in modules)


def test_traced_run_matches_untraced(traced, tmp_path):
    _, train_out = _train(tmp_path / "ck")
    assert traced["train_out"]["losses"] == train_out["losses"]
    engine, _ = _serve()
    tokens = {c.request_id: c.tokens for c in engine.completions}
    assert {c.request_id: c.tokens
            for c in traced["engine"].completions} == tokens


def test_published_duty_is_kept(traced):
    history = traced["trainer"].history
    assert [h["step"] for h in history] == [0, 1]
    assert all(h["duty"] > 0 for h in history)
    stats = traced["serve_out"]
    assert stats["duty_mean"] > 0
    assert stats["admitted"] == len(PROMPT_LENS)
    assert stats["prefill_tokens"] == sum(PROMPT_LENS)
