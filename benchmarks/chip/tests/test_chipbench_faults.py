"""A run with the timed path broken underneath comes out not correct: once
for each fault a cell can have (a single chip has no exchange to leave
out), and for the control, the reference in fp8 put in the program's place.
"""
import pytest
import chipbench_tiny as tiny

import repro.train.trainer as trainer_mod
from repro.serve.engine import ServeEngine
from repro.train.train_step import make_train_step


def _broken_step(fault):
    def make(cfg, opt_cfg, **kw):
        real = make_train_step(cfg, opt_cfg, **kw)

        def step(state, batch):
            if fault == "half_batch":
                half = batch["tokens"].shape[0] // 2
                return real(state, {k: v[:half] for k, v in batch.items()})
            new, metrics = real(state, batch)
            return state, metrics            # the state comes back unchanged
        return step
    return make


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
def test_train_fault_is_not_correct(tmp_path, capsys, monkeypatch, fault):
    monkeypatch.setattr(trainer_mod, "make_train_step", _broken_step(fault))
    res = tiny.run(tmp_path, "tiny.train", capsys=capsys)
    assert res["correct"] is False
    assert any(c["value"] > c["limit"] for c in res["checks"].values())


def test_serve_altered_token_is_not_correct(tmp_path, capsys, monkeypatch):
    select = ServeEngine._select

    def altered(self, logits, step):
        tok = select(self, logits, step)
        return (tok + 1) % logits.shape[-1]   # each token, where it is made

    monkeypatch.setattr(ServeEngine, "_select", altered)
    res = tiny.run(tmp_path, "tiny.serve", capsys=capsys)
    assert res["correct"] is False
    assert res["checks"]["served_logit_gap"]["value"] > \
        res["checks"]["served_logit_gap"]["limit"]


@pytest.mark.parametrize("workload", ["tiny.train", "tiny.serve"])
def test_control_is_not_correct(tmp_path, workload):
    ctx, cell = tiny.cell(tmp_path, workload)
    program = cell.readings()
    assert all(v <= ctx.limits[k] for k, v in program.items())
    control = cell.control_readings()["control_fp8"]
    assert any(v > ctx.limits[k] for k, v in control.items())
