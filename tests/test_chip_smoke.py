"""chip_smoke.py rehearsed on the CPU at the reduced llsc-100m size.

The phases run here in interpret mode; the device check, which refuses
the CPU, is tested on its own.
"""
import importlib.util
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from repro.configs import reduced_config

ROOT = Path(__file__).resolve().parents[1]
_spec = importlib.util.spec_from_file_location("chip_smoke",
                                               ROOT / "chip_smoke.py")
chip_smoke = sys.modules["chip_smoke"] = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(chip_smoke)

SMALL = chip_smoke.Sizes(attn=(1, 2, 128, 64), slots=2, max_seq=32,
                         requests=3, prompt=8, max_new=4, train_batch=2,
                         train_seq=32, train_steps=3)


def test_phases_pass_at_reduced_size(capsys):
    # the CPU has no published peak: pass one explicitly
    report = chip_smoke.run_phases(reduced_config("llsc-100m"), SMALL, seed=0,
                                   peak_flops=5e10)
    assert report["kernel"]["mosaic"] is False   # interpret mode on the CPU
    assert report["serve"]["requests"] == SMALL.requests
    assert len(report["train"]["losses"]) == SMALL.train_steps
    out = capsys.readouterr().out
    for tag in ("[kernel]", "[serve]", "[train]"):
        assert tag in out
    assert "xla_compile_s=" in out and "peak_bytes_in_use=" in out


def test_check_raises_on_failure():
    with pytest.raises(chip_smoke.SmokeFailure, match="boom"):
        chip_smoke.check(False, "boom")


def test_refuses_the_cpu_and_prints_no_result(capsys):
    assert chip_smoke.main([]) != 0
    captured = capsys.readouterr()
    assert "'cpu'" in captured.err
    assert '"ok"' not in captured.out


def test_fails_without_the_repo(tmp_path):
    """Alone in a directory, the script cannot import the program."""
    shutil.copy(ROOT / "chip_smoke.py", tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    run = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                         env=env, capture_output=True, text=True, timeout=120)
    assert run.returncode != 0
    assert '"ok"' not in run.stdout
    assert "repro" in run.stderr
