"""Each kind of cell run end to end on the CPU at a tiny size, through the
harness's own ``main``, with the device refusal bypassed only here; the
refusal itself; and a cell, a mix, a metric and an architecture found by
name."""
import json
import subprocess
import sys

import pytest
import chipbench_tiny as tiny


@pytest.mark.parametrize("workload,trace", [
    ("tiny.train", 0), ("tiny.train", 1), ("tiny.serve", 0),
    ("tiny.serve", 1), ("tiny-untied.serve", 0), ("tiny-wrapped.train", 0),
    ("tiny-wrapped.serve", 0)])
def test_cell_runs_and_reports(tmp_path, capsys, workload, trace):
    res = tiny.run(tmp_path, workload, seed=2 ** 31 + 12345, trace=trace,
                   capsys=capsys)
    assert res["correct"] is True
    assert res["failed"] == 0 and res["attempted"] > 0
    assert list(res)[-1] == "checks"
    kind = workload.rsplit(".", 1)[1]
    kind = "train" if kind == "train" else "serve"
    if trace:
        assert f"{kind}_mfu" in res["metrics"]
        assert f"monitor_hook_ms.{kind}" in res["metrics"]
        assert 0 < res["metrics"][f"{kind}_mfu"]["value"] < 100
    else:
        assert set(res["metrics"]) == {"setup_s", f"{kind}_tokens_per_s"}
        assert all(m["value"] > 0 for m in res["metrics"].values())
    assert res["device"]["platform"] == "cpu"


def test_number_without_limit_is_not_compared(tmp_path, capsys, monkeypatch):
    """A reading that the cell's limits file sets no limit for is logged and
    left out of ``checks`` and of ``correct``."""
    limits = {k: v for k, v in tiny.TRAIN_LIMITS.items() if k != "loss_rel_gap"}
    monkeypatch.setattr(tiny, "TRAIN_LIMITS", limits)
    tiny.harness.main(["--workload", "tiny.train", "--seed", "7", "--seconds",
                       "1", "--trace", "0"], bench=tiny.bench(tmp_path),
                      allow_cpu=True, peak=tiny.PEAK)
    out = capsys.readouterr()
    res = json.loads(out.out.strip().splitlines()[-1])
    assert res["correct"] is True
    assert set(res["checks"]) == set(limits)
    assert "reading loss_rel_gap:" in out.err


def test_refuses_without_tpu(tmp_path):
    """On the CPU the command exits non-zero and prints no result."""
    p = subprocess.run(
        [sys.executable, str(tiny.BENCH / "run.py"), "--workload",
         "phi3-mini-4k-l4.train", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tiny.CHECKOUT, capture_output=True, text=True,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin",
             "HOME": str(tmp_path)})
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_parts_found_by_name(tmp_path):
    """A new configuration, mix and metric are files and entries only."""
    b = tiny.bench(tmp_path)
    (tmp_path / "metrics/extra_metric.py").write_text(
        "def read(run):\n    return 2.0 * run.tokens\n")
    b.spec["per_layer"].append(
        {"name": "extra_metric", "unit": "tokens", "better": "higher",
         "source": "program_counter", "layer": "serving engine",
         "moves": "serve_tokens_per_s", "workloads": ["tiny.serve"]})
    w = b.workload("tiny.serve")
    assert b.config(w["config"])["hidden_size"] == 64
    assert b.traffic(w["traffic"])["slots"] == 4
    names = [m["name"] for m in b.metrics_for(w, trace=True)]
    assert "extra_metric" in names and "train_mfu" not in names
    run = type("Run", (), {"tokens": 21})()
    assert b.reader("extra_metric")(run) == 42.0
    assert [m["name"] for m in b.metrics_for(w, trace=False)] == [
        "serve_tokens_per_s", "setup_s"]


@pytest.mark.parametrize("workload", ["tiny-wrapped.train",
                                      "tiny-wrapped.serve"])
def test_architecture_found_by_name(tmp_path, workload):
    """An architecture that a configuration file names, and that only the
    bench root has, gives the cell its dims, program config, counts and
    reference."""
    ctx, cell = tiny.harness.build(tiny.bench(tmp_path), workload, 7,
                                   allow_cpu=True, peak=tiny.PEAK)
    assert ctx.arch.__file__ == str(tmp_path / "archs/wrapped_gqa.py")
    assert ctx.ref.__file__ == str(tmp_path / "references/wrapped_gqa.py")
    assert (ctx.dims.d_model, ctx.cfg.d_model) == (64, 64)
    assert ctx.arch.prefill_flops(ctx.dims, 8) > 0
    assert cell.kind == workload.rsplit(".", 1)[1]


def test_missing_architecture_names_both_paths(tmp_path):
    b = tiny.bench(tmp_path)
    conf = b.config("tiny")
    conf["architecture"] = "no_such_arch"
    (tmp_path / "configs/tiny.json").write_text(json.dumps(conf))
    with pytest.raises(SystemExit) as e:
        tiny.harness.build(b, "tiny.serve", 7, allow_cpu=True, peak=tiny.PEAK)
    for sub in ("archs", "references"):
        assert str(tmp_path / sub / "no_such_arch.py") in str(e.value)


def test_benchmark_json_names_only_files_that_exist():
    spec = json.loads((tiny.CHECKOUT / "BENCHMARK.json").read_text())
    for c in spec["configs"]:
        assert (tiny.CHECKOUT / c["file"]).is_file()
    for w in spec["workloads"]:
        assert (tiny.BENCH / "traffic" / f"{w['traffic']}.json").is_file()
        assert (tiny.BENCH / "limits" / f"{w['name']}.json").is_file()
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert (tiny.BENCH / "metrics" / f"{m['name']}.py").is_file()
