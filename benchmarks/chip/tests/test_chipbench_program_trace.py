"""The reduction of the program's own spans and named programs on small
recorded traces, one of each kind of cell; times in ns, one device."""
import types

import pytest

from chipbench import program_trace as pt

# a serving window of 100 us: two steps, one admission, a gap in no span
SERVE_SPANS = [
    ("chipbench.window", 5_000, 105_000),
    ("llload.serve.step", 5_000, 60_000),
    ("llload.serve.admit", 8_000, 45_000),
    ("llload.serve.prefill", 8_000, 20_000),
    ("llload.serve.first_token", 20_000, 32_000),
    ("llload.serve.splice", 32_000, 45_000),
    ("llload.serve.decode", 45_000, 50_000),
    ("llload.serve.step", 60_000, 105_000),
    ("llload.serve.sample", 50_000, 72_000),
    ("llload.serve.bookkeep", 72_000, 80_000),
    ("llload.monitor.publish", 80_000, 85_000),
    ("llload.serve.decode", 90_000, 100_000),
]
SERVE_DEVICE = {"/device:TPU:0": [
    ("%fusion.1", 10_000, 30_000, "serve_prefill"),
    ("%copy.2", 35_000, 40_000, "dynamic_update_slice"),
    ("%while.3", 47_000, 70_000, "serve_decode"),
    ("%dot.4", 50_000, 60_000, "serve_decode"),       # inside the loop
    ("%while.3", 95_000, 120_000, "serve_decode"),    # past the window
]}
SERVE_COUNTERS = {"steps": 2, "admitted": 1, "prefill_tokens": 100}

# a training window of 50 us: one step, its feed on the device too
TRAIN_SPANS = [
    ("chipbench.window", 0, 50_000),
    ("llload.train.step", 0, 50_000),
    ("llload.train.feed", 0, 10_000),
    ("llload.train.dispatch", 10_000, 12_000),
    ("llload.train.sync", 12_000, 40_000),
    ("llload.monitor.publish", 40_000, 42_000),
]
TRAIN_DEVICE = {"/device:TPU:0": [
    ("%convert.1", 3_000, 6_000, "random_bits"),
    ("%fusion.2", 11_000, 38_000, "train_step"),
]}
TRAIN_COUNTERS = {"steps": 1}


def test_serve_idle_by_span_and_busy_by_module():
    r = pt.reduce(SERVE_DEVICE, SERVE_SPANS)
    # busy 10..30, 35..40, 47..70, 95..105: 58 of 100 us; idle 5..10,
    # 30..35, 40..47, 70..95
    assert r["window_s"] == pytest.approx(100e-6)
    assert r["idle_in"] == pytest.approx({
        "llload.serve.step": 42e-6, "llload.serve.admit": 12e-6,
        "llload.serve.prefill": 2e-6, "llload.serve.first_token": 2e-6,
        "llload.serve.splice": 8e-6, "llload.serve.decode": 7e-6,
        "llload.serve.sample": 2e-6, "llload.serve.bookkeep": 8e-6,
        "llload.monitor.publish": 5e-6})
    # 5..8 lies under the step span alone, 85..90 under none
    assert r["idle_unattributed"] == pytest.approx(8e-6)
    assert r["busy_by_module"] == pytest.approx({
        "serve_decode": 33e-6, "serve_prefill": 20e-6,
        "dynamic_update_slice": 5e-6})
    # the loop's 23 us less the 10 us inside it, and its 10 us in the window
    assert r["device_ops"] == [
        ["serve_decode", "%while.3", pytest.approx(23e-6)],
        ["serve_prefill", "%fusion.1", pytest.approx(20e-6)],
        ["serve_decode", "%dot.4", pytest.approx(10e-6)],
        ["dynamic_update_slice", "%copy.2", pytest.approx(5e-6)]]


@pytest.mark.parametrize("kind,device,spans", [
    ("serve", SERVE_DEVICE, SERVE_SPANS),
    ("train", TRAIN_DEVICE, TRAIN_SPANS)])
def test_parts_add_up_to_the_idle(kind, device, spans):
    r = pt.reduce(device, spans)
    busy = sum(r["busy_by_module"].values())
    assert sum(pt.parts(kind, r).values()) == pytest.approx(
        r["window_s"] - busy)


@pytest.mark.parametrize("name,value", [
    ("feed_idle_share.train", 14.0),           # 0..3 and 6..10 of 50 us
    ("feed_device_ms.train", 3e-3),
    ("unattributed_idle_share.train", 16.0),   # 42..50
    ("admit_idle_share.serve", 12.0),
    ("decode_loop_idle_share.serve", 22.0),
    ("unattributed_idle_share.serve", 8.0),
    ("prefill_device_us_per_token.serve", 0.2),
    ("decode_device_ms.serve", 0.0165),
    ("splice_device_ms.serve", 5e-3),
])
def test_each_reading(name, value):
    kind = name.rsplit(".", 1)[1]
    own = {"train": (TRAIN_DEVICE, TRAIN_SPANS, TRAIN_COUNTERS),
           "serve": (SERVE_DEVICE, SERVE_SPANS, SERVE_COUNTERS)}
    device, spans, counters = own[kind]
    r = pt.reduce(device, spans)
    assert pt.reading(name, kind, r, counters) == pytest.approx(value)
    other = "serve" if kind == "train" else "train"
    assert pt.reading(name, other, r, counters) is None
    assert pt.reading(name, kind, None, counters) is None


def test_a_program_without_spans_or_names_reads_nothing():
    """A program that opens no ``llload.*`` span and runs anonymous
    programs, and returns no admission counts, gives no reading."""
    device = {k: [(n, s, e, "_lambda_") for n, s, e, _ in v]
              for k, v in SERVE_DEVICE.items()}
    spans = [s for s in SERVE_SPANS if not s[0].startswith("llload.")]
    r = pt.reduce(device, spans)
    assert r["idle_in"] == {}
    for name in pt.NAMES:
        if name.endswith(".serve"):
            assert pt.reading(name, "serve", r, {"steps": 2}) is None


def _event(name, start, end, **stats):
    return types.SimpleNamespace(name=name, start_ns=start, end_ns=end,
                                 stats=list(stats.items()))


def _plane(name, **lines):
    return types.SimpleNamespace(name=name, lines=[
        types.SimpleNamespace(name=k.replace("_", " "), events=v)
        for k, v in lines.items()])


def test_planes_give_modules_and_program_spans():
    """An operation's module comes from its ``hlo_module`` stat, else from
    the module event that holds its start; host spans other than the
    program's and the window are left out."""
    tpu = _plane(
        "/device:TPU:0",
        XLA_Modules=[_event("jit_serve_prefill(7)", 0, 100),
                     _event("jit_serve_decode(9)", 200, 300)],
        XLA_Ops=[_event("%fusion.1 = f32[2] fusion()", 10, 50),
                 _event("%copy.2", 120, 150),
                 _event("%dot.3", 210, 250),
                 _event("%add.4", 400, 410, hlo_module="jit_pad")])
    host = _plane("/host:CPU", python=[
        _event("chipbench.window", 0, 500), _event("chipbench.decode", 1, 2),
        _event("llload.serve.decode", 200, 300), _event("other", 0, 1)])
    device, spans = pt.from_planes([tpu, host, _plane("/device:CPU:0")])
    assert device == {"/device:TPU:0": [
        ("%fusion.1", 10, 50, "serve_prefill"),
        ("%copy.2", 120, 150, pt.UNKNOWN),
        ("%dot.3", 210, 250, "serve_decode"),
        ("%add.4", 400, 410, "pad")]}
    assert spans == [("chipbench.window", 0, 500),
                     ("llload.serve.decode", 200, 300)]


def test_nothing_to_read():
    assert pt.reduce({}, SERVE_SPANS) is None
    assert pt.reduce(SERVE_DEVICE, SERVE_SPANS[1:]) is None


@pytest.mark.parametrize("workload", ["tiny.train", "tiny.serve"])
def test_chip_script_runs_a_cell(tmp_path, capsys, monkeypatch, workload):
    """``program_spans.py`` on a tiny cell on the CPU: the window, the
    program's counters and the span costs; the CPU has no device plane,
    so there is no reduction to report."""
    import json

    import chipbench_tiny as tiny
    import program_spans
    from chipbench import spec

    monkeypatch.setattr(spec, "CHECKOUT", tmp_path)
    program_spans.main(["--workload", workload, "--seed", str(2 ** 31 + 5),
                        "--seconds", "1", "--span-calls", "100"],
                       bench=tiny.bench(tmp_path / "bench"), allow_cpu=True,
                       peak=tiny.PEAK)
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    saved = tmp_path / "chiprun_out" / f"program-spans-{workload}.json"
    assert json.loads(saved.read_text()) == out
    assert out["window"]["compiles"] == 0 and out["window"]["steps"] > 0
    assert set(out["span_cost"]) == {"inactive_us", "active_us"}
    # the host's program spans are read, though the CPU has no device plane
    assert out["found"]["program_spans"] > out["window"]["steps"]
    assert out["found"]["program_ops"] == 0
    if workload == "tiny.serve":
        assert out["counters"]["admitted"] > 0
        assert out["counters"]["prefill_tokens"] > out["counters"]["admitted"]
    else:
        assert out["counters"] == {"steps": out["window"]["steps"]}
    assert "readings" not in out
