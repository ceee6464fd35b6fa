"""The trace reduction on a small recorded trace: one device, a window of
100 us, operations of known lengths and host spans around them."""
import pytest

from chipbench import trace

# (name, start_ns, end_ns) on one device; a loop holds two operations
DEVICE = {"/device:TPU:0": [
    ("%fusion.1", 0, 5_000),           # before the window: not counted
    ("%fusion.1", 10_000, 30_000),
    ("%while.4", 40_000, 70_000),
    ("%dot.2", 42_000, 50_000),        # inside the loop
    ("%copy.3", 52_000, 68_000),       # inside the loop
    ("%dot.2", 95_000, 120_000),       # runs past the window's end
]}
SPANS = [
    ("chipbench.window", 5_000, 105_000),
    ("chipbench.decode", 8_000, 32_000),
    ("chipbench.prefill_splice", 30_000, 45_000),
    ("chipbench.select", 72_000, 90_000),
    ("not.ours", 0, 200_000),
]


def test_busy_and_idle():
    r = trace.reduce(DEVICE, SPANS)
    # busy: 10..30, 40..70, 95..105 -> 20 + 30 + 10 = 60 us of 100 us
    assert r["busy_s"] == pytest.approx(60e-6)
    assert r["window_s"] == pytest.approx(100e-6)
    # self times: the loop's 30 us less the 8 + 16 us inside it
    assert dict((k, v) for k, v in r["device_ops"]) == pytest.approx(
        {"%fusion.1": 20e-6, "%dot.2": 18e-6, "%copy.3": 16e-6,
         "%while.4": 6e-6})
    assert [k for k, _ in r["device_ops"]] == [
        "%fusion.1", "%dot.2", "%copy.3", "%while.4"]


def test_gaps_named_after_the_innermost_span():
    r = trace.reduce(DEVICE, SPANS)
    # gaps: 5..10 (window only), 30..40 (prefill_splice), 70..95 (select)
    assert r["idle_gaps"] == [["select", pytest.approx(25e-6)],
                              ["prefill_splice", pytest.approx(10e-6)],
                              ["window", pytest.approx(5e-6)]]
    assert r["idle_by_span"] == pytest.approx(
        {"select": 25e-6, "prefill_splice": 10e-6, "window": 5e-6})


def test_two_devices_are_averaged():
    dev = dict(DEVICE)
    dev["/device:TPU:1"] = [("%dot.2", 5_000, 105_000)]
    r = trace.reduce(dev, SPANS)
    assert r["busy_s"] == pytest.approx((60e-6 + 100e-6) / 2)


def test_nothing_to_read():
    assert trace.reduce({}, SPANS) is None
    assert trace.reduce(DEVICE, [("chipbench.decode", 0, 1)]) is None
