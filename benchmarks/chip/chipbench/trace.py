"""From a profiler trace to device busy time, idle share, the heaviest
device operations and the longest idle gaps.

The reduction works on plain lists, so that a test can give it a small
recorded trace:

    device  {device plane name: [(op name, start_ns, end_ns), ...]}
    spans   [(span name, start_ns, end_ns), ...]   the harness's host spans

Busy time is the union of a device's operation intervals inside the
window; the idle share is 1 - busy / window, averaged over the devices
used.  Operations nest (a loop holds its body's operations), so each is
ranked by its self time: its length less that of the operations inside it.
An operation is named by its HLO name (``%fusion.12``), the text before
`` = `` in the trace.  An idle gap is a stretch of the window in which a
device ran nothing; it is named after the innermost harness span that
covers its midpoint, or ``host (no span)``.
"""
from __future__ import annotations

import glob
import os

SPAN_PREFIX = "chipbench."
DEVICE_LINES = ("XLA Ops",)


def start(logdir: str):
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0      # the harness's spans say what the host does
    opts.host_tracer_level = 2
    jax.profiler.start_trace(logdir, profiler_options=opts)


def stop():
    import jax

    jax.profiler.stop_trace()


def load(logdir: str) -> tuple:
    """(device, spans, inventory) from the newest ``.xplane.pb`` under
    ``logdir``; the inventory counts the events of every plane's lines."""
    from jax.profiler import ProfileData

    files = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        return {}, [], {}
    pd = ProfileData.from_file(max(files, key=os.path.getmtime))
    device, spans, inventory = {}, [], {}
    for plane in pd.planes:
        inventory[plane.name] = {line.name: sum(1 for _ in line.events)
                                 for line in plane.lines}
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            evs = [(e.name.split(" = ")[0], e.start_ns, e.end_ns)
                   for line in plane.lines if line.name in DEVICE_LINES
                   for e in line.events]
            if evs:
                device[plane.name] = evs
        elif plane.name.startswith("/host:"):
            spans += [(e.name, e.start_ns, e.end_ns) for line in plane.lines
                      for e in line.events if e.name.startswith(SPAN_PREFIX)]
    return device, spans, inventory


def _union(intervals: list) -> list:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _self_times(events: list) -> list:
    """(name, self time) of each (start, end, name): its length less that of
    the events nested in it."""
    events = sorted(events, key=lambda ev: (ev[0], -ev[1]))
    selfs = [e - s for s, e, _ in events]
    stack = []
    for i, (s, e, _) in enumerate(events):
        while stack and events[stack[-1]][1] <= s:
            stack.pop()
        if stack:
            selfs[stack[-1]] -= min(e, events[stack[-1]][1]) - s
        stack.append(i)
    return [(ev[2], t) for ev, t in zip(events, selfs)]


def _span_at(spans: list, t: float) -> str:
    best = None
    for name, s, e in spans:
        if s <= t <= e and (best is None or e - s < best[2] - best[1]):
            best = (name, s, e)
    return best[0][len(SPAN_PREFIX):] if best else "host (no span)"


def reduce(device: dict, spans: list, window: str = "chipbench.window",
           top: int = 10) -> dict | None:
    """busy_s and window_s averaged over the devices, with the top device
    operations by total time and the longest idle gaps.  None where the
    trace holds no window span or no device operation in it."""
    wins = [(s, e) for name, s, e in spans if name == window]
    if not wins or not device:
        return None
    w0, w1 = wins[0]
    busy, by_op, gaps = [], {}, []
    for evs in device.values():
        clipped = [(max(s, w0), min(e, w1), n) for n, s, e in evs
                   if e > w0 and s < w1]
        for n, secs in _self_times(clipped):
            by_op[n] = by_op.get(n, 0.0) + secs
        merged = _union([(s, e) for s, e, _ in clipped])
        busy.append(sum(e - s for s, e in merged))
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                gaps.append((_span_at(spans, (a + b) / 2), (b - a) / 1e9))
    n = len(device)
    ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:top]
    gaps.sort(key=lambda g: -g[1])
    return {"busy_s": sum(busy) / n / 1e9, "window_s": (w1 - w0) / 1e9,
            "device_ops": [[k, v / n / 1e9] for k, v in ops],
            "idle_gaps": [list(g) for g in gaps[:top]],
            "idle_by_span": _idle_by_span(gaps, n)}


def _idle_by_span(gaps: list, n: int) -> dict:
    out = {}
    for name, secs in gaps:
        out[name] = out.get(name, 0.0) + secs / n
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))
