"""The program's own spans and named device programs in a profiler trace.

``Trainer.run`` and ``ServeEngine.run`` open ``llload.*`` host spans at
their layer boundaries, and their jitted programs are named
(``jit_train_step``, ``jit_serve_prefill``, ``jit_serve_decode``).  This
reduction puts the window's device idle time down to those spans and splits
its busy time by XLA module.  It sits beside ``trace.py`` and changes
nothing there: ``trace.reduce`` still reads the harness's ``chipbench.``
spans alone.  It works on plain lists, so that a test can give it a small
recorded trace:

    device  {device plane name: [(op name, start_ns, end_ns, module), ...]}
    spans   [(span name, start_ns, end_ns), ...]   ``llload.*`` and the
            harness's window span

``reduce`` gives, averaged over the devices as ``trace.reduce`` does:

- ``idle_in``: for each span name, the device-idle seconds of the window
  that overlap that name's spans (by interval overlap, each instant once);
- ``idle_unattributed``: the idle seconds that no ``llload.*`` span covers,
  the two step spans aside;
- ``busy_by_module``: operation self time summed by module;
- ``device_ops``: the heaviest operations by self time, each with its
  module.

A module is named by the text before ``(`` with ``jit_`` dropped:
``jit_serve_decode(12)`` reads ``serve_decode``.  An operation takes it from
its own ``hlo_module`` stat where the plane carries one, else from the event
on the plane's ``XLA Modules`` line that holds the operation's start.
"""
from __future__ import annotations

import bisect
import glob
import os

from chipbench.trace import DEVICE_LINES, _self_times, _union

PREFIX = "llload."
WINDOW = "chipbench.window"
STEP_SPANS = ("llload.train.step", "llload.serve.step")
MODULE_LINE = "XLA Modules"
UNKNOWN = "(no module)"

# the spans directly below each step span: disjoint in time, so that their
# idle and the unattributed idle add up to the window's idle
TOP_SPANS = {
    "train": ("llload.train.init", "llload.train.feed",
              "llload.train.dispatch", "llload.train.sync",
              "llload.train.checkpoint", "llload.monitor.publish"),
    "serve": ("llload.serve.init", "llload.serve.admit",
              "llload.serve.decode", "llload.serve.sample",
              "llload.serve.bookkeep", "llload.monitor.publish"),
}
DECODE_LOOP = ("llload.serve.decode", "llload.serve.sample",
               "llload.serve.bookkeep", "llload.monitor.publish")


def module_name(name: str) -> str:
    """``jit_serve_decode(12)`` -> ``serve_decode``."""
    name = name.split("(")[0].strip()
    return name[len("jit_"):] if name.startswith("jit_") else name


def _plane_ops(plane) -> list:
    """(op name, start_ns, end_ns, module) of a device plane's operations."""
    modules = sorted((e.start_ns, e.end_ns, module_name(e.name))
                     for line in plane.lines if line.name == MODULE_LINE
                     for e in line.events)
    starts = [m[0] for m in modules]
    out = []
    for line in plane.lines:
        if line.name not in DEVICE_LINES:
            continue
        for e in line.events:
            stats = dict(e.stats)
            if "hlo_module" in stats:
                mod = module_name(str(stats["hlo_module"]))
            else:
                i = bisect.bisect_right(starts, e.start_ns) - 1
                mod = (modules[i][2] if i >= 0 and e.start_ns < modules[i][1]
                       else UNKNOWN)
            out.append((e.name.split(" = ")[0], e.start_ns, e.end_ns, mod))
    return out


def from_planes(planes) -> tuple:
    """(device, spans) from the planes of a ``ProfileData``: the TPU
    planes' operations with their modules, and the host's ``llload.*``
    spans and window span."""
    device, spans = {}, []
    for plane in planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            ops = _plane_ops(plane)
            if ops:
                device[plane.name] = ops
        elif plane.name.startswith("/host:"):
            spans += [(e.name, e.start_ns, e.end_ns) for line in plane.lines
                      for e in line.events
                      if e.name.startswith(PREFIX) or e.name == WINDOW]
    return device, spans


def load(logdir: str) -> tuple:
    """(device, spans) from the newest ``.xplane.pb`` under ``logdir``."""
    from jax.profiler import ProfileData

    files = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        return {}, []
    return from_planes(ProfileData.from_file(
        max(files, key=os.path.getmtime)).planes)


def _overlap(a: list, b: list) -> float:
    """Length of the intersection of two sorted unions of intervals."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def reduce(device: dict, spans: list, window: str = WINDOW,
           top: int = 20) -> dict | None:
    """idle_in, idle_unattributed, busy_by_module and the ``top``
    device_ops, in seconds, averaged over the devices.  None where the
    trace holds no window span or no device operation."""
    wins = [(s, e) for name, s, e in spans if name == window]
    if not wins or not device:
        return None
    w0, w1 = wins[0]
    by_name = {}
    for name, s, e in spans:
        if name.startswith(PREFIX) and e > w0 and s < w1:
            by_name.setdefault(name, []).append((max(s, w0), min(e, w1)))
    covered = _union([iv for name, ivs in by_name.items()
                      if name not in STEP_SPANS for iv in ivs])
    by_name = {k: _union(v) for k, v in by_name.items()}
    idle_in, unattributed, by_op = {}, 0.0, {}
    for ops in device.values():
        clipped = [(max(s, w0), min(e, w1), (mod, op)) for op, s, e, mod in ops
                   if e > w0 and s < w1]
        for key, secs in _self_times(clipped):
            by_op[key] = by_op.get(key, 0.0) + secs
        busy = _union([(s, e) for s, e, _ in clipped])
        edges = [w0] + [x for iv in busy for x in iv] + [w1]
        idle = [[a, b] for a, b in zip(edges[0::2], edges[1::2]) if b > a]
        for name, ivs in by_name.items():
            idle_in[name] = idle_in.get(name, 0.0) + _overlap(idle, ivs)
        unattributed += (sum(b - a for a, b in idle)
                         - _overlap(idle, covered))
    n = len(device)
    by_module = {}
    for (mod, _), secs in by_op.items():
        by_module[mod] = by_module.get(mod, 0.0) + secs
    ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:top]
    return {"window_s": (w1 - w0) / 1e9,
            "idle_in": {k: v / n / 1e9 for k, v in sorted(
                idle_in.items(), key=lambda kv: -kv[1])},
            "idle_unattributed": unattributed / n / 1e9,
            "busy_by_module": {k: v / n / 1e9 for k, v in sorted(
                by_module.items(), key=lambda kv: -kv[1])},
            "device_ops": [[mod, op, v / n / 1e9] for (mod, op), v in ops]}


def _share(red, names) -> float | None:
    """Idle in the named spans, in % of the window; None where the program
    opened none of them."""
    if not any(n in red["idle_in"] for n in names):
        return None
    return 100.0 * sum(red["idle_in"].get(n, 0.0) for n in names) \
        / red["window_s"]


def _unattributed(red) -> float | None:
    if not red["idle_in"]:          # a program without spans: nothing read
        return None
    return 100.0 * red["idle_unattributed"] / red["window_s"]


def _module_per(red, keep, exclude, count) -> float | None:
    """Self time of module ``keep`` (or of every module but ``exclude``)
    over ``count``; None where a named module or the count is missing."""
    mods = red["busy_by_module"]
    if not count or not all(m in mods for m in (keep or exclude)):
        return None
    if keep:
        return sum(mods[m] for m in keep) / count
    return sum(v for m, v in mods.items() if m not in exclude) / count


NAMES = ("feed_idle_share.train", "feed_device_ms.train",
         "unattributed_idle_share.train", "admit_idle_share.serve",
         "decode_loop_idle_share.serve", "unattributed_idle_share.serve",
         "prefill_device_us_per_token.serve", "decode_device_ms.serve",
         "splice_device_ms.serve")


def reading(name: str, kind: str, red: dict | None,
            counters: dict) -> float | None:
    """Per-layer reading ``name`` of a cell of ``kind``, from ``reduce``'s
    output and the program's counters (train: ``steps``; serve: ``steps``,
    the decode steps, and ``admitted`` and ``prefill_tokens`` from
    ``ServeEngine.run``).  None on the other kind of cell, without a
    reduction, or where the program has no such span, module or counter."""
    if name not in NAMES:
        raise KeyError(name)
    if not name.endswith("." + kind) or not red or red["window_s"] <= 0:
        return None
    c = counters
    if name == "feed_idle_share.train":
        return _share(red, ("llload.train.feed",))
    if name == "feed_device_ms.train":
        return _scaled(1e3, _module_per(red, (), ("train_step",),
                                        c.get("steps")))
    if name.startswith("unattributed_idle_share."):
        return _unattributed(red)
    if name == "admit_idle_share.serve":
        return _share(red, ("llload.serve.admit",))
    if name == "decode_loop_idle_share.serve":
        return _share(red, DECODE_LOOP)
    if name == "prefill_device_us_per_token.serve":
        return _scaled(1e6, _module_per(red, ("serve_prefill",), (),
                                        c.get("prefill_tokens")))
    if name == "decode_device_ms.serve":
        return _scaled(1e3, _module_per(red, ("serve_decode",), (),
                                        c.get("steps")))
    return _scaled(1e3, _module_per(red, (), ("serve_prefill", "serve_decode"),
                                    c.get("admitted")))


def _scaled(k: float, x: float | None) -> float | None:
    return None if x is None else k * x


def parts(kind: str, red: dict) -> dict:
    """The idle of the spans directly below the step spans and the
    unattributed idle, in seconds: they add up to the window's idle."""
    out = {n: red["idle_in"].get(n, 0.0) for n in TOP_SPANS[kind]}
    out["unattributed"] = red["idle_unattributed"]
    return out
