"""One run of one cell:

    python3 benchmarks/chip/run.py --workload NAME --seed N --seconds S --trace 0|1

Set-up (timed as ``setup_s`` from the process's start) builds the cell and
compiles and warms every program the window uses; the window then runs
with no compile in it (the count is printed).  Once it has closed, the
device's peak memory is read, the program's state is let go, and the cell
compares what the window's program produced with the plain reference.
The last line of standard output is one JSON object; the numbers compared,
each beside its limit, are the last lines of standard error and the last
key of that object.  A run that finds no TPU, or fewer chips than the cell
asks for, exits non-zero and prints no result.
"""
from __future__ import annotations

import argparse
import contextlib
import importlib
import json
import shutil
import sys
import time
import types

from chipbench import spec as spec_mod

T_START = time.perf_counter()


def log(msg: str):
    print(f"[chipbench] {msg}", file=sys.stderr, flush=True)


def use_compile_cache():
    """JAX's persistent cache at a fixed directory inside the checkout, every
    program in it, however quick its compile."""
    import jax

    jax.config.update("jax_compilation_cache_dir",
                      str(spec_mod.CHECKOUT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def build(bench, workload: str, seed: int, allow_cpu=False, peak=None):
    """The cell's context and its cell object, after the device check."""
    import jax

    w = bench.workload(workload)
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu" and not allow_cpu:
        log(f"needs a TPU, but JAX found {dev.platform!r} ({dev.device_kind})")
        raise SystemExit(2)
    if len(devices) < w["chips"]:
        log(f"the cell asks for {w['chips']} chips, JAX found {len(devices)}")
        raise SystemExit(2)
    if peak is None:
        from chipbench.peaks import peak as lookup

        peak = lookup(dev.device_kind)
    conf = bench.config(w["config"])
    mix = bench.traffic(w["traffic"])
    arch, ref = bench.architecture(conf)
    ctx = types.SimpleNamespace(
        bench=bench, workload=w, conf=conf, mix=mix, seed=seed, arch=arch,
        ref=ref, dims=arch.dims(conf), cfg=arch.program_config(conf),
        limits=bench.limits(workload), peak=peak, log=log,
        key=jax.random.PRNGKey(seed),
        # the program's own seeds (its feed, its sampler) take 31 bits
        program_seed=seed % 2 ** 31,
        # off the TPU the program has no table entry: give it the peak
        program_peak=None if dev.platform == "tpu" else peak["flops_bf16"],
        device=dev, n_devices=len(devices))
    cell = importlib.import_module(f"chipbench.{mix['kind']}").Cell(ctx)
    return ctx, cell


def memory_peak(dev) -> int | None:
    stats = dev.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def main(argv=None, *, bench=None, allow_cpu=False, peak=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import jax

    from chipbench import spans as spans_mod
    from chipbench import trace as trace_mod

    if not allow_cpu:
        use_compile_cache()
    bench = bench or spec_mod.Bench()
    ctx, cell = build(bench, args.workload, args.seed, allow_cpu, peak)
    cell.setup(args.seconds)
    setup_s = time.perf_counter() - T_START

    spans = spans_mod.Spans()
    logdir = spec_mod.CHECKOUT / ".chipbench_trace"
    with contextlib.ExitStack() as stack:
        if args.trace:
            for wrapper in cell.spans(spans):
                stack.enter_context(wrapper)
            shutil.rmtree(logdir, ignore_errors=True)
            trace_mod.start(str(logdir))
        with spans_mod.CompileClock() as clock, \
                jax.profiler.TraceAnnotation("chipbench.window"):
            window = cell.window()
        if args.trace:
            trace_mod.stop()
    log(f"window: {window['window_s']:.3f} s, {window['steps']} steps, "
        f"{window['tokens']} tokens; {clock.programs} backend compiles in "
        f"the window ({clock.seconds:.3f} s)")
    mem = memory_peak(ctx.device)
    cell.release()

    reduced = None
    if args.trace:
        device_events, host_spans, inventory = trace_mod.load(str(logdir))
        shutil.rmtree(logdir, ignore_errors=True)
        log(f"trace planes: {json.dumps(inventory)}")
        reduced = trace_mod.reduce(device_events, host_spans)
        if reduced:
            log(f"trace: busy {reduced['busy_s']:.6f} s of "
                f"{reduced['window_s']:.6f} s; idle by span "
                f"{json.dumps(reduced['idle_by_span'])}")

    run = types.SimpleNamespace(kind=cell.kind, setup_s=setup_s, trace=reduced,
                                spans=spans.seconds, peak=ctx.peak, **window)
    metrics = {}
    for m in bench.metrics_for(ctx.workload, bool(args.trace)):
        value = bench.reader(m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    # a number the cell's limits file sets no limit for is read, not compared
    readings = cell.readings()
    checks = {k: {"value": v, "limit": ctx.limits[k]}
              for k, v in readings.items() if k in ctx.limits}
    for k in readings.keys() - checks.keys():
        log(f"reading {k}: {readings[k]!r}, not compared (no limit)")
    correct = window["failed"] == 0 and all(
        c["value"] <= c["limit"] for c in checks.values())

    device = {"platform": ctx.device.platform, "kind": ctx.device.device_kind,
              "count": ctx.n_devices, "memory_peak_bytes": mem}
    result = {"correct": correct, "attempted": window["attempted"],
              "failed": window["failed"], "metrics": metrics, "device": device}
    if reduced:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    result["checks"] = checks
    log(f"attempted {window['attempted']}, failed {window['failed']}")
    for k, c in checks.items():
        print(f"check {k}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0
