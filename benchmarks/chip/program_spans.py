#!/usr/bin/env python3
"""The program's own spans and named programs on the chip, for one cell.
The benchmark's own runs never run this.

    python3 benchmarks/chip/program_spans.py --workload NAME --seed N \
        --seconds S

In one process: the cell is built and set up as a run of the benchmark
does it; the window runs traced as a ``--trace 1`` run traces it (the
harness's spans on, the same profiler options), and the trace is reduced
twice: by ``trace.reduce``, from the harness's spans, as the benchmark's
metrics read it, and by ``program_trace.reduce``, from the program's
``llload.*`` spans and named programs.  Then the cost of one span is timed
with no profiler session and inside one.  It reports the idle of the spans
below the step spans against the window's idle, the modules' busy time
against ``busy_s``, each ``program_trace`` reading, and the mean duty the
program's monitor hook published against the measured busy share.  Prints
one JSON object and writes it to
``chiprun_out/program-spans-<workload>.json``.
"""
import argparse
import contextlib
import json
import shutil
import statistics
import sys
import tempfile
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parents[1] / "src")]

from chipbench import harness, program_trace, spec  # noqa: E402
from chipbench import spans as spans_mod  # noqa: E402
from chipbench import trace as trace_mod  # noqa: E402


def span_cost(n: int, active: bool) -> dict:
    """Microseconds per entered and left span, each kind, on this host;
    ``active``: inside a profiler session that records them."""
    import jax

    kinds = {"TraceAnnotation": lambda: jax.profiler.TraceAnnotation(
                 "llload.cost", request_id=1),
             "StepTraceAnnotation": lambda: jax.profiler.StepTraceAnnotation(
                 "llload.cost", step_num=1)}
    out = {}
    with tempfile.TemporaryDirectory() as logdir:
        if active:
            trace_mod.start(logdir)
        try:
            for kind, make in kinds.items():
                t0 = time.perf_counter()
                for _ in range(n):
                    with make():
                        pass
                out[kind] = 1e6 * (time.perf_counter() - t0) / n
        finally:
            if active:
                trace_mod.stop()
    return out


def _counters(cell, window: dict, served: list) -> tuple:
    """(counters, mean published duty) of the window's program run."""
    if cell.kind == "train":
        duties = [h["duty"] for h in cell.trainer.history
                  if h.get("duty") is not None]
        counters = {"steps": window["steps"]}
    else:
        stats = served[-1] if served else {}
        duty = stats.get("duty_mean")
        duties = [] if duty is None else [duty]
        counters = {k: stats.get(k) for k in
                    ("steps", "admitted", "prefill_tokens")}
    return counters, (statistics.mean(duties) if duties else None)


def main(argv=None, *, bench=None, allow_cpu=False, peak=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--span-calls", type=int, default=100_000)
    args = ap.parse_args(argv)

    import jax
    from jax.profiler import ProfileData

    if not allow_cpu:
        harness.use_compile_cache()
    ctx, cell = harness.build(bench or spec.Bench(), args.workload, args.seed,
                              allow_cpu, peak)
    cell.setup(args.seconds)

    served = []
    if cell.kind == "serve":        # keep what ServeEngine.run returns
        run = cell.engine.run
        cell.engine.run = lambda **kw: served.append(run(**kw)) or served[-1]
    spans = spans_mod.Spans()
    logdir = spec.CHECKOUT / ".chipbench_trace"
    shutil.rmtree(logdir, ignore_errors=True)
    with contextlib.ExitStack() as stack:
        for wrapper in cell.spans(spans):
            stack.enter_context(wrapper)
        trace_mod.start(str(logdir))
        with spans_mod.CompileClock() as clock, \
                jax.profiler.TraceAnnotation("chipbench.window"):
            window = cell.window()
        trace_mod.stop()
    cell.release()
    counters, duty = _counters(cell, window, served)

    device, host_spans, _ = trace_mod.load(str(logdir))
    old = trace_mod.reduce(device, host_spans)
    files = sorted(logdir.glob("**/*.xplane.pb"))
    planes = (list(ProfileData.from_file(str(files[-1])).planes) if files
              else [])
    events, prog_spans = program_trace.from_planes(planes)
    red = program_trace.reduce(events, prog_spans)
    del planes
    shutil.rmtree(logdir, ignore_errors=True)
    cost = {"inactive_us": span_cost(args.span_calls, False),
            "active_us": span_cost(args.span_calls, True)}

    out = {"workload": args.workload, "seed": args.seed,
           "device": ctx.device.device_kind,
           "window": {"window_s": window["window_s"], "steps": window["steps"],
                      "tokens": window["tokens"],
                      "tokens_per_s": window["tokens"] / window["window_s"],
                      "compiles": clock.programs},
           "counters": counters, "span_cost": cost,
           "found": {"device_ops": sum(map(len, device.values())),
                     "harness_spans": len(host_spans),
                     "program_ops": sum(map(len, events.values())),
                     "program_spans": len(prog_spans)}}
    if old and red:
        idle_s = old["window_s"] - old["busy_s"]
        parts = program_trace.parts(cell.kind, red)
        out.update(
            busy_s=old["busy_s"], window_s=old["window_s"],
            idle_share=100 * idle_s / old["window_s"],
            idle_by_span=old["idle_by_span"],
            idle_parts=parts,
            idle_parts_gap_points=100 * (sum(parts.values()) - idle_s)
            / old["window_s"],
            idle_in=red["idle_in"],
            busy_by_module=red["busy_by_module"],
            device_ops=red["device_ops"],
            modules_gap_share=(sum(red["busy_by_module"].values())
                               - old["busy_s"]) / old["busy_s"],
            readings={n: program_trace.reading(n, cell.kind, red, counters)
                      for n in program_trace.NAMES},
            duty_mean_published=None if duty is None else 100 * duty,
            busy_share=100 * old["busy_s"] / old["window_s"])
    line = json.dumps(out)
    print(line, flush=True)
    dest = spec.CHECKOUT / "chiprun_out"
    dest.mkdir(exist_ok=True)
    (dest / f"program-spans-{args.workload}.json").write_text(line)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
