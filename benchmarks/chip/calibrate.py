#!/usr/bin/env python3
"""What the limits of ``correct`` and the bounds are set from, for one cell.
The benchmark's own runs never run this.

    python3 benchmarks/chip/calibrate.py readings --workload NAME \
        --seeds 1,2,... --seconds S [--controls K]

In one process, for each seed: the cell is built and set up as a run of the
benchmark does it, its window runs for ``--seconds`` (long enough to finish
the mix's longest requests), and the numbers compared are read against the
reference.  For the first ``--controls`` seeds the control (the reference
in fp8 in the program's place) and, for training, a planted fault (half of
each batch left out) are read too.  One JSON line per seed.

    python3 benchmarks/chip/calibrate.py sets --workload NAME \
        --seeds 1,2,3,4,5,6 [--sets 2] [--seconds 30] [--warm SEED] \
        [--trace-seeds 7,8,9]

Repeated runs of the benchmark, each a process of its own started with the
command line it records; this process never touches JAX.  ``--warm`` runs
once first, so that the compile cache is full; each set runs every seed
once, in order, the same seeds in every set; then each ``--trace-seeds``
seed runs once with ``--trace 1``.  For each end-to-end metric a set's
spread is the distance between its first and third quartiles
(``statistics.quantiles(values, n=4)``) as a share of its median.

Both write every line, as it comes, to
``chiprun_out/<mode>-<workload>.json``.
"""
import argparse
import gc
import json
import statistics
import subprocess
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parents[1] / "src")]

from chipbench import harness, spec  # noqa: E402


def _save(mode: str, workload: str, obj):
    out = spec.CHECKOUT / "chiprun_out"
    out.mkdir(exist_ok=True)
    (out / f"{mode}-{workload}.json").write_text(json.dumps(obj))


def readings(args) -> int:
    harness.use_compile_cache()
    bench = spec.Bench()
    rows = []
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        ctx, cell = harness.build(bench, args.workload, seed)
        cell.setup(args.seconds)
        window = cell.window()
        cell.release()
        row = {"seed": seed, "failed": window["failed"],
               "attempted": window["attempted"],
               "window_s": window["window_s"], "program": cell.readings()}
        if i < args.controls:
            row.update(cell.control_readings())
        print(json.dumps(row), flush=True)
        rows.append(row)
        _save("readings", args.workload, rows)
        del ctx, cell
        gc.collect()
    return 0


def _one(workload, seed, seconds, trace) -> dict:
    cmd = [sys.executable, "benchmarks/chip/run.py", "--workload", workload,
           "--seed", str(seed), "--seconds", f"{seconds:g}",
           "--trace", str(trace)]
    t0 = time.perf_counter()
    p = subprocess.run(cmd, cwd=spec.CHECKOUT, capture_output=True, text=True)
    try:
        result = json.loads(p.stdout.strip().splitlines()[-1])
    except (IndexError, json.JSONDecodeError):
        result = None
    err = p.stderr.splitlines()
    if result:
        err = [ln for ln in err if ln.startswith(("[chipbench]", "check "))]
    return {"cmd": " ".join(cmd[1:]), "seed": seed, "trace": trace,
            "rc": p.returncode, "wall_s": time.perf_counter() - t0,
            "result": result, "stderr": err[-30:]}


def spread(values) -> float:
    q1, med, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / med


def sets(args) -> int:
    runs = []

    def record(r, label):
        runs.append(dict(r, label=label))
        _save("sets", args.workload, {"runs": runs})
        res = r["result"] or {}
        print(json.dumps({"label": label, "cmd": r["cmd"], "rc": r["rc"],
                          "wall_s": round(r["wall_s"], 1),
                          "correct": res.get("correct"),
                          "metrics": {k: v["value"] for k, v in
                                      res.get("metrics", {}).items()},
                          "checks": res.get("checks"),
                          "device": res.get("device")}), flush=True)
        print("\n".join(r["stderr"][-8:]), flush=True)

    seeds = [int(s) for s in args.seeds.split(",") if s]
    if args.warm is not None:
        record(_one(args.workload, args.warm, args.seconds, 0), "warm")
    for s in range(args.sets):
        for seed in seeds:
            record(_one(args.workload, seed, args.seconds, 0), f"set{s + 1}")
    for seed in (int(x) for x in args.trace_seeds.split(",") if x):
        record(_one(args.workload, seed, args.seconds, 1), "trace")

    summary = {}
    for s in range(args.sets):
        got = [r["result"] for r in runs
               if r["label"] == f"set{s + 1}" and r["result"]]
        for name in sorted({k for g in got for k in g["metrics"]}):
            vals = [g["metrics"][name]["value"] for g in got
                    if name in g["metrics"]]
            if len(vals) >= 2:
                summary.setdefault(name, {})[f"set{s + 1}"] = {
                    "median": statistics.median(vals), "spread": spread(vals),
                    "values": vals}
    print(json.dumps({"summary": summary}), flush=True)
    _save("sets", args.workload, {"runs": runs, "summary": summary})
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    sub = ap.add_subparsers(dest="mode", required=True)
    r = sub.add_parser("readings")
    r.add_argument("--controls", type=int, default=3)
    r.add_argument("--seconds", type=float, required=True)
    s = sub.add_parser("sets")
    s.add_argument("--sets", type=int, default=2)
    s.add_argument("--seconds", type=float, default=30)
    s.add_argument("--warm", type=int)
    s.add_argument("--trace-seeds", default="")
    for p in (r, s):
        p.add_argument("--workload", required=True)
        p.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    return readings(args) if args.mode == "readings" else sets(args)


if __name__ == "__main__":
    raise SystemExit(main())
