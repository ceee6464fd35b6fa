import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape) on the production
meshes and extract memory / cost / collective statistics for the roofline.

The two lines above MUST precede any jax import: jax locks the device count
at first initialization, and the dry-run needs 512 placeholder host devices
to build the (pod=2, data=16, model=16) mesh.  (Smoke tests and benchmarks
never import this module, so they keep seeing 1 device.)

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen1.5-4b \
        --shape train_4k [--multi-pod]
    PYTHONPATH=src python -m repro.launch.dryrun --all --out results/dryrun
"""
import argparse        # noqa: E402
import dataclasses     # noqa: E402
import json            # noqa: E402
import sys             # noqa: E402
import time            # noqa: E402
import traceback       # noqa: E402

import jax             # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.configs import (SHAPES, get_config, input_specs,  # noqa: E402
                           shape_applicable)
from repro.configs.archs import ASSIGNED  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.launch.sharding import (batch_shardings, cache_shardings,  # noqa: E402
                                   hint_context, param_shardings)
from repro.models import model as model_lib  # noqa: E402
from repro.roofline.analysis import roofline  # noqa: E402
from repro.train.train_step import (default_opt_cfg,  # noqa: E402
                                    init_train_state_shape, make_train_step)


# --------------------------------------------------------------------------
# Step builders: (fn, example_args, in_shardings, donate_argnums)
# --------------------------------------------------------------------------


def build_cell(cfg, shape, mesh):
    specs = input_specs(cfg, shape)
    dt = jnp.dtype(cfg.dtype)

    if shape.kind == "train":
        opt_cfg = default_opt_cfg(cfg)
        step = make_train_step(cfg, opt_cfg)
        state = init_train_state_shape(cfg, opt_cfg)
        batch = {k: v for k, v in specs.items()}
        args = (state, batch)
        shardings = (param_shardings(mesh, state), batch_shardings(mesh, batch))
        return step, args, shardings, (0,)

    params = model_lib.init_params_shape(cfg, dtype=dt)
    p_sh = param_shardings(mesh, params)

    if shape.kind == "prefill":
        def prefill_fn(params, batch):
            return model_lib.prefill(params, cfg, batch["tokens"],
                                     batch.get("frontend"))
        batch = dict(specs)
        args = (params, batch)
        return prefill_fn, args, (p_sh, batch_shardings(mesh, batch)), ()

    if shape.kind == "decode":
        def serve_step(params, caches, token, cache_len):
            return model_lib.decode_step(params, cfg, token, caches,
                                         cache_len)
        caches = specs["caches"]
        args = (params, caches, specs["token"], specs["cache_len"])
        shardings = (p_sh, cache_shardings(mesh, caches),
                     batch_shardings(mesh, specs["token"]),
                     jax.sharding.NamedSharding(
                         mesh, jax.sharding.PartitionSpec()))
        return serve_step, args, shardings, (1,)

    raise ValueError(shape.kind)


# --------------------------------------------------------------------------
# One cell
# --------------------------------------------------------------------------


def _compile_cell(cfg, shape, mesh, *, unroll: bool):
    from contextlib import nullcontext

    from repro.models.scan_util import unroll_scans

    ctx = unroll_scans() if unroll else nullcontext()
    with jax.set_mesh(mesh), hint_context(mesh), ctx:
        fn, args, shardings, donate = build_cell(cfg, shape, mesh)
        jfn = jax.jit(fn, in_shardings=shardings, donate_argnums=donate)
        lowered = jfn.lower(*args)
        compiled = lowered.compile()
    return compiled


def _extract_cost(compiled) -> dict:
    cost_list = compiled.cost_analysis()
    cost = cost_list[0] if isinstance(cost_list, (list, tuple)) else cost_list
    cost = dict(cost) if cost else {}
    from repro.roofline.analysis import parse_collective_bytes

    coll = parse_collective_bytes(compiled.as_text())
    return {
        "flops": float(cost.get("flops", 0.0)),
        "bytes": float(cost.get("bytes accessed", 0.0)),
        "collective": {k: v for k, v in coll.items() if k != "_op_counts"},
        "op_counts": coll.get("_op_counts"),
    }


def _reduced_depth(cfg, periods: int):
    return dataclasses.replace(
        cfg, name=f"{cfg.name}",
        n_layers=cfg.n_dense_layers + cfg.period * periods + cfg.n_remainder)


def probe_costs(cfg, shape, mesh) -> dict:
    """Exact per-device cost via two unrolled reduced-depth compiles.

    cost_analysis counts while-loop bodies once, so the scanned full model
    under-reports by ~n_periods.  Costs are affine in the period count
    (identical periods), so cost(P) = c1 + (P-1) * (c2 - c1) is exact.
    """
    P = cfg.n_periods
    if P <= 2:
        c = _extract_cost(_compile_cell(cfg, shape, mesh, unroll=True))
        c["probe"] = f"unrolled-full(P={P})"
        return c
    c1 = _extract_cost(_compile_cell(_reduced_depth(cfg, 1), shape, mesh,
                                     unroll=True))
    c2 = _extract_cost(_compile_cell(_reduced_depth(cfg, 2), shape, mesh,
                                     unroll=True))

    def affine(a, b):
        return a + (P - 1) * (b - a)

    coll = {k: affine(c1["collective"][k], c2["collective"][k])
            for k in c1["collective"]}
    return {
        "flops": affine(c1["flops"], c2["flops"]),
        "bytes": affine(c1["bytes"], c2["bytes"]),
        "collective": coll,
        "op_counts": c2.get("op_counts"),
        "probe": f"two-point(P=1,2 -> {P})",
    }


def run_cell(arch: str, shape_name: str, *, multi_pod: bool,
             verbose: bool = True, flags=None) -> dict:
    from contextlib import nullcontext

    from repro.models.perf_flags import PerfFlags, perf_flags

    flags = flags or PerfFlags()
    with perf_flags(flags):
        return _run_cell_inner(arch, shape_name, multi_pod=multi_pod,
                               verbose=verbose, flags=flags)


def _run_cell_inner(arch: str, shape_name: str, *, multi_pod: bool,
                    verbose: bool, flags) -> dict:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    ok, reason = shape_applicable(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape_name, "multi_pod": multi_pod,
                "status": "skipped", "reason": reason}

    mesh = make_production_mesh(multi_pod=multi_pod)
    n_dev = mesh.size
    t0 = time.time()
    # 1) full-config compile (scanned): proves lowering + memory analysis
    compiled = _compile_cell(cfg, shape, mesh, unroll=False)
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    mem_info = {}
    if mem is not None:
        for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                     "temp_size_in_bytes", "alias_size_in_bytes",
                     "generated_code_size_in_bytes"):
            mem_info[attr] = getattr(mem, attr, None)

    # 2) cost probes (unrolled, depth-extrapolated): exact FLOPs/bytes/comm
    t1 = time.time()
    cost = probe_costs(cfg, shape, mesh)
    t_probe = time.time() - t1

    n_tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode"
                                     else 1)
    mf = model_lib.model_flops(cfg, n_tokens, training=(shape.kind == "train"))
    hlo_stub = ""  # collective bytes already extracted by the probes
    terms = roofline({"flops": cost["flops"], "bytes accessed": cost["bytes"]},
                     hlo_stub, n_devices=n_dev, model_flops_global=mf)
    # overwrite collective numbers with probe-extrapolated values
    from repro.roofline import hw
    coll_bytes = sum(cost["collective"].values())
    terms.collective_bytes = coll_bytes
    terms.collective_s = coll_bytes / hw.ICI_BW_PER_LINK
    terms.collective_breakdown = {**cost["collective"],
                                  "op_counts": cost.get("op_counts")}
    tmap = {"compute": terms.compute_s, "memory": terms.memory_s,
            "collective": terms.collective_s}
    terms.dominant = max(tmap, key=tmap.get)
    t_lower, t_compile = 0.0, t_compile

    result = {
        "arch": arch, "shape": shape_name, "multi_pod": multi_pod,
        "status": "ok", "mesh": dict(mesh.shape),
        "n_devices": n_dev,
        "perf_flags": flags.active(),
        "compile_s": round(t_compile, 2), "probe_s": round(t_probe, 2),
        "cost_probe": cost.get("probe"),
        "memory_analysis": mem_info,
        "flops_per_device": terms.flops,
        "hbm_bytes_per_device": terms.hbm_bytes,
        "collective_bytes_per_device": terms.collective_bytes,
        "collective_breakdown": terms.collective_breakdown,
        "compute_s": terms.compute_s,
        "memory_s": terms.memory_s,
        "collective_s": terms.collective_s,
        "dominant": terms.dominant,
        "model_flops_global": mf,
        "useful_flops_ratio": terms.useful_ratio,
        "params": model_lib.count_params(cfg),
        "params_active": model_lib.count_params_analytic(cfg, True),
    }
    if verbose:
        print(f"[dryrun] {arch} x {shape_name} "
              f"({'multi' if multi_pod else 'single'}-pod {n_dev} chips): "
              f"compile {t_compile:.1f}s probe {t_probe:.1f}s "
              f"[{cost.get('probe')}]")
        print(f"  memory_analysis: {mem_info}")
        print(f"  flops/dev={terms.flops:.3e} hbm/dev={terms.hbm_bytes:.3e} "
              f"coll/dev={terms.collective_bytes:.3e}")
        print(f"  terms: compute={terms.compute_s * 1e3:.2f}ms "
              f"memory={terms.memory_s * 1e3:.2f}ms "
              f"collective={terms.collective_s * 1e3:.2f}ms "
              f"-> dominant={terms.dominant} "
              f"useful={terms.useful_ratio:.2f}")
    return result


def cells(archs=None, shapes=None):
    for arch in (archs or ASSIGNED):
        cfg = get_config(arch)
        for shape_name in (shapes or list(SHAPES)):
            yield arch, shape_name


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--flags", default="",
                    help="comma-separated perf flags (see models/perf_flags)")
    args = ap.parse_args(argv)

    from repro.models.perf_flags import PerfFlags

    flags = PerfFlags.parse(args.flags)
    suffix = ("__" + "+".join(flags.active())) if flags.active() else ""

    os.makedirs(args.out, exist_ok=True)
    archs = [args.arch] if args.arch else None
    shapes = [args.shape] if args.shape else None
    meshes = [False, True] if (args.both_meshes or args.all) else \
        [args.multi_pod]

    failures = []
    for arch, shape_name in cells(archs, shapes):
        for mp in meshes:
            tag = f"{arch}__{shape_name}__{'mp' if mp else 'sp'}{suffix}"
            path = os.path.join(args.out, tag + ".json")
            if os.path.exists(path):
                print(f"[dryrun] {tag}: cached")
                continue
            try:
                res = run_cell(arch, shape_name, multi_pod=mp, flags=flags)
            except Exception as e:  # noqa: BLE001
                traceback.print_exc()
                res = {"arch": arch, "shape": shape_name, "multi_pod": mp,
                       "status": "error", "error": repr(e)}
                failures.append(tag)
            with open(path, "w") as f:
                json.dump(res, f, indent=2, default=str)
    if failures:
        print(f"[dryrun] FAILURES: {failures}")
        return 1
    print("[dryrun] all requested cells passed")
    return 0


if __name__ == "__main__":
    sys.exit(main())
