"""Finding a cell's parts by name: its entry in ``BENCHMARK.json``, its
configuration (``configs/<name>.json``), the architecture that file names
(``archs/<a>.py`` and ``references/<a>.py``; see ``archs/__init__.py``), its
traffic mix (``traffic/<name>.json``), its limits
(``limits/<workload>.json``) and the reader of each metric
(``metrics/<name>.py``).

A later cell, mix, metric or architecture is a new file and a new entry;
nothing here names one.
"""
from __future__ import annotations

import importlib.util
import json
import sys
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parents[1]      # benchmarks/chip
CHECKOUT = BENCH_DIR.parents[1]                        # root of the checkout


class Bench:
    """The benchmark as ``BENCHMARK.json`` and the files under ``root``
    describe it."""

    def __init__(self, spec: dict | None = None, root: Path | None = None):
        self.root = Path(root) if root else BENCH_DIR
        self.spec = spec if spec is not None else json.loads(
            (CHECKOUT / "BENCHMARK.json").read_text())
        self._modules = {}

    def _json(self, sub: str, name: str) -> dict:
        return json.loads((self.root / sub / f"{name}.json").read_text())

    def workload(self, name: str) -> dict:
        for w in self.spec["workloads"]:
            if w["name"] == name:
                return w
        raise SystemExit(f"chipbench: no workload {name!r} in BENCHMARK.json")

    def config(self, name: str) -> dict:
        return self._json("configs", name)

    def traffic(self, name: str) -> dict:
        return self._json("traffic", name)

    def limits(self, workload: str) -> dict:
        return self._json("limits", workload)

    def metrics_for(self, workload: dict, trace: bool) -> list:
        """The metric entries a run of ``workload`` reports: its end-to-end
        metrics without a trace, its per-layer metrics with one."""
        name = workload["name"]
        e2e = [m for m in self.spec["end_to_end"]
               if name in m.get("workloads", [name])]
        if not trace:
            return e2e
        moved = {m["name"] for m in e2e}

        def wanted(m):
            if "workloads" in m:
                return name in m["workloads"]
            return m["moves"] in moved
        return [m for m in self.spec["per_layer"] if wanted(m)]

    def _module(self, sub: str, name: str):
        """``<sub>/<name>.py`` under the root, executed once for this
        bench; it stands in ``sys.modules`` under a name of its own, as a
        dataclass defined in it needs."""
        if (sub, name) not in self._modules:
            path = self.root / sub / f"{name}.py"
            mod_name = f"chipbench_{sub}_" + name.replace(".", "_")
            mod_spec = importlib.util.spec_from_file_location(mod_name, path)
            mod = importlib.util.module_from_spec(mod_spec)
            sys.modules[mod_name] = mod
            mod_spec.loader.exec_module(mod)
            self._modules[sub, name] = mod
        return self._modules[sub, name]

    def reader(self, metric: str):
        return self._module("metrics", metric).read

    def architecture(self, conf: dict) -> tuple:
        """(the program-facing module, the plain reference) of the
        architecture a configuration file names."""
        name = conf.get("architecture")
        if name is None:
            name = self._module("archs", "__init__").DEFAULT
        missing = [str(self.root / sub / f"{name}.py")
                   for sub in ("archs", "references")
                   if not (self.root / sub / f"{name}.py").is_file()]
        if missing:
            raise SystemExit(f"chipbench: architecture {name!r} of "
                             f"configuration {conf.get('name')!r} has no "
                             f"{' and no '.join(missing)}")
        return self._module("archs", name), self._module("references", name)
