"""Finding a cell's parts by name: its entry in ``BENCHMARK.json``, its
configuration (``configs/<name>.json``), its traffic mix
(``traffic/<name>.json``), its limits (``limits/<workload>.json``) and the
reader of each metric (``metrics/<name>.py``).

A later cell, mix or metric is a new file and a new entry; nothing here
names one.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parents[1]      # benchmarks/chip
CHECKOUT = BENCH_DIR.parents[1]                        # root of the checkout


@dataclasses.dataclass(frozen=True)
class Dims:
    """The sizes of a dense GQA decoder, in the program's terms."""
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_head: int
    d_ff: int
    vocab_size: int
    tie_embeddings: bool
    norm_eps: float
    rope_theta: float
    dtype: str
    window: int | None = None     # a query attends to keys q - k < window


# configuration file key (as in a Hugging Face config.json) -> Dims field
_KEYS = {"num_hidden_layers": "n_layers", "hidden_size": "d_model",
         "num_attention_heads": "n_heads", "num_key_value_heads": "n_kv_heads",
         "intermediate_size": "d_ff", "vocab_size": "vocab_size",
         "tie_word_embeddings": "tie_embeddings", "rms_norm_eps": "norm_eps",
         "rope_theta": "rope_theta", "torch_dtype": "dtype"}


class Bench:
    """The benchmark as ``BENCHMARK.json`` and the files under ``root``
    describe it."""

    def __init__(self, spec: dict | None = None, root: Path | None = None):
        self.root = Path(root) if root else BENCH_DIR
        self.spec = spec if spec is not None else json.loads(
            (CHECKOUT / "BENCHMARK.json").read_text())

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

    def reader(self, metric: str):
        path = self.root / "metrics" / f"{metric}.py"
        mod_spec = importlib.util.spec_from_file_location(
            "chipbench_metric_" + metric.replace(".", "_"), path)
        mod = importlib.util.module_from_spec(mod_spec)
        mod_spec.loader.exec_module(mod)
        return mod.read


def dims_of(conf: dict) -> Dims:
    vals = {field: conf[key] for key, field in _KEYS.items()}
    vals["d_head"] = conf.get("head_dim",
                              conf["hidden_size"] // conf["num_attention_heads"])
    vals["window"] = conf.get("sliding_window")
    return Dims(**vals)


def model_config(conf: dict):
    """The program's ``ModelConfig`` for a configuration file: the program's
    registered architecture named by ``program_base``, with every size the
    file states put in.  A ``sliding_window`` makes every layer a windowed
    one (the program's ``attn_local``), as the config states it."""
    from repro.configs import get_config

    if conf.get("hidden_act") != "silu":
        raise SystemExit("chipbench: only SwiGLU (hidden_act silu) blocks")
    d = dims_of(conf)
    kind = "attn_local" if d.window else "attn"
    return dataclasses.replace(
        get_config(conf["program_base"]), name=conf["name"],
        n_layers=d.n_layers, d_model=d.d_model, n_heads=d.n_heads,
        n_kv_heads=d.n_kv_heads, d_head=d.d_head, d_ff=d.d_ff,
        vocab_size=d.vocab_size, tie_embeddings=d.tie_embeddings,
        norm_eps=d.norm_eps, rope_theta=d.rope_theta, act="swiglu",
        dtype=d.dtype, layer_pattern=(kind,), mlp_pattern=("mlp",),
        qkv_bias=False, embed_scale=1.0, attn_window=d.window,
        rope_theta_local=None, attn_logit_softcap=None)
