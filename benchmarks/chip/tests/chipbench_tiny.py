"""A benchmark of tiny cells for CPU tests, built in a temporary directory
from files found by name, as a later cell's would be: a configuration
(Phi-3-mini's file with small widths, windowed or not), a training and a
serving mix, their limits, the real metric readers and architectures, and
one architecture that only this directory has (``wrapped_gqa``: the dense
GQA files under another name)."""
import json
import shutil
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
CHECKOUT = BENCH.parents[1]
for p in (str(CHECKOUT / "src"), str(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)

from chipbench import harness, spec  # noqa: E402

PEAK = {"flops_bf16": 5e10}

SERVE = {"kind": "serve", "slots": 4, "max_seq_len": 128, "deck": 8,
         "prompt": {"median": 32, "sigma": 0.5, "min": 8, "max": 64, "grid": 8},
         "output": {"median": 8, "sigma": 0.5, "min": 2, "max": 16},
         "calibration_steps": 10, "check_requests": 3, "reference_rows": 2}
TRAIN = {"kind": "train", "batch": 4, "seq_len": 64, "schedule_steps": 10000,
         "reference_rows": 2}
# Set as the chip cells' limits are, from CPU readings at this size (3
# seeds): the program read at most 2.5e-4, 3.3e-3 and 1.8e-3 (train) and
# 0.021 (serve); the fp8 control at least 1.0e-3 (loss), 1.4e-2 (gradient)
# and 0.30 (serve); half of each batch left out at least 1.1e-2, 5.8e-2
# and 3.5e-2.
TRAIN_LIMITS = {"loss_rel_gap": 6e-4, "grad_norm_gap": 7e-3,
                "update_norm_gap": 1e-2}
SERVE_LIMITS = {"served_logit_gap": 0.1}

# archs/wrapped_gqa.py and references/wrapped_gqa.py: their sibling
# dense_gqa.py under another name
WRAPPED = '''"""dense_gqa under another name."""
import importlib.util
import sys
from pathlib import Path

_path = Path(__file__).with_name("dense_gqa.py")
_name = f"wrapped_{_path.parent.name}_dense_gqa"
_spec = importlib.util.spec_from_file_location(_name, _path)
_mod = sys.modules[_name] = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_mod)
globals().update({k: v for k, v in vars(_mod).items()
                  if not k.startswith("__")})
'''


def _write(path: Path, obj):
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(obj))


def bench(root: Path) -> spec.Bench:
    for sub in ("metrics", "archs", "references"):
        shutil.copytree(BENCH / sub, root / sub,
                        ignore=shutil.ignore_patterns("__pycache__"))
        if sub != "metrics":
            (root / sub / "wrapped_gqa.py").write_text(WRAPPED)
    for name, tied, window, arch in (
            ("tiny", True, 40, None), ("tiny-untied", False, None, None),
            ("tiny-wrapped", True, 40, "wrapped_gqa")):
        conf = json.loads((BENCH / "configs/phi3-mini-4k-l4.json").read_text())
        conf.update(name=name, num_hidden_layers=2, hidden_size=64,
                    num_attention_heads=4, num_key_value_heads=2,
                    intermediate_size=128, vocab_size=512,
                    tie_word_embeddings=tied, sliding_window=window)
        if arch:
            conf["architecture"] = arch
        _write(root / "configs" / f"{name}.json", conf)
    _write(root / "traffic/tiny-train.json", TRAIN)
    _write(root / "traffic/tiny-serve.json", SERVE)
    workloads = [("tiny.train", "tiny", "tiny-train", TRAIN_LIMITS),
                 ("tiny.serve", "tiny", "tiny-serve", SERVE_LIMITS),
                 ("tiny-untied.serve", "tiny-untied", "tiny-serve",
                  SERVE_LIMITS),
                 ("tiny-wrapped.train", "tiny-wrapped", "tiny-train",
                  TRAIN_LIMITS),
                 ("tiny-wrapped.serve", "tiny-wrapped", "tiny-serve",
                  SERVE_LIMITS)]
    b = json.loads((CHECKOUT / "BENCHMARK.json").read_text())
    b["workloads"] = []
    for name, conf, mix, lim in workloads:
        b["workloads"].append({"name": name, "config": conf, "traffic": mix,
                               "chips": 1, "why": "CPU test"})
        _write(root / "limits" / f"{name}.json", lim)
    for m in b["end_to_end"] + b["per_layer"]:
        if "workloads" in m:
            kind = "train" if "train" in m["name"] else "serve"
            m["workloads"] = [w[0] for w in workloads if kind in w[0]]
    return spec.Bench(b, root)


def run(root: Path, workload: str, seed: int = 7, trace: int = 0,
        seconds: float = 1.0, capsys=None) -> dict:
    """One run of a tiny cell on the CPU; the result line as a dict."""
    harness.main(["--workload", workload, "--seed", str(seed), "--seconds",
                  str(seconds), "--trace", str(trace)],
                 bench=bench(root), allow_cpu=True, peak=PEAK)
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1])


def cell(root: Path, workload: str, seed: int = 7, seconds: float = 1.0):
    """A tiny cell set up and run through its window, its state let go."""
    ctx, c = harness.build(bench(root), workload, seed, allow_cpu=True,
                           peak=PEAK)
    c.setup(seconds)
    c.window()
    c.release()
    return ctx, c
