"""Run the benchmark's cells on the CPU at a tiny size.

``tiny_root`` copies the benchmark's files into a temporary root and
shrinks the model and the shapes there; ``run`` drives one cell through
``harness.run_cell`` with the chip gate replaced. The tests never
describe a TPU topology.
"""
from __future__ import annotations

import json
import shutil
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
for _p in (REPO / "src", REPO):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

from bench import harness, peaks  # noqa: E402

TINY_MODEL = dict(hidden_size=256, intermediate_size=512, num_hidden_layers=2,
                  num_attention_heads=4, num_key_value_heads=2, head_dim=64,
                  vocab_size=4096)


def cpu_gate(chips: int) -> harness.Device:
    return harness.Device("cpu", "TPU v5 lite", chips,
                          peaks.lookup("TPU v5 lite"))


def _load(p: Path):
    return json.loads(p.read_text())


def _dump(p: Path, obj) -> None:
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(json.dumps(obj, indent=1))


def tiny_root(dst: Path) -> Path:
    """A copy of BENCHMARK.json and bench/{configs,traffic,metrics} with
    a two-layer model, short sequences and a 128-wide matmul."""
    dst = Path(dst)
    (dst / "bench").mkdir(parents=True, exist_ok=True)
    shutil.copy(REPO / "BENCHMARK.json", dst / "BENCHMARK.json")
    for d in ("configs", "traffic", "metrics"):
        shutil.copytree(REPO / "bench" / d, dst / "bench" / d,
                        dirs_exist_ok=True)
    p = dst / "bench/configs/mlpipe-qwen2-0.5b.json"
    cfg = _load(p)
    cfg["model"].update(TINY_MODEL)
    cfg["serve_check"] = {"requests": 8, "block": 8}
    _dump(p, cfg)
    p = dst / "bench/traffic/train.json"
    tr = _load(p)
    for s in tr["stages"]:
        if "seq" in s:
            s["seq"] = 32 if s["kind"] == "prefill" else 64
        if s["kind"] == "decode":
            s.update(steps=4, cache=48)
        if s["kind"] == "train":
            s["steps"] = 3
    _dump(p, tr)
    p = dst / "bench/configs/paper4-matmul.json"
    cfg = _load(p)
    cfg["payload"].update(n=128, iters=4)
    _dump(p, cfg)
    return dst


def run(root: Path, workload: str, seed: int = 2_200_000_017,
        seconds: float = 0.5) -> dict:
    return harness.run_cell(Path(root), workload, seed, seconds, False,
                            time.perf_counter(), gate=cpu_gate)
