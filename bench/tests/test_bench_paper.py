"""CPU rehearsal of the paper4 cell, the chip gate, and finding a cell
that lives only in new files."""
import json
import os
import subprocess
import sys

import pytest

from bench import harness
from bench.tests import cpu_cell
from bench.tests.cpu_cell import REPO


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return cpu_cell.tiny_root(tmp_path_factory.mktemp("bench_paper"))


def assert_well_formed(result, e2e):
    assert list(result)[-1] == "checks"
    for key in ("correct", "attempted", "failed", "metrics", "device"):
        assert key in result
    assert set(result["metrics"]) == set(e2e)
    for m in result["metrics"].values():
        assert m["value"] > 0 and m["unit"]
    dev = result["device"]
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(dev)
    assert result["compiles_in_window"] == 0
    json.dumps(result)


def test_paper4_runs_through_the_control_plane(root):
    r = cpu_cell.run(root, "paper4.flood", seconds=1.0)
    assert_well_formed(r, {"setup_s", "wf_per_s", "wf_lifecycle_p95_s"})
    assert r["correct"] is True, r["checks"]
    assert r["attempted"] >= 4 and r["failed"] == 0
    assert r["checks"]["order_violations"]["value"] == 0


def test_altered_answer_is_not_correct(root, monkeypatch):
    from repro.core import payloads
    real = payloads.matmul_payload

    def altered(n, iters):
        run = real(n, iters)

        def broken(volume, task):
            run(volume, task)
            volume.put(f"{task.id}/out", volume.get(f"{task.id}/out") + 0.05)
        return broken
    monkeypatch.setattr(payloads, "matmul_payload", altered)
    r = cpu_cell.run(root, "paper4.flood", seconds=1.0)
    assert r["correct"] is False
    assert r["checks"]["matmul_gap"]["value"] > r["checks"]["matmul_gap"]["limit"]
    assert r["failed"] == r["attempted"] > 0


def test_cli_refuses_a_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, str(REPO / "bench/run.py"),
                        "--workload", "paper4.flood", "--seed", "3000000001",
                        "--seconds", "1", "--trace", "0"],
                       cwd=REPO, env=env, capture_output=True, text=True,
                       timeout=120)
    assert p.returncode != 0
    assert "needs a TPU" in p.stderr
    assert "{" not in p.stdout


def test_a_cell_in_new_files_is_found_by_name(root, tmp_path):
    """A new configuration, traffic mix and per-layer metric are files
    and BENCHMARK.json entries only; no existing file changes."""
    new = cpu_cell.tiny_root(tmp_path)
    bench = json.loads((new / "BENCHMARK.json").read_text())
    cfg = json.loads((new / "bench/configs/paper4-matmul.json").read_text())
    cfg.update(name="paper2-matmul", workflows={
        k: cfg["workflows"][k] for k in ("montage", "ligo")})
    (new / "bench/configs/paper2-matmul.json").write_text(json.dumps(cfg))
    (new / "bench/traffic/pair.json").write_text(json.dumps({
        "arrival": "serial", "repeats_cap": 500, "min_completed": 2,
        "tenants": [{"name": "a", "workflow": "montage"},
                    {"name": "b", "workflow": "ligo"}]}))
    (new / "bench/metrics/pods_per_s.py").write_text(
        "def read(rec):\n    return rec.window['pods'] / rec.window['loop_s']\n")
    bench["configs"].append({"name": "paper2-matmul", "source": "test",
                             "file": "bench/configs/paper2-matmul.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "paper2.pair", "config": "paper2-matmul",
                               "traffic": "pair", "chips": 1, "why": "test"})
    bench["per_layer"].append({"name": "pods_per_s", "unit": "pods/s",
                               "better": "higher", "source": "host_clock",
                               "layer": "payload", "moves": "wf_per_s",
                               "workloads": ["paper2.pair"]})
    (new / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = harness.load_cell(new, "paper2.pair")
    assert [m["name"] for m in cell.per_layer] == ["pods_per_s"]
    r = cpu_cell.run(new, "paper2.pair", seconds=1.0)
    assert r["correct"] is True and r["attempted"] >= 2
    reader = harness.load_reader(cell, "pods_per_s")
    assert reader(harness.Record(cell, cpu_cell.cpu_gate(1),
                                 {"pods": 10, "loop_s": 2.0})) == 5.0
