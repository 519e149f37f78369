"""The real-payload path keeps the device in view: payload timing waits
for device work, one process holds the chip, nothing falls back to the
CPU behind the caller's back, and the chip smoke refuses to run without
a TPU."""
import inspect
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import sim as sim_mod
from repro.core.dag import Task, Workflow
from repro.core.payloads import fn_payload
from repro.core.runner import run_experiment
from repro.core.shard import ShardedControlPlane

ROOT = Path(__file__).resolve().parent.parent


@pytest.fixture
def blocked(monkeypatch):
    """Records every object handed to jax.block_until_ready."""
    seen = []
    real = jax.block_until_ready

    def record(x):
        seen.append(x)
        return real(x)

    monkeypatch.setattr(jax, "block_until_ready", record)
    return seen


def test_sharded_plane_refuses_real_payloads_in_processes():
    with pytest.raises(ValueError, match="processes=False"):
        ShardedControlPlane(2, payload_mode="real", processes=True)
    plane = ShardedControlPlane(2, payload_mode="real", processes=False)
    assert not plane.processes


def test_measure_wall_blocks_on_fn_payload_result(blocked):
    out = jnp.arange(4.0) * 2
    payload = fn_payload(lambda: out)
    task = Task(id="t")
    dur = sim_mod.measure_wall(lambda: payload(None, task))
    assert dur >= 0.0
    assert len(blocked) == 1 and blocked[0] is out


def test_measure_wall_skips_block_for_virtual_payloads(blocked):
    assert sim_mod.measure_wall(lambda: None) >= 0.0
    assert blocked == []


def test_real_mode_pod_duration_waits_for_device(blocked):
    results = {}

    def thunk():
        results["y"] = jnp.ones((64, 64)) @ jnp.ones((64, 64))
        return {"y": results["y"]}

    tasks = {"a": Task(id="a", outputs=["b"], payload=fn_payload(thunk)),
             "b": Task(id="b", inputs=["a"], payload=fn_payload(thunk))}
    wf = Workflow("dev", tasks)
    res = run_experiment("kubeadaptor", wf, payload_mode="real")
    assert res.metrics.order_consistent(wf.with_instance(0))
    assert len(blocked) == 2
    assert all(b["y"] is not None for b in blocked)
    np.testing.assert_allclose(np.asarray(results["y"]), 64.0)


def test_kernel_defaults_run_natively():
    from repro.kernels.flash_attention import flash_attention
    from repro.kernels.ssd_scan import ssd_scan
    for fn in (flash_attention, ssd_scan):
        assert inspect.signature(fn).parameters["interpret"].default is False


@pytest.mark.parametrize("op", ["attention", "ssd"])
def test_ops_has_no_silent_auto_backend(op):
    from repro.kernels import ops
    with pytest.raises(ValueError):
        if op == "attention":
            q = jnp.zeros((1, 8, 1, 8))
            ops.attention(q, q, q, impl="auto")
        else:
            x = jnp.zeros((1, 8, 1, 4))
            d = jnp.zeros((1, 8, 1))
            bc = jnp.zeros((1, 8, 4))
            ops.ssd(x, d, jnp.zeros((1,)), bc, bc, impl="auto")
    assert "impl" not in {
        n for n, p in inspect.signature(getattr(ops, op)).parameters.items()
        if p.default is not inspect.Parameter.empty}


def test_runconfig_has_no_unread_kernel_switch():
    from repro.models import RunConfig
    assert not hasattr(RunConfig(), "use_pallas")


def _run(args, env_extra, cwd=ROOT):
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_COMPILATION_CACHE_DIR",)}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT / "src"))
    env.update(env_extra)
    return subprocess.run([sys.executable] + args, cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_chip_smoke_refuses_cpu():
    r = _run([str(ROOT / "chip_smoke.py")], {})
    assert r.returncode != 0
    assert "needs a TPU" in r.stderr and "platform='cpu'" in r.stderr
    assert '"ok"' not in r.stdout


_CACHE_PROBE = """
import jax
from repro.runtime.compile_cache import use_compile_cache
print(use_compile_cache())
print(jax.config.jax_compilation_cache_dir)
"""


def test_compile_cache_from_outside_is_left_alone(tmp_path):
    r = _run(["-c", _CACHE_PROBE],
             {"JAX_COMPILATION_CACHE_DIR": str(tmp_path)})
    assert r.returncode == 0, r.stderr
    assert r.stdout.split() == [str(tmp_path), str(tmp_path)]


def test_compile_cache_defaults_to_fixed_repo_path():
    r = _run(["-c", _CACHE_PROBE], {})
    assert r.returncode == 0, r.stderr
    expect = str(ROOT / ".jax_cache")
    assert r.stdout.split() == [expect, expect]
