"""The program's host spans (``repro.core.tracing``): nothing recorded
and no gc hook with the profiler off; under a profiler session, one
``pod.payload`` and one of each ``payload.*`` span per pod, self times
that leave out the child spans, garbage collections as ``gc`` spans, and
totals that cover one session. ``matmul_payload`` builds its operand
once per closure (one ``payload.build`` span) and keeps it resident."""
import gc
import glob
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

from repro.configs.workflows import get_workflow_spec
from repro.core import tracing
from repro.core.cluster import SUCCEEDED
from repro.core.dag import Task, Workflow, make_workflow
from repro.core.payloads import matmul_payload
from repro.core.runner import ControlPlane

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench.ref.matmul import payload_input  # noqa: E402
from bench.trace import SPAN_PRIORITY  # noqa: E402
PAYLOAD_SPANS = ("payload.input", "payload.compute", "payload.output")


def real_plane(wf, payload=None):
    payload = payload or matmul_payload(n=64, iters=2)
    for t in wf.tasks.values():
        t.payload = payload
    plane = ControlPlane("kubeadaptor", payload_mode="real")
    plane.add_stream(wf)
    return plane


def montage():
    return make_workflow("montage", get_workflow_spec("montage"))


def pair():
    return Workflow("pair", {"a": Task(id="a", outputs=["b"]),
                             "b": Task(id="b", inputs=["a"])})


def run_plane(wf, payload=None) -> int:
    """Runs one workflow with real payloads; returns the pods run."""
    plane = real_plane(wf, payload)
    plane.run()
    pods = plane.cluster.pod_log
    assert pods and all(p.phase == SUCCEEDED for p in pods)
    return len(pods)


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """One traced one-workflow run with a forced collection: the pods
    run, the session's totals and the trace file."""
    d = tmp_path_factory.mktemp("trace")
    run_plane(pair())                          # compile outside the trace
    with jax.profiler.trace(str(d)):
        n = run_plane(montage())
        gc.collect()
    snap = tracing.snapshot()
    xplane, = glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)
    return n, snap, xplane


def test_profiler_off_records_nothing():
    before = tracing.snapshot()
    run_plane(montage())
    gc.collect()
    assert tracing.snapshot() == before
    assert tracing._gc_hook not in gc.callbacks


def test_one_span_of_each_kind_per_pod(traced):
    n, snap, _ = traced
    assert snap["sim.run"]["count"] == 1
    assert snap["pod.payload"]["count"] == n
    for name in PAYLOAD_SPANS:
        assert snap[name]["count"] == n


def test_self_time_leaves_out_child_spans(traced):
    _, snap, _ = traced
    pod = snap["pod.payload"]
    inner = sum(snap[name]["total_s"] for name in PAYLOAD_SPANS)
    assert 0 < inner <= pod["total_s"]
    assert pod["self_s"] == pytest.approx(pod["total_s"] - inner, abs=1e-9)
    run = snap["sim.run"]
    assert 0 < run["self_s"] <= run["total_s"]
    assert run["self_s"] == pytest.approx(run["total_s"] - pod["total_s"],
                                          abs=1e-9)
    for s in snap.values():
        assert 0 < s["max_s"] <= s["total_s"]


def test_forced_collection_is_a_gc_span(traced):
    _, snap, _ = traced
    assert snap["gc"]["count"] >= 1
    assert snap["gc"]["self_s"] == snap["gc"]["total_s"] > 0


def test_spans_land_in_the_trace_host_plane(traced):
    n, snap, xplane = traced
    from jax.profiler import ProfileData
    seen, args = {}, []
    for plane in ProfileData.from_file(xplane).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name in snap:
                    seen[e.name] = seen.get(e.name, 0) + 1
                    if e.name == "pod.payload":
                        args.append({k: v for k, v in e.stats})
    assert seen == {k: v["count"] for k, v in snap.items()}
    assert len(args) == n
    assert all(a["namespace"].startswith("wf-montage") and a["task"]
               for a in args)


def test_no_span_name_is_a_benchmark_span(traced):
    _, snap, _ = traced
    assert set(snap) == {"sim.run", "pod.payload", "gc", "payload.build",
                         *PAYLOAD_SPANS}
    assert not set(snap) & set(SPAN_PRIORITY)


def test_totals_cover_the_latest_session(tmp_path):
    with jax.profiler.trace(str(tmp_path / "one")):
        run_plane(montage())
    with jax.profiler.trace(str(tmp_path / "two")):
        n = run_plane(pair())
    snap = tracing.snapshot()
    assert n == 2
    assert snap["pod.payload"]["count"] == 2
    assert snap["sim.run"]["count"] == 1
    gc.collect()                     # the hook finds the profiler off
    assert tracing._gc_hook not in gc.callbacks
    assert tracing.snapshot() == snap


def test_operand_is_built_once_per_closure(tmp_path):
    payload = matmul_payload(n=64, iters=2)
    with jax.profiler.trace(str(tmp_path / "first")):
        n = run_plane(montage(), payload)
    snap = tracing.snapshot()
    assert n > 1
    assert snap["payload.build"]["count"] == 1
    assert snap["payload.input"]["count"] == n
    build, inp = snap["payload.build"], snap["payload.input"]
    assert build["total_s"] <= inp["total_s"] - inp["self_s"] + 1e-9
    with jax.profiler.trace(str(tmp_path / "again")):
        n = run_plane(montage(), payload)
    snap = tracing.snapshot()
    assert "payload.build" not in snap
    assert snap["payload.input"]["count"] == n


def _captured(payload, outputs):
    """``payload``, keeping each pod's output as it writes it."""
    def run(volume, task):
        payload(volume, task)
        outputs.append(volume.get(f"{task.id}/out"))
    return run


def test_outputs_match_a_fresh_operand():
    payload = matmul_payload(n=64, iters=2)
    outputs = []
    n = run_plane(montage(), _captured(payload, outputs))
    body = inspect.getclosurevars(payload).nonlocals["body"]
    want = np.asarray(body(jnp.asarray(payload_input(64)))[0, :4])
    assert len(outputs) == n > 1
    for got in outputs:
        assert np.array_equal(got, want)


def test_resident_operand_survives_the_pods():
    payload = matmul_payload(n=64, iters=2)
    assert run_plane(montage(), payload) > 1
    run_plane(pair(), payload)
    operand = inspect.getclosurevars(payload).nonlocals["operand"]
    assert not operand.is_deleted()
    assert np.array_equal(np.asarray(operand), payload_input(64))


def test_virtual_run_keeps_jax_out():
    code = (
        "import gc, json, sys\n"
        "from repro.configs.workflows import get_workflow_spec\n"
        "from repro.core import tracing\n"
        "from repro.core.dag import make_workflow\n"
        "from repro.core.runner import ControlPlane\n"
        "plane = ControlPlane('kubeadaptor')\n"
        "plane.add_stream(make_workflow('montage', "
        "get_workflow_spec('montage')))\n"
        "plane.run()\n"
        "gc.collect()\n"
        "print(json.dumps({'jax': 'jax' in sys.modules,\n"
        "                  'totals': tracing.snapshot(),\n"
        "                  'hooked': tracing._gc_hook in gc.callbacks,\n"
        "                  'events': plane.sim.events_processed}))\n")
    env = dict(os.environ, PYTHONPATH=str(ROOT / "src"))
    p = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode == 0, p.stderr
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out == {"jax": False, "totals": {}, "hooked": False,
                   "events": out["events"]}
    assert out["events"] > 0
