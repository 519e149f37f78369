"""The per-layer metrics read from the program's own spans
(``bench/program_spans.py``): each agrees with the span totals it is
defined by, and reads None where there are none, or no tracer."""
import gc
import sys

import pytest

from bench import harness
from bench.tests.cpu_cell import REPO, cpu_gate

PAPER = ("payload_input_ms_per_pod", "payload_compute_ms_per_pod",
         "payload_output_ms_per_pod", "cp_self_ms_per_pod",
         "host_gc_ms_per_s")
PIPELINE = ("pipeline_host_gc_ms_per_s",)
WINDOW_S = 2.5


def record(workload):
    cell = harness.load_cell(REPO, workload)
    rec = harness.Record(cell, cpu_gate(1), {}, {"window_s": WINDOW_S})
    return cell, rec


def read_all():
    out = {}
    for workload, names in (("paper4.flood", PAPER),
                            ("mlpipe.train", PIPELINE)):
        cell, rec = record(workload)
        for name in names:
            out[name] = harness.load_reader(cell, name)(rec)
    return out


@pytest.fixture(scope="module")
def snap(tmp_path_factory):
    """Span totals of a traced one-workflow run with real payloads."""
    import jax

    from repro.configs.workflows import get_workflow_spec
    from repro.core import tracing
    from repro.core.dag import make_workflow
    from repro.core.payloads import matmul_payload
    from repro.core.runner import ControlPlane
    wf = make_workflow("ligo", get_workflow_spec("ligo"))
    payload = matmul_payload(n=64, iters=2)
    for t in wf.tasks.values():
        t.payload = payload
    plane = ControlPlane("kubeadaptor", payload_mode="real")
    plane.add_stream(wf)
    with jax.profiler.trace(str(tmp_path_factory.mktemp("trace"))):
        plane.run()
        gc.collect()
    return tracing.snapshot()


def test_the_cells_list_their_span_metrics():
    for workload, names in (("paper4.flood", PAPER),
                            ("mlpipe.train", PIPELINE)):
        listed = {m["name"] for m in harness.load_cell(REPO, workload).per_layer}
        assert set(names) <= listed
    assert not set(PAPER) & {m["name"] for m in harness.load_cell(
        REPO, "mlpipe.train").per_layer}


def test_readers_agree_with_the_span_totals(snap):
    got = read_all()
    pods = snap["pod.payload"]["count"]
    for name in ("input", "compute", "output"):
        s = snap[f"payload.{name}"]
        assert s["count"] == pods
        assert got[f"payload_{name}_ms_per_pod"] == pytest.approx(
            s["total_s"] / s["count"] * 1e3)
    run = snap["sim.run"]
    assert got["cp_self_ms_per_pod"] == pytest.approx(
        (run["total_s"] - snap["pod.payload"]["total_s"]) / pods * 1e3)
    gc_ms = snap["gc"]["total_s"] * 1e3 / WINDOW_S
    assert got["host_gc_ms_per_s"] == pytest.approx(gc_ms)
    assert got["pipeline_host_gc_ms_per_s"] == pytest.approx(gc_ms)
    assert all(v > 0 for v in got.values())
    payload_ms = snap["pod.payload"]["total_s"] / pods * 1e3
    assert sum(got[f"payload_{n}_ms_per_pod"]
               for n in ("input", "compute", "output")) <= payload_ms


def test_readers_read_none_without_spans(monkeypatch):
    from repro.core import tracing
    monkeypatch.setattr(tracing, "snapshot", dict)
    assert read_all() == dict.fromkeys(PAPER + PIPELINE)


def test_a_window_without_collections_reads_no_gc_time(snap, monkeypatch):
    from repro.core import tracing
    no_gc = {k: v for k, v in snap.items() if k != "gc"}
    monkeypatch.setattr(tracing, "snapshot", lambda: no_gc)
    got = read_all()
    assert got["host_gc_ms_per_s"] == got["pipeline_host_gc_ms_per_s"] == 0.0
    assert got["payload_input_ms_per_pod"] > 0


def test_readers_read_none_without_a_tracer(monkeypatch):
    """A program that predates the tracer: the readers return None and
    raise nothing."""
    import repro.core
    monkeypatch.delattr(repro.core, "tracing", raising=False)
    monkeypatch.setitem(sys.modules, "repro.core.tracing", None)
    assert read_all() == dict.fromkeys(PAPER + PIPELINE)
