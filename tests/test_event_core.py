"""10k-workflow event core (ISSUE 3): exactness pins + satellite fixes.

The pod-lifecycle fast path, the calendar event queue, and event-driven
usage accounting must not move a single scheduling decision.  These
tests pin:

* calendar-queue vs heap pop-order equivalence (property test + a
  deterministic mixed workload), and full-scenario binding equivalence
  across queue backends;
* fast vs chained lifecycle: identical binding sequences, workflow
  records, and watch-visible timestamps;
* ``events_per_pod`` <= 7 on the smoke stress scenario (the 10k-tier
  budget; the fast path actually lands near 2);
* ``Sim.run(until=...)`` parks the clock at the horizon even when the
  queue drains early, while ``last_event_t`` keeps the drain time;
* exact O(1) ``used()`` totals vs the node scan, and event-driven
  usage accounting agreeing with the 0.5 s sampler;
* ``on_retry_exhausted="fail-workflow"`` quarantining one poisoned
  workflow instead of tearing down the run;
* exact arrival-trace replay through the gateway and ControlPlane.
"""
import itertools
import json
import random
from pathlib import Path

import pytest

from repro.configs.workflows import get_workflow_spec, wide_fanout
from repro.core import calibration as cal
from repro.core.cluster import RUNNING, Cluster, PodObj
from repro.core.dag import make_workflow
from repro.core.runner import ControlPlane
from repro.core.sim import CalendarQueue, Event, HeapQueue, Sim
from repro.core.stats import StepAccumulator

EXAMPLE_TRACE = Path(__file__).resolve().parent.parent / "examples" / \
    "trace_mixed.json"


# ---------------------------------------------------------------------------
# queue backends: exact (t, seq) pop order
# ---------------------------------------------------------------------------
def _drive(delays, pop_every=3):
    """Feed both backends the same push/pop schedule; return pop logs."""
    hq, cq = HeapQueue(), CalendarQueue()
    seq = itertools.count()
    ev = Event(lambda: None, (), "", False)
    now = 0.0
    out_h, out_c = [], []

    def pop_one(until=None):
        nonlocal now
        a, b = hq.pop_due(until), cq.pop_due(until)
        assert (a is None) == (b is None)
        if a is not None:
            assert a[:2] == b[:2]
            now = a[0]
            out_h.append(a[:2])
            out_c.append(b[:2])

    for i, d in enumerate(delays):
        t, s = now + d, next(seq)
        hq.push(t, s, ev)
        cq.push(t, s, ev)
        if i % pop_every == 0:
            pop_one()
        if i % 17 == 0:
            pop_one(until=now + d / 2)    # horizon peek: may return None
    while len(hq):
        assert len(hq) == len(cq)
        pop_one()
    assert len(cq) == 0
    return out_h, out_c


def test_queue_backends_identical_deterministic():
    rng = random.Random(0)
    # the sim's bimodal mix: same-instant batches, control-plane
    # latencies, pod durations, far-future daemons
    choices = [0.0, 0.0, 0.02, 0.05, 0.08, 0.25, 1.15, 1.2, 10.0, 13.4,
               30.0, 64.5, 500.0, 5000.0]
    delays = [rng.choice(choices) for _ in range(5000)]
    out_h, out_c = _drive(delays)
    assert out_h == out_c and len(out_h) == 5000


def test_queue_backends_identical_property():
    hypothesis = pytest.importorskip("hypothesis")
    st = pytest.importorskip("hypothesis.strategies")

    @hypothesis.settings(max_examples=200, deadline=None)
    @hypothesis.given(st.lists(st.one_of(
        st.just(0.0),
        st.floats(min_value=0.0, max_value=0.5),
        st.floats(min_value=0.0, max_value=3000.0)),
        min_size=1, max_size=300))
    def check(delays):
        out_h, out_c = _drive(delays)
        assert out_h == out_c and len(out_h) == len(delays)

    check()


def test_sim_queue_selection():
    assert Sim(queue="heap").queue_name == "heap"
    assert Sim(queue="calendar").queue_name == "calendar"
    with pytest.raises(ValueError):
        Sim(queue="wat")


@pytest.mark.parametrize("backend", ["calendar", "heap"])
def test_declined_horizon_pop_leaves_queue_exact(backend):
    """A bounded run that pops nothing must not disturb pop order for
    events pushed afterwards below the peeked time (regression: the
    calendar cursor used to commit its advance on a declined peek)."""
    sim = Sim(queue=backend)
    order = []
    sim.after(500.0, lambda: order.append(("a", sim.t)))
    sim.run(until=10.0)              # peeks t=500, pops nothing
    assert sim.t == 10.0 and sim.events_processed == 0
    sim.after(30.0, lambda: order.append(("b", sim.t)))   # below the peek
    sim.after(505.0, lambda: order.append(("c", sim.t)))
    sim.run()
    assert order == [("b", 40.0), ("a", 500.0), ("c", 515.0)]
    assert sim.last_event_t == 515.0


def test_sim_run_parks_clock_at_horizon_on_drain():
    """Satellite: run(until=...) sets t = until even when the queue
    drains before the horizon; last_event_t keeps the drain time."""
    sim = Sim()
    sim.after(3.0, lambda: None)
    sim.run(until=100.0)
    assert sim.t == 100.0
    assert sim.last_event_t == 3.0
    # horizon hit: pending event survives, clock stops at the horizon
    sim2 = Sim()
    sim2.after(50.0, lambda: None)
    sim2.run(until=10.0)
    assert sim2.t == 10.0 and sim2.events_processed == 0
    sim2.run(until=60.0)
    assert sim2.last_event_t == 50.0 and sim2.events_processed == 1
    # no horizon: clock stays on the last event
    sim3 = Sim()
    sim3.after(2.0, lambda: None)
    sim3.run()
    assert sim3.t == 2.0


def test_sim_run_counts_events_before_a_payload_error():
    """An exception out of an event (a payload's error, a benchmark
    window closing) unwinds run(); its counters still cover the events
    processed before the raise."""
    sim = Sim()
    ran = []

    def payload(i):
        if i == 3:
            raise RuntimeError("payload failed")
        ran.append(i)

    for i in range(6):
        sim.at(float(i), payload, args=(i,))
    with pytest.raises(RuntimeError, match="payload failed"):
        sim.run()
    assert ran == [0, 1, 2]
    assert sim.events_processed == 3
    assert sim.run_wall_s > 0.0 and sim.run_cpu_s >= 0.0
    sim.run()                                  # resumes after the raise
    assert ran == [0, 1, 2, 4, 5] and sim.events_processed == 5


# ---------------------------------------------------------------------------
# cross-layer equivalence: fast vs chained lifecycle, calendar vs heap
# ---------------------------------------------------------------------------
def _stress_plane(**kw):
    plane = ControlPlane("kubeadaptor", admission_policy="fair-share",
                         cluster_cfg=cal.PaperCluster(n_nodes=3), seed=11,
                         **kw)
    mont = make_workflow("montage", get_workflow_spec("montage"))
    fan = make_workflow("fan", wide_fanout(width=12))
    plane.add_stream(mont, repeats=2, tenant="a", arrival="concurrent",
                     concurrency=2, weight=2.0)
    plane.add_stream(fan, repeats=3, tenant="b", arrival="poisson",
                     rate=0.2, burst=2, weight=1.0)
    return plane


def _run_traced(plane):
    seq = []
    orig = plane.cluster._bind

    def record(pod, node):
        seq.append(f"{pod.namespace}/{pod.name}->{node.name}"
                   f"@{plane.sim.now():.4f}")
        orig(pod, node)

    plane.cluster._bind = record
    res = plane.run(horizon_s=500_000)
    records = {k: (r.ns_created, r.ns_deleted, sorted(r.starts),
                   sorted(r.finishes.items()), r.retries)
               for k, r in res.metrics.workflows.items()}
    return seq, records, res


@pytest.mark.parametrize("kw", [
    {"lifecycle": "chained"},
    {"queue": "heap"},
    {"queue": "heap", "lifecycle": "chained"},
])
def test_fast_calendar_run_matches_fallback_modes(kw):
    """The fast lifecycle on the calendar queue must reproduce the
    chained/heap run event for event: same binding sequence, same
    workflow records (watch-visible timestamps included)."""
    seq_fast, rec_fast, _ = _run_traced(_stress_plane())
    seq_ref, rec_ref, _ = _run_traced(_stress_plane(**kw))
    assert seq_fast == seq_ref
    assert rec_fast == rec_ref


def test_chained_lifecycle_costs_more_events():
    """The fast path must actually collapse events, not just relabel
    them: the same scenario costs strictly fewer sim events."""
    _, _, res_fast = _run_traced(_stress_plane())
    _, _, res_ref = _run_traced(_stress_plane(lifecycle="chained"))
    assert res_fast.cluster.pods_created == res_ref.cluster.pods_created
    # sparse scenario, so amortization is modest here; the dense-tier
    # budget is pinned by test_events_per_pod_smoke_regression
    assert res_fast.sim.events_processed < 0.8 * res_ref.sim.events_processed


def test_events_per_pod_smoke_regression():
    """ISSUE 3 budget: <= 7 sim events per pod on the smoke stress
    scenario (pre-fast-path cost was ~8-15)."""
    bench_scale = pytest.importorskip("benchmarks.bench_scale")
    rec = bench_scale.run_policy("fifo", 50, 20, seed=42)
    assert rec["completed_workflows"] == 50
    assert rec["events_per_pod"] is not None
    assert rec["events_per_pod"] <= 7.0, rec


# ---------------------------------------------------------------------------
# event-driven usage accounting
# ---------------------------------------------------------------------------
def test_step_accumulator_exact():
    acc = StepAccumulator(t0=0.0)
    acc.set(1.0, 100)     # level 0 for [0,1)
    acc.set(3.0, 300)     # level 100 for [1,3)
    acc.set(4.0, 0)       # level 300 for [3,4)
    acc.close(10.0)       # level 0 for [4,10)
    assert acc.total_time == 10.0
    assert acc.mean() == pytest.approx((0 + 100 * 2 + 300 * 1 + 0 * 6) / 10.0)
    assert acc.peak == 300
    assert acc.changes == 3
    # time-weighted percentiles: 70% of the run sits at level 0
    assert acc.percentile(50) == 0
    assert acc.percentile(75) == 100
    assert acc.percentile(99) == 300
    acc.close(10.0)       # idempotent
    assert acc.total_time == 10.0


def test_used_totals_match_node_scan():
    plane = ControlPlane("kubeadaptor", seed=3)
    wf = make_workflow("ligo", get_workflow_spec("ligo"))
    checks = []

    def probe():
        checks.append(plane.cluster.used() == plane.cluster.used_scan())
        if plane.sim.now() < 120:
            plane.sim.after(2.5, probe, daemon=True)

    plane.sim.after(1.0, probe, daemon=True)
    plane.gateway.load([wf.with_instance(0)])
    plane.run(horizon_s=500_000)
    assert len(checks) > 20 and all(checks)
    assert plane.cluster.used() == (0, 0)


def test_usage_event_mode_matches_sampler():
    def run(usage_mode):
        plane = ControlPlane("kubeadaptor", seed=6, usage_mode=usage_mode)
        wf = make_workflow("montage", get_workflow_spec("montage"))
        plane.gateway.load([wf.with_instance(i) for i in range(3)])
        return plane.run(horizon_s=500_000)

    sampled = run("sampled")
    event = run("event")
    # removing the 0.5s polling daemon must not move any decision
    assert {k: r.ns_deleted for k, r in sampled.metrics.workflows.items()} \
        == {k: r.ns_deleted for k, r in event.metrics.workflows.items()}
    # ... but it must remove the daemon's events
    assert event.sim.events_processed < sampled.sim.events_processed
    s_cpu, s_mem = sampled.metrics.overall_usage()
    e_cpu, e_mem = event.metrics.overall_usage()
    assert e_cpu == pytest.approx(s_cpu, rel=0.05)
    assert e_mem == pytest.approx(s_mem, rel=0.05)
    summary = event.metrics.usage_summary()
    assert summary["cpu"]["basis"] == "event"
    assert summary["cpu"]["peak_rate"] == pytest.approx(
        sampled.metrics.usage_summary()["cpu"]["peak_rate"], rel=0.05)
    # per-tenant step accumulators carry the bound-cpu breakdown
    assert "default" in event.metrics.tenant_cpu_accs
    assert event.metrics.tenant_cpu_accs["default"].peak > 0


def test_usage_event_mode_unaffected_by_parked_horizon():
    """Regression: with sample_resources=False nothing calls
    stop_sampling, and the accumulators used to be closed at the run
    horizon (sim.t) instead of the drain time — diluting the mean by
    horizon/makespan."""
    def run(sample_resources):
        plane = ControlPlane("kubeadaptor", seed=6, usage_mode="event",
                             sample_resources=sample_resources)
        wf = make_workflow("montage", get_workflow_spec("montage"))
        plane.gateway.load([wf.with_instance(0)])
        return plane.run(horizon_s=500_000)

    wired = run(True)       # stop_sampling freezes at gateway drain
    bare = run(False)       # closed lazily on read, at last_event_t —
    #                         a few cleanup events past the drain callback
    assert bare.sim.t == 500_000.0
    b_cpu, b_mem = bare.metrics.overall_usage()
    w_cpu, w_mem = wired.metrics.overall_usage()
    assert b_cpu == pytest.approx(w_cpu, rel=1e-2)
    assert b_mem == pytest.approx(w_mem, rel=1e-2)
    assert b_cpu > 0.01     # was ~1300x diluted before the fix


# ---------------------------------------------------------------------------
# retry exhaustion: fail one workflow, not the whole run
# ---------------------------------------------------------------------------
def _poisoned_plane(on_exhausted):
    params = cal.ClusterParams(on_retry_exhausted=on_exhausted)
    plane = ControlPlane("kubeadaptor", params=params, seed=9)
    wf = make_workflow("fan", wide_fanout(width=4))
    plane.add_stream(wf, repeats=2, tenant="t", arrival="concurrent",
                     concurrency=2)
    doomed = wf.with_tenant("t").with_instance(0).namespace()

    def sabotage(pod):
        # kill every incarnation of the doomed workflow's pods
        if pod.namespace == doomed and pod.phase == RUNNING:
            plane.cluster.fail_pod(pod.namespace, pod.name)

    plane.informers.pods.add_handlers(on_update=sabotage)
    return plane, wf, doomed


def test_retry_exhausted_default_raises():
    plane, _wf, _doomed = _poisoned_plane("raise")
    with pytest.raises(RuntimeError, match="exceeded retries"):
        plane.run(horizon_s=500_000)


def test_retry_exhausted_fail_workflow_quarantines():
    plane, wf, doomed = _poisoned_plane("fail-workflow")
    res = plane.run(horizon_s=500_000)
    m = res.metrics
    recs = list(m.workflows.values())
    failed = [r for r in recs if r.failed]
    ok = [r for r in recs if not r.failed]
    assert len(failed) == 1 and "exceeded" in failed[0].failure
    assert len(ok) == 1 and ok[0].ns_deleted > 0        # sibling finished
    assert failed[0].ns_deleted > 0                     # namespace cleaned
    assert doomed not in res.cluster.namespaces
    assert not any(ns == doomed for ns, _ in res.cluster.pods)
    summary = m.tenant_summary()["t"]
    assert summary["failed"] == 1.0 and summary["completed"] == 1.0
    assert res.gateway.pending() == 0                   # gateway not stuck


# ---------------------------------------------------------------------------
# arrival-trace replay
# ---------------------------------------------------------------------------
def test_gateway_trace_replays_exactly():
    from repro.core.injector import GRPC_LATENCY, WorkflowGateway

    sim = Sim()
    got = []
    gw = WorkflowGateway(sim, lambda wf: got.append(
        (round(sim.now(), 4), wf.tenant, wf.name, wf.instance)))
    records = [
        {"t": 5.0, "tenant": "b", "topology": "w"},
        {"t": 0.5, "tenant": "a", "topology": "w"},
        {"t": 5.0, "tenant": "a", "topology": "w"},   # tie: file order
    ]
    wf = make_workflow("w", wide_fanout(width=2))
    gw.load_trace(records, make=lambda topo: wf)
    gw.start()
    sim.run(until=100.0)
    lat = round(GRPC_LATENCY, 4)
    assert got == [(round(0.5 + lat, 4), "a", "w", 0),
                   (round(5.0 + lat, 4), "b", "w", 1),
                   (round(5.0 + lat, 4), "a", "w", 2)]


def test_control_plane_trace_end_to_end():
    trace = json.loads(EXAMPLE_TRACE.read_text())
    plane = ControlPlane("kubeadaptor", admission_policy="priority",
                         cluster_cfg=cal.PaperCluster(n_nodes=3), seed=1,
                         usage_mode="event", sample_mode="streaming")
    plane.add_trace(trace["arrivals"], tenants=trace.get("tenants"))
    res = plane.run(horizon_s=500_000)
    n = len(trace["arrivals"])
    done = [r for r in res.metrics.workflows.values() if r.ns_deleted > 0]
    assert len(done) == n
    # tenant shares from the trace header registered on the arbiter
    assert res.arbiter.tenants["sci"].priority == 5
    assert res.arbiter.tenants["adhoc"].weight == 1.0
    # open-loop replay: submission times equal the recorded arrivals
    arrivals = sorted(float(a["t"]) for a in trace["arrivals"])
    submitted = sorted(r.submitted_at for r in done)
    from repro.core.injector import GRPC_LATENCY
    for t_rec, t_sub in zip(arrivals, submitted):
        assert t_sub == pytest.approx(t_rec + GRPC_LATENCY, abs=1e-9)
