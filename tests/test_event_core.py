"""The event core: exactness pins + satellite fixes.

The sim's heap queue, the batched pod lifecycle and event dispatch,
and event-driven usage accounting must not move a single scheduling
decision.  These tests pin:

* exact ``(t, seq)`` pop order against a sorted reference (a
  deterministic mixed workload and a property test), horizon stops
  and daemons included;
* the stress scenario's workflow records (watch-visible timestamps)
  and its event count against the one-event-per-hop lifecycle; its
  binding sequence is pinned in tests/test_scale_core.py;
* same-instant emits and pod create/delete calls sharing one event;
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
import hashlib
import itertools
import json
import random
from pathlib import Path

import pytest

from repro.configs.workflows import get_workflow_spec, wide_fanout
from repro.core import calibration as cal
from repro.core.cluster import RUNNING, Cluster, PodObj
from repro.core.dag import make_workflow
from repro.core.events import EventRegistry
from repro.core.runner import ControlPlane
from repro.core.sim import Sim
from repro.core.stats import StepAccumulator
from tests.test_scale_core import _stress_plane

EXAMPLE_TRACE = Path(__file__).resolve().parent.parent / "examples" / \
    "trace_mixed.json"


# ---------------------------------------------------------------------------
# the sim's queue: exact (t, seq) pop order
# ---------------------------------------------------------------------------
# the sim's bimodal mix: same-instant batches, control-plane latencies,
# pod durations, far-future daemons
DELAY_MIX = [0.0, 0.0, 0.02, 0.05, 0.08, 0.25, 1.15, 1.2, 10.0, 13.4,
             30.0, 64.5, 500.0, 5000.0]


def _drive(delays, horizons=()):
    """Feed ``delays`` to a Sim, scheduling each from inside an event
    (``after(0)`` included) so the queue grows and drains; every pop is
    checked against the minimum of a plain reference set of
    ``(t, seq)`` keys.  Each horizon in ``horizons`` is a bounded run
    (``run(until=now + h)``); a final unbounded run follows.  Returns
    the popped keys in order.  Every 7th event is a daemon."""
    sim = Sim()
    pending = {}                       # (t, seq) -> daemon
    popped = []
    seq = itertools.count()
    todo = iter(delays)

    def schedule(n):
        for d in itertools.islice(todo, n):
            key = (sim.t + d, next(seq))
            daemon = key[1] % 7 == 6
            pending[key] = daemon
            sim.after(d, fire, daemon=daemon, args=(key,))

    def fire(key):
        assert key == min(pending) and sim.t == key[0]
        del pending[key]
        popped.append(key)
        schedule(2)

    schedule(16)
    for h in horizons:
        until = sim.t + h
        sim.run(until=until)
        assert sim.t == until
        live = [k for k, daemon in pending.items() if not daemon]
        # stopped at the horizon, or with only daemons live
        assert not live or min(pending)[0] > until
    while True:
        sim.run()
        assert all(pending.values())   # only daemons outlive a run
        n = len(pending)
        schedule(16)                   # feed the rest from outside
        if len(pending) == n:
            break
    assert sim.events_processed == len(popped)
    return popped


def test_sim_pops_in_t_seq_order():
    rng = random.Random(0)
    delays = [rng.choice(DELAY_MIX) for _ in range(5000)]
    popped = _drive(delays, horizons=(0.0, 0.03, 1.0, 7.5, 40.0, 600.0))
    # daemons still pending when the last live event ran never pop
    assert 4000 < len(popped) <= 5000
    ties = sum(a[0] == b[0] for a, b in zip(popped, popped[1:]))
    assert ties > 500                  # same-instant FIFO ties exercised


def test_sim_pops_in_t_seq_order_property():
    hypothesis = pytest.importorskip("hypothesis")
    st = pytest.importorskip("hypothesis.strategies")

    @hypothesis.settings(max_examples=200, deadline=None)
    @hypothesis.given(
        st.lists(st.one_of(st.just(0.0),
                           st.floats(min_value=0.0, max_value=0.5),
                           st.floats(min_value=0.0, max_value=3000.0)),
                 min_size=1, max_size=300),
        st.lists(st.floats(min_value=0.0, max_value=100.0), max_size=4))
    def check(delays, horizons):
        _drive(delays, horizons=horizons)

    check()


def test_declined_horizon_pop_leaves_queue_exact():
    """A bounded run that pops nothing must not disturb pop order for
    events pushed afterwards below the peeked time."""
    sim = Sim()
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
# batched dispatch and pod lifecycle: pinned stress scenario, event costs
# ---------------------------------------------------------------------------
# sha256 over repr(sorted(records)) of the stress scenario's workflow
# records, and the scenario's sim event count on the one-event-per-hop
# lifecycle; both recorded on the commit that still had that lifecycle
# (the batched lifecycle reproduced its records exactly)
STRESS_RECORDS_DIGEST = \
    "3d2a9150affc9f8243425365718d8324c249b3740317325163e2ca7cce783db5"
STRESS_EVENTS_ONE_PER_HOP = 1387


def _stress_records():
    res = _stress_plane().run(horizon_s=500_000)
    records = {k: (r.ns_created, r.ns_deleted, sorted(r.starts),
                   sorted(r.finishes.items()), r.retries)
               for k, r in res.metrics.workflows.items()}
    return records, res


def test_stress_workflow_records_pinned():
    """Every watch-visible timestamp of the stress scenario's workflows
    stays where the one-event-per-hop lifecycle put it."""
    records, _ = _stress_records()
    assert len(records) == 5
    digest = hashlib.sha256(repr(sorted(records.items())).encode()).hexdigest()
    assert digest == STRESS_RECORDS_DIGEST


def test_stress_events_below_one_per_hop():
    """The batched lifecycle collapses events, not just relabels them:
    the stress scenario costs under 0.8x its one-event-per-hop count
    (sparse scenario, so amortization is modest; the dense-tier budget
    is test_events_per_pod_smoke_regression)."""
    _, res = _stress_records()
    assert res.cluster.pods_created == 84
    assert res.sim.events_processed < 0.8 * STRESS_EVENTS_ONE_PER_HOP


def test_same_instant_emits_share_one_event():
    sim = Sim()
    reg = EventRegistry(sim)
    calls = []
    reg.register("x", lambda v: calls.append(("a", v)))
    reg.register("x", lambda v: calls.append(("b", v)))
    reg.register("y", lambda v: calls.append(("c", v)))

    def emitter():
        reg.emit("x", 1)
        reg.emit("y", 2)
        reg.emit("z", 9)               # no subscriber: nothing queued
        reg.emit("x", 3)

    sim.at(1.0, emitter)
    sim.run()
    assert calls == [("a", 1), ("b", 1), ("c", 2), ("a", 3), ("b", 3)]
    assert sim.events_processed == 2   # the emitter and one flush
    assert sim.last_event_t == 1.0


def test_emit_during_flush_opens_new_batch():
    """A nested emit queues behind the flush it was issued from: the
    rest of the current batch runs first, then a second flush event."""
    sim = Sim()
    reg = EventRegistry(sim)
    calls = []
    reg.register("x", lambda: (calls.append("x1"), reg.emit("y")))
    reg.register("x", lambda: calls.append("x2"))
    reg.register("y", lambda: calls.append("y"))
    sim.at(2.0, lambda: reg.emit("x"))
    sim.at(2.0, lambda: calls.append("later"))
    sim.run()
    assert calls == ["later", "x1", "x2", "y"]
    assert sim.events_processed == 4   # 2 scheduled + 2 flushes
    assert sim.last_event_t == 2.0


class _NotedSim(Sim):
    """Sim that records the note of every event scheduled on it."""

    def __init__(self):
        super().__init__()
        self.notes = []

    def at(self, t, fn, note="", daemon=False, args=()):
        self.notes.append(note)
        super().at(t, fn, note, daemon=daemon, args=args)


def test_same_instant_pod_calls_share_one_apiserver_event():
    sim = _NotedSim()
    cluster = Cluster(sim)
    cluster.create_namespace("ns1")
    sim.run()
    created, deleted = [], []
    for i in range(3):
        cluster.create_pod(PodObj(name=f"p{i}", namespace="ns1",
                                  task_id=f"p{i}", workflow="w",
                                  cpu_m=100, mem_mi=100, duration_s=1e9),
                           cb=lambda pod: created.append(pod.name))
    assert sim.notes.count("pod-create") == 1
    sim.run(until=sim.now() + 5)
    assert created == ["p0", "p1", "p2"]
    assert cluster.api_calls == 4
    for name in ("p2", "p0", "p1"):
        cluster.delete_pod("ns1", name,
                           cb=lambda pod: deleted.append(pod.name))
    assert sim.notes.count("pod-delete") == 1
    sim.run(until=sim.now() + 5)
    assert sim.notes.count("pod-remove") == 1
    assert deleted == ["p2", "p0", "p1"]
    assert cluster.api_calls == 7 and not cluster.pods


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
