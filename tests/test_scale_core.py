"""Scale-out event core (ISSUE 2): exactness pins + satellite fixes.

The perf refactor (indexed cluster state, native/bulk shuffle, batched
watch fan-out, specialized admission walks, informer aggregates) must
not move a single scheduling decision. These tests pin:

* the disordered scheduler's pod->node binding sequence for fixed
  seeds — hashes recorded against the pre-refactor core;
* ExactShuffler draw-stream equivalence with ``random.shuffle`` on
  every backend;
* specialized admission walks vs the generic re-sort loop;
* informer aggregates vs a full cache scan;
* zero apiserver cost of listers, resync deletion reconciliation, the
  pvc informer cache, sim note diagnostics, and streaming metrics.
"""
import hashlib
import random

import pytest

from repro.configs.workflows import get_workflow_spec, wide_fanout
from repro.core import calibration as cal
from repro.core.cluster import Cluster, PodObj
from repro.core.dag import make_workflow
from repro.core.informer import Informer, InformerSet
from repro.core.runner import ControlPlane
from repro.core.shuffle import ExactShuffler, _load_native
from repro.core.sim import Sim
from repro.core.stats import StreamingStat

# sha256 over the binding sequence "ns/pod->node@t", recorded on the
# pre-optimization core (commit 1bd52e9) — the refactor must not move it
PINNED = {
    "paper": ("3832b6fec9f1d4afd55898e04dba44377eb37258b3fb3b19c94f9a994f70a3ca", 42),
    "multi": ("546262a798da1d30d32312751fd6aa026f80e335a1e6b0fb56d33d9ef66f1834", 70),
    # recorded on the commit that still had the one-event-per-hop pod
    # lifecycle and the calendar queue (all four combinations agreed)
    "stress": ("721c97cb371d9f9d093262afe18c68e86edc5e0eee28c24b822ee0f16182412a", 84),
}


def _binding_sequence(plane, loader):
    seq = []
    orig = plane.cluster._bind

    def record(pod, node):
        seq.append(f"{pod.namespace}/{pod.name}->{node.name}"
                   f"@{plane.sim.now():.4f}")
        orig(pod, node)

    plane.cluster._bind = record
    loader(plane)
    plane.run(horizon_s=500_000)
    return seq


def _paper_scenario():
    plane = ControlPlane("kubeadaptor", seed=7)
    wf = make_workflow("montage", get_workflow_spec("montage"))
    return _binding_sequence(
        plane, lambda p: p.gateway.load([wf.with_instance(i)
                                         for i in range(2)]))


def _multi_scenario():
    plane = ControlPlane("kubeadaptor", admission_policy="fair-share",
                         cluster_cfg=cal.PaperCluster(n_nodes=3), seed=11)
    mont = make_workflow("montage", get_workflow_spec("montage"))
    fan = make_workflow("fan", wide_fanout(width=12))

    def load(p):
        p.add_stream(mont, repeats=2, tenant="a", arrival="concurrent",
                     concurrency=2, weight=2.0)
        p.add_stream(fan, repeats=2, tenant="b", arrival="concurrent",
                     concurrency=2, weight=1.0)
    return _binding_sequence(plane, load)


def _stress_plane():
    """The event core's stress scenario: fair-share, montage x2 with
    concurrency 2 plus a 12-wide fan-out Poisson stream, 3 nodes."""
    plane = ControlPlane("kubeadaptor", admission_policy="fair-share",
                         cluster_cfg=cal.PaperCluster(n_nodes=3), seed=11)
    mont = make_workflow("montage", get_workflow_spec("montage"))
    fan = make_workflow("fan", wide_fanout(width=12))
    plane.add_stream(mont, repeats=2, tenant="a", arrival="concurrent",
                     concurrency=2, weight=2.0)
    plane.add_stream(fan, repeats=3, tenant="b", arrival="poisson",
                     rate=0.2, burst=2, weight=1.0)
    return plane


def _stress_scenario():
    return _binding_sequence(_stress_plane(), lambda p: None)


@pytest.mark.parametrize("name,scenario",
                         [("paper", _paper_scenario),
                          ("multi", _multi_scenario),
                          ("stress", _stress_scenario)])
def test_binding_sequence_pinned(name, scenario):
    """Fixed seed => the exact pre-refactor pod->node binding order."""
    seq = scenario()
    digest = hashlib.sha256("\n".join(seq).encode()).hexdigest()
    want_digest, want_n = PINNED[name]
    assert len(seq) == want_n
    assert digest == want_digest, f"binding sequence moved for {name!r}"


def test_binding_sequence_deterministic():
    assert _paper_scenario() == _paper_scenario()


# ---------------------------------------------------------------------------
# shuffle replica
# ---------------------------------------------------------------------------
def _backends():
    out = [False]                      # pure python always
    if _load_native() is not None:
        out.append(True)
    return out


@pytest.mark.parametrize("native", _backends())
def test_exact_shuffler_matches_random_shuffle(native):
    for seed in (0, 7, 12345):
        ref, mine = random.Random(seed), random.Random(seed)
        sh = ExactShuffler(mine, native=native)
        for _ in range(120):           # enough to span buffer refills
            for ln in (2, 3, 6, 17, 56, 100, 101, 257):
                a, b = list(range(ln)), list(range(ln))
                ref.shuffle(a)
                sh.shuffle(b)
                assert a == b


@pytest.mark.parametrize("native", _backends())
def test_draw_apply_matches_shuffle_permutation(native):
    ref, mine = random.Random(3), random.Random(3)
    sh = ExactShuffler(mine, native=native)
    perm = sh.make_perm(64)
    for _ in range(200):
        a = list(range(64))
        ref.shuffle(a)
        sh.reset_perm(perm, 64)
        sh.draw_apply(perm, 64)
        assert list(perm) == a


# ---------------------------------------------------------------------------
# admission: specialized walks == generic re-sort loop
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("policy", ["fifo", "priority", "fair-share"])
def test_fast_walks_match_generic_evaluate(policy):
    """Same scenario through the specialized walk and the generic loop
    must grant in the same order with the same deferral counts."""
    import repro.core.resources as rs

    def run(fast):
        grants = []
        orig_init = rs.AdmissionArbiter.__init__
        orig_ck = rs.AdmissionArbiter._create_bookkeep

        def pinit(self, *a, **k):
            orig_init(self, *a, **k)
            self._fast = fast

        def pck(self, req):
            grants.append((self.inf.pods.sim.now(), req.namespace,
                           req.task.id))
            return orig_ck(self, req)

        rs.AdmissionArbiter.__init__ = pinit
        rs.AdmissionArbiter._create_bookkeep = pck
        try:
            plane = ControlPlane("kubeadaptor", admission_policy=policy,
                                 cluster_cfg=cal.PaperCluster(n_nodes=2),
                                 seed=5)
            fan = make_workflow("fan", wide_fanout(width=16))
            mont = make_workflow("montage", get_workflow_spec("montage"))
            plane.add_stream(fan, repeats=2, tenant="heavy",
                             arrival="concurrent", concurrency=2,
                             priority=5, weight=3.0)
            plane.add_stream(mont, repeats=2, tenant="light",
                             arrival="poisson", rate=0.1, burst=2,
                             priority=0, weight=1.0)
            res = plane.run(horizon_s=500_000)
            return grants, res.arbiter.deferrals, res.arbiter.admitted
        finally:
            rs.AdmissionArbiter.__init__ = orig_init
            rs.AdmissionArbiter._create_bookkeep = orig_ck

    fast = run(True)
    generic = run(False)
    assert fast == generic


def test_informer_pod_aggregates_match_scan():
    plane = ControlPlane("kubeadaptor", seed=2)
    wf = make_workflow("cybershake", get_workflow_spec("cybershake"))
    arb = plane.arbiter
    checks = []
    orig = type(arb).evaluate

    def checked(self):
        checks.append(self.requested() == self._requested_scan())
        orig(self)

    plane.arbiter.evaluate = checked.__get__(plane.arbiter)
    plane.gateway.load([wf.with_instance(0)])
    plane.run(horizon_s=500_000)
    assert checks and all(checks)


# ---------------------------------------------------------------------------
# listers stay zero-cost; pvc informer; resync reconciliation
# ---------------------------------------------------------------------------
def test_api_calls_unchanged_by_lister_fast_path():
    sim = Sim()
    cluster = Cluster(sim)
    informers = InformerSet(sim, cluster)
    cluster.create_namespace("bench")
    sim.run()
    for i in range(20):
        cluster.create_pod(PodObj(name=f"p{i}", namespace="bench",
                                  task_id=f"p{i}", workflow="w",
                                  cpu_m=100, mem_mi=100, duration_s=1e9))
    sim.run(until=sim.now() + 5)
    before = cluster.api_calls
    for _ in range(500):
        informers.pods.lister()
        informers.pods.lister("bench")
        informers.nodes.lister()
        informers.pvcs.lister("bench")
    assert cluster.api_calls == before, "listers must not hit the apiserver"
    assert len(informers.pods.lister("bench")) == 20


def test_pvc_informer_cache_populated():
    """Satellite: the pvc informer's initial list / resync now see PVCs
    (Cluster.list_pvcs existed nowhere before)."""
    sim = Sim()
    cluster = Cluster(sim)
    cluster.create_namespace("ns1")
    sim.run()
    cluster.create_pvc("ns1", "vol1")
    sim.run(until=sim.now() + 5)
    fresh = Informer(sim, cluster, "pvc")       # initial list
    assert ("ns1", "vol1") in fresh.cache
    assert [p.name for p in fresh.lister("ns1")] == ["vol1"]
    assert cluster.list_pvcs("ns1")[0].bound


def test_resync_reconciles_missed_delete():
    """Satellite: a DELETED watch event that never arrives leaves a
    stale cache key; resync must drop it and fire on_delete (after the
    two-resync grace that protects in-flight events)."""
    sim = Sim()
    cluster = Cluster(sim)
    informer = Informer(sim, cluster, "pod")
    deleted = []
    informer.add_handlers(on_delete=deleted.append)
    cluster.create_namespace("ns1")
    sim.run()
    pod = PodObj(name="ghost", namespace="ns1", task_id="t", workflow="w",
                 cpu_m=100, mem_mi=100, duration_s=1e9)
    cluster.create_pod(pod)
    sim.run(until=sim.now() + 2)
    assert ("ns1", "ghost") in informer.cache
    # simulate a missed DELETED event: remove from the apiserver state
    # without notifying the watch stream
    del cluster.pods[("ns1", "ghost")]
    cluster._pending_pods.pop(("ns1", "ghost"), None)
    cluster._pods_by_ns["ns1"].pop(("ns1", "ghost"), None)
    p = cal.DEFAULT_PARAMS
    sim.after(2.5 * p.resync_interval, lambda: None)   # keep sim alive
    sim.run(until=sim.now() + 2.5 * p.resync_interval)
    assert ("ns1", "ghost") not in informer.cache
    assert [q.name for q in deleted] == ["ghost"]
    # aggregates reconciled too
    assert informer.nonterminal_cpu == 0


def test_resync_survives_normal_operation():
    """Reconciliation must not fire on_delete for objects that are
    still present (or only transiently in flight)."""
    res = None
    plane = ControlPlane("kubeadaptor", seed=4)
    wf = make_workflow("ligo", get_workflow_spec("ligo"))
    deleted = []
    plane.informers.namespaces.add_handlers(on_delete=deleted.append)
    plane.gateway.load([wf.with_instance(0)])
    res = plane.run(horizon_s=500_000)
    # exactly the workflow's own namespace deletion, no resync ghosts
    assert len(deleted) == 1
    assert res.metrics.wf_record(wf.with_instance(0)).ns_deleted > 0


# ---------------------------------------------------------------------------
# sim diagnostics + streaming metrics
# ---------------------------------------------------------------------------
def test_sim_runaway_error_names_pending_notes():
    sim = Sim()

    def loop():
        sim.after(0.1, loop, note="culprit-poller")

    loop()
    with pytest.raises(RuntimeError) as err:
        sim.run(max_events=50)
    assert "culprit-poller" in str(err.value)
    assert sim.events_processed == 50


def test_sim_counts_events():
    sim = Sim()
    for i in range(10):
        sim.after(i, lambda: None)
    sim.run()
    assert sim.events_processed == 10


def test_streaming_stat_matches_list_stats():
    rng = random.Random(9)
    xs = [rng.uniform(0, 100) for _ in range(5000)]
    st = StreamingStat(reservoir=256)
    for x in xs:
        st.add(x)
    assert st.count == len(xs)
    assert st.mean == pytest.approx(sum(xs) / len(xs))
    assert st.max == max(xs)
    assert st.min == min(xs)
    # reservoir percentile is approximate but must be in-range and sane
    p50 = st.percentile(50)
    assert min(xs) <= p50 <= max(xs)
    xs_sorted = sorted(xs)
    assert abs(p50 - xs_sorted[len(xs) // 2]) < 10.0


def test_streaming_sample_mode_keeps_memory_flat():
    plane = ControlPlane("kubeadaptor", seed=1, sample_mode="streaming",
                         retain_pod_log=False)
    wf = make_workflow("montage", get_workflow_spec("montage"))
    plane.gateway.load([wf.with_instance(i) for i in range(3)])
    res = plane.run(horizon_s=500_000)
    m = res.metrics
    assert m.samples == [] and m.tenant_samples == []
    assert m.cpu_stat.count > 0
    cpu_rate, mem_rate = m.overall_usage()
    assert 0 < cpu_rate <= 1 and 0 < mem_rate <= 1
    assert res.cluster.pod_log == []
    assert res.cluster.exec_stat.count > 0        # exec times still tracked
    assert res.cluster.max_pending_pods > 0
    assert res.arbiter.max_pending >= 0


def test_full_mode_unchanged_for_paper_runs():
    plane = ControlPlane("kubeadaptor", seed=1)
    wf = make_workflow("montage", get_workflow_spec("montage"))
    plane.gateway.load([wf.with_instance(0)])
    res = plane.run(horizon_s=500_000)
    assert len(res.metrics.samples) > 10
    assert len(res.cluster.pod_log) == len(wf.tasks)
