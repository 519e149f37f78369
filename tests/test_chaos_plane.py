"""Deterministic chaos plane (ISSUE 7): seeded faults, exact replay,
bit-identity with chaos off, and shard-worker failure recovery.

Guarantee layers:

* stream spawning: chaos seeds are sha256-spawned, decorrelated from
  the scheduler/shard seeds, reproducible, and per-shard distinct;
* bit-identity: ``chaos=None`` and an inactive ``ChaosSchedule()``
  perform zero draws — binding sequences match the chaos-free run
  exactly (the PR-6 pinned hashes in test_shard_plane.py run against
  this same code, so the pin is transitive);
* exact replay: a fixed chaos seed reproduces identical binding
  sequences, injection counters, and recovery metrics;
* recovery semantics: node kill/drain removes capacity and fails
  resident pods as ``node_lost`` (re-admitted with NO retry-budget
  charge), restore returns the capacity, transient apiserver faults
  are absorbed by the backoff path, task crashes DO charge the §4.5
  retry budget, and a mid-run node kill still completes 100% of
  workflows under every admission policy;
* teardown race (satellite): a pod evicted in the same instant its
  workflow fails must not re-enter the dead workflow's ready pool;
* sharded plane: fail-workflow counts and recovery metrics merge
  exactly across shards, and a dead shard worker is detected and
  handled per ``on_shard_failure`` (raise / restart / degrade)
  instead of hanging the parent forever.
"""
import os
from dataclasses import replace

import pytest

from repro.configs.workflows import get_workflow_spec
from repro.core import calibration as cal
from repro.core.chaos import (ChaosSchedule, chaos_shard_seed,
                              chaos_stream_seed)
from repro.core.cluster import PENDING, RUNNING
from repro.core.dag import make_workflow
from repro.core.runner import ControlPlane
from repro.core.shard import ShardedControlPlane, ShardFailure, shard_seed

MONTAGE = make_workflow("montage", get_workflow_spec("montage"))
EPIGENOMICS = make_workflow("epigenomics", get_workflow_spec("epigenomics"))


def _canon(obj):
    """NaN-tolerant deep compare form (NaN != NaN breaks dict ==)."""
    if isinstance(obj, dict):
        return {k: _canon(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_canon(v) for v in obj]
    if isinstance(obj, float) and obj != obj:
        return "nan"
    return obj


# --------------------------------------------------------------------------
# stream spawning
# --------------------------------------------------------------------------
def test_chaos_seed_spawning():
    assert chaos_stream_seed(42) == chaos_stream_seed(42)
    assert chaos_stream_seed(42) != chaos_stream_seed(43)
    # decorrelated from the shard-seed spawn of the same root
    assert chaos_stream_seed(42) != shard_seed(42, 0)
    per_shard = [chaos_shard_seed(42, i) for i in range(16)]
    assert len(set(per_shard)) == 16
    assert per_shard == [chaos_shard_seed(42, i) for i in range(16)]


def test_schedule_spawn_and_active():
    sched = ChaosSchedule(seed=5, node_kill_interval_s=100.0)
    assert sched.active
    assert not ChaosSchedule().active
    child = sched.spawn(3)
    assert child.seed == chaos_shard_seed(5, 3)
    assert child.node_kill_interval_s == 100.0


# --------------------------------------------------------------------------
# single-plane runs
# --------------------------------------------------------------------------
def _run_single(chaos, seed=7, policy="fair-share", params=None,
                n_nodes=8, repeats=5):
    plane = ControlPlane(
        "kubeadaptor", admission_policy=policy, seed=seed,
        params=params or cal.DEFAULT_PARAMS,
        cluster_cfg=cal.PaperCluster(n_nodes=n_nodes),
        sample_mode="streaming", usage_mode="event",
        retain_pod_log=False, chaos=chaos)
    bindings = []
    inner = plane.cluster._bind

    def recording_bind(pod, node):
        bindings.append(f"{pod.namespace}/{pod.name}->{node.name}"
                        f"@{plane.sim.now():.4f}")
        return inner(pod, node)

    plane.cluster._bind = recording_bind
    plane.add_stream(MONTAGE, repeats=repeats, tenant="prod",
                     arrival="concurrent", concurrency=2, priority=10,
                     weight=3.0, deadline_s=1800.0)
    plane.add_stream(EPIGENOMICS, repeats=repeats, tenant="batch",
                     arrival="poisson", rate=0.5, burst=2,
                     deadline_s=3600.0)
    res = plane.run()
    return res, bindings


def test_inactive_schedule_is_bit_identical_to_chaos_none():
    res_none, b_none = _run_single(None)
    res_idle, b_idle = _run_single(ChaosSchedule(seed=99))
    assert b_idle == b_none
    assert res_idle.sim.events_processed == res_none.sim.events_processed
    assert _canon(res_idle.metrics.tenant_summary()) == \
        _canon(res_none.metrics.tenant_summary())
    # the injector is armed but performed zero draws
    assert res_idle.chaos is not None
    assert all(v == 0 for v in res_idle.chaos.counters().values())
    assert res_none.chaos is None


CHAOS = ChaosSchedule(seed=3, node_kill_interval_s=150.0,
                      node_drain_interval_s=400.0, node_downtime_s=60.0,
                      api_fault_rate=0.05, task_crash_rate=0.02,
                      start_after_s=30.0)


def test_fixed_chaos_seed_replays_exactly():
    res1, b1 = _run_single(CHAOS)
    res2, b2 = _run_single(CHAOS)
    assert b1 == b2
    assert res1.chaos.counters() == res2.chaos.counters()
    p1 = res1.metrics.export_partial()
    p2 = res2.metrics.export_partial()
    assert _canon(p1.recovery_summary()) == _canon(p2.recovery_summary())
    assert _canon(p1.tenant_summary()) == _canon(p2.tenant_summary())
    # a different chaos seed draws a different fault sequence
    res3, _ = _run_single(replace(CHAOS, seed=4))
    assert res3.chaos.counters() != res1.chaos.counters() or \
        res3.metrics.tenant_summary() != res1.metrics.tenant_summary()


def test_chaos_run_recovers_completely():
    res, _ = _run_single(CHAOS)
    c = res.chaos.counters()
    assert c["node_kills"] + c["node_drains"] >= 1
    assert c["api_faults"] >= 1
    p = res.metrics.export_partial()
    # every workflow completed despite the injected faults
    assert p.completed == 10
    assert p.failed == 0
    rec = p.recovery_summary()
    assert rec["node_lost"] == c["pods_lost"]
    # every disrupted (non-twin) task was re-created, with latency stats
    assert rec["rescheduled"] == rec["node_lost"]
    if rec["rescheduled"]:
        assert rec["resched_mean_s"] > 0.0
        assert rec["resched_p95_s"] >= rec["resched_p50_s"]


def test_scripted_kill_restore_and_no_retry_charge():
    # a scripted mid-run node kill: pods on node2 fail as node_lost and
    # are re-admitted WITHOUT charging the §4.5 retry budget; the
    # scripted restore returns the capacity and accounts the downtime
    sched = ChaosSchedule(seed=1, events=((60.0, "kill", "node2"),
                                          (220.0, "restore", "node2")))
    res, _ = _run_single(sched)
    c = res.chaos.counters()
    assert c["node_kills"] == 1
    assert c["node_restores"] == 1
    assert c["node_downtime_s"] == pytest.approx(160.0)
    assert c["pods_lost"] >= 1
    assert res.cluster.nodes["node2"].ready       # restored
    summary = res.metrics.tenant_summary()
    assert sum(row["node_lost"] for row in summary.values()) == \
        c["pods_lost"]
    part = res.metrics.export_partial()
    # node loss is disruption, not failure: zero retry-budget charges
    assert sum(a.retries for a in part.tenant_aggs.values()) == 0
    assert part.completed == 10


def test_drain_charges_api_calls_but_not_evictions():
    kill = ChaosSchedule(seed=1, events=((60.0, "kill", "node2"),))
    drain = ChaosSchedule(seed=1, events=((60.0, "drain", "node2"),))
    res_k, _ = _run_single(kill)
    res_d, _ = _run_single(drain)
    assert res_k.chaos.counters()["node_kills"] == 1
    assert res_d.chaos.counters()["node_drains"] == 1
    lost = res_d.chaos.counters()["pods_lost"]
    assert lost >= 1
    # the graceful drain pays one apiserver round-trip per resident pod
    # (everything else about the two runs is identical: same seed, same
    # victim, same instant)
    assert res_d.cluster.api_calls == res_k.cluster.api_calls + lost
    # neither path counts as arbiter preemption
    assert res_d.cluster.evictions == res_k.cluster.evictions


def test_transient_api_faults_absorbed():
    sched = ChaosSchedule(seed=11, api_fault_rate=0.25)
    res, _ = _run_single(sched)
    c = res.chaos.counters()
    assert c["api_faults"] > 10           # faults actually fired...
    p = res.metrics.export_partial()
    assert p.completed == 10              # ...and were all absorbed
    assert p.failed == 0


def test_task_crashes_charge_retry_budget():
    sched = ChaosSchedule(seed=13, task_crash_rate=0.10)
    res, _ = _run_single(sched)
    c = res.chaos.counters()
    assert c["task_crashes"] >= 1
    part = res.metrics.export_partial()
    # unlike node loss, a crash is a real failure: retries were charged
    assert sum(a.retries for a in part.tenant_aggs.values()) == \
        c["task_crashes"]
    assert part.completed == 10


def test_mid_run_node_kill_completes_under_every_policy():
    sched = ChaosSchedule(seed=7, node_kill_interval_s=120.0,
                          node_downtime_s=60.0, start_after_s=30.0)
    kills = 0
    for policy in ("fifo", "priority", "fair-share", "drf", "quota",
                   "preempt"):
        res, _ = _run_single(sched, policy=policy)
        p = res.metrics.export_partial()
        assert p.completed == 10, f"{policy}: {p.completed}/10"
        assert p.failed == 0, f"{policy} failed workflows"
        kills += res.chaos.counters()["node_kills"]
    assert kills >= 6                     # the kills genuinely happened


# --------------------------------------------------------------------------
# teardown race (satellite): evict during workflow failure
# --------------------------------------------------------------------------
def test_evict_during_teardown_does_not_requeue():
    plane = ControlPlane(
        "kubeadaptor", admission_policy="fair-share", seed=7,
        cluster_cfg=cal.PaperCluster(n_nodes=6),
        sample_mode="streaming", usage_mode="event",
        retain_pod_log=False)
    plane.add_stream(MONTAGE, repeats=3, tenant="prod",
                     arrival="concurrent", concurrency=3)
    plane.gateway.start()
    plane.sim.run(until=20.0)             # mid-flight
    eng = plane.engine
    target = None
    for ns, ws in eng._ws.items():
        if ws.done:
            continue
        running = [p for p in plane.cluster.pods.values()
                   if p.namespace == ns and p.phase == RUNNING
                   and not p.evicted]
        if running:
            target = (ws, running[0])
            break
    assert target is not None, "no running pod at t=40 (workload shape?)"
    ws, pod = target
    tid = pod.task_id
    # same sim instant: the workflow starts tearing down AND the pod is
    # evicted — the pod's FAILED event lands after ws.done is set
    eng._fail_workflow(ws, "test: teardown race")
    assert ws.done
    assert plane.cluster.evict_pod(pod.namespace, pod.name)
    plane.sim.run(until=500_000.0)
    # the regression: the evicted task must NOT re-enter the dead
    # workflow's ready pool (double-count into a torn-down run)
    assert tid not in ws.ready_pool
    # and nothing was resurrected in the dead namespace
    assert not any(p.namespace == ws.ns and p.phase in (PENDING, RUNNING)
                   for p in plane.cluster.pods.values())
    # the other two workflows finished normally
    p = plane.metrics.export_partial()
    assert p.completed == 2
    assert p.failed == 1


# --------------------------------------------------------------------------
# sharded plane: chaos + fail-workflow merge exactness
# --------------------------------------------------------------------------
def _sharded(processes, chaos=None, params=None, **kw):
    plane = ShardedControlPlane(
        2, admission_policy="fair-share", seed=42,
        params=params or cal.DEFAULT_PARAMS,
        cluster_cfg=cal.PaperCluster(n_nodes=8),
        sample_mode="streaming", usage_mode="event", retain_pod_log=False,
        processes=processes, chaos=chaos,
        heartbeat_s=0.2, **kw)
    # tenant names chosen to span both shards under the crc32 partition:
    # batch-a/alpha -> shard 0, prod-a/gamma -> shard 1
    for tenant in ("batch-a", "prod-a"):
        plane.add_stream(MONTAGE, repeats=4, tenant=tenant,
                         arrival="concurrent", concurrency=2, priority=10,
                         weight=3.0, deadline_s=180.0)
    for tenant in ("alpha", "gamma"):
        plane.add_stream(EPIGENOMICS, repeats=4, tenant=tenant,
                         arrival="poisson", rate=0.5, burst=2,
                         deadline_s=3600.0)
    return plane


def test_fail_workflow_counts_merge_exactly_across_shards():
    # task-crash chaos + a tight retry budget + fail-workflow: failed
    # counts and SLO rates must merge exactly (sum over shards == the
    # single-process run), with the workload still quarantined per
    # workflow
    params = replace(cal.DEFAULT_PARAMS, max_retries=1,
                     on_retry_exhausted="fail-workflow")
    chaos = ChaosSchedule(seed=9, task_crash_rate=0.30)
    r_in = _sharded(processes=False, chaos=chaos, params=params).run()
    r_mp = _sharded(processes=True, chaos=chaos, params=params).run()
    assert r_in.failed_workflows > 0      # the scenario genuinely fails
    assert r_in.completed_workflows + r_in.failed_workflows == 16
    assert r_mp.failed_workflows == r_in.failed_workflows
    assert _canon(r_mp.tenant_summary()) == _canon(r_in.tenant_summary())
    assert r_mp.chaos_counters() == r_in.chaos_counters()
    # merged failed == sum of per-shard partials
    assert sum(s["failed_workflows"] for s in r_in.shards) == \
        r_in.failed_workflows
    assert r_in.metrics.failed == r_in.failed_workflows


def test_recovery_metrics_merge_exactly_across_shards():
    chaos = ChaosSchedule(seed=5, node_kill_interval_s=120.0,
                          node_downtime_s=60.0, start_after_s=20.0)
    r_in = _sharded(processes=False, chaos=chaos).run()
    r_mp = _sharded(processes=True, chaos=chaos).run()
    assert r_in.chaos_counters() == r_mp.chaos_counters()
    assert _canon(r_in.recovery_summary()) == _canon(r_mp.recovery_summary())
    assert _canon(r_in.tenant_summary()) == _canon(r_mp.tenant_summary())
    c = r_in.chaos_counters()
    assert c.get("node_kills", 0) >= 1
    assert r_in.recovery_summary()["node_lost"] == c["pods_lost"]
    # per-shard counters sum to the merged view
    per_shard = [s["chaos"] for s in r_in.shards if s["chaos"]]
    assert sum(d["node_kills"] for d in per_shard) == c["node_kills"]
    assert r_in.completed_workflows == 16
    assert r_in.failed_workflows == 0


# --------------------------------------------------------------------------
# shard-worker failure recovery (satellite: no more silent hang)
# --------------------------------------------------------------------------
def test_dead_shard_raises_structured_failure(monkeypatch):
    monkeypatch.setenv("REPRO_SHARD_KILL", "1")
    with pytest.raises(ShardFailure) as exc:
        _sharded(processes=True, on_shard_failure="raise").run()
    assert exc.value.shard == 1
    assert exc.value.tenants          # the stranded tenants are named
    assert "died" in exc.value.reason


def test_dead_shard_restart_reproduces_healthy_result(monkeypatch):
    healthy = _sharded(processes=True).run()
    monkeypatch.setenv("REPRO_SHARD_KILL", "1")
    restarted = _sharded(processes=True, on_shard_failure="restart").run()
    # the respawned shard re-runs the identical spec (same tenant
    # partition + spawned seed), so the merged result is unchanged
    assert not restarted.degraded
    assert _canon(restarted.tenant_summary()) == \
        _canon(healthy.tenant_summary())
    assert restarted.completed_workflows == healthy.completed_workflows


def test_dead_shard_degrade_merges_survivors(monkeypatch):
    monkeypatch.setenv("REPRO_SHARD_KILL", "0")
    res = _sharded(processes=True, on_shard_failure="degrade").run()
    assert res.degraded
    assert [f["shard"] for f in res.failures] == [0]
    assert res.failures[0]["tenants"]
    # the surviving shard's results are intact
    surviving = {t for s in res.shards for t in s["tenants"]}
    assert surviving == set(res.tenant_summary())
    assert res.completed_workflows == \
        sum(s["completed_workflows"] for s in res.shards)


def test_inline_worker_exception_maps_to_policy():
    # in-process mode applies the same policy: a shard raising maps to
    # ShardFailure under "raise" and to a degraded merge under
    # "degrade" (strict horizon => unfinished workflows raise)
    def tiny(on_shard_failure):
        plane = _sharded(processes=False,
                         on_shard_failure=on_shard_failure)
        return plane.run(horizon_s=5.0)   # nothing can finish in 5s

    with pytest.raises(ShardFailure):
        tiny("raise")
    res = tiny("degrade")
    assert res.degraded
    assert len(res.failures) == 2
    assert res.completed_workflows == 0
