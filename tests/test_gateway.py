"""Durable submission front door (ISSUE 10): WAL, backpressure,
exactly-once shard crash recovery.

Guarantee layers:

* stream spawning: the gate's retry-jitter stream is sha256-spawned
  under its own tag — reproducible, per-shard distinct, decorrelated
  from the scheduler/chaos/shard seeds;
* WAL integrity: records are sha256-chained, the head hash detects any
  mutation, a torn tail line (crash mid-write) is truncated on load, a
  corrupt complete record or a diverging replay raises instead of
  silently double-running;
* bit-identity: an unsaturated gateway adds zero sim events and zero
  draws — binding sequences, event counts, and tenant summaries match
  the gateway-off run exactly (so every pre-existing pinned hash holds
  with the gate armed but idle);
* backpressure: ``peak_pending`` never exceeds ``max_pending``, the
  ledger balances exactly (admitted + shed == submissions, queued
  drains to 0), and the three shed modes differ in WHO is dropped but
  all preserve the accounting identity;
* exactly-once: chaos transport drops are recovered by WAL redelivery
  and duplicates are suppressed by the dedup set — every submission id
  reaches the engine at most once, and a crash between the WAL append
  and the engine submit delivers exactly once on restart;
* crash recovery (the tentpole pin): a mid-run shard kill + restart
  replays the WAL prefix under verification; merged behavioral metrics
  are bit-identical to a never-crashed same-seed run under all six
  admission policies, and the recovered WAL file is byte-identical to
  the clean run's.
"""
import json
import os

import pytest

from repro.configs.workflows import get_workflow_spec
from repro.core import calibration as cal
from repro.core.chaos import ChaosSchedule, chaos_stream_seed
from repro.core.dag import make_workflow
from repro.core.gateway import (WAL_GENESIS, BackpressurePolicy,
                                SubmissionWAL, WalReplayError,
                                gate_stream_seed, merge_gateway_snapshots,
                                workflow_digest)
from repro.core.runner import ControlPlane
from repro.core.shard import ShardedControlPlane, shard_seed

MONTAGE = make_workflow("montage", get_workflow_spec("montage"))
EPIGENOMICS = make_workflow("epigenomics", get_workflow_spec("epigenomics"))

ALL_POLICIES = ("fifo", "priority", "fair-share", "drf", "quota", "preempt")


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
# policy validation + stream spawning
# --------------------------------------------------------------------------
def test_backpressure_policy_validation():
    p = BackpressurePolicy(max_pending=8, per_tenant_cap=2,
                           shed="fair-shed", retry_after_s=3.0,
                           max_client_retries=5)
    assert p.shed == "fair-shed"
    with pytest.raises(ValueError):
        BackpressurePolicy(max_pending=0)
    with pytest.raises(ValueError):
        BackpressurePolicy(per_tenant_cap=-1)
    with pytest.raises(ValueError):
        BackpressurePolicy(retry_after_s=0.0)
    with pytest.raises(ValueError):
        BackpressurePolicy(shed="drop-table")
    # frozen + picklable: it crosses the fork inside ShardSpec
    import pickle
    assert pickle.loads(pickle.dumps(p)) == p
    with pytest.raises(Exception):
        p.max_pending = 9


def test_gate_stream_seed_spawning():
    assert gate_stream_seed(42, 0) == gate_stream_seed(42, 0)
    assert gate_stream_seed(42, 0) != gate_stream_seed(42, 1)
    assert gate_stream_seed(42, 0) != gate_stream_seed(43, 0)
    # decorrelated from the other sha256-spawned consumers of the seed
    assert gate_stream_seed(42, 0) != shard_seed(42, 0)
    assert gate_stream_seed(42, 0) != chaos_stream_seed(42)
    per_shard = [gate_stream_seed(7, i) for i in range(16)]
    assert len(set(per_shard)) == 16


def test_workflow_digest_is_deterministic_and_keyed():
    d = workflow_digest("prod", "montage", 3)
    assert d == workflow_digest("prod", "montage", 3)
    assert len(d) == 16
    assert d != workflow_digest("prod", "montage", 4)
    assert d != workflow_digest("batch", "montage", 3)


# --------------------------------------------------------------------------
# WAL: chain integrity, file sink, torn tail, replay verification
# --------------------------------------------------------------------------
def test_wal_chain_and_segments():
    wal = SubmissionWAL(segment_size=3)
    for i in range(8):
        rec = wal.append(f"t{i % 2}", float(i), workflow_digest("t", "m", i))
        assert rec["id"] == i
    assert wal.count == 8
    assert len(wal.segments) == 3          # 3+3+2 under segment_size=3
    assert [r["id"] for r in wal.records()] == list(range(8))
    assert wal.chain != WAL_GENESIS
    assert wal.verify()
    # any in-place mutation breaks the running head hash
    wal.segments[1][0]["tenant"] = "evil"
    assert not wal.verify()
    with pytest.raises(ValueError):
        SubmissionWAL(segment_size=0)


def _fill(wal, n):
    for i in range(n):
        wal.append("prod", float(i), workflow_digest("prod", "montage", i))


def test_wal_file_sink_and_replay(tmp_path):
    path = str(tmp_path / "shard-0.wal")
    first = SubmissionWAL(path=path)
    _fill(first, 5)
    chain = first.chain
    first.close()
    assert len(open(path).read().splitlines()) == 5

    # a new incarnation replays the durable prefix: records verified
    # field-for-field, NOT rewritten, and the chain head matches
    second = SubmissionWAL(path=path)
    _fill(second, 5)
    assert second.replayed == 5
    assert second.chain == chain
    second.close()
    assert open(path).read() == open(path).read()  # idempotent on disk


def test_wal_truncates_torn_tail(tmp_path):
    path = str(tmp_path / "shard-0.wal")
    wal = SubmissionWAL(path=path)
    _fill(wal, 4)
    wal.close()
    whole = open(path).read()
    # crash mid-write: a partial last line with no terminating newline
    with open(path, "a") as f:
        f.write('{"id":4,"tenant":"pr')
    recovered = SubmissionWAL(path=path)
    _fill(recovered, 4)
    assert recovered.replayed == 4         # the valid prefix survived
    recovered.close()
    assert open(path).read() == whole      # the torn tail is gone


def test_wal_replay_divergence_and_corruption_raise(tmp_path):
    path = str(tmp_path / "shard-0.wal")
    wal = SubmissionWAL(path=path)
    _fill(wal, 3)
    wal.close()
    # regenerated arrivals that disagree with the log must never
    # silently double-run
    diverged = SubmissionWAL(path=path)
    diverged.append("prod", 0.0, workflow_digest("prod", "montage", 0))
    with pytest.raises(WalReplayError):
        diverged.append("prod", 99.0, workflow_digest("prod", "montage", 1))
    diverged.close()
    # a corrupt COMPLETE record (newline-terminated, mid-file) is not a
    # torn tail: fail loudly instead of truncating real history
    lines = open(path).read().splitlines()
    lines[1] = lines[1][:-2] + "!!"        # no longer valid JSON
    (tmp_path / "bad.wal").write_text("\n".join(lines) + "\n")
    with pytest.raises(WalReplayError):
        SubmissionWAL(path=str(tmp_path / "bad.wal"))
    # a value-level mutation survives load (the line is still
    # well-formed) but is caught the moment replay regenerates the
    # true record — the chain head is authoritative either way
    lines = open(path).read().splitlines()
    lines[1] = lines[1].replace('"tenant":"prod"', '"tenant":"evil"')
    (tmp_path / "mut.wal").write_text("\n".join(lines) + "\n")
    mutated = SubmissionWAL(path=str(tmp_path / "mut.wal"))
    mutated.append("prod", 0.0, workflow_digest("prod", "montage", 0))
    with pytest.raises(WalReplayError):
        mutated.append("prod", 1.0, workflow_digest("prod", "montage", 1))
    mutated.close()


# --------------------------------------------------------------------------
# single-plane runs: bit-identity off==idle, bounds, shed modes
# --------------------------------------------------------------------------
def _run_single(gateway=None, wal_path=None, chaos=None, seed=7,
                policy="fair-share", n_nodes=8, repeats=5, concurrency=2):
    plane = ControlPlane(
        "kubeadaptor", admission_policy=policy, seed=seed,
        cluster_cfg=cal.PaperCluster(n_nodes=n_nodes),
        sample_mode="streaming", usage_mode="event",
        retain_pod_log=False, chaos=chaos,
        gateway=gateway, wal_path=wal_path)
    bindings = []
    inner = plane.cluster._bind

    def recording_bind(pod, node):
        bindings.append(f"{pod.namespace}/{pod.name}->{node.name}"
                        f"@{plane.sim.now():.4f}")
        return inner(pod, node)

    plane.cluster._bind = recording_bind
    plane.add_stream(MONTAGE, repeats=repeats, tenant="prod",
                     arrival="concurrent", concurrency=concurrency,
                     priority=10, weight=3.0, deadline_s=1800.0)
    plane.add_stream(EPIGENOMICS, repeats=repeats, tenant="batch",
                     arrival="poisson", rate=0.5, burst=2,
                     deadline_s=3600.0)
    res = plane.run()
    return res, bindings


def _strip_gateway(summary):
    """tenant_summary minus the gateway_* columns the armed run adds."""
    return {t: {k: v for k, v in row.items()
                if not k.startswith("gateway_")}
            for t, row in summary.items()}


def test_unsaturated_gateway_is_bit_identical_to_disabled():
    res_off, b_off = _run_single(gateway=None)
    res_idle, b_idle = _run_single(
        gateway=BackpressurePolicy(max_pending=10_000))
    # zero draws, zero extra events: the traces are identical
    assert b_idle == b_off
    assert res_idle.sim.events_processed == res_off.sim.events_processed
    assert _canon(_strip_gateway(res_idle.metrics.tenant_summary())) == \
        _canon(res_off.metrics.tenant_summary())
    snap = res_idle.gate.snapshot()
    assert snap["totals"]["rejected"] == 0
    assert snap["totals"]["shed"] == 0
    assert snap["totals"]["admitted"] == snap["totals"]["submissions"] == 10
    assert snap["totals"]["done"] == 10
    assert snap["wal"] == {"records": 10, "replayed": 0,
                           "chain": res_idle.gate.wal.chain}
    assert res_idle.gate.wal.verify()


def test_backpressure_bounds_and_exact_accounting():
    pol = BackpressurePolicy(max_pending=3, retry_after_s=10.0,
                             max_client_retries=40)
    res, _ = _run_single(gateway=pol, repeats=8, concurrency=4)
    snap = res.gate.snapshot()
    tot = snap["totals"]
    assert snap["peak_pending"] <= 3
    assert tot["rejected"] > 0             # the scenario genuinely saturates
    assert tot["queued"] == 0 and tot["running"] == 0   # fully drained
    assert tot["admitted"] + tot["shed"] == tot["submissions"] == 16
    assert tot["done"] == tot["admitted"]
    assert tot["retried"] > 0
    assert snap["retry_horizon_t"] > 0.0
    # per-tenant rows sum to the totals
    for key in ("submissions", "admitted", "rejected", "shed", "done"):
        assert sum(r[key] for r in snap["tenants"].values()) == tot[key]
    # satellite 6: the arbiter exposes the same counts
    arb = res.arbiter.counters()
    assert arb["gateway_rejects"] == tot["rejected"]
    assert arb["gateway_retries"] == tot["retried"]
    assert arb["gateway_shed"] == tot["shed"]
    # and the tenant summary reports them without gateway internals
    summary = res.metrics.tenant_summary()
    for tenant, row in snap["tenants"].items():
        assert summary[tenant]["gateway_rejects"] == float(row["rejected"])
        assert summary[tenant]["gateway_shed"] == float(row["shed"])


def test_per_tenant_cap_rejects_below_global_bound():
    pol = BackpressurePolicy(max_pending=1_000, per_tenant_cap=1,
                             retry_after_s=10.0, max_client_retries=40)
    res, _ = _run_single(gateway=pol, repeats=6, concurrency=3)
    snap = res.gate.snapshot()
    # the global bound was never under pressure — every rejection came
    # from the per-tenant slice
    assert snap["peak_pending"] < 1_000
    assert snap["totals"]["rejected"] > 0
    assert snap["totals"]["admitted"] + snap["totals"]["shed"] == \
        snap["totals"]["submissions"]


def test_shed_modes_bound_waiting_room_and_balance():
    seen = {}
    for shed in ("reject-newest", "shed-oldest", "fair-shed"):
        pol = BackpressurePolicy(max_pending=2, shed=shed,
                                 retry_after_s=50.0,
                                 max_client_retries=2)
        res, _ = _run_single(gateway=pol, repeats=8, concurrency=4)
        snap = res.gate.snapshot()
        tot = snap["totals"]
        assert snap["peak_pending"] <= 2
        assert tot["admitted"] + tot["shed"] == tot["submissions"]
        assert tot["queued"] == 0
        assert tot["shed"] > 0
        if shed != "reject-newest":
            # server-side eviction bounds the retry room itself
            assert snap["peak_waiting"] <= pol.max_pending
        seen[shed] = (tot["admitted"], tot["shed"],
                      {t: r["shed"] for t, r in snap["tenants"].items()})
    # the disciplines genuinely differ in who (or how many) gets dropped
    assert len({v[:2] for v in seen.values()}) > 1 or \
        len({tuple(sorted(v[2].items())) for v in seen.values()}) > 1
    # fair-shed targets the tenant hogging the retry room — here the
    # concurrent-burst tenant, not the trickling poisson one
    fair = seen["fair-shed"][2]
    assert fair.get("prod", 0) > 0


def test_same_seed_run_is_exactly_reproducible():
    pol = BackpressurePolicy(max_pending=3, retry_after_s=10.0,
                             max_client_retries=40)
    res_a, b_a = _run_single(gateway=pol, repeats=8, concurrency=4)
    res_b, b_b = _run_single(gateway=pol, repeats=8, concurrency=4)
    assert b_a == b_b
    assert res_a.gate.snapshot() == res_b.gate.snapshot()
    assert _canon(res_a.metrics.tenant_summary()) == \
        _canon(res_b.metrics.tenant_summary())
    assert res_a.gate.trace_events() == res_b.gate.trace_events()


# --------------------------------------------------------------------------
# exactly-once under chaos transport faults
# --------------------------------------------------------------------------
def test_chaos_drop_and_dup_are_recovered_exactly_once():
    pol = BackpressurePolicy(max_pending=10_000, retry_after_s=5.0)
    chaos = ChaosSchedule(seed=3, gateway_drop_rate=0.2,
                          gateway_dup_rate=0.2)
    res, _ = _run_single(gateway=pol, chaos=chaos, repeats=6)
    snap = res.gate.snapshot()
    f = snap["faults"]
    assert f["dropped"] > 0 and f["duplicated"] > 0
    # every duplicate was suppressed, every drop redelivered
    assert f["deduped"] == f["duplicated"]
    assert f["redelivered"] >= f["dropped"]
    assert snap["totals"]["done"] == snap["totals"]["submissions"] == 12
    assert res.metrics.export_partial().completed == 12
    assert res.chaos.counters()["gateway_drops"] == f["dropped"]
    assert res.chaos.counters()["gateway_dups"] == f["duplicated"]


def test_gateway_fault_draw_requires_armed_rates():
    # both rates zero => zero draws (the PR-7 chaos stream is untouched
    # by an armed-but-fault-free gateway)
    chaos = ChaosSchedule(seed=3)
    assert not chaos.active
    assert ChaosSchedule(seed=3, gateway_drop_rate=0.1).active


# --------------------------------------------------------------------------
# crash recovery: WAL replay on a fresh plane
# --------------------------------------------------------------------------
def test_wal_replay_after_crash_is_bit_identical(tmp_path):
    pol = BackpressurePolicy(max_pending=10_000)
    clean_path = str(tmp_path / "clean.wal")
    res_clean, b_clean = _run_single(gateway=pol, wal_path=clean_path)
    res_clean.gate.close()
    clean_bytes = open(clean_path, "rb").read()

    # simulate a crash that persisted only the first K submissions
    crash_path = str(tmp_path / "crashed.wal")
    lines = clean_bytes.decode().splitlines()
    with open(crash_path, "w") as f:
        f.write("\n".join(lines[:4]) + "\n")
    res_rec, b_rec = _run_single(gateway=pol, wal_path=crash_path)
    snap = res_rec.gate.snapshot()
    assert snap["wal"]["replayed"] == 4    # the prefix was verified
    assert snap["wal"]["records"] == len(lines)
    assert snap["wal"]["chain"] == res_clean.gate.wal.chain
    res_rec.gate.close()
    # the recovered log converges to the clean run's bytes, and the
    # behavioral result is bit-identical
    assert open(crash_path, "rb").read() == clean_bytes
    assert b_rec == b_clean
    assert _canon(res_rec.metrics.tenant_summary()) == \
        _canon(res_clean.metrics.tenant_summary())


def test_kill_between_wal_append_and_submit(tmp_path):
    """The nastiest window: the WAL holds a record the engine never saw
    (the worker died after append, before the arbiter submit).  On
    restart the regenerated arrival replays against the logged record
    and is delivered exactly once."""
    pol = BackpressurePolicy(max_pending=10_000)
    # build the one-record WAL the doomed incarnation left behind: the
    # first submission of the same seeded workload
    probe, _ = _run_single(gateway=pol)
    first = probe.gate.wal.records()[0]
    path = str(tmp_path / "shard-0.wal")
    orphan = SubmissionWAL(path=path)
    orphan.append(first["tenant"], first["t"], first["digest"])
    orphan.close()

    res, _ = _run_single(gateway=pol, wal_path=path)
    snap = res.gate.snapshot()
    assert snap["wal"]["replayed"] == 1
    assert snap["totals"]["done"] == snap["totals"]["submissions"] == 10
    assert snap["faults"]["deduped"] == 0  # delivered once, not twice
    assert res.metrics.export_partial().completed == 10
    res.gate.close()


# --------------------------------------------------------------------------
# sharded plane: merge exactness + the tentpole crash-recovery pin
# --------------------------------------------------------------------------
GATE = BackpressurePolicy(max_pending=64, retry_after_s=5.0,
                          max_client_retries=20)


def _sharded(processes, policy="fair-share", wal_dir=None, **kw):
    plane = ShardedControlPlane(
        2, admission_policy=policy, seed=42,
        cluster_cfg=cal.PaperCluster(n_nodes=8),
        sample_mode="streaming", usage_mode="event", retain_pod_log=False,
        processes=processes, heartbeat_s=0.2,
        gateway=GATE, wal_dir=wal_dir, **kw)
    # tenant names span both shards under the crc32 partition:
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


def test_sharded_inline_equals_forked_with_gateway():
    r_in = _sharded(processes=False).run()
    r_mp = _sharded(processes=True).run()
    assert _canon(r_in.tenant_summary()) == _canon(r_mp.tenant_summary())
    assert r_in.gateway_summary() == r_mp.gateway_summary()
    assert r_in.completed_workflows == r_mp.completed_workflows == 16
    gw = r_in.gateway_summary()
    assert gw["totals"]["submissions"] == 16
    assert gw["totals"]["done"] == 16
    assert r_in.peak_pending_gateway == max(
        s["gateway"]["peak_pending"] for s in r_in.shards)
    # per-shard tenants are disjoint, so the merged totals are exact
    assert sum(s["gateway"]["totals"]["submissions"]
               for s in r_in.shards) == 16


def test_merge_gateway_snapshots_sums_and_maxes():
    r = _sharded(processes=False).run()
    snaps = [s["gateway"] for s in r.shards]
    merged = merge_gateway_snapshots(snaps)
    assert merged == r.gateway_summary()
    for key in ("submissions", "admitted", "done"):
        assert merged["totals"][key] == \
            sum(s["totals"][key] for s in snaps)
    assert merged["peak_pending"] == max(s["peak_pending"] for s in snaps)
    assert merged["wal"]["records"] == sum(s["wal"]["records"]
                                           for s in snaps)
    assert "chain" not in merged.get("wal", {})   # per-log, never merged
    assert merge_gateway_snapshots([]) == {}
    assert merge_gateway_snapshots([None, snaps[0]])["totals"] == \
        snaps[0]["totals"]


@pytest.mark.parametrize("policy", ALL_POLICIES)
def test_midrun_kill_restart_is_bit_identical(policy, tmp_path,
                                              monkeypatch):
    """The tentpole pin: kill shard 1 mid-run (os._exit at a sim
    instant, after real submissions and WAL appends), restart it, and
    the merged behavioral metrics are bit-identical to a never-crashed
    same-seed run — under every admission policy.  The restarted
    shard's WAL replay is observable (replayed > 0) and its final log
    file is byte-identical to the clean run's."""
    clean_dir = str(tmp_path / "clean")
    clean = _sharded(processes=True, policy=policy, wal_dir=clean_dir).run()
    kill_t = clean.sim_makespan_s / 2.0
    crash_dir = str(tmp_path / "crash")
    monkeypatch.setenv("REPRO_SHARD_KILL", f"1@{kill_t}")
    crashed = _sharded(processes=True, policy=policy, wal_dir=crash_dir,
                       on_shard_failure="restart").run()
    assert not crashed.degraded
    gw = crashed.gateway_summary()
    assert gw["wal"]["replayed"] > 0       # the restart really replayed
    assert _canon(crashed.tenant_summary()) == _canon(clean.tenant_summary())
    assert crashed.completed_workflows == clean.completed_workflows == 16
    # gateway summaries agree on everything but the replay provenance
    gw_clean = clean.gateway_summary()
    assert gw_clean["wal"]["replayed"] == 0
    gw["wal"]["replayed"] = 0
    assert gw == gw_clean
    # the recovered logs converge to the clean run's bytes
    for i in range(2):
        a = open(os.path.join(clean_dir, f"shard-{i}.wal"), "rb").read()
        b = open(os.path.join(crash_dir, f"shard-{i}.wal"), "rb").read()
        assert a == b


def test_shard_restart_merge_has_no_double_count(monkeypatch):
    """Satellite 1 (PR-7 audit): a killed shard sends NO result record
    — only the restarted incarnation's record reaches the merge, so
    nothing is counted twice."""
    healthy = _sharded(processes=True).run()
    monkeypatch.setenv("REPRO_SHARD_KILL", "1")
    restarted = _sharded(processes=True, on_shard_failure="restart").run()
    assert not restarted.degraded
    # exactly one record per shard index in the merged result
    assert sorted(s["shard"] for s in restarted.shards) == [0, 1]
    assert _canon(restarted.tenant_summary()) == \
        _canon(healthy.tenant_summary())
    assert restarted.completed_workflows == healthy.completed_workflows
    assert restarted.events == healthy.events
    # per-shard event counts sum exactly once into the merged total
    assert sum(s["events"] for s in restarted.shards) == restarted.events


# --------------------------------------------------------------------------
# arrival_trace/v2 (satellite 2)
# --------------------------------------------------------------------------
def test_trace_v2_records_gateway_events_and_v1_still_loads(tmp_path):
    pol = BackpressurePolicy(max_pending=3, retry_after_s=10.0,
                             max_client_retries=40)
    plane = ControlPlane(
        "kubeadaptor", admission_policy="fair-share", seed=7,
        cluster_cfg=cal.PaperCluster(n_nodes=8),
        sample_mode="streaming", usage_mode="event",
        retain_pod_log=False, gateway=pol)
    plane.add_stream(MONTAGE, repeats=8, tenant="prod",
                     arrival="concurrent", concurrency=4)
    res = plane.run()
    path = str(tmp_path / "trace.json")
    doc = plane.record_trace(path)
    assert doc["schema"] == "arrival_trace/v2"
    assert json.loads(open(path).read()) == doc
    assert doc["gateway"]["policy"]["max_pending"] == 3
    kinds = {e["event"] for e in doc["gateway"]["events"]}
    assert "reject" in kinds
    assert all(set(e) == {"t", "id", "tenant", "event"}
               for e in doc["gateway"]["events"])
    # a v2 doc replays through the v1 loader (arrivals are unchanged)
    replay = ControlPlane("kubeadaptor", seed=7)
    replay.add_trace(doc["arrivals"], tenants=doc["tenants"])
    res2 = replay.run()
    assert res2.metrics.export_partial().completed == \
        res.metrics.export_partial().completed
    # and a genuine v1 doc (no gateway) still loads — schema untouched
    v1 = json.load(open(os.path.join(
        os.path.dirname(__file__), "..", "examples", "trace_mixed.json")))
    assert v1["schema"] == "arrival_trace/v1"
    v1_plane = ControlPlane("kubeadaptor", seed=1)
    v1_plane.add_trace(v1["arrivals"], tenants=v1.get("tenants"))


# --------------------------------------------------------------------------
# arbiter exposure (satellite 6)
# --------------------------------------------------------------------------
def test_arbiter_counters_expose_gateway_pressure():
    arb = ControlPlane("kubeadaptor", admission_policy="fifo").arbiter
    c = arb.counters()
    assert c["gateway_rejects"] == 0
    assert c["gateway_retries"] == 0
    assert c["gateway_shed"] == 0
    arb.note_gateway("reject")
    arb.note_gateway("retry")
    arb.note_gateway("retry")
    arb.note_gateway("shed")
    c = arb.counters()
    assert (c["gateway_rejects"], c["gateway_retries"],
            c["gateway_shed"]) == (1, 2, 1)
    with pytest.raises(ValueError):
        arb.note_gateway("explode")


def test_runner_rejects_wal_without_gateway():
    with pytest.raises(ValueError):
        ControlPlane("kubeadaptor", wal_path="/tmp/nope.wal")
