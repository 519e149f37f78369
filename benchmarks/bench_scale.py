"""Scale-out stress tiers: 1000-wf/100-node, 10k-wf/1000-node and
100k-wf/1000-node runs through the multi-tenant ControlPlane (ROADMAP
scale track).

Eight streams (two tenants per paper topology) drive the full
KubeAdaptor stack — gateway, admission arbiter, informers, disordered
scheduler — on a synthetic ``PaperCluster`` scaled to ``--nodes``.
Each topology contributes a closed-loop "prod" tenant (concurrent
arrivals, priority 10, fair-share weight 3) and an open-loop "batch"
tenant (Poisson surge, the whole queue arriving in the first ~minute),
so the admission backlog grows to thousands of pending requests while
interactive load keeps flowing. Per admission policy the run records
real wall-clock, sim events/sec, *events per pod* (the 10k-tier
bottleneck ISSUE 3 attacks), usage-accounting mode,
peak pending depths, per-tenant makespan, and peak RSS, then writes
everything to ``BENCH_scale.json`` (``bench_scale/v2`` schema:
benchmarks/README.md).

Usage:
    PYTHONPATH=src python -m benchmarks.bench_scale \
        [--workflows 1000] [--nodes 100] [--workers 1] \
        [--tiers 1000x100,10000x1000,100000x1000,1000000x8000x8] \
        [--seed 42] [--policies fifo,priority,fair-share,drf,quota,preempt] \
        [--usage-mode event|sampled] [--trace examples/trace_mixed.json] \
        [--out BENCH_scale.json] [--budget-s 0] [--profile] \
        [--min-events-per-sec 0] [--max-events-per-pod 0] \
        [--max-peak-rss-mib 0] [--max-shard-rss-mib 0] [--shard-procs 0] \
        [--chaos-node-kill-interval 0] [--chaos-drain-interval 0] \
        [--chaos-node-downtime 0] [--chaos-api-fault-rate 0] \
        [--chaos-task-crash-rate 0] [--chaos-start-after 0] \
        [--chaos-seed 0] [--require-complete] [--append] \
        [--placement first-fit|scored-spread|scored-pack] \
        [--node-mix uniform|big-small|cpu-mem-skew] \
        [--deschedule-interval 0] [--deschedule-threshold 0.9] \
        [--deschedule-victim youngest|largest-request] \
        [--autoscale-interval 0] [--autoscale-pending-threshold 1] \
        [--autoscale-sustain 30] [--autoscale-idle 60] \
        [--autoscale-min-frac 0.25] [--autoscale-scale-step 1] \
        [--autoscale-start-after 0] \
        [--gateway-max-pending 0] [--gateway-per-tenant-cap 0] \
        [--gateway-shed reject-newest|shed-oldest|fair-shed] \
        [--gateway-retry-after 5] [--gateway-max-retries 8] \
        [--gateway-wal-dir DIR] [--chaos-gateway-drop-rate 0] \
        [--chaos-gateway-dup-rate 0] [--min-complete-frac 0]

``--budget-s`` exits 2 when total wall time exceeds the budget;
``--min-events-per-sec`` / ``--max-events-per-pod`` /
``--max-peak-rss-mib`` exit 2 when any run breaches the floor/ceiling
— the ``bench-scale-smoke`` CI job uses them so event-core and memory
regressions fail the build (``peak_rss_mib`` is a process-lifetime
high-water mark, so the RSS gate budgets the whole sweep).
``--profile`` wraps each policy run in cProfile and prints the top-20
cumulative-time hotspots, so perf PRs can cite before/after profiles
instead of guessing. ``--trace`` replays a recorded arrival trace
(see ``arrival_trace/v1`` in benchmarks/README.md) instead of the
synthetic streams. The module's ``run()`` (for ``benchmarks.run``)
executes a reduced 50-workflow/20-node smoke variant of the synthetic
scenario.

Throughput accounting (ISSUE 5): ``events_per_sec`` divides by the
sim's event-loop wall time (``Sim.run_wall_s``, which ends at
``last_event_t``'s event), not the full ``plane.run`` wall — plane
setup, result assembly and post-completion drain no longer understate
throughput on short tiers or pollute cross-tier comparisons.
``wall_s`` stays the full run wall (the budget gate's basis).

Sharded control plane (ISSUE 6): ``--workers N`` (or a third tier
component, ``WFxNODESxWORKERS``) partitions the scenario's tenants
across N arbiter shards (``repro.core.shard``): 2·topologies streams
*per worker* (tenants ``{topo}-{klass}{j}``, which the crc32 partition
spreads evenly), each shard owning a disjoint node slice and running
its own event loop in a forked worker process.  Sharded rows report
``workers``, per-shard ``shards[]`` rows, per-shard self-reported
``peak_rss_mib`` and the fork-proof ``total_peak_rss_mib`` (parent
RSS + Σ shard self-reports — the ``--max-peak-rss-mib`` gate reads
this, so forking cannot hide memory; ``--max-shard-rss-mib`` gates
each shard's own peak).  ``events_per_sec`` on a sharded row is
Σ shard events / max shard loop-wall with at most ``--shard-procs``
loops running concurrently (weak-scaling aggregate — see
benchmarks/README.md); ``wall_s`` stays the true end-to-end wall and
``loop_cpu_s`` the CPU-second basis.  ``--profile`` collects each
shard's own cProfile and prints the top-20 labeled by shard.
``workers=1`` takes the unsharded in-process path, byte-identical to
v3 behavior.

Admission-pipeline policies (ISSUE 4): ``--policies`` also accepts
``drf`` (dominant-resource fair share), ``quota`` (fifo ordering with
hard per-tenant CPU caps — prod 20% / batch 10% of the cluster, so the
caps genuinely bind), and ``preempt`` (priority ordering + starvation
eviction).  Every stream carries an SLO deadline (prod 180 s / batch
3600 s — metrics only); runs report per-tenant deadline hit-rates plus
preemption and quota-reject counts.

Chaos tier (ISSUE 7): the ``--chaos-*`` flags arm a seeded
``ChaosSchedule`` (repro.core.chaos) on every policy run — node
kills/drains on exponential timers with seeded downtime, transient
apiserver faults absorbed by the engine's capped
exponential-backoff-with-jitter retry, and mid-run task crashes that
ride the ordinary retry budget.  Chaos draws come from their own
sha256-spawned stream, so runs without the flags are bit-identical to
``bench_scale/v4`` behavior and a fixed ``--chaos-seed`` replays
exactly (sharded runs spawn per-shard sub-streams).  Chaos rows add
``"chaos"`` (injection counters: node kills/drains/restores, pods
lost, api faults, task crashes, cumulative node downtime) and
``"recovery"`` (node_lost vs preempted eviction split,
time-to-reschedule percentiles).  ``--require-complete`` exits 2
unless every run completes all workflows with zero failures — the
``chaos-smoke`` CI job uses it to assert full recovery under faults
across all six policies.  ``--append`` merges the new tiers into an
existing ``--out`` report instead of overwriting it, so the chaos
tier can ride alongside previously recorded tiers.

Heterogeneous placement tier (ISSUE 8): ``--node-mix`` swaps the
uniform ``PaperCluster`` for a ``hetero_cluster`` preset
(``big-small`` or ``cpu-mem-skew`` — weighted node-class cycles whose
per-node average equals the paper node, so total allocatable stays
comparable), and ``--placement`` picks the node-selection mode:
``first-fit`` (default, bit-identical to every pinned v5 binding
hash) or the utilization-scored ``scored-spread`` /
``scored-pack`` modes fused into the native scheduler cycle.  Scored
placement consumes the identical shuffle word stream as first-fit —
only the pick among feasible nodes changes.  ``--deschedule-interval``
/ ``--deschedule-threshold`` arm the periodic descheduler daemon
(repro.core.descheduler): pods evicted off hot nodes requeue through
the recovery machinery with no retry-budget charge.  v6 rows add
``placement``, ``node_hotspot`` (per-node peak-utilization
mean/max/min/variance — the hotspot-variance comparison between
first-fit and scored-spread is the tier's headline), ``rebalances``,
``descheduler`` counters (when armed) and a ``p99`` tail in
``pod_exec_s``; hetero scenarios record ``node_mix`` +
``node_classes``.  ``--append`` refuses (exit 2) to merge tiers into
a report written under a different schema version.

Elastic autoscaling tier (ISSUE 9): ``--autoscale-interval`` arms the
deterministic node-pool autoscaler (repro.core.autoscaler) on every
policy run — the full roster is materialized (fixed native-mirror
indices) but each node class starts at a ``--autoscale-min-frac``
floor, scales up by ``--autoscale-scale-step`` nodes per tick while
pending depth stays >= ``--autoscale-pending-threshold`` for
``--autoscale-sustain`` seconds, and drains nodes idle for
``--autoscale-idle`` seconds back down when the queues are empty.
The daemon draws zero RNG words, so runs without the flags stay
bit-identical to ``bench_scale/v6`` behavior.  v7 rows always add
``"cost"`` (``Cluster.cost_summary``: provisioned node/cpu/mem
seconds, time-weighted utilization over *provisioned* capacity, and
provisioning peak/low/flips — flat provisioning on fixed rosters, so
fixed-vs-autoscaled comparisons read straight off the report), plus
``"autoscaler"`` counters when the daemon was armed; autoscaled
scenarios echo the knobs under ``scenario["autoscale"]`` and
descheduler scenarios gain the ``victim`` eviction-order echo
(``--deschedule-victim``).  Sharded runs slice explicit pool bounds
across shards and merge cost exactly (areas/flips sum, ratios
recomputed from pooled areas).

Durable front door tier (ISSUE 10): ``--gateway-max-pending`` arms the
``DurableGateway`` (repro.core.gateway) on every policy run — a
per-shard append-only submission WAL plus admission backpressure: at
most ``max-pending`` submissions admitted-but-unfinished per shard,
rejects carrying deterministic retry-after timers from a dedicated
sha256-spawned stream, and ``--gateway-shed`` picking the overload
victim (``reject-newest`` / ``shed-oldest`` / ``fair-shed``).
``--gateway-wal-dir`` arms the crash-durable file sink
(``shard-{i}.wal``), so a shard killed mid-run (REPRO_SHARD_KILL) and
restarted replays its log with exactly-once dedup.  An unsaturated
gateway performs zero draws and adds zero events, so runs without the
flags stay bit-identical to ``bench_scale/v7`` behavior.  v8 rows add
``"gateway"`` (the merged qstat snapshot: per-tenant
queued/admitted/running/done/rejected/retried/shed, peak pending /
waiting depths, retry horizon, transport-fault and WAL counters) plus
the arbiter's submission-edge counters, and two gates arm
automatically on every gateway row: peak pending must stay <=
max-pending (BACKPRESSURE BREACH) and admitted + shed must equal
submissions with an empty retry room at drain (GATEWAY ACCOUNTING).
``--require-complete`` on a gateway row asserts completed + shed ==
workflows instead of completed == workflows; ``--min-complete-frac``
sets the eventual-completion floor for the overload tier (e.g. 0.99).
``--chaos-gateway-drop-rate`` / ``--chaos-gateway-dup-rate`` extend
the chaos plane to the gate->arbiter hop: dropped submissions are
redelivered from the WAL, duplicates are suppressed by the dedup set.

v9 rows drop ``queue`` and ``lifecycle``: the sim has one event queue
and one pod lifecycle, so neither field can vary.

The script still runs against the pre-optimization core (counters it
introduced are read via getattr) so speedups can be measured by
checking out two revisions and comparing ``wall_s``.
"""
import argparse
import cProfile
import inspect
import json
import platform
import pstats
import resource
import sys
import time

from benchmarks.common import row
from repro.configs.workflows import get_workflow_spec
from repro.core import calibration as cal
from repro.core.dag import make_workflow
from repro.core.runner import ControlPlane

TOPOLOGIES = ("montage", "epigenomics", "cybershake", "ligo")
POLICIES = ("fifo", "priority", "fair-share")
# pipeline policies (ISSUE 4) accepted by --policies next to the three
# legacy names: drf ordering, hard quota caps, priority preemption
PIPELINE_POLICIES = ("drf", "quota", "preempt")
# per-stream SLO deadlines (reported as deadline hit-rates; pure
# metrics — legacy-policy scheduling is unaffected)
PROD_DEADLINE_S = 180.0
BATCH_DEADLINE_S = 3600.0
# under --policies quota: per-tenant caps as fractions of cluster CPU
# (sum over the 8 streams = 120%, so caps genuinely bind under load)
PROD_QUOTA_FRAC = 0.20
BATCH_QUOTA_FRAC = 0.10
SCHEMA = "bench_scale/v9"


def _plane_kwargs(usage_mode, placement="first-fit",
                  deschedule=None, autoscale=None, gateway=None):
    """Knobs that only the optimized core understands."""
    params = inspect.signature(ControlPlane.__init__).parameters
    kw = {}
    if "sample_mode" in params:
        kw["sample_mode"] = "streaming"
    if "retain_pod_log" in params:
        kw["retain_pod_log"] = False
    if "usage_mode" in params:
        kw["usage_mode"] = usage_mode
    if "placement" in params and placement != "first-fit":
        kw["placement"] = placement
    if "deschedule" in params and deschedule is not None:
        kw["deschedule"] = deschedule
    if "autoscale" in params and autoscale is not None:
        kw["autoscale"] = autoscale
    if "gateway" in params and gateway is not None:
        kw["gateway"] = gateway
    return kw


def _cluster_cfg(n_nodes, node_mix="uniform"):
    """The tier's cluster config: the paper's uniform nodes, or a
    heterogeneous node-class mix (ISSUE 8)."""
    if node_mix and node_mix != "uniform":
        return cal.hetero_cluster(n_nodes, node_mix)
    return cal.PaperCluster(n_nodes=n_nodes)


def build_plane(policy, n_workflows, n_nodes, seed, usage_mode="event",
                trace=None, workers=1, shard_procs=None, processes=True,
                profile=False, chaos=None, placement="first-fit",
                node_mix="uniform", deschedule=None, autoscale=None,
                gateway=None, wal_dir=None):
    cfg = _cluster_cfg(n_nodes, node_mix)
    if workers > 1:
        from repro.core.shard import ShardedControlPlane
        extra = {}
        if gateway is not None and wal_dir:
            extra["wal_dir"] = wal_dir
        plane = ShardedControlPlane(
            workers, admission_policy=policy,
            cluster_cfg=cfg, seed=seed,
            fold_completed=True, capture_trace=False,
            shard_procs=shard_procs, processes=processes, profile=profile,
            chaos=chaos, **extra,
            **_plane_kwargs(usage_mode, placement, deschedule, autoscale,
                            gateway))
    else:
        extra = {}
        if gateway is not None and wal_dir:
            import os as _os
            extra["wal_path"] = _os.path.join(wal_dir, "shard-0.wal")
        plane = ControlPlane("kubeadaptor", admission_policy=policy,
                             cluster_cfg=cfg,
                             seed=seed, chaos=chaos, **extra,
                             **_plane_kwargs(usage_mode, placement,
                                             deschedule, autoscale,
                                             gateway))
    if trace is not None:
        plane.add_trace(trace.get("arrivals", []),
                        tenants=trace.get("tenants"))
        return plane
    # sharded scenarios scale the stream count with the shard count —
    # 2·topologies streams per worker, tenant names "{topo}-{klass}{j}"
    # (the crc32 partition spreads each such family across all shards
    # exactly evenly, so every shard sees the full topology/class mix)
    n_streams = 2 * len(TOPOLOGIES) * (workers if workers > 1 else 1)
    per, rem = divmod(n_workflows, n_streams)
    # enough closed-loop concurrency to keep ~666 pod slots/100 nodes busy
    conc = max(2, (n_nodes * 7) // (n_streams * 4))
    # allocatable CPU from the actual node list: identical to
    # n_nodes * node_cpu_m on the uniform cluster, and the true sum
    # over the class cycle on a heterogeneous mix
    total_cpu_m = sum(cpu for _, cpu, _ in cfg.nodes())
    # quota caps bind against what a stream's arbiter can actually see:
    # its own shard's slice of the cluster (= the whole cluster at
    # workers=1), keeping per-shard contention geometry tier-invariant
    quota_cpu_m = total_cpu_m // workers if workers > 1 else total_cpu_m
    quotas = {"prod": 0, "batch": 0}
    if policy == "quota":           # caps only bind under the quota preset
        quotas = {"prod": int(PROD_QUOTA_FRAC * quota_cpu_m),
                  "batch": int(BATCH_QUOTA_FRAC * quota_cpu_m)}
    deadlines = {"prod": PROD_DEADLINE_S, "batch": BATCH_DEADLINE_S}
    i = 0
    for topo in TOPOLOGIES:
        wf = make_workflow(topo, get_workflow_spec(topo))
        for klass, prio, weight in (("prod", 10, 3.0), ("batch", 0, 1.0)):
            for j in range(workers if workers > 1 else 1):
                tenant = (f"{topo}-{klass}{j}" if workers > 1
                          else f"{topo}-{klass}")
                repeats = per + (1 if i < rem else 0)
                extra = {}
                if quotas[klass]:
                    extra["quota_cpu_m"] = quotas[klass]
                if _add_stream_accepts("deadline_s"):
                    extra["deadline_s"] = deadlines[klass]
                if klass == "prod":     # closed-loop interactive tenant
                    plane.add_stream(wf, repeats=repeats, tenant=tenant,
                                     arrival="concurrent", concurrency=conc,
                                     priority=prio, weight=weight, **extra)
                else:                   # open-loop surge: deep pending queue
                    plane.add_stream(wf, repeats=repeats, tenant=tenant,
                                     arrival="poisson", rate=0.5, burst=2,
                                     priority=prio, weight=weight, **extra)
                i += 1
    return plane


def _add_stream_accepts(name):
    return name in inspect.signature(ControlPlane.add_stream).parameters


def _round_gateway(snap):
    """Round the snapshot's float fields for the report (counters and
    gauges stay exact ints)."""
    out = dict(snap)
    out["retry_horizon_t"] = round(snap.get("retry_horizon_t", 0.0), 2)
    if "wal" in out:
        out["wal"] = {k: v for k, v in out["wal"].items() if k != "chain"}
    return out


def run_policy(policy, n_workflows, n_nodes, seed, horizon_s=400_000.0,
               usage_mode="event", trace=None,
               profile=False, workers=1, shard_procs=None, chaos=None,
               placement="first-fit", node_mix="uniform", deschedule=None,
               autoscale=None, gateway=None, wal_dir=None):
    if wal_dir:
        # one WAL namespace per (policy, tier) run: a later run must
        # never replay a previous policy's log as its own durable prefix
        import os as _os
        wal_dir = _os.path.join(
            wal_dir, f"{policy}-{n_workflows}wf-{n_nodes}n")
    if workers > 1:
        return _run_policy_sharded(
            policy, n_workflows, n_nodes, seed, horizon_s=horizon_s,
            usage_mode=usage_mode, trace=trace, profile=profile,
            workers=workers, shard_procs=shard_procs, chaos=chaos,
            placement=placement, node_mix=node_mix, deschedule=deschedule,
            autoscale=autoscale, gateway=gateway, wal_dir=wal_dir)
    plane = build_plane(policy, n_workflows, n_nodes, seed,
                        usage_mode=usage_mode, trace=trace, chaos=chaos,
                        placement=placement, node_mix=node_mix,
                        deschedule=deschedule, autoscale=autoscale,
                        gateway=gateway, wal_dir=wal_dir)
    try:
        import repro.core.cluster as _cluster_mod
        copies0 = _cluster_mod.SNAPSHOTS_MADE
    except AttributeError:            # pre-zero-copy core
        _cluster_mod, copies0 = None, 0
    profiler = None
    if profile:
        profiler = cProfile.Profile()
        profiler.enable()
    t0 = time.perf_counter()
    res = plane.run(horizon_s=horizon_s)
    wall = time.perf_counter() - t0
    if profiler is not None:
        profiler.disable()
        print(f"--- profile [{n_workflows}wf/{n_nodes}n {policy}] "
              f"top-20 by cumulative time ---", flush=True)
        pstats.Stats(profiler).sort_stats("cumulative").print_stats(20)
    m = res.metrics
    completed = sum(1 for r in m.workflows.values()
                    if r.ns_deleted > 0 and not r.failed)
    failed = sum(1 for r in m.workflows.values() if r.failed)
    events = getattr(res.sim, "events_processed", None)
    pods = getattr(res.cluster, "pods_created", None)
    summary_by_tenant = m.tenant_summary()
    # pre-optimization cores leave sim.t at the drain time; the current
    # core parks it at the horizon and keeps the drain in last_event_t
    makespan = getattr(res.sim, "last_event_t", res.sim.t)
    # throughput over the event loop's own wall (ends at last_event_t's
    # event): excludes setup/epilogue/drain — see module docstring
    loop_wall = getattr(res.sim, "run_wall_s", 0.0) or wall
    rec = {
        "policy": policy,
        "wall_s": round(wall, 3),
        "loop_wall_s": round(loop_wall, 3),
        "sim_makespan_s": round(makespan, 2),
        "events": events,
        "events_per_sec": (round(events / loop_wall) if events else None),
        "pods_created": pods,
        "events_per_pod": (round(events / pods, 2)
                           if events and pods else None),
        "usage_mode": getattr(m, "usage_mode", "sampled"),
        "peak_pending_admission": getattr(res.arbiter, "max_pending", None),
        "peak_pending_pods": getattr(res.cluster, "max_pending_pods", None),
        "completed_workflows": completed,
        "failed_workflows": failed,
        "api_calls": res.cluster.api_calls,
        "admitted": res.arbiter.admitted,
        "deferrals": res.arbiter.deferrals,
        "peak_rss_mib": round(
            resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024, 1),
        # fork-proof memory accounting (ISSUE 6): even an unsharded run
        # reports the children's high-water mark, so work moved into
        # forked processes can never slip past the --max-peak-rss-mib
        # gate (total = parent + reaped-children peak; 0 children here)
        "rusage_children_mib": round(
            resource.getrusage(resource.RUSAGE_CHILDREN).ru_maxrss / 1024, 1),
        "total_peak_rss_mib": round(
            (resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
             + resource.getrusage(resource.RUSAGE_CHILDREN).ru_maxrss)
            / 1024, 1),
        "tenant_makespan_s": {
            t: round(s["makespan"], 2)
            for t, s in summary_by_tenant.items()},
    }
    # admission-pipeline observables (ISSUE 4): zero/absent on cores
    # that predate them; always emitted by the pipeline core so the
    # quota/preempt sweeps land in the same schema
    rec["preemptions"] = getattr(res.arbiter, "preemptions", None)
    rec["quota_rejects"] = getattr(res.arbiter, "quota_rejects", None)
    # scale observables (ISSUE 5): multi-grant admission rounds and the
    # object copies the zero-copy informer views actually materialized
    rec["grant_batches"] = getattr(res.arbiter, "grant_batches", None)
    if _cluster_mod is not None:
        rec["informer_copies"] = _cluster_mod.SNAPSHOTS_MADE - copies0
    slo = {t: {"deadline_s": s["deadline_s"],
               "hit_rate": (round(s["deadline_hit_rate"], 4)
                            if s["deadline_hit_rate"] == s["deadline_hit_rate"]
                            else None)}
           for t, s in summary_by_tenant.items() if "deadline_s" in s}
    if slo:
        rec["slo"] = slo
    summary = getattr(m, "usage_summary", None)
    if summary is not None:
        cpu = summary().get("cpu")
        if cpu:
            rec["cpu_usage"] = {
                k: (round(v, 4) if isinstance(v, float) else v)
                for k, v in cpu.items()}
    else:                          # pre-optimization fallback
        cpu_stat = getattr(m, "cpu_stat", None)
        if cpu_stat is not None and cpu_stat.count:
            cpu_a, _ = res.cluster.allocatable()
            rec["cpu_usage"] = {"samples": cpu_stat.count,
                                "mean_rate": round(cpu_stat.mean / cpu_a, 4),
                                "peak_rate": round(cpu_stat.max / cpu_a, 4),
                                "p95_rate": round(
                                    cpu_stat.percentile(95) / cpu_a, 4)}
    exec_stat = getattr(res.cluster, "exec_stat", None)
    if exec_stat is not None and exec_stat.count:
        rec["pod_exec_s"] = {"count": exec_stat.count,
                             "mean": round(exec_stat.mean, 2),
                             "max": round(exec_stat.max, 2),
                             "p95": round(exec_stat.percentile(95), 2),
                             "p99": round(exec_stat.percentile(99), 2)}
    # placement observables (ISSUE 8): per-node peak-utilization
    # profile (the first-fit vs scored hotspot comparison), the active
    # placement mode, and descheduler accounting when the daemon ran
    rec["placement"] = getattr(res.cluster, "placement", "first-fit")
    hotspot = getattr(res.cluster, "hotspot_summary", None)
    if hotspot is not None:
        rec["node_hotspot"] = {
            k: (round(v, 6) if isinstance(v, float) else v)
            for k, v in hotspot().items()}
    rec["rebalances"] = getattr(res.cluster, "rebalances", 0)
    desched = getattr(res, "descheduler", None)
    if desched is not None:
        rec["descheduler"] = desched.counters()
    # cost accounting (ISSUE 9): always emitted — fixed rosters report
    # flat provisioning, so cost-vs-makespan comparisons between fixed
    # and autoscaled rows read straight off the report
    cost = getattr(res.cluster, "cost_summary", None)
    if cost is not None:
        rec["cost"] = {k: (round(v, 4) if isinstance(v, float) else v)
                       for k, v in cost().items()}
    autoscaler = getattr(res, "autoscaler", None)
    if autoscaler is not None:
        rec["autoscaler"] = autoscaler.counters()
    # chaos/recovery observables (ISSUE 7): only emitted when a chaos
    # schedule was armed — chaos-free rows keep the exact v4 key set
    chaos_inj = getattr(res, "chaos", None)
    if chaos_inj is not None:
        rec["chaos"] = chaos_inj.counters()
        rec["recovery"] = {
            k: (round(v, 4) if isinstance(v, float) else v)
            for k, v in m.export_partial().recovery_summary().items()}
    # durable front door observables (ISSUE 10): only emitted when the
    # gateway was armed — gateway-free rows keep the pre-v8 key set.
    # The submission-edge counters come off the arbiter (satellite:
    # counters() exposes them), not gateway internals.
    gate = getattr(res, "gate", None)
    if gate is not None:
        rec["gateway"] = _round_gateway(gate.snapshot())
        rec["gateway_rejects"] = getattr(res.arbiter, "gateway_rejects", 0)
        rec["gateway_retries"] = getattr(res.arbiter, "gateway_retries", 0)
        rec["gateway_shed"] = getattr(res.arbiter, "gateway_shed", 0)
    return rec


def _run_policy_sharded(policy, n_workflows, n_nodes, seed,
                        horizon_s=400_000.0, usage_mode="event",
                        trace=None, profile=False,
                        workers=2, shard_procs=None, chaos=None,
                        placement="first-fit", node_mix="uniform",
                        deschedule=None, autoscale=None, gateway=None,
                        wal_dir=None):
    """One policy run through the tenant-partitioned control plane
    (repro.core.shard): same row schema as the unsharded path plus
    ``workers`` / ``shards[]`` / fork-proof RSS totals."""
    import os as _os

    plane = build_plane(policy, n_workflows, n_nodes, seed,
                        usage_mode=usage_mode, trace=trace, workers=workers,
                        shard_procs=shard_procs, profile=profile,
                        chaos=chaos, placement=placement, node_mix=node_mix,
                        deschedule=deschedule, autoscale=autoscale,
                        gateway=gateway, wal_dir=wal_dir)
    t0 = time.perf_counter()
    res = plane.run(horizon_s=horizon_s)
    wall = time.perf_counter() - t0
    if profile:
        for s in res.shards:
            if s["profile"]:
                print(f"--- profile [{n_workflows}wf/{n_nodes}n {policy} "
                      f"shard {s['shard']}] top-20 by cumulative time ---",
                      flush=True)
                print(s["profile"], flush=True)
    summary_by_tenant = res.tenant_summary()
    arb = res.arbiter_totals()
    parent_rss = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024
    children_rss = \
        resource.getrusage(resource.RUSAGE_CHILDREN).ru_maxrss / 1024
    # the gate-bearing total: parent + every shard's own self-reported
    # peak (RUSAGE_CHILDREN only keeps the max over reaped children and
    # accumulates across the sweep — reported for cross-checking)
    total_rss = parent_rss + sum(s["peak_rss_mib"] for s in res.shards)
    events = res.events
    pods = res.pods_created
    loop_wall = res.loop_wall_s
    loop_cpu = res.loop_cpu_s
    rec = {
        "policy": policy,
        "workers": workers,
        "shard_procs": min(shard_procs or _os.cpu_count() or 1, workers),
        "wall_s": round(wall, 3),
        "loop_wall_s": round(loop_wall, 3),
        "loop_cpu_s": round(loop_cpu, 3),
        "sim_makespan_s": round(res.sim_makespan_s, 2),
        "events": events,
        # weak-scaling aggregate: sum of shard events over the slowest
        # shard's loop wall, each loop unoversubscribed (shard_procs
        # waves) — see benchmarks/README.md; wall_s is end-to-end truth
        "events_per_sec": (round(events / loop_wall)
                           if events and loop_wall else None),
        "events_per_cpu_sec": (round(events / loop_cpu)
                               if events and loop_cpu else None),
        "pods_created": pods,
        "events_per_pod": (round(events / pods, 2)
                           if events and pods else None),
        "usage_mode": res.shards[0]["usage_mode"],
        "peak_pending_admission": res.peak_pending_admission,
        "peak_pending_pods": res.peak_pending_pods,
        "completed_workflows": res.completed_workflows,
        "failed_workflows": res.failed_workflows,
        "api_calls": res.api_calls,
        "admitted": arb.get("admitted", 0),
        "deferrals": arb.get("deferrals", 0),
        "peak_rss_mib": round(parent_rss, 1),
        "rusage_children_mib": round(children_rss, 1),
        "total_peak_rss_mib": round(total_rss, 1),
        "peak_shard_rss_mib": round(res.peak_shard_rss_mib, 1),
        "tenant_makespan_s": {
            t: round(s["makespan"], 2)
            for t, s in summary_by_tenant.items()},
        "preemptions": arb.get("preemptions", 0),
        "quota_rejects": arb.get("quota_rejects", 0),
        "grant_batches": arb.get("grant_batches", 0),
        "informer_copies": res.informer_copies,
        "shards": [{
            "shard": s["shard"],
            "nodes": s["nodes"],
            "seed": s["seed"],
            "tenants": len(s["tenants"]),
            "wall_s": round(s["wall_s"], 3),
            "loop_wall_s": round(s["loop_wall_s"], 3),
            "loop_cpu_s": round(s["loop_cpu_s"], 3),
            "sim_makespan_s": round(s["last_event_t"], 2),
            "events": s["events"],
            "events_per_sec": (round(s["events"] / s["loop_wall_s"])
                               if s["loop_wall_s"] else None),
            "pods_created": s["pods_created"],
            "completed_workflows": s["completed_workflows"],
            "failed_workflows": s["failed_workflows"],
            "peak_pending_admission": s["arbiter"].get("max_pending", 0),
            "peak_pending_pods": s["peak_pending_pods"],
            "peak_rss_mib": round(s["peak_rss_mib"], 1),
            **({"gateway_peak_pending": s["gateway"]["peak_pending"],
                "wal_records": s["gateway"]["wal"]["records"],
                "wal_replayed": s["gateway"]["wal"]["replayed"]}
               if s.get("gateway") else {}),
        } for s in res.shards],
    }
    slo = {t: {"deadline_s": s["deadline_s"],
               "hit_rate": (round(s["deadline_hit_rate"], 4)
                            if s["deadline_hit_rate"] == s["deadline_hit_rate"]
                            else None)}
           for t, s in summary_by_tenant.items() if "deadline_s" in s}
    if slo:
        rec["slo"] = slo
    cpu = res.usage_summary().get("cpu")
    if cpu:
        # merged across shard slices: rates normalized per slice, so
        # mean is the time-weighted mean slice utilization and peak the
        # max per-slice peak (basis "event" + merged shard windows)
        rec["cpu_usage"] = {k: (round(v, 4) if isinstance(v, float) else v)
                            for k, v in cpu.items()}
    if res.exec_stat is not None and res.exec_stat.count:
        rec["pod_exec_s"] = {"count": res.exec_stat.count,
                             "mean": round(res.exec_stat.mean, 2),
                             "max": round(res.exec_stat.max, 2),
                             "p95": round(res.exec_stat.percentile(95), 2),
                             "p99": round(res.exec_stat.percentile(99), 2)}
    # placement observables (ISSUE 8): hotspot profiles merge exactly
    # across disjoint shard node slices
    rec["placement"] = placement
    rec["node_hotspot"] = {
        k: (round(v, 6) if isinstance(v, float) else v)
        for k, v in res.hotspot_summary().items()}
    rec["rebalances"] = res.rebalances
    desched_counters = res.descheduler_counters()
    if desched_counters:
        rec["descheduler"] = desched_counters
    # cost accounting (ISSUE 9): exact pooled merge over the disjoint
    # shard slices (areas/flips sum; ratios recomputed from the sums)
    cost = res.cost_summary()
    if cost:
        rec["cost"] = {k: (round(v, 4) if isinstance(v, float) else v)
                       for k, v in cost.items()}
    autoscaler_counters = res.autoscaler_counters()
    if autoscaler_counters:
        rec["autoscaler"] = autoscaler_counters
    # chaos/recovery observables (ISSUE 7): per-shard counters summed
    # by ShardedRunResult.chaos_counters; recovery merges exactly
    # across shards (node_lost/preempted are sums, resched percentiles
    # come from the merged StreamingStat)
    if chaos is not None:
        rec["chaos"] = res.chaos_counters()
        rec["recovery"] = {
            k: (round(v, 4) if isinstance(v, float) else v)
            for k, v in res.recovery_summary().items()}
        if res.degraded:
            rec["degraded"] = True
            rec["shard_failures"] = res.failures
    # durable front door observables (ISSUE 10): merged qstat snapshot
    # (counters/gauges sum over the disjoint tenant partition, peaks
    # max) plus the summed arbiter submission-edge counters
    gw = res.gateway_summary()
    if gw:
        rec["gateway"] = _round_gateway(gw)
        rec["gateway_rejects"] = arb.get("gateway_rejects", 0)
        rec["gateway_retries"] = arb.get("gateway_retries", 0)
        rec["gateway_shed"] = arb.get("gateway_shed", 0)
    return rec


def run_scenario(n_workflows, n_nodes, seed, policies, usage_mode="event",
                 trace=None, trace_path=None,
                 profile=False, workers=1, shard_procs=None, chaos=None,
                 placement="first-fit", node_mix="uniform", deschedule=None,
                 autoscale=None, gateway=None, wal_dir=None):
    runs = [run_policy(p, n_workflows, n_nodes, seed, usage_mode=usage_mode,
                       trace=trace,
                       profile=profile, workers=workers,
                       shard_procs=shard_procs, chaos=chaos,
                       placement=placement, node_mix=node_mix,
                       deschedule=deschedule, autoscale=autoscale,
                       gateway=gateway, wal_dir=wal_dir)
            for p in policies]
    scenario = {"workflows": n_workflows, "nodes": n_nodes,
                "node_cpu_m": cal.PaperCluster.node_cpu_m,
                "node_mem_mi": cal.PaperCluster.node_mem_mi,
                "seed": seed, "topologies": list(TOPOLOGIES),
                "streams": 2 * len(TOPOLOGIES) * max(1, workers)}
    if placement != "first-fit":
        scenario["placement"] = placement
    if node_mix and node_mix != "uniform":
        cfg = _cluster_cfg(n_nodes, node_mix)
        scenario["node_mix"] = node_mix
        scenario["node_classes"] = [
            {"name": c.name, "cpu_m": c.cpu_m, "mem_mi": c.mem_mi,
             "weight": c.weight} for c in cfg.classes]
    if deschedule is not None:
        scenario["deschedule"] = {
            "interval_s": deschedule.interval_s,
            "util_threshold": deschedule.util_threshold,
            "max_evict_per_node": deschedule.max_evict_per_node,
            "victim": getattr(deschedule, "victim", "youngest")}
    if autoscale is not None:
        scenario["autoscale"] = {
            "interval_s": autoscale.interval_s,
            "pending_threshold": autoscale.pending_threshold,
            "sustain_s": autoscale.sustain_s,
            "idle_s": autoscale.idle_s,
            "min_frac": autoscale.min_frac,
            "scale_step": autoscale.scale_step,
            "start_after_s": autoscale.start_after_s}
    if workers > 1:
        scenario["workers"] = workers
    if gateway is not None:
        scenario["gateway"] = {
            "max_pending": gateway.max_pending,
            "per_tenant_cap": gateway.per_tenant_cap,
            "shed": gateway.shed,
            "retry_after_s": gateway.retry_after_s,
            "max_client_retries": gateway.max_client_retries,
            "wal_dir": wal_dir or None}
    if chaos is not None:
        scenario["chaos"] = {
            "seed": chaos.seed,
            "node_kill_interval_s": chaos.node_kill_interval_s,
            "node_drain_interval_s": chaos.node_drain_interval_s,
            "node_downtime_s": chaos.node_downtime_s,
            "api_fault_rate": chaos.api_fault_rate,
            "task_crash_rate": chaos.task_crash_rate,
            "gateway_drop_rate": chaos.gateway_drop_rate,
            "gateway_dup_rate": chaos.gateway_dup_rate,
            "start_after_s": chaos.start_after_s}
    if trace is not None:
        arrivals = trace.get("arrivals", [])
        scenario.update({"trace": trace_path,
                         "workflows": len(arrivals),
                         "streams": 1, "topologies": sorted(
                             {a["topology"] for a in arrivals})})
    return {
        "scenario": scenario,
        "runs": runs,
        "total_wall_s": round(sum(r["wall_s"] for r in runs), 3),
    }


def run():
    """benchmarks.run entry: reduced smoke variant of the stress tier."""
    tier = run_scenario(50, 20, seed=42, policies=("fifo", "fair-share"))
    rows = []
    for r in tier["runs"]:
        rows.append(row(
            f"scale_smoke_50wf_20n_{r['policy']}", r["wall_s"] * 1e6,
            f"makespan_s={r['sim_makespan_s']};"
            f"events_per_sec={r['events_per_sec']};"
            f"peak_pending={r['peak_pending_admission']};"
            f"completed={r['completed_workflows']}"))
    return rows


def _parse_tiers(args):
    if args.tiers:
        out = []
        for part in args.tiers.split(","):
            fields = part.split("x")
            if len(fields) not in (2, 3):
                raise SystemExit(f"bad tier {part!r}: want WFxNODES or "
                                 f"WFxNODESxWORKERS")
            wf, nodes = int(fields[0]), int(fields[1])
            workers = int(fields[2]) if len(fields) == 3 else args.workers
            out.append((wf, nodes, workers))
        return out
    return [(args.workflows, args.nodes, args.workers)]


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workflows", type=int, default=1000)
    ap.add_argument("--nodes", type=int, default=100)
    ap.add_argument("--tiers", default="",
                    help="comma list of WFxNODES or WFxNODESxWORKERS "
                         "(e.g. 1000x100,10000x1000,1000000x8000x8); "
                         "overrides --workflows/--nodes/--workers")
    ap.add_argument("--workers", type=int, default=1,
                    help="tenant-partitioned arbiter shards (forked "
                         "worker processes); 1 = unsharded legacy path")
    ap.add_argument("--shard-procs", type=int, default=0,
                    help="max shard processes running at once (default "
                         "cpu count): shards run in unoversubscribed "
                         "waves — see README on events_per_sec")
    ap.add_argument("--seed", type=int, default=42)
    ap.add_argument("--policies", default=",".join(POLICIES))
    ap.add_argument("--usage-mode", default="event",
                    choices=("event", "sampled"))
    ap.add_argument("--trace", default="",
                    help="arrival_trace/v1 JSON to replay instead of the "
                         "synthetic streams")
    ap.add_argument("--out", default="BENCH_scale.json")
    ap.add_argument("--budget-s", type=float, default=0.0,
                    help="fail (exit 2) if total wall time exceeds this")
    ap.add_argument("--min-events-per-sec", type=float, default=0.0,
                    help="fail (exit 2) if any run throughput drops below")
    ap.add_argument("--max-events-per-pod", type=float, default=0.0,
                    help="fail (exit 2) if any run exceeds this event cost")
    ap.add_argument("--max-peak-rss-mib", type=float, default=0.0,
                    help="fail (exit 2) if any run's peak RSS exceeds this "
                         "(process-lifetime high-water mark: budget the "
                         "whole sweep; sharded runs are gated on "
                         "total_peak_rss_mib = parent + all shards)")
    ap.add_argument("--max-shard-rss-mib", type=float, default=0.0,
                    help="fail (exit 2) if any single shard's "
                         "self-reported peak RSS exceeds this")
    ap.add_argument("--profile", action="store_true",
                    help="cProfile each policy run and print the top-20 "
                         "cumulative-time hotspots")
    ap.add_argument("--chaos-node-kill-interval", type=float, default=0.0,
                    help="mean seconds between node kills (exponential "
                         "stream; 0 = off)")
    ap.add_argument("--chaos-drain-interval", type=float, default=0.0,
                    help="mean seconds between node drains (graceful "
                         "spot-reclaim; 0 = off)")
    ap.add_argument("--chaos-node-downtime", type=float, default=0.0,
                    help="seconds until a killed/drained node rejoins "
                         "(0 = permanent loss)")
    ap.add_argument("--chaos-api-fault-rate", type=float, default=0.0,
                    help="probability each create/delete call returns a "
                         "retryable apiserver fault")
    ap.add_argument("--chaos-task-crash-rate", type=float, default=0.0,
                    help="probability a running task crashes mid-execution "
                         "(charges the ordinary retry budget)")
    ap.add_argument("--chaos-start-after", type=float, default=0.0,
                    help="sim seconds of calm before the first node event")
    ap.add_argument("--chaos-seed", type=int, default=0,
                    help="chaos stream seed (sha256-spawned; independent "
                         "of --seed)")
    ap.add_argument("--require-complete", action="store_true",
                    help="fail (exit 2) unless every run completes all "
                         "workflows with zero failures (the chaos-smoke "
                         "recovery gate)")
    ap.add_argument("--append", action="store_true",
                    help="merge the new tiers into an existing --out "
                         "report instead of overwriting it (refuses — "
                         "exit 2 — when the existing report was written "
                         "under a different schema version)")
    ap.add_argument("--placement", default="first-fit",
                    choices=("first-fit", "scored-spread", "scored-pack"),
                    help="node-selection mode: first-fit (bit-identical "
                         "to v5 behavior) or utilization-scored "
                         "spread/pack (same shuffle word stream)")
    ap.add_argument("--node-mix", default="uniform",
                    choices=("uniform", "big-small", "cpu-mem-skew"),
                    help="cluster composition: the paper's uniform nodes "
                         "or a heterogeneous node-class preset (per-node "
                         "average equals the paper node)")
    ap.add_argument("--deschedule-interval", type=float, default=0.0,
                    help="descheduler daemon period in sim seconds "
                         "(0 = daemon off)")
    ap.add_argument("--deschedule-threshold", type=float, default=0.9,
                    help="node utilization fraction above which the "
                         "descheduler evicts (requeued pods are not "
                         "charged retry budget)")
    ap.add_argument("--deschedule-victim", default="youngest",
                    choices=("youngest", "largest-request"),
                    help="eviction order on a hot node: youngest "
                         "(least sunk work) or largest-request "
                         "(most utilization relief per eviction)")
    ap.add_argument("--autoscale-interval", type=float, default=0.0,
                    help="autoscaler daemon period in sim seconds "
                         "(0 = daemon off: full roster, bit-identical "
                         "to v6 behavior)")
    ap.add_argument("--autoscale-pending-threshold", type=int, default=1,
                    help="pending depth (admission queue + unbound "
                         "pods) that counts as scale-up pressure")
    ap.add_argument("--autoscale-sustain", type=float, default=30.0,
                    help="seconds the pending depth must stay above "
                         "the threshold before the first scale-up")
    ap.add_argument("--autoscale-idle", type=float, default=60.0,
                    help="seconds a node must hold zero bound pods "
                         "before idle scale-down drains it")
    ap.add_argument("--autoscale-min-frac", type=float, default=0.25,
                    help="per-node-class provisioned floor as a "
                         "fraction of the class population")
    ap.add_argument("--autoscale-scale-step", type=int, default=1,
                    help="nodes provisioned per sustained-pressure tick")
    ap.add_argument("--autoscale-start-after", type=float, default=0.0,
                    help="sim seconds of calm before the first "
                         "autoscaler tick")
    ap.add_argument("--gateway-max-pending", type=int, default=0,
                    help="durable front-door admission bound: max "
                         "in-flight (admitted, not yet done) workflows "
                         "per shard (0 = gateway off, bit-identical to "
                         "v7 behavior)")
    ap.add_argument("--gateway-per-tenant-cap", type=int, default=0,
                    help="per-tenant slice of the pending bound "
                         "(0 = no per-tenant cap)")
    ap.add_argument("--gateway-shed", default="reject-newest",
                    choices=("reject-newest", "shed-oldest", "fair-shed"),
                    help="overload shed discipline at the gate")
    ap.add_argument("--gateway-retry-after", type=float, default=5.0,
                    help="base client retry-after horizon in sim "
                         "seconds (jittered from the gate stream)")
    ap.add_argument("--gateway-max-retries", type=int, default=8,
                    help="client retry budget before a rejected "
                         "submission is shed for good")
    ap.add_argument("--gateway-wal-dir", default="",
                    help="directory for per-shard submission WAL files "
                         "(empty = in-memory segments only)")
    ap.add_argument("--chaos-gateway-drop-rate", type=float, default=0.0,
                    help="per-admitted-submission probability the "
                         "gate->engine hop drops it (WAL redelivers)")
    ap.add_argument("--chaos-gateway-dup-rate", type=float, default=0.0,
                    help="per-admitted-submission probability of a "
                         "duplicate delivery (dedup suppresses it)")
    ap.add_argument("--min-complete-frac", type=float, default=0.0,
                    help="gate: fail unless completed workflows >= this "
                         "fraction of submissions on every row (0 = off; "
                         "the overload tier uses 0.99)")
    args = ap.parse_args()

    policies = [p for p in args.policies.split(",") if p]
    trace = None
    if args.trace:
        with open(args.trace) as f:
            trace = json.load(f)
    chaos = None
    if (args.chaos_node_kill_interval or args.chaos_drain_interval
            or args.chaos_api_fault_rate or args.chaos_task_crash_rate
            or args.chaos_gateway_drop_rate or args.chaos_gateway_dup_rate):
        from repro.core.chaos import ChaosSchedule
        chaos = ChaosSchedule(
            seed=args.chaos_seed,
            node_kill_interval_s=args.chaos_node_kill_interval,
            node_drain_interval_s=args.chaos_drain_interval,
            node_downtime_s=args.chaos_node_downtime,
            api_fault_rate=args.chaos_api_fault_rate,
            task_crash_rate=args.chaos_task_crash_rate,
            gateway_drop_rate=args.chaos_gateway_drop_rate,
            gateway_dup_rate=args.chaos_gateway_dup_rate,
            start_after_s=args.chaos_start_after)
    gateway = None
    if args.gateway_max_pending > 0:
        from repro.core.gateway import BackpressurePolicy
        gateway = BackpressurePolicy(
            max_pending=args.gateway_max_pending,
            per_tenant_cap=args.gateway_per_tenant_cap,
            shed=args.gateway_shed,
            retry_after_s=args.gateway_retry_after,
            max_client_retries=args.gateway_max_retries)
    elif (args.chaos_gateway_drop_rate or args.chaos_gateway_dup_rate
          or args.gateway_wal_dir):
        print("--chaos-gateway-*-rate / --gateway-wal-dir require "
              "--gateway-max-pending > 0", file=sys.stderr)
        raise SystemExit(2)
    deschedule = None
    if args.deschedule_interval > 0.0:
        from repro.core.descheduler import DeschedulePolicy
        deschedule = DeschedulePolicy(
            interval_s=args.deschedule_interval,
            util_threshold=args.deschedule_threshold,
            victim=args.deschedule_victim)
    autoscale = None
    if args.autoscale_interval > 0.0:
        from repro.core.autoscaler import AutoscalePolicy
        autoscale = AutoscalePolicy(
            interval_s=args.autoscale_interval,
            pending_threshold=args.autoscale_pending_threshold,
            sustain_s=args.autoscale_sustain,
            idle_s=args.autoscale_idle,
            min_frac=args.autoscale_min_frac,
            scale_step=args.autoscale_scale_step,
            start_after_s=args.autoscale_start_after)
    tiers = []
    for n_wf, n_nodes, n_workers in _parse_tiers(args):
        tier = run_scenario(n_wf, n_nodes, args.seed, policies,
                            usage_mode=args.usage_mode,
                            trace=trace, trace_path=args.trace or None,
                            profile=args.profile, workers=n_workers,
                            shard_procs=args.shard_procs or None,
                            chaos=chaos, placement=args.placement,
                            node_mix=args.node_mix, deschedule=deschedule,
                            autoscale=autoscale, gateway=gateway,
                            wal_dir=args.gateway_wal_dir or None)
        tiers.append(tier)
        n_wf = tier["scenario"]["workflows"]
        shard_tag = f"/{n_workers}w" if n_workers > 1 else ""
        for r in tier["runs"]:
            print(f"[{n_wf}wf/{n_nodes}n{shard_tag}] {r['policy']:>11}: "
                  f"wall={r['wall_s']:.1f}s "
                  f"makespan={r['sim_makespan_s']:.0f}s "
                  f"events/s={r['events_per_sec']} "
                  f"events/pod={r['events_per_pod']} "
                  f"completed={r['completed_workflows']}", flush=True)
        if trace is not None:
            break                     # a trace defines its own workload

    out_tiers = tiers
    if args.append:
        try:
            with open(args.out) as f:
                prior = json.load(f)
        except FileNotFoundError:
            prior = None
        if prior is not None:
            # never splice rows across schema versions: a merged report
            # must be interpretable under exactly one field contract
            prior_schema = prior.get("schema")
            if prior_schema != SCHEMA:
                print(f"--append refused: {args.out} has schema "
                      f"{prior_schema!r}, this build writes {SCHEMA!r}; "
                      f"regenerate the report (or move it aside) instead "
                      f"of mixing schema versions", file=sys.stderr)
                raise SystemExit(2)
            out_tiers = prior.get("tiers", []) + tiers
    report = {
        "schema": SCHEMA,
        "host": {"python": platform.python_version(),
                 "platform": platform.platform()},
        "tiers": out_tiers,
        "total_wall_s": round(sum(t["total_wall_s"] for t in out_tiers), 3),
    }
    with open(args.out, "w") as f:
        json.dump(report, f, indent=2)
        f.write("\n")
    print(f"total wall: {report['total_wall_s']:.1f}s -> {args.out}")

    failures = []
    # gates apply to the tiers run NOW (under --append, prior tiers in
    # the merged report are not re-gated)
    new_wall = round(sum(t["total_wall_s"] for t in tiers), 3)
    if args.budget_s and new_wall > args.budget_s:
        failures.append(f"BUDGET EXCEEDED: {new_wall:.1f}s "
                        f"> {args.budget_s:.1f}s")
    for tier in tiers:
        for r in tier["runs"]:
            label = (f"{tier['scenario']['workflows']}wf/"
                     f"{tier['scenario']['nodes']}n {r['policy']}")
            gw = r.get("gateway")
            if gw is not None:
                # automatic gates on every gateway row: the admission
                # bound must actually hold, and the ledger must balance
                # exactly (nothing lost, nothing stuck in the gate)
                tot = gw["totals"]
                if gw["peak_pending"] > gateway.max_pending:
                    failures.append(
                        f"BACKPRESSURE BREACH: {label} peak pending "
                        f"{gw['peak_pending']} > {gateway.max_pending}")
                if (tot["admitted"] + tot["shed"] != tot["submissions"]
                        or tot["queued"]):
                    failures.append(
                        f"GATEWAY ACCOUNTING: {label} admitted "
                        f"{tot['admitted']} + shed {tot['shed']} != "
                        f"submissions {tot['submissions']} "
                        f"(queued {tot['queued']})")
            if args.require_complete:
                want = tier["scenario"]["workflows"]
                if gw is not None:
                    # under backpressure some submissions are shed by
                    # design; everything admitted must still complete
                    done, shed = r["completed_workflows"], gw["totals"]["shed"]
                    if done + shed != want or r["failed_workflows"]:
                        failures.append(
                            f"INCOMPLETE RECOVERY: {label} completed "
                            f"{done} + shed {shed} != {want}, failed "
                            f"{r['failed_workflows']}")
                elif (r["completed_workflows"] != want
                        or r["failed_workflows"]):
                    failures.append(
                        f"INCOMPLETE RECOVERY: {label} completed "
                        f"{r['completed_workflows']}/{want}, failed "
                        f"{r['failed_workflows']}")
                if r.get("degraded"):
                    failures.append(
                        f"DEGRADED RESULT: {label} dropped shards "
                        f"{[s['shard'] for s in r['shard_failures']]}")
            if args.min_complete_frac:
                want = tier["scenario"]["workflows"]
                frac = r["completed_workflows"] / want if want else 1.0
                if frac < args.min_complete_frac:
                    failures.append(
                        f"COMPLETION FLOOR: {label} completed "
                        f"{r['completed_workflows']}/{want} "
                        f"({frac:.3f} < {args.min_complete_frac:.3f})")
            if (args.min_events_per_sec and r["events_per_sec"]
                    and r["events_per_sec"] < args.min_events_per_sec):
                failures.append(
                    f"THROUGHPUT FLOOR: {label} {r['events_per_sec']}/s "
                    f"< {args.min_events_per_sec:.0f}/s")
            if (args.max_events_per_pod and r["events_per_pod"]
                    and r["events_per_pod"] > args.max_events_per_pod):
                failures.append(
                    f"EVENT-COST CEILING: {label} {r['events_per_pod']} "
                    f"events/pod > {args.max_events_per_pod:.1f}")
            gate_rss = r.get("total_peak_rss_mib") or r["peak_rss_mib"]
            if (args.max_peak_rss_mib and gate_rss
                    and gate_rss > args.max_peak_rss_mib):
                failures.append(
                    f"RSS CEILING: {label} {gate_rss} MiB "
                    f"> {args.max_peak_rss_mib:.0f} MiB")
            if args.max_shard_rss_mib:
                for s in r.get("shards", []):
                    if s["peak_rss_mib"] > args.max_shard_rss_mib:
                        failures.append(
                            f"SHARD RSS CEILING: {label} shard "
                            f"{s['shard']} {s['peak_rss_mib']} MiB "
                            f"> {args.max_shard_rss_mib:.0f} MiB")
    if failures:
        for msg in failures:
            print(msg, file=sys.stderr)
        raise SystemExit(2)


if __name__ == "__main__":
    main()
