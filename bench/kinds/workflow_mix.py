"""Driver for deployments of scientific workflows with device payloads.

Each tenant of the traffic mix is a serial closed loop over one
workflow family: the control plane is handed the tenant's next workflow
when its last one completes. Every task runs the program's
``matmul_payload``. The window ends at the first pod start after the
deadline.

Correct means: every workflow completed in the window has all of its
tasks SUCCEEDED, its start order agrees with its DAG
(``order_consistent``), and every task's written output agrees with the
float64 reference of the recurrence (``bench/ref/matmul.py``).
"""
from __future__ import annotations

import random
import time
from collections import defaultdict
from typing import Dict, List

from bench.harness import (Check, WindowClosed, engine_faults,
                           finished_workflows, p95, run_until_closed)
from bench.ref import matmul as ref


class Driver:
    def __init__(self, cell, seed: int, spans):
        self.cell = cell
        self.cfg = cell.config
        self.traffic = cell.traffic
        self.seed = seed
        self.spans = spans
        self.deadline = float("inf")
        self.recording = False
        self.stamps: List[tuple] = []             # (ns, task, w0, w1)
        self.outputs: Dict[tuple, object] = {}
        self.plane = None
        self.phases: Dict[str, float] = {}
        self.readings: Dict[str, float] = {}

    # -- the payload the engine runs for every task -------------------------
    def _wrap(self, payload):
        spans = self.spans

        def run(volume, task):
            w0 = time.perf_counter()
            if w0 >= self.deadline:
                raise WindowClosed
            with spans("payload"):
                out = payload(volume, task)
            w1 = time.perf_counter()
            if self.recording:
                ns = volume.name[:-len("-pvc")]
                self.stamps.append((ns, task.id, w0, w1))
                self.outputs[(ns, task.id)] = volume.get(f"{task.id}/out")
            return out
        return run

    def _workflows(self):
        from repro.configs.workflows import get_workflow_spec
        from repro.core.dag import make_workflow
        wfs = []
        for tenant in self.traffic["tenants"]:
            fam = tenant["workflow"]
            wf = make_workflow(fam, get_workflow_spec(fam))
            want = self.cfg["workflows"][fam]["tasks"]
            if len(wf.tasks) != want:
                raise RuntimeError(f"{fam}: {len(wf.tasks)} tasks, the "
                                   f"configuration states {want}")
            for t in wf.tasks.values():
                t.payload = self.payload
            wfs.append((tenant["name"], wf))
        # every seed runs the same tenants, registered in its own order
        random.Random(self.seed).shuffle(wfs)
        return wfs

    def _plane(self, repeats: int):
        from repro.core.calibration import PaperCluster
        from repro.core.runner import ControlPlane
        cfg = self.cfg
        plane = ControlPlane(cfg["engine"],
                             cluster_cfg=PaperCluster(**cfg["cluster"]),
                             payload_mode="real", seed=self.seed,
                             scheduler=cfg["scheduler"],
                             admission_policy=cfg["admission_policy"])
        for tenant, wf in self.wfs:
            plane.add_stream(wf, repeats=repeats, tenant=tenant,
                             arrival=self.traffic["arrival"])
        return plane

    def setup(self):
        from repro.core.payloads import matmul_payload
        p = self.cfg["payload"]
        self.payload = self._wrap(matmul_payload(p["n"], p["iters"]))
        self.wfs = self._workflows()
        t = time.perf_counter()
        # warm-up outside the window: one workflow per tenant
        self._plane(repeats=1).run()
        self.phases["warmup_workflow"] = time.perf_counter() - t
        self.plane = self._plane(self.traffic["repeats_cap"])

    def window(self, seconds: float) -> dict:
        self.recording = True
        self.t0 = time.perf_counter()
        self.deadline = self.t0 + seconds
        run_until_closed(self.plane, self.spans)
        self.t_close = time.perf_counter()
        self.recording = False
        return self._summarise()

    def _summarise(self) -> dict:
        ends: Dict[str, float] = defaultdict(float)
        for ns, _task, _w0, w1 in self.stamps:
            ends[ns] = max(ends[ns], w1)
        self.done = finished_workflows(self.plane, self.wfs)
        self.not_succeeded, self.order_bad, self.failed_ns = engine_faults(
            self.plane, self.done)
        per_tenant: Dict[str, List[float]] = defaultdict(list)
        for ns, wf in self.done.items():
            per_tenant[wf.tenant].append(ends[ns])
        lifecycles = []
        for times in per_tenant.values():
            prev = self.t0
            for t in sorted(times):
                lifecycles.append(t - prev)
                prev = t
        finish = [ends[ns] for ns in self.done]
        e2e = {}
        if finish:
            e2e["wf_per_s"] = len(finish) / (max(finish) - self.t0)
        if len(lifecycles) >= 2:
            e2e["wf_lifecycle_p95_s"] = p95(lifecycles)
        return {
            "end_to_end": e2e,
            "pods": len(self.stamps),
            "payload_s": sum(w1 - w0 for _n, _t, w0, w1 in self.stamps),
            "loop_s": self.t_close - self.t0,
        }

    def memory_peak_bytes(self) -> int:
        import jax
        stats = jax.devices()[0].memory_stats() or {}
        return int(stats.get("peak_bytes_in_use", 0))

    def release(self):
        """The control plane holds no device state."""

    def outcome(self):
        return len(self.done), len(self.failed_ns)

    def check(self) -> List[Check]:
        p = self.cfg["payload"]
        want = ref.recurrence(p["n"], p["iters"])
        worst, missing = 0.0, 0
        for ns, wf in self.done.items():
            for tid in wf.tasks:
                got = self.outputs.get((ns, tid))
                if got is None:
                    missing += 1
                    continue
                g = ref.gap(got, want)
                if not g <= worst:
                    worst = g
                if not g <= self.cfg["limits"]["matmul_gap"]:
                    self.failed_ns.add(ns)
        lim = self.cfg["limits"]
        return [Check("tasks_not_succeeded", float(self.not_succeeded), 0.0),
                Check("order_violations", float(self.order_bad), 0.0),
                Check("outputs_missing", float(missing), 0.0),
                Check("completed_short",
                      float(self.traffic["min_completed"] - len(self.done)),
                      0.0),
                Check("matmul_gap", worst, lim["matmul_gap"])]
