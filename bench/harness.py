"""The benchmark harness: finds a cell's files by name, gates the device,
times set-up and the measured window, reads the per-layer metrics and
prints the result line.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric lives in a file of its own, found by the name that
``BENCHMARK.json`` gives it:

    <bench>/configs/<config>.json     sizes, engine, limits (``file``)
    <bench>/traffic/<traffic>.json    the mix one driver kind reads
    <bench>/metrics/<metric>.py       ``read(rec) -> float | None``

where ``<bench>`` is the first of ``paths``. A configuration names its
driver with ``kind``; the drivers live in ``bench/kinds``.
"""
from __future__ import annotations

import importlib
import importlib.util
import json
import os
import shutil
import statistics
import sys
import tempfile
import time
from contextlib import nullcontext
from dataclasses import dataclass
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

from bench import peaks as peaks_mod

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class WindowClosed(Exception):
    """Raised by a payload wrapper at the first pod start after the
    window's deadline; it unwinds the control plane's event loop."""


# ---------------------------------------------------------------------------
# Finding a cell's files
# ---------------------------------------------------------------------------
@dataclass
class Cell:
    root: Path
    bench_dir: Path
    workload: dict
    config: dict
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]

    @property
    def name(self) -> str:
        return self.workload["name"]

    @property
    def chips(self) -> int:
        return int(self.workload["chips"])


def _read_json(path: Path) -> Any:
    with open(path) as f:
        return json.load(f)


def load_cell(root: Path, workload: str) -> Cell:
    """The cell ``workload`` of ``<root>/BENCHMARK.json`` with its files."""
    root = Path(root)
    bench = _read_json(root / "BENCHMARK.json")
    bench_dir = root / bench["paths"][0]
    by_name = {w["name"]: w for w in bench["workloads"]}
    if workload not in by_name:
        raise SystemExit(f"unknown workload {workload!r}; "
                         f"BENCHMARK.json has {sorted(by_name)}")
    wl = by_name[workload]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[wl["config"]]
    config = _read_json(root / cfg_entry["file"])
    traffic = _read_json(bench_dir / "traffic" / f"{wl['traffic']}.json")
    e2e = [m for m in bench["end_to_end"]
           if workload in m.get("workloads", [workload])]
    moved = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if (workload in m["workloads"] if "workloads" in m
                     else m["moves"] in moved)]
    return Cell(root, bench_dir, wl, config, traffic, e2e, per_layer)


def load_reader(cell: Cell, metric: str) -> Callable:
    path = cell.bench_dir / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def load_driver(cell: Cell):
    return importlib.import_module(f"bench.kinds.{cell.config['kind']}").Driver


# ---------------------------------------------------------------------------
# Device, compile cache, compile counting, spans
# ---------------------------------------------------------------------------
@dataclass
class Device:
    platform: str
    kind: str
    count: int
    peaks: dict


def tpu_gate(chips: int) -> Device:
    """The first device must be a TPU of a kind in the peak table, and
    there must be ``chips`` of them. There is no CPU fallback."""
    import jax
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise SystemExit(f"bench: needs a TPU, JAX found platform="
                         f"{d.platform!r} kind={d.device_kind!r}")
    if len(devs) < chips:
        raise SystemExit(f"bench: the cell needs {chips} chips, JAX found "
                         f"{len(devs)}")
    return Device(d.platform, d.device_kind, len(devs),
                  peaks_mod.lookup(d.device_kind))


def use_compile_cache(root: Path) -> str:
    """JAX's persistent compile cache: ``JAX_COMPILATION_CACHE_DIR`` if
    set, else ``<root>/.jax_cache``, a fixed path (the path is part of
    the cache key). Every program is cached, however fast it compiled."""
    import jax
    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        Path(root) / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return cache


class CompileCounter:
    """Host-clock instants of every backend compile (or cache load)."""

    def __init__(self):
        import jax
        self.at: List[float] = []
        self._cb = self._on
        jax.monitoring.register_event_duration_secs_listener(self._cb)

    def _on(self, event: str, duration: float, **_kw) -> None:
        if event == COMPILE_EVENT:
            self.at.append(time.perf_counter())

    def between(self, t0: float, t1: float) -> int:
        return sum(1 for t in self.at if t0 <= t <= t1)

    def close(self) -> None:
        import jax
        jax.monitoring.unregister_event_duration_listener(self._cb)


class Spans:
    """Host spans around the harness's calls into the program. They are
    written into the profiler's trace only in a traced run."""

    def __init__(self, traced: bool):
        self.traced = traced

    def __call__(self, name: str):
        if not self.traced:
            return nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)


@dataclass
class Check:
    """One number compared with its limit: passes when value <= limit."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value == self.value and self.value <= self.limit


@dataclass
class Record:
    """What a per-layer metric reader may read."""
    cell: Cell
    device: Device
    window: Dict[str, Any]           # what the cell's kind measured
    trace: Optional[Dict[str, Any]] = None


def p95(values: List[float]) -> float:
    return statistics.quantiles(values, n=100, method="inclusive")[94]


# ---------------------------------------------------------------------------
# What the drivers share: the window's loop and the engine's own checks
# ---------------------------------------------------------------------------
def run_until_closed(plane, spans: Spans) -> None:
    """Drive the control plane until a payload raises WindowClosed."""
    try:
        with spans("engine_loop"):
            plane.run()
    except WindowClosed:
        return
    raise RuntimeError("the streams ran dry before the window closed; "
                       "raise repeats_cap")


def finished_workflows(plane, streams) -> Dict[str, Any]:
    """namespace -> instance of every workflow of ``streams`` (pairs of
    tenant and workflow) that the engine finished, namespace deleted."""
    recs = plane.metrics.workflows
    done = {}
    for tenant, wf in streams:
        base, i = wf.with_tenant(tenant), 0
        while (wf.name, i) in recs:
            if recs[(wf.name, i)].ns_deleted >= 0:
                inst = base.with_instance(i)
                done[inst.namespace()] = inst
            i += 1
    return done


def engine_faults(plane, done: Dict[str, Any]):
    """(tasks not SUCCEEDED, workflows out of DAG order, namespaces at
    fault) over the finished workflows ``done``."""
    from repro.core.cluster import SUCCEEDED
    ok: Dict[str, set] = {}
    for pod in plane.cluster.pod_log:
        if pod.phase == SUCCEEDED:
            ok.setdefault(pod.namespace, set()).add(pod.task_id)
    missing, order_bad, bad = 0, 0, set()
    for ns, wf in done.items():
        lost = set(wf.tasks) - ok.get(ns, set())
        missing += len(lost)
        out_of_order = not plane.metrics.order_consistent(wf)
        order_bad += out_of_order
        if lost or out_of_order:
            bad.add(ns)
    return missing, order_bad, bad


# ---------------------------------------------------------------------------
# One run
# ---------------------------------------------------------------------------
def run_cell(root: Path, workload: str, seed: int, seconds: float,
             trace: bool, t_start: float,
             gate: Callable[[int], Device] = tpu_gate,
             keep_trace: Optional[str] = None) -> Dict[str, Any]:
    """Set up, measure and check one cell; returns the result object.

    ``gate`` finds the device (the CPU tests pass their own);
    ``keep_trace`` is a path to copy the traced window's ``.xplane.pb``
    to."""
    cell = load_cell(root, workload)
    device = gate(cell.chips)
    use_compile_cache(cell.root)
    counter = CompileCounter()
    spans = Spans(trace)
    driver = load_driver(cell)(cell, seed, spans)
    with spans("setup"):
        driver.setup()

    import jax
    trace_dir = None
    if trace:
        trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0      # spans only, no per-call events
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    t0 = time.perf_counter()
    setup_s = t0 - t_start
    with spans("window"):
        win = driver.window(seconds)
    t1 = time.perf_counter()
    if trace:
        jax.profiler.stop_trace()
    in_window = counter.between(t0, t1)
    counter.close()
    memory_peak = driver.memory_peak_bytes()

    reduction = None
    if trace:
        from bench import trace as trace_mod
        try:
            if keep_trace:
                shutil.copyfile(trace_mod.find_xplane(trace_dir), keep_trace)
            reduction = trace_mod.reduce_dir(trace_dir)
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)

    driver.release()
    t_check = time.perf_counter()
    checks: List[Check] = driver.check()
    attempted, failed = driver.outcome()
    timing = dict(driver.phases, setup_s=setup_s, window_s=t1 - t0,
                  check_s=time.perf_counter() - t_check)

    if trace:
        rec = Record(cell, device, win, reduction)
        metrics = {}
        for m in cell.per_layer:
            value = load_reader(cell, m["name"])(rec)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        values = dict(win["end_to_end"], setup_s=setup_s)
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end if m["name"] in values}

    dev = {"platform": device.platform, "kind": device.kind,
           "count": device.count, "memory_peak_bytes": memory_peak}
    if reduction is not None:
        dev["busy_s"] = reduction["busy_s"]
        dev["window_s"] = reduction["window_s"]
    result: Dict[str, Any] = {
        "correct": all(c.ok for c in checks),
        "attempted": attempted,
        "failed": failed,
        "metrics": metrics,
        "device": dev,
    }
    if reduction is not None:
        result["breakdown"] = {"device_ops": reduction["device_ops"],
                               "idle_gaps": reduction["idle_gaps"]}
        result["idle_by_span"] = reduction["idle_by_span"]
        result["device_programs"] = reduction["modules"]
    result["timing"] = timing
    result["compiles_in_window"] = in_window
    result["readings"] = driver.readings
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                        for c in checks}
    return result


def emit(result: Dict[str, Any], out=None, err=None) -> None:
    """The compared numbers as the last lines of stderr, the result as
    the last line of stdout."""
    out = out or sys.stdout
    err = err or sys.stderr
    print("timing " + " ".join(f"{k}={v:.3f}" for k, v in
                               result["timing"].items()), file=err)
    print(f"compiles_in_window={result['compiles_in_window']}", file=err)
    for name, v in result["readings"].items():
        print(f"reading {name} value={v!r} (not compared)", file=err)
    for name, c in result["checks"].items():
        ok = c["value"] == c["value"] and c["value"] <= c["limit"]
        print(f"check {name} value={c['value']!r} limit={c['limit']!r} "
              f"{'ok' if ok else 'FAIL'}", file=err)
    err.flush()
    print(json.dumps(result), file=out, flush=True)
