"""Reduction of a profiler trace to device busy time, per-op device time
and idle gaps attributed to the harness's host spans.

The trace is the ``.xplane.pb`` that ``jax.profiler`` writes. Device
planes are ``/device:TPU:<n>``; their ``XLA Ops`` line holds one event
per operation run and their ``XLA Modules`` line one event per program
run. The harness's spans (``harness.Spans``) are events of the host
plane. The window is the ``window`` span.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Tuple

Interval = Tuple[float, float]

# the harness's spans, most specific first: a stretch of idle device
# time is charged to the first of these that covers it
SPAN_PRIORITY = ("compile", "payload_wait", "stage_glue", "payload",
                 "engine_loop", "window")
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
TOP = 10


def module_name(event_name: str) -> str:
    """``jit_train_step(123)`` -> ``jit_train_step``."""
    return re.sub(r"\(\d+\)$", "", event_name).strip()


def op_name(event_name: str) -> str:
    """``%fusion.9 = f32[512,512]{...} fusion(...)`` -> ``%fusion.9``."""
    return event_name.split(" = ", 1)[0].strip()


def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def clip(intervals: Iterable[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def total(intervals: Iterable[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def gaps(busy: List[Interval], lo: float, hi: float) -> List[Interval]:
    """The complement of sorted disjoint ``busy`` within [lo, hi]."""
    out, t = [], lo
    for a, b in busy:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


def attribute(gap_list: List[Interval],
              spans: Dict[str, List[Interval]]) -> List[Dict[str, float]]:
    """For each gap, the time covered by each span name, every instant
    charged to the most specific span (SPAN_PRIORITY) covering it and to
    ``other`` where none does."""
    names = [n for n in SPAN_PRIORITY if spans.get(n)]
    edges = []                                   # (t, order, kind, idx)
    for i, (a, b) in enumerate(gap_list):
        edges.append((a, 1, "g", i))
        edges.append((b, 0, "g", i))
    for k, n in enumerate(names):
        for a, b in spans[n]:
            edges.append((a, 1, "s", k))
            edges.append((b, 0, "s", k))
    edges.sort(key=lambda e: (e[0], e[1]))
    depth = [0] * len(names)
    out: List[Dict[str, float]] = [defaultdict(float) for _ in gap_list]
    cur_gap: Optional[int] = None
    t_prev = None
    for t, order, kind, idx in edges:
        if cur_gap is not None and t_prev is not None and t > t_prev:
            who = next((names[k] for k in range(len(names)) if depth[k] > 0),
                       "other")
            out[cur_gap][who] += t - t_prev
        t_prev = t
        if kind == "g":
            cur_gap = idx if order == 1 else None
        else:
            depth[idx] += 1 if order == 1 else -1
    return [dict(d) for d in out]


def load_planes(path: str):
    from jax.profiler import ProfileData
    return ProfileData.from_file(path).planes


def find_xplane(trace_dir: str) -> str:
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(found, key=os.path.getmtime)


def reduce_dir(trace_dir: str) -> dict:
    return reduce_file(find_xplane(trace_dir))


def reduce_file(path: str) -> dict:
    """See the module docstring. Times come back in seconds."""
    host_spans: Dict[str, List[Interval]] = defaultdict(list)
    devices = []
    for plane in load_planes(path):
        if DEVICE_PLANE.match(plane.name):
            ops, modules = [], []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops = [(e.start_ns, e.start_ns + e.duration_ns,
                            op_name(e.name)) for e in line.events]
                elif line.name == MODULES_LINE:
                    modules = [(e.start_ns, e.start_ns + e.duration_ns,
                                module_name(e.name)) for e in line.events]
            devices.append((ops, modules))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in SPAN_PRIORITY:
                        host_spans[e.name].append(
                            (e.start_ns, e.start_ns + e.duration_ns))
    if not host_spans.get("window"):
        raise ValueError(f"{path}: no 'window' span in the host plane")
    if not devices:
        raise ValueError(f"{path}: no /device:TPU plane")
    lo = min(a for a, _ in host_spans["window"])
    hi = max(b for _, b in host_spans["window"])
    spans = {n: union(clip(v, lo, hi)) for n, v in host_spans.items()}

    busy_ns = []
    op_ns: Dict[str, float] = defaultdict(float)
    mod_ns: Dict[str, float] = defaultdict(float)
    mod_runs: Dict[str, int] = defaultdict(int)
    gap_list: List[Interval] = []
    for dev_i, (ops, modules) in enumerate(devices):
        busy = union(clip(((a, b) for a, b, _ in ops), lo, hi))
        busy_ns.append(total(busy))
        mods = sorted(modules)
        starts = [a for a, _, _ in mods]
        for a, b, name in ops:
            if b <= lo or a >= hi:
                continue
            op_ns[f"{_owner(starts, mods, a)}/{name}"] += min(b, hi) - max(a, lo)
        for a, b, name in mods:
            if a >= lo and b <= hi:
                mod_ns[name] += b - a
                mod_runs[name] += 1
        if dev_i == 0:
            gap_list = gaps(busy, lo, hi)
    n = len(devices)
    charged = attribute(gap_list, spans)
    idle_by_span: Dict[str, float] = defaultdict(float)
    labelled = []
    for (a, b), parts in zip(gap_list, charged):
        for who, t in parts.items():
            idle_by_span[who] += t / 1e9
        who = max(parts, key=parts.get) if parts else "other"
        labelled.append((b - a, who))
    labelled.sort(reverse=True)
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(busy_ns) / n / 1e9,
        "n_devices": n,
        "device_ops": [[k, v / n / 1e9] for k, v in
                       sorted(op_ns.items(), key=lambda kv: -kv[1])[:TOP]],
        "idle_gaps": [[who, d / 1e9] for d, who in labelled[:TOP]],
        "idle_by_span": dict(idle_by_span),
        "modules": {k: {"runs": mod_runs[k] / n, "seconds": mod_ns[k] / n / 1e9}
                    for k in mod_ns},
        "spans_s": {k: total(v) / 1e9 for k, v in spans.items()},
    }


def _owner(starts, mods, t) -> str:
    """The module whose run covers instant ``t`` on this device."""
    import bisect
    i = bisect.bisect_right(starts, t) - 1
    if i >= 0 and mods[i][1] >= t:
        return mods[i][2]
    return "?"
