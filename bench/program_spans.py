"""Readers of the program's own span totals (``repro.core.tracing``),
shared by the per-layer metrics that read them. The totals are the
latest profiler session's, the traced window's. Each reader returns
None where the totals hold no span of the name it reads (the gc reader:
no span at all), and where the program has no tracer."""


def totals() -> dict:
    try:
        from repro.core import tracing
    except ImportError:
        return {}
    return tracing.snapshot()


def ms_per_call(name: str):
    """Mean milliseconds of one ``name`` span: one a pod for the
    ``payload.*`` spans."""
    s = totals().get(name)
    if not s or not s["count"]:
        return None
    return s["total_s"] / s["count"] * 1e3


def cp_self_ms_per_pod(rec):
    """Self time of the control plane's event loop (``sim.run`` less its
    ``pod.payload`` children) per ``pod.payload``."""
    t = totals()
    run, pods = t.get("sim.run"), t.get("pod.payload")
    if not run or not pods or not pods["count"]:
        return None
    return run["self_s"] / pods["count"] * 1e3


def gc_ms_per_s(rec):
    """Milliseconds of garbage collection (``gc`` spans) per second of
    the traced window. The gc hook watches from the session's first
    span on, so a session with spans and no ``gc`` span reads 0."""
    t = totals()
    window = (rec.trace or {}).get("window_s")
    if not t or not window:
        return None
    return t.get("gc", {}).get("total_s", 0.0) * 1e3 / window
