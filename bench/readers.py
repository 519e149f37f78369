"""Readers shared by per-layer metrics that two end-to-end metrics each
have a copy of (``metrics/<name>.py`` imports from here)."""


def cp_ms_per_pod(rec):
    """Control-plane host time per pod: the event loop's wall time in the
    window minus the payload walls the harness stamped, per pod run."""
    w = rec.window
    if not w.get("pods"):
        return None
    return (w["loop_s"] - w["payload_s"]) / w["pods"] * 1e3


def device_idle(rec):
    """Share of the traced window in which no operation ran on the
    device: 1 - (union of device-op intervals) / window, mean over chips."""
    t = rec.trace
    if not t or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
