"""Device time of one decode step: the trace's runs of the program's
decode step, compiled as ``bench_decode``."""

MODULE = "jit_bench_decode"


def read(rec):
    mod = (rec.trace or {}).get("modules", {}).get(MODULE)
    if not mod or not mod["runs"]:
        return None
    return mod["seconds"] / mod["runs"] * 1e3
