"""Device time of one train step: the trace's runs of the program's
train step, compiled as ``bench_train_step``."""

MODULE = "jit_bench_train_step"


def read(rec):
    mod = (rec.trace or {}).get("modules", {}).get(MODULE)
    if not mod or not mod["runs"]:
        return None
    return mod["seconds"] / mod["runs"] * 1e3
