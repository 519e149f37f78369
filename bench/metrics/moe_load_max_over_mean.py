"""Expert load imbalance in the train step: the largest held expert's
routed slots over the mean held expert's, from the program's counters
(``moe.load_max``, each MoE layer's largest load, and ``moe.slots_held``,
summed over layers and train steps): the mean over layers and steps of
the largest load over the mean over layers and steps of the mean load.
1 is an even split; the grouped product runs as long as its fullest
expert."""

from bench.program_spans import totals


def read(rec):
    t = totals()
    top, held = t.get("moe.load_max"), t.get("moe.slots_held")
    if not top or not held or not held["total"]:
        return None
    return top["total"] / (held["total"] / rec.window["model"]["n_routed_experts"])
