"""Model FLOP utilisation of the latent-attention MoE train step: the
forward and backward FLOPs of one step (``bench/work_mla_moe.py``: MLA,
the dense layer, the shared experts and the routed slots the held
experts computed, read from the program's ``moe.slots_held`` counter;
recomputation not counted) over the step's device time in the trace
times the chip's bf16 peak."""

from bench import work_mla_moe
from bench.program_spans import totals

MODULE = "jit_bench_train_step"


def read(rec):
    mod = (rec.trace or {}).get("modules", {}).get(MODULE)
    held = totals().get("moe.slots_held")
    w = rec.window
    if not mod or not mod["runs"] or not held or not held["count"] \
            or not w.get("train_shape"):
        return None
    batch, seq = w["train_shape"]
    flops = work_mla_moe.train_flops(w["model"], batch, seq,
                                     held["total"] / held["count"])
    step_s = mod["seconds"] / mod["runs"]
    return 100.0 * flops / (step_s * rec.device.peaks["bf16_flops"])
