"""Roofline share of the latent-attention MoE decode step: the least
time the chip could take (the larger of its FLOPs over peak FLOP/s and
its bytes over peak bandwidth: every weight but the routed experts',
each held expert its tokens touched, read from the program's
``moe.experts_touched`` counter, and the latent cache, at the declared
dtype) over the step's device time in the trace. The routed FLOPs count
one slot for each expert touched, a lower bound: the step is bound by
its bytes."""

from bench import work, work_mla_moe
from bench.program_spans import totals

MODULE = "jit_bench_decode"


def read(rec):
    mod = (rec.trace or {}).get("modules", {}).get(MODULE)
    touched = totals().get("moe.experts_touched")
    w = rec.window
    if not mod or not mod["runs"] or not touched or not touched["count"] \
            or not w.get("decode_shape"):
        return None
    batch, prompt, steps = w["decode_shape"]
    pos = prompt + (steps - 1) / 2          # mean cache position
    experts = touched["total"] / touched["count"]
    m = w["model"]
    least = work.roofline_s(
        work_mla_moe.decode_flops(m, batch, pos, experts),
        work_mla_moe.decode_bytes(m, batch, pos, experts, w["dtype"]),
        rec.device.peaks)
    return 100.0 * least / (mod["seconds"] / mod["runs"])
