"""Roofline share of the whole decode step: the least time the chip
could take (the larger of its FLOPs over peak FLOP/s and its bytes over
peak bandwidth: every weight at the declared dtype plus the KV cache
read) over the step's device time in the trace."""

from bench import work

MODULE = "jit_bench_decode"


def read(rec):
    mod = (rec.trace or {}).get("modules", {}).get(MODULE)
    w = rec.window
    if not mod or not mod["runs"] or not w.get("decode_shape"):
        return None
    batch, prompt, steps = w["decode_shape"]
    pos = prompt + (steps - 1) / 2          # mean cache position
    least = work.roofline_s(work.decode_flops(w["model"], batch, pos),
                            work.decode_bytes(w["model"], batch, pos,
                                              w["dtype"]),
                            rec.device.peaks)
    return 100.0 * least / (mod["seconds"] / mod["runs"])
