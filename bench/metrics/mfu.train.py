"""Model FLOP utilisation of training: the forward and backward FLOPs
of every train step in the window (from the configuration's shapes,
recomputation not counted), over the train pods' wall seconds times
chips times the chip's bf16 peak."""

from bench import work


def read(rec):
    w = rec.window
    runs = w.get("stage_runs", {}).get("train", 0)
    wall = w.get("stage_s", {}).get("train", 0.0)
    if not runs or wall <= 0:
        return None
    b, s = w["train_shape"]
    flops = work.train_flops(w["model"], b, s) * w["train_steps"] * runs
    return 100.0 * flops / (wall * rec.cell.chips
                            * rec.device.peaks["bf16_flops"])
