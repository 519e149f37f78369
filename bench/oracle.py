#!/usr/bin/env python3
"""Readings that set the limits of ``correct``: the program's, the
lower-precision control's and the planted faults', per seed, at the
cell's own size, on the chip.

    python3 bench/oracle.py --workload mlpipe.train --seconds 4 \\
        --seeds 11 12 13 --control-seeds 3

For each seed the cell is set up and driven for a short window, as a
run of ``bench/run.py`` is; then each number the check compares is read
for the program and, on the first ``--control-seeds`` seeds, for the
control (the reference at the configuration's ``control`` operand
precision, put in the program's place) and for the faults the cell can
have (half of each batch left out; every served token shifted by one).
One JSON line per seed goes to stdout. The benchmark's runs never call
this.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent


def readings(driver, cell, control: bool) -> dict:
    out = {}
    if cell.config["kind"] == "workflow_mix":
        import ml_dtypes

        from bench.ref import matmul as ref
        p = cell.config["payload"]
        out["program"] = {c.name: c.value for c in driver.check()}
        if control:
            want = ref.recurrence(p["n"], p["iters"])
            dt = getattr(ml_dtypes, cell.config["control"]["operand_dtype"])
            out["control"] = {"matmul_gap": ref.gap(
                ref.recurrence(p["n"], p["iters"], dt), want)}
            out["fault_altered"] = {"matmul_gap": ref.gap(
                want + 1e-2, want)}
        return out
    import jax.numpy as jnp
    dt = getattr(jnp, cell.config["control"]["operand_dtype"])
    names = ("loss_gap", "grad_gap", "change_gap")
    out["program"] = dict(zip(names, driver.train_numbers()),
                          serve_gap=driver.serve_gap())
    if control:
        out["control"] = dict(zip(names, driver.train_numbers(dt=dt)),
                              serve_gap=driver.serve_gap(dt=dt))
        half = driver._stage("train")["batch"] // 2
        out["fault_half_batch"] = dict(zip(names,
                                           driver.train_numbers(rows=half)))
        out["fault_altered"] = {"serve_gap": driver.serve_gap(alter=1)}
    return out


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    args = ap.parse_args()
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from bench import harness
    cell = harness.load_cell(ROOT, args.workload)
    device = harness.tpu_gate(cell.chips)
    harness.use_compile_cache(ROOT)
    for i, seed in enumerate(args.seeds):
        t0 = time.perf_counter()
        driver = harness.load_driver(cell)(cell, seed, harness.Spans(False))
        driver.setup()
        t1 = time.perf_counter()
        driver.window(args.seconds)
        driver.release()
        rec = {"workload": args.workload, "seed": seed, "device": device.kind,
               "setup_s": t1 - t0, "completed": driver.outcome()[0]}
        rec.update(readings(driver, cell, i < args.control_seeds))
        rec["check_s"] = time.perf_counter() - t1 - args.seconds
        print(json.dumps(rec), flush=True)
        del driver
        gc.collect()


if __name__ == "__main__":
    main()
