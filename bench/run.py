#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json on the chip this process finds.

    python3 bench/run.py --workload paper4.flood --seed 7 --seconds 30 --trace 0

Set-up (imports, weights, compiles, one warm-up workflow) is timed from
process start; then the cell's traffic runs through the program's
``ControlPlane`` for ``--seconds``; then its outputs are compared with
the plain references. ``--trace 0`` reports the cell's end-to-end
metrics with the profiler off; ``--trace 1`` traces the window and
reports its per-layer metrics. The last line of stdout is the result
object; the last lines of stderr are the numbers compared, each beside
its limit. Without a TPU the run exits non-zero and prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    for p in (ROOT / "src", ROOT):
        if str(p) not in sys.path:
            sys.path.insert(0, str(p))
    from bench import harness
    result = harness.run_cell(ROOT, args.workload, args.seed, args.seconds,
                              bool(args.trace), T_START)
    harness.emit(result)


if __name__ == "__main__":
    main()
