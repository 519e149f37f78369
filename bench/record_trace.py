#!/usr/bin/env python3
"""Record a short traced window of one cell and keep its .xplane.pb.

    python3 bench/record_trace.py --workload paper4.flood --seconds 0.3 \\
        --out bench/tests/data/paper4_flood.xplane.pb

The trace reduction's tests read the file this writes.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from bench import harness
    result = harness.run_cell(ROOT, args.workload, args.seed, args.seconds,
                              True, T_START, keep_trace=args.out)
    harness.emit(result)


if __name__ == "__main__":
    main()
