"""Chip benchmark of the SDFLMQ trainer: one cell, one run.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

The cell's configuration, traffic and limits are data files under
``benchmarks/chip`` found by the names in ``BENCHMARK.json``.  With
``--trace 0`` the result carries the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read from a profiler trace of the
window.  The last line of standard output is the result as one JSON
object; the last lines of standard error are the numbers that decided
``correct``, each beside its limit.  Without a TPU, or with fewer chips
than the cell asks for, the run exits 2 and prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(HERE)),
                                "src"))


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    from chipbench import harness
    try:
        result, checked, detail = harness.run(
            args.workload, args.seed, args.seconds, bool(args.trace),
            T_START)
    except harness.NoChip as e:
        print(f"no chip: {e}", file=sys.stderr)
        sys.exit(2)
    harness.print_result(result, checked, detail)


if __name__ == "__main__":
    main()
