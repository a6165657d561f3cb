"""Chip benchmark: one cell of BENCHMARK.json, one seed, one run.

    python3 chipbench/run.py --workload resnet50_224.periodic_b1 \
        --seed 7 --seconds 10 --trace 0

Runs from the root of a checkout that holds the program under `src/`.
With `--trace 0` the result has the cell's end-to-end metrics, with
`--trace 1` its per-layer metrics, read from a profiler trace of the
window. The last line of standard output is one JSON object (`correct`,
`attempted`, `failed`, `metrics`, `device`, `breakdown` when traced, and
`checks` last: each compared number with its limit); the compared
numbers are also the last lines on standard error. The run exits 1 and
prints no result when JAX's platform is not "tpu", when it sees fewer
chips than the cell asks for, or when the program or a file of the cell
is missing.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a non-negative integer")
    import harness
    try:
        cell = harness.load_cell(args.workload)
        result = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                             T_START)
    except (harness.BenchError, ImportError, OSError) as e:
        print(f"chipbench: cannot measure: {type(e).__name__}: {e}",
              file=sys.stderr, flush=True)
        return 1
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
