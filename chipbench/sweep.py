"""Find an open-loop cell's knee: one process, one compile, rising rates.

    python3 chipbench/sweep.py --workload resnet50_224.periodic_b1 \
        --seed 3 --seconds 3 --start-hz 50 --factor 1.15

Sets the cell up once, then drives one window per rate, from --start-hz
up by --factor, until two rates in a row fail. A rate holds when the
frames' 95th-percentile latency is within the mix's deadline (one period)
and the queue does not grow: the last third of the window's frames waited
no longer for their step, on average, than the first third plus a tenth of
a period. The knee is the highest rate that held; a periodic cell runs at
four fifths of it. Prints one
line per rate and, last, one JSON object with the table and the knee. It
measures the chip only, as run.py does.
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

import harness  # noqa: E402
import stats  # noqa: E402


def holds(frames: list, period_s: float) -> tuple[bool, float | None, float]:
    """(held, p95 latency in s, growth of the mean queue wait in s)."""
    p95 = stats.percentile([f.latency_s for f in frames], 95)
    waits = [f.step_start - f.due if f.step_start is not None else 1e9
             for f in frames]
    third = max(1, len(waits) // 3)
    growth = stats.mean(waits[-third:]) - stats.mean(waits[:third])
    return (p95 is not None and p95 <= period_s
            and growth <= 0.1 * period_s), p95, growth


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--start-hz", type=float, required=True)
    ap.add_argument("--factor", type=float, default=1.15)
    ap.add_argument("--max-rates", type=int, default=40)
    args = ap.parse_args(argv)
    try:
        cell = harness.load_cell(args.workload)
        if cell.mix["loop"] != "open":
            raise harness.BenchError(f"{args.workload} is not an open loop")
        ses = harness.set_up(cell, args.seed, T_START)
    except (harness.BenchError, ImportError, OSError) as e:
        print(f"sweep: cannot measure: {e}", file=sys.stderr)
        return 1
    table, knee, fails, rate = [], None, 0, args.start_hz
    for _ in range(args.max_rates):
        d, _ = harness.window(ses, args.seconds, False, rate_hz=rate)
        d.drain(harness.DRAIN_S)
        ok, p95, growth = holds(d.records, 1.0 / rate)
        p50 = stats.percentile([f.latency_s for f in d.records], 50)
        row = {"rate_hz": rate, "frames": len(d.records), "held": bool(ok),
               "p50_ms": p50 and p50 * 1e3, "p95_ms": p95 and p95 * 1e3,
               "wait_growth_ms": growth * 1e3}
        table.append(row)
        print(json.dumps(row), flush=True)
        if ok:
            knee, fails = rate, 0
        else:
            fails += 1
            if fails == 2:
                break
        rate *= args.factor
    print(json.dumps({"workload": args.workload, "device": ses.device,
                      "knee_hz": knee,
                      "cell_rate_hz": knee and 0.8 * knee, "table": table}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
