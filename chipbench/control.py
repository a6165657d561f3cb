"""The control of a cell's comparison, at the cell's own size.

    python3 chipbench/control.py --workload resnet50_224.periodic_b1 \
        --seeds 31,32,33 --frames 400

For each seed: the cell's weights drawn on the chip as a run draws them,
its frames, and a window's worth of frame indices (`--frames`); then the
run's comparison, over every output of each sampled frame, with the plain
reference put in the program's place and computed one precision step
down (int4 for the configurations' int8).
Prints one JSON line per seed with the numbers the comparison reads; the
control must fail it on every seed. Refuses any platform but "tpu".
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
import traffic  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--frames", type=int, default=400)
    args = ap.parse_args(argv)
    try:
        cell = harness.load_cell(args.workload)
        harness.enable_cache()
        device = harness.device_info(cell.chips)
    except (harness.BenchError, ImportError, OSError) as e:
        print(f"control: cannot measure: {e}", file=sys.stderr)
        return 1
    net = harness.reference_net(cell.config)
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        params = harness.make_params(net, seed, cell.config["requant_gain"])
        frames = traffic.Frames(net.shapes["input"],
                                cell.mix["frame_pool"], seed)
        served = dict.fromkeys(range(args.frames))
        records = [harness.Frame(k, 0.0, 0.0, 0.0, "done") for k in served]
        checks, n = harness.compare(net, params, frames, served, records,
                                    seed, cell.mix["check_sample"],
                                    arith="int4")
        print(json.dumps({
            "workload": cell.name, "seed": seed, "arith": "int4",
            "compared_frames": n, "device": device,
            "correct": all(v <= lim for v, lim in checks.values()),
            "checks": {k: {"value": v, "limit": lim}
                       for k, (v, lim) in checks.items()},
            "seconds": time.perf_counter() - t}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
