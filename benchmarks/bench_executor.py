"""Executor benchmark: seed interpreter vs compiled schedule executor.

Per CNN preset (smallest -> largest) this measures, on one machine model:

  * ``interp_seed``  — seed-equivalent replay: per-call setup (sort + dict
    resolution) + loop im2col, fresh every call;
  * ``interp``       — the retained oracle with hoisted setup
    (`ScheduleReplayer`, vectorized im2col);
  * ``compiled_np``  — the registry's ``numpy`` backend (fused per-op tile
    batches, exact BLAS GEMM);
  * ``compiled_jax`` — the registry's ``jax`` backend (jitted+vmapped
    program), reported per-sample at batch 1 and batch 8 (compile time
    excluded; that's the cached cost);
  * ``compiled_pallas`` — the registry's ``pallas`` backend: the fused
    per-core megakernel (`repro.core.megakernel`, scratchpad-sized
    segments, requant fused in epilogues). Real Mosaic
    kernels on TPU, interpret mode on CPU CI.

All compiled paths go through one `repro.compile` Deployment per preset
and its backend-registry runners — the same artifact serving uses.

Every path is checked bit-exact against ``reference_forward`` before being
timed; a mismatch raises ``BackendMismatch`` (which `benchmarks.run`
treats as immediately fatal). Results go to stdout (table), the harness
CSV, and a JSON artifact (``BENCH_executor.json`` — CI uploads it and
gates on it via ``benchmarks/check_regression.py``; see
docs/performance.md).
"""

from __future__ import annotations

import argparse
import json
import time

import jax
import numpy as np

import repro
from repro.core import cnn, init_params, reference_forward
from repro.core.executor import (ScheduleReplayer,
                                 _execute_schedule_unprepared)
from repro.hw import scaled_paper_machine


class BackendMismatch(AssertionError):
    """A timed backend produced values that differ from the oracle."""

# name -> (graph factory, input hw shape); ordered smallest -> largest
PRESETS = {
    "small_cnn_32": (lambda: cnn.small_cnn(), (32, 32, 3)),
    "resnet50_64_w025": (lambda: cnn.resnet50(
        h=64, w=64, width=0.25, blocks=(1, 1, 1, 1), num_classes=16),
        (64, 64, 3)),
    "yolov5s_128_w025": (lambda: cnn.yolov5s_backbone(
        h=128, w=128, width=0.25), (128, 128, 3)),
    "resnet50_160_full": (lambda: cnn.resnet50(h=160, w=160),
                          (160, 160, 3)),
}
SMOKE = ("small_cnn_32", "resnet50_64_w025")
CORES = 16
BATCH = 8


def _time(fn, reps):
    fn()                                   # warmup (jit compile / caches)
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn()
    jax.block_until_ready(out)             # a failed sync raises
    return (time.perf_counter() - t0) / reps


def _bench_preset(name: str, reps: int) -> dict:
    build, shape = PRESETS[name]
    g = build()
    hw = scaled_paper_machine(CORES)
    params = init_params(g)
    rng = np.random.default_rng(0)
    x = rng.integers(-64, 64, size=shape).astype(np.int8)
    xb = rng.integers(-64, 64, size=(BATCH,) + shape).astype(np.int8)
    ref = reference_forward(g, params, {"input": x})

    # one compile, every backend: the deployment the serving engines use
    dep = repro.compile(g, hw, backend="jax", params=params,
                        num_cores=CORES, validate=False)
    subtasks = dep.artifacts["partition"]
    mapping, sched = dep.artifacts["map"], dep.schedule
    replayer = ScheduleReplayer(g, subtasks, mapping, sched)
    runners = {be: dep.runner(backend=be)
               for be in ("numpy", "jax", "pallas")}
    jfn_b = dep.runner(batched=True, backend="jax")

    # correctness first: every timed path is bit-exact vs the oracle
    # (including the batched jax runner — vmap is a different compiled
    # function than the single-sample jit)
    checks = [("interp", replayer.run(params, {"input": x}))]
    checks += [(be, run({"input": x})) for be, run in runners.items()]
    checks.append(("jax_batched",
                   {t: v[0] for t, v in jfn_b({"input": x[None]}).items()}))
    for backend, out in checks:
        for t in g.outputs:
            if not np.array_equal(ref[t], out[t]):
                raise BackendMismatch(
                    f"{name}: {backend} backend not bit-exact on {t}")

    x1, xbb = x[None], xb
    times = {
        "interp_seed": _time(lambda: _execute_schedule_unprepared(
            g, params, {"input": x}, subtasks, mapping, sched), reps),
        "interp": _time(lambda: replayer.run(params, {"input": x}), reps),
        "compiled_np": _time(lambda: runners["numpy"]({"input": x}), reps),
        "compiled_jax_b1": _time(lambda: jfn_b({"input": x1}), reps),
        "compiled_pallas": _time(
            lambda: runners["pallas"]({"input": x}), reps),
    }
    times["compiled_jax_b8_per_sample"] = _time(
        lambda: jfn_b({"input": xbb}), reps) / BATCH
    return {
        "preset": name, "cores": CORES, "subtasks": len(subtasks),
        "ops": len(g.ops), "times_s": times,
        "backends": repro.compiler.list_backends(),
        "speedup_np_vs_seed": times["interp_seed"] / times["compiled_np"],
        "speedup_jax_b8_vs_seed": (times["interp_seed"]
                                   / times["compiled_jax_b8_per_sample"]),
        "speedup_pallas_vs_seed": (times["interp_seed"]
                                   / times["compiled_pallas"]),
    }


def run(csv_rows: list, smoke: bool = False,
        json_path: str | None = "BENCH_executor.json") -> list[dict]:
    names = SMOKE if smoke else tuple(PRESETS)
    reps = 2 if smoke else 3
    print("\n== Schedule executor: interpreter vs compiled "
          f"(x{CORES} cores, batch {BATCH}) ==")
    print(f"{'preset':<20}{'subtasks':>9}{'seed_ms':>9}{'interp_ms':>10}"
          f"{'np_ms':>8}{'jax_b1':>8}{'jax_b8/s':>9}{'pallas':>8}"
          f"{'np_speedup':>11}")
    results = []
    for name in names:
        r = _bench_preset(name, reps)
        t = r["times_s"]
        print(f"{name:<20}{r['subtasks']:>9}"
              f"{t['interp_seed'] * 1e3:>9.1f}"
              f"{t['interp'] * 1e3:>10.1f}"
              f"{t['compiled_np'] * 1e3:>8.1f}"
              f"{t['compiled_jax_b1'] * 1e3:>8.1f}"
              f"{t['compiled_jax_b8_per_sample'] * 1e3:>9.2f}"
              f"{t['compiled_pallas'] * 1e3:>8.1f}"
              f"{r['speedup_np_vs_seed']:>10.1f}x")
        for k, v in t.items():
            csv_rows.append((f"executor/{name}/{k}", v * 1e6,
                             f"speedup_np={r['speedup_np_vs_seed']:.1f}"))
        results.append(r)
    largest = results[-1]
    print(f"  largest preset ({largest['preset']}): compiled numpy is "
          f"{largest['speedup_np_vs_seed']:.1f}x the seed interpreter")
    if json_path:
        with open(json_path, "w") as f:
            json.dump({"cores": CORES, "batch": BATCH, "smoke": smoke,
                       "presets": results}, f, indent=2)
        print(f"  wrote {json_path}")
    return results


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="small presets only (CI)")
    ap.add_argument("--json", default="BENCH_executor.json",
                    help="artifact path ('' disables)")
    args = ap.parse_args(argv)
    csv_rows: list = []
    run(csv_rows, smoke=args.smoke, json_path=args.json or None)
    print("\nname,us_per_call,derived")
    for name, us, derived in csv_rows:
        print(f"{name},{us:.2f},{derived}")


if __name__ == "__main__":
    main()
