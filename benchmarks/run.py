"""Benchmark harness — one section per paper claim/table:

  bench_wcet      WCET composition + vs-TDMA + mapping ablation
                  (paper Abstract, §II, §III.B)
  bench_schedule  scheduler-construction eventq-vs-rescan timing + the
                  cores x VLEN x scratchpad design-space sweep (paper §V)
  bench_taskset   multi-network hyperperiod scheduling sweep (#nets x cores)
  bench_executor  interpreter vs compiled schedule executor (numpy, jitted
                  batched JAX, Pallas kernels); emits BENCH_executor.json
  bench_kernels   worker-core kernels (int8 GEMM / conv-im2col; §IV.A)
  bench_serve     sustained Server throughput/latency/miss-rate for a mixed
                  CNN+LM taskset on numpy+jax, continuous-vs-static batching
                  comparison, and (full mode) the per-token LM WCET table;
                  emits BENCH_serve.json
  bench_cluster   4-replica ClusterServer vs one Server at capacity load,
                  modeled-time throughput behind the WCET-aware router;
                  emits BENCH_cluster.json

``--smoke`` runs a fast subset (taskset sweep + executor backends + serve
runtime) suitable for CI; ``--only name[,name...]`` restricts the run to
the named sections (the CI perf-smoke job uses this to own the
BENCH_executor.json perf gate and the serve-smoke step separately).

Every section is timed: a ``== section <name>: ok|FAILED (wall s) ==``
line is printed as it finishes, and a per-section wall-time table is
printed at the end, so a slow or failing section is identifiable by name
without reading tracebacks. A backend-vs-oracle mismatch
(``bench_executor.BackendMismatch`` or any AssertionError) aborts the
whole run immediately with a non-zero exit naming the section; any other
section failure is reported at the end and also exits non-zero with the
failed section names.

Prints ``name,us_per_call,derived`` CSV at the end (harness contract).
"""

from __future__ import annotations

import sys
import time
import traceback


def main(argv: list[str] | None = None) -> None:
    argv = sys.argv[1:] if argv is None else argv
    smoke = "--smoke" in argv
    only: set[str] | None = None
    if "--only" in argv:
        idx = argv.index("--only")
        if idx + 1 >= len(argv):
            print("--only requires a comma-separated section list",
                  file=sys.stderr)
            sys.exit(2)
        only = set(argv[idx + 1].split(","))
    csv_rows: list[tuple] = []
    from . import bench_cluster, bench_executor, bench_serve, bench_taskset
    if smoke:
        # the executor section owns BENCH_executor.json: CI's perf-smoke
        # job runs this once, then gates the artifact with
        # benchmarks/check_regression.py (no separate bench_executor step)
        sections = [
            ("taskset", lambda: bench_taskset.run(csv_rows, smoke=True)),
            ("executor", lambda: bench_executor.run(csv_rows, smoke=True)),
            ("serve", lambda: bench_serve.run(csv_rows, smoke=True)),
            ("cluster", lambda: bench_cluster.run(csv_rows, smoke=True)),
        ]
    else:
        from . import bench_wcet, bench_schedule, bench_kernels
        sections = [
            ("wcet", lambda: (bench_wcet.run(csv_rows),
                              bench_wcet.run_mapping_ablation(csv_rows))),
            ("schedule_sweep", lambda: bench_schedule.run(csv_rows)),
            ("taskset", lambda: bench_taskset.run(csv_rows)),
            ("executor", lambda: bench_executor.run(csv_rows)),
            ("kernels", lambda: bench_kernels.run(csv_rows)),
            ("serve", lambda: bench_serve.run(csv_rows)),
            ("cluster", lambda: bench_cluster.run(csv_rows)),
        ]
    if only is not None:
        unknown = only - {name for name, _ in sections}
        if unknown:
            print(f"--only: unknown sections {sorted(unknown)} "
                  f"(have: {[n for n, _ in sections]})", file=sys.stderr)
            sys.exit(2)
        sections = [(n, f) for n, f in sections if n in only]
    failed = []
    walls: list[tuple[str, float, str]] = []
    for name, fn in sections:
        t0 = time.perf_counter()
        try:
            fn()
            status = "ok"
        except bench_executor.BackendMismatch:
            # a backend producing wrong values is never "just" a failed
            # section — abort the run immediately
            traceback.print_exc()
            print(f"== section {name}: FAILED "
                  f"({time.perf_counter() - t0:.2f} s) ==")
            print(f"FATAL: backend mismatch in section {name}",
                  file=sys.stderr)
            sys.exit(1)
        except Exception:  # noqa: BLE001 — report all sections
            failed.append(name)
            traceback.print_exc()
            status = "FAILED"
        wall = time.perf_counter() - t0
        walls.append((name, wall, status))
        print(f"== section {name}: {status} ({wall:.2f} s) ==")
    print("\n== section wall time ==")
    for name, wall, status in walls:
        print(f"{name:<16}{wall:>8.2f} s  {status}")
    print("\n== CSV ==")
    print("name,us_per_call,derived")
    for name, us, derived in csv_rows:
        print(f"{name},{us:.2f},{derived}")
    if failed:
        print(f"FAILED sections: {failed}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
