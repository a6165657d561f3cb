"""Kernel micro-benchmarks: Pallas (interpret-mode correctness + modeled
TPU cycles) vs jnp oracle wall time on CPU. Interpret mode cannot time real
TPU execution, so the perf column is the deterministic model from repro.hw
(the same numbers the WCET/roofline pipeline uses): MXU-bound cycles for
the tile schedule the BlockSpec encodes."""

from __future__ import annotations

import time

import jax
import numpy as np

from repro.hw import TPU_V5E
from repro.kernels import ops, ref


def _time(fn, *args, reps=3):
    jax.block_until_ready(fn(*args))         # compile; a failed sync raises
    t0 = time.perf_counter()
    for _ in range(reps):
        # sync INSIDE the timed loop: async dispatch would otherwise queue
        # all reps and only the last result's readiness would be awaited,
        # under-reporting jitted times
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / reps


def run(csv_rows: list):
    rng = np.random.default_rng(0)
    print("\n== int8 GEMM kernel (paper's worker-core inner loop on MXU) ==")
    print(f"{'M':>6}{'K':>6}{'N':>6}{'ref_cpu_ms':>11}{'mxu_model_us':>13}"
          f"{'exact':>7}")
    for M, K, N in ((256, 512, 256), (512, 2048, 512), (1024, 1024, 1024)):
        x = rng.integers(-128, 128, (M, K)).astype(np.int8)
        w = rng.integers(-128, 128, (K, N)).astype(np.int8)
        t_ref = _time(lambda a, b: ref.gemm_int8(a, b), x, w)
        out_p = ops.gemm_int8(x, w, backend="interpret")
        exact = np.array_equal(np.asarray(out_p),
                               x.astype(np.int32) @ w.astype(np.int32))
        model_us = TPU_V5E.compute_time_s(2.0 * M * K * N, int8=True) * 1e6
        print(f"{M:>6}{K:>6}{N:>6}{t_ref*1e3:>11.2f}{model_us:>13.2f}"
              f"{str(exact):>7}")
        csv_rows.append((f"gemm_int8/{M}x{K}x{N}", t_ref * 1e6,
                         f"mxu_model_us={model_us:.2f};exact={exact}"))

    print("\n== conv2d implicit-im2col kernel ==")
    for H, W, C, N, k, s in ((56, 56, 64, 64, 3, 1),
                             (28, 28, 128, 128, 3, 2)):
        x = rng.integers(-128, 128, (H, W, C)).astype(np.int8)
        wgt = rng.integers(-128, 128, (k * k * C, N)).astype(np.int8)
        t_ref = _time(lambda a, b: ref.conv2d_int8(a, b, stride=s,
                                                   padding=1), x, wgt)
        oh = (H + 2 - k) // s + 1
        ow = (W + 2 - k) // s + 1
        flops = 2.0 * oh * ow * k * k * C * N
        model_us = TPU_V5E.compute_time_s(flops, int8=True) * 1e6
        print(f"  {H}x{W}x{C}->{N} k{k}s{s}: ref {t_ref*1e3:.2f} ms, "
              f"mxu model {model_us:.2f} us")
        csv_rows.append((f"conv2d/{H}x{W}x{C}_{N}", t_ref * 1e6,
                         f"mxu_model_us={model_us:.2f}"))

    print("\n== flash attention / ssm scan (oracle wall, CPU) ==")
    q = rng.standard_normal((1, 8, 1024, 64)).astype(np.float32)
    kv = rng.standard_normal((1, 2, 1024, 64)).astype(np.float32)
    t = _time(lambda a, b, c: ref.flash_attention(a, b, c), q, kv, kv)
    csv_rows.append(("flash_attention/1k", t * 1e6, "gqa4"))
    print(f"  attention 1k (GQA 8/2): {t*1e3:.2f} ms")
    a = (rng.random((2, 2048, 256)) * 0.9).astype(np.float32)
    xs = rng.standard_normal((2, 2048, 256)).astype(np.float32)
    t = _time(lambda u, v: ref.ssm_scan(u, v), a, xs)
    csv_rows.append(("ssm_scan/2k", t * 1e6, "assoc"))
    print(f"  ssm scan 2k x 256: {t*1e3:.2f} ms")
