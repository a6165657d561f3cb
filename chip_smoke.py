"""On-chip smoke test of the main path: compile -> bound -> serve the
paper's network at full width on a TPU.

    python chip_smoke.py                # one chip: Server on the pallas backend
    python chip_smoke.py --chips 4      # four chips: the mesh backend only

One chip: builds int8 ResNet-50 (224x224, width 1.0, 1000 classes) with
weights drawn from --seed, compiles it for the 16-core paper machine with
the schedule sanitizer on, registers it with a `Server` on the Pallas
megakernel backend (real Mosaic kernels), serves 8 frames through
`submit`/`run`, and checks every ticket `done` and bit-exact against the
numpy oracle (`reference_forward`) and against the same Deployment on the
`jax` backend. Four chips: the same network on the `mesh` backend over a
(1, 4) and a (4, 1) device mesh, each compared bit-exactly with the
single-device `jax` backend.

Everything runs in this one process. The script exits non-zero, printing
no result line, when JAX's platform is not "tpu" or a check fails. Its
last stdout line is one JSON object naming the device. The times it
prints are host wall-clock seconds of compilation and set-up, not device
metrics.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

FRAMES = 8          # requests served
SLOTS = 4           # batch slots per job: two jobs serve the eight frames


def fail(msg: str) -> None:
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def log(msg: str) -> None:
    print(msg, flush=True)


def check_equal(what: str, got: dict, want: dict) -> None:
    import numpy as np
    for k, v in want.items():
        if not np.array_equal(np.asarray(got[k]), np.asarray(v)):
            fail(f"{what}: output {k!r} differs")


def serve_phase(g, params, frames, slots: int) -> None:
    """Server(backend="pallas") on one chip, checked against the oracle
    and the jax backend."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import repro
    from repro.core import compiled as C
    from repro.core import megakernel as MK
    from repro.core import reference_forward
    from repro.hw import scaled_paper_machine
    from repro.serve import Server

    if C.resolve_interpret(None) is not False:
        fail("pallas interpret mode resolves on; the kernels would not run "
             "through Mosaic")
    hw = scaled_paper_machine(16)
    t0 = time.perf_counter()
    dep = repro.compile(g, hw, backend="pallas", params=params, num_cores=16)
    log(f"repro.compile (verify on, no suppressions): "
        f"{time.perf_counter() - t0:.2f} s; verify: {dep.stages[-1].summary}")
    bound = dep.wcet_bound_s
    log(f"WCET bound on {hw.name} x16: {bound * 1e3:.3f} ms")
    prog = dep.program
    segments = MK.plan_segments(prog)
    cap = hw.scratchpad_bytes
    log(f"segment plan: {sum(s.emits_call for s in segments)} kernels, "
        f"{sum(not s.emits_call for s in segments)} XLA-level steps")
    for i, seg in enumerate(segments):
        if not seg.emits_call:
            continue
        foot = MK.segment_footprint(prog, seg) if seg.kind == "fused" else 0
        if foot > cap:
            fail(f"segment {i} holds {foot} bytes, over the {cap}-byte "
                 f"scratchpad")
        log(f"  kernel {i:3d} {seg.kind:6s} core {seg.core:2d} "
            f"{seg.steps[0].batch.name:12s} steps={len(seg.steps):2d} "
            f"vmem={MK.segment_vmem_bytes(prog, seg)}")

    srv = Server(hw, backend="pallas", num_cores=16)
    verdict = srv.register("resnet50", g, period_s=2 * bound,
                           params=params, slots=slots)
    log(f"admitted: {verdict}")
    served = srv.executors["resnet50"]
    if served.backend != "pallas" or served.options.interpret is not None:
        fail(f"served deployment is {served.backend!r} with options "
             f"{served.options}")

    # compile the serving program ahead of time to report it; the runner
    # below uses this same jitted function, so the compile is cached
    batched = MK.megakernel_batched(served.program, interpret=False)
    x_spec = {"input": jax.ShapeDtypeStruct((slots,) + g.tensors["input"].shape,
                                            jnp.int8)}
    t0 = time.perf_counter()
    compiled = batched.func.lower(batched.args[0], x_spec).compile()
    log(f"compile pallas (batch {slots}): {time.perf_counter() - t0:.2f} s")
    log(f"memory_analysis pallas: {compiled.memory_analysis()}")
    text = compiled.as_text()
    log(f"tpu_custom_call in pallas program: {text.count('tpu_custom_call')}")
    jax_fn = C.jit_batched(served.program)
    t0 = time.perf_counter()
    jcompiled = jax_fn.lower(x_spec).compile()
    log(f"compile jax (batch {slots}): {time.perf_counter() - t0:.2f} s")
    log(f"memory_analysis jax: {jcompiled.memory_analysis()}")

    tickets = [srv.submit("resnet50", f) for f in frames]
    t0 = time.perf_counter()
    rounds = 0
    while not all(t.terminal for t in tickets):
        srv.run()
        rounds += 1
        if rounds > 4 * len(frames):
            fail("tickets still queued after serving")
    log(f"served {len(tickets)} tickets in {rounds} hyperperiods, "
        f"{time.perf_counter() - t0:.2f} s host wall time incl. first-call "
        f"set-up; statuses {[t.status for t in tickets]}")
    for t in tickets:
        if t.status != "done":
            fail(f"ticket {t.tid} is {t.status}: {t.error}")

    out_name = g.outputs[0]
    exact = 0
    t0 = time.perf_counter()
    for t, f in zip(tickets, frames):
        ref = reference_forward(g, params, {"input": f})
        check_equal(f"ticket {t.tid} vs reference_forward",
                    t.result().output, {out_name: ref[out_name]})
        exact += 1
    log(f"{exact}/{len(tickets)} tickets done and bit-exact vs "
        f"reference_forward ({time.perf_counter() - t0:.1f} s of numpy)")

    jax_dep = served.with_backend("jax")
    for i in range(0, len(frames), slots):
        batch = np.stack(frames[i:i + slots])
        want = jax_dep.run({"input": batch}, batched=True)
        got = {out_name: np.stack([t.result().output[out_name]
                                   for t in tickets[i:i + slots]])}
        check_equal(f"frames {i}..{i + len(batch) - 1} pallas vs jax",
                    got, want)
    log(f"{len(tickets)}/{len(tickets)} tickets bit-exact vs the jax "
        f"backend of the same Deployment")


def mesh_phase(g, params, frames) -> None:
    """The mesh backend over a (1, 4) and a (4, 1) mesh, each checked
    bit-exactly against the single-device jax backend."""
    import numpy as np
    import repro
    from repro.hw import scaled_paper_machine

    hw = scaled_paper_machine(16)
    ref_dep = repro.compile(g, hw, backend="jax", params=params,
                            num_cores=16)
    for data, model, batch in ((1, 4, 1), (4, 1, 4)):
        mhw = hw.with_mesh(data, model)
        dep = repro.compile(g, mhw, backend="mesh", params=params,
                            num_cores=16)
        x = {"input": np.stack(frames[:batch])}
        t0 = time.perf_counter()
        if batch == 1:
            got = dep.run({"input": frames[0]})
            want = ref_dep.run({"input": frames[0]})
        else:
            got = dep.run(x, batched=True)
            want = ref_dep.run(x, batched=True)
        check_equal(f"mesh {data}x{model} vs jax", got, want)
        log(f"mesh (data={data}, model={model}) batch {batch}: bit-exact "
            f"vs single-device jax ({time.perf_counter() - t0:.2f} s host "
            f"wall time incl. compile)")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()

    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        fail(f"JAX's platform is {platform!r}, not 'tpu': this smoke test "
             f"runs the kernels on the chip and does not fall back")
    if len(devices) < args.chips:
        fail(f"--chips {args.chips} needs {args.chips} devices, JAX sees "
             f"{len(devices)}")
    try:
        import numpy as np
        from repro.core import cnn, init_params
        from repro.kernels import ops
        from repro.launch.cache import enable_compile_cache
    except ImportError as e:
        fail(f"cannot import the repro package from {ROOT}/src: {e}")
    log(f"device: platform={platform} kind={devices[0].device_kind} "
        f"count={len(devices)}")
    log(f"compile cache: {enable_compile_cache()}")
    if ops.resolve_backend() != "pallas":
        fail(f"kernel dispatch resolves to {ops.resolve_backend()!r}")

    g = cnn.resnet50()
    params = init_params(g, seed=args.seed)
    rng = np.random.default_rng(args.seed)
    frames = list(rng.integers(-128, 128, size=(FRAMES,)
                               + g.tensors["input"].shape, dtype=np.int8))
    log(f"network {g.name}: {len(g.ops)} ops, "
        f"{sum(p.size for p in params.values() if p.dtype == np.int8)} "
        f"int8 weights, seed {args.seed}")
    if args.chips == 4:
        mesh_phase(g, params, frames)
    else:
        serve_phase(g, params, frames, SLOTS)
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)


if __name__ == "__main__":
    main()
