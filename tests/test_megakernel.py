"""The fused per-core megakernel backend and the capability-aware backend
API (PR 8).

Megakernel contract (repro/core/megakernel.py): walking the pallas plan,
packing steps into segments that never exceed the scratchpad, and emitting
`num_cores` fused `pallas_call`s per program where the scratchpad allows,
must stay bit-exact against `reference_forward` on every CNN preset —
single sample and vmapped batch.

Backend API contract (repro/compiler/backends.py): `BackendOptions` are
validated against `BackendCapabilities` at compile/swap time (not on first
run) and persisted through `Deployment.save`/`load`; a saved artifact
whose options carry a key this version no longer has still loads.
"""

import hashlib
import json
import pickle
import zipfile

import numpy as np
import pytest

import repro
from repro.compiler import BackendError, BackendOptions, get_backend
from repro.core import (analyze, cnn, init_params, lower_program,
                        reference_forward)
from repro.core import megakernel as MK
from repro.hw import scaled_paper_machine

PRESETS = {
    "small_cnn": (lambda: cnn.small_cnn(), (32, 32, 3)),
    "resnet50": (lambda: cnn.resnet50(h=32, w=32, width=0.25,
                                      blocks=(1, 1, 1, 1), num_classes=16),
                 (32, 32, 3)),
    "yolov5s": (lambda: cnn.yolov5s_backbone(h=64, w=64, width=0.25),
                (64, 64, 3)),
}


def _compiled(preset, cores=4, seed=1):
    g, shape = PRESETS[preset][0](), PRESETS[preset][1]
    hw = scaled_paper_machine(cores)
    rep, sched, subtasks, mapping = analyze(g, hw, num_cores=cores)
    params = init_params(g, seed=seed)
    prog = lower_program(g, params, subtasks, mapping, sched, hw=hw)
    return g, shape, params, prog


# -- megakernel numerics ------------------------------------------------------

@pytest.mark.parametrize("preset", sorted(PRESETS))
def test_megakernel_bit_exact(preset):
    """The fused megakernel == whole-graph oracle on every CNN preset (the
    acceptance bar: fusion must not change a single bit)."""
    g, shape, params, prog = _compiled(preset)
    x = np.random.default_rng(2).integers(-64, 64, size=shape).astype(np.int8)
    ref = reference_forward(g, params, {"input": x})
    out = MK.run_megakernel(prog, {"input": x}, interpret=True)
    for t in g.outputs:
        assert np.array_equal(ref[t], out[t])


@pytest.mark.parametrize("preset", sorted(PRESETS))
def test_megakernel_call_count_invariant(preset):
    """<= num_cores pallas_call equations per program when the scratchpad
    holds the program in that many segments (it does for these reduced
    presets), verified on the actual jaxpr (not the plan): the paper's
    one-kernel-per-core model."""
    g, shape, params, prog = _compiled(preset)
    import jax.numpy as jnp
    x = jnp.zeros(shape, jnp.int8)
    fn = MK.megakernel_single(prog, interpret=True)
    n = MK.count_pallas_calls(fn, {"input": x})
    assert 1 <= n <= prog.num_cores
    # and the plan agrees with the emission
    segments = MK.plan_segments(prog)
    assert n == sum(s.emits_call for s in segments)


def test_megakernel_fuses_below_per_op():
    """The whole point: far fewer kernel launches than the plan has kernel
    steps (one gemm or conv2d kernel per op)."""
    g, shape, params, prog = _compiled("resnet50")
    import jax.numpy as jnp
    from repro.core import compiled as C
    x = jnp.zeros(shape, jnp.int8)
    n_mega = MK.count_pallas_calls(
        MK.megakernel_single(prog, interpret=True), {"input": x})
    counts = C.plan_counts(prog)
    n_perop = counts.get("gemm", 0) + counts.get("conv2d", 0)
    assert n_mega <= prog.num_cores < n_perop


def test_megakernel_batched_vmap():
    g, shape, params, prog = _compiled("small_cnn")
    import jax.numpy as jnp
    B = 3
    xb = np.random.default_rng(5).integers(
        -64, 64, size=(B,) + shape).astype(np.int8)
    fn = MK.megakernel_batched(prog, interpret=True)
    out = fn({"input": jnp.asarray(xb)})
    for b in range(B):
        ref = reference_forward(g, params, {"input": xb[b]})
        for t in g.outputs:
            assert np.array_equal(ref[t], np.asarray(out[t])[b])


def test_megakernel_budget_and_cap_options():
    """scratchpad_budget shapes the pack (smaller budget -> at least as
    many segments); max_kernels is a target the planner meets only within
    the scratchpad: the budget grows toward the capacity, never past it,
    so max_kernels=1 cannot force the whole program into one launch."""
    g, shape, params, prog = _compiled("resnet50")
    capacity = prog.hw.scratchpad_bytes

    def n_calls(segments):
        return sum(s.emits_call for s in segments)

    def fits(segments):
        return all(MK.segment_footprint(prog, s) <= capacity
                   for s in segments if s.kind == "fused")

    default = MK.plan_segments(prog)
    squeezed = MK.plan_segments(prog, budget=64 * 1024)
    assert n_calls(squeezed) >= n_calls(default)
    assert n_calls(squeezed) <= prog.num_cores or squeezed == default
    one = MK.plan_segments(prog, max_kernels=1)
    # the whole program exceeds one scratchpad: the cap gives way
    assert sum(MK.segment_footprint(prog, s) for s in default
               if s.kind == "fused") > capacity
    assert n_calls(one) == n_calls(default) > 1
    # a budget above the capacity is clamped to it
    assert MK.plan_segments(prog, budget=64 * capacity) == default
    assert fits(default) and fits(squeezed) and fits(one)
    # numerics hold under both overrides
    x = np.random.default_rng(2).integers(-64, 64, size=shape).astype(np.int8)
    ref = reference_forward(g, params, {"input": x})
    import jax.numpy as jnp
    for kw in (dict(budget=64 * 1024), dict(max_kernels=1)):
        out = MK.megakernel_single(prog, interpret=True, **kw)(
            {"input": jnp.asarray(x)})
        for t in g.outputs:
            assert np.array_equal(ref[t], np.asarray(out[t]))


def test_small_scratchpad_emits_more_kernels_never_larger_segments():
    """On a machine whose scratchpad cannot hold the program in num_cores
    segments, the planner emits more kernels rather than over-packing: every
    fused segment fits the physical scratchpad, the sanitizer reports no
    SPM002, and the pallas backend stays bit-exact."""
    g, shape = PRESETS["resnet50"][0](), PRESETS["resnet50"][1]
    hw = scaled_paper_machine(4, scratchpad_bytes=192 * 1024)
    params = init_params(g, seed=1)
    dep = repro.compile(g, hw, backend="pallas", params=params,
                        num_cores=4,
                        backend_options=BackendOptions(interpret=True))
    assert not [d for d in dep.artifacts["verify"].diagnostics
                if d.rule == "SPM002"]
    prog = dep.program
    segments = MK.plan_segments(prog)
    assert sum(s.emits_call for s in segments) > prog.num_cores
    assert all(MK.segment_footprint(prog, s) <= hw.scratchpad_bytes
               for s in segments if s.kind == "fused")
    x = np.random.default_rng(3).integers(-64, 64, size=shape).astype(np.int8)
    ref = reference_forward(g, params, {"input": x})
    out = dep.run({"input": x})
    for t in g.outputs:
        assert np.array_equal(ref[t], out[t])


def _pointwise_program(hw_size, stride, c_in, c_out, requant):
    """One 1×1 conv over an odd-sized (hw_size²) input, its accumulator
    requantized (fused into the kernel) or itself the graph output."""
    from repro.core.graph import Graph, conv2d, requant as rq
    g = Graph("pointwise")
    g.add_tensor("input", (hw_size, hw_size, c_in), "int8", is_input=True)
    y = conv2d(g, "pw", "input", c_out, 1, stride=stride)
    g.mark_output(rq(g, "pw.rq", y) if requant else y)
    g.validate()
    hw = scaled_paper_machine(2)
    rep, sched, subtasks, mapping = analyze(g, hw, num_cores=2)
    params = init_params(g, seed=11)
    return g, lower_program(g, params, subtasks, mapping, sched, hw=hw)


@pytest.mark.parametrize("budget", [None, 1024])
@pytest.mark.parametrize("requant", [True, False])
@pytest.mark.parametrize("hw_size,stride,c_in,c_out", [
    (55, 1, 64, 256), (55, 2, 64, 64), (7, 1, 256, 64), (7, 2, 128, 256)])
def test_pointwise_conv_runs_on_gemm_kernel_bit_exact(
        hw_size, stride, c_in, c_out, requant, budget):
    """A 1×1 conv is planned as a GEMM and runs bit for bit equal to
    `run_numpy` at the megakernel's default plan and as its tiled segment
    (a budget below its working set); wherever it lands in a tiled
    segment it runs on the GEMM kernel: exact int32 contraction, same
    requant epilogue, stride by subsampling."""
    import jax.numpy as jnp
    from repro.core import compiled as C
    from repro.kernels.gemm_int8 import gemm_kernel_name
    g, prog = _pointwise_program(hw_size, stride, c_in, c_out, requant)
    (step,) = [s for s in C._pallas_plan(prog) if s.mode != "skip"]
    oh = (hw_size - 1) // stride + 1
    assert step.mode == "gemm"
    assert step.gemm == (oh * oh, c_in, c_out, stride)
    assert (step.mult is not None) == requant
    x = np.random.default_rng(hw_size + stride).integers(
        -128, 128, size=(hw_size, hw_size, c_in)).astype(np.int8)
    (seg,) = MK.plan_segments(prog, budget=budget)
    if budget is not None:
        assert seg.kind == "tiled"
    fn = MK.megakernel_single(prog, interpret=True, budget=budget)
    if seg.kind == "tiled":
        assert MK.pallas_call_names(fn, {"input": jnp.asarray(x)}) == [
            gemm_kernel_name(oh * oh, c_in, c_out)]
    ref = C.run_numpy(prog, {"input": x})
    out = fn({"input": jnp.asarray(x)})
    (t,) = g.outputs
    assert out[t].shape == ref[t].shape == (oh, oh, c_out)
    assert out[t].dtype == ref[t].dtype
    assert np.array_equal(ref[t], np.asarray(out[t]))


def _stem_program(frame, k, stride, pad, c_out, requant=True,
                  second_consumer=False):
    """One conv over the graph input, its accumulator requantized (fused
    into the kernel) or itself the graph output; with `second_consumer`
    a max pool reads the input too."""
    from repro.core.graph import Graph, conv2d, pool2d, requant as rq
    g = Graph("stem")
    g.add_tensor("input", frame, "int8", is_input=True)
    y = conv2d(g, "c", "input", c_out, k, stride=stride, padding=pad)
    g.mark_output(rq(g, "c.rq", y) if requant else y)
    if second_consumer:
        g.mark_output(pool2d(g, "pool", "maxpool", "input", 2, 2))
    g.validate()
    hw = scaled_paper_machine(2)
    rep, sched, subtasks, mapping = analyze(g, hw, num_cores=2)
    params = init_params(g, seed=13)
    return g, lower_program(g, params, subtasks, mapping, sched, hw=hw)


@pytest.mark.parametrize("requant", [True, False])
@pytest.mark.parametrize("frame,k,stride,pad,c_out", [
    ((16, 20, 3), 6, 2, 2, 32),   # YOLOv5s's stem geometry
    ((12, 44, 3), 7, 2, 3, 64),   # ResNet-50's; rows of 264 lanes
])
def test_stem_conv_bit_exact_per_step_and_megakernel(frame, k, stride, pad,
                                                     c_out, requant):
    """A conv that alone reads a 3-channel graph input is planned as the
    stem: the program takes the input as row groups, the kernel carries
    the stem's name, and the per-step path (`run_kernel_step`), the
    megakernel for one frame and for 4, given the row groups or the
    (H, W, C) frames, all equal `run_numpy` bit for bit."""
    import jax.numpy as jnp
    from repro.core import compiled as C
    from repro.kernels.stem_conv import stem_input_shape, stem_kernel_name
    g, prog = _stem_program(frame, k, stride, pad, c_out, requant)
    (step,) = [s for s in C._pallas_plan(prog) if s.mode != "skip"]
    assert step.mode == "stem" and (step.mult is not None) == requant
    assert C.plan_counts(prog)["dense_stem"] == 1
    H, W, Cn = frame
    rows = stem_input_shape(H, W, Cn, stride)
    assert MK.input_layout(prog) == {"input": rows}
    (t,) = g.outputs
    xs = np.random.default_rng(sum(frame) + k).integers(
        -128, 128, size=(4,) + frame).astype(np.int8)
    want = [C.run_numpy(prog, {"input": x})[t] for x in xs]

    vals = [None] * len(prog.buffers)
    vals[prog.input_idx["input"]] = jnp.asarray(xs[0].reshape(rows))
    C.run_kernel_step(prog, step, vals, MK.device_weights(prog),
                      interpret=True)
    got = np.asarray(vals[step.out_idx])
    assert got.dtype == want[0].dtype and np.array_equal(got, want[0])

    fn = MK.megakernel_single(prog, interpret=True)
    assert MK.pallas_call_names(fn, {"input": jnp.asarray(xs[0])}) == [
        stem_kernel_name(C.stem_geometry_of(step.batch))]
    assert np.array_equal(MK.run_megakernel(prog, {"input": xs[0]},
                                            interpret=True)[t], want[0])
    batched = MK.megakernel_batched(prog, interpret=True)
    for x in (xs, xs.reshape((4,) + rows)):
        out = np.asarray(batched({"input": jnp.asarray(x)})[t])
        for b in range(4):
            assert np.array_equal(out[b], want[b])


@pytest.mark.parametrize("case", ["two_consumers", "wide_input"])
def test_a_shared_or_wide_input_keeps_the_windowed_kernel(case):
    """The stem path needs an input that only the conv reads, narrow enough
    that a window row fits 128 lanes: an input that a pool reads too, or
    one of 64 channels (64·3 lanes), stays on the windowed kernel in its
    (H, W, C) shape, bit-exact."""
    import jax.numpy as jnp
    from repro.core import compiled as C
    frame = (16, 16, 64) if case == "wide_input" else (16, 16, 3)
    g, prog = _stem_program(frame, 3, 2, 1, 32,
                            second_consumer=case == "two_consumers")
    conv = [s for s in C._pallas_plan(prog) if s.batch.name == "c"]
    assert conv[0].mode == "conv2d"
    assert C.plan_counts(prog)["dense_stem"] == 0
    assert MK.input_layout(prog) == {"input": frame}
    x = np.random.default_rng(4).integers(-128, 128,
                                          size=frame).astype(np.int8)
    want = C.run_numpy(prog, {"input": x})
    out = MK.megakernel_single(prog, interpret=True)({"input": jnp.asarray(x)})
    for t in g.outputs:
        assert np.array_equal(np.asarray(out[t]), want[t])


@pytest.mark.parametrize("preset", ["resnet50", "yolov5s", "yolov5s_v6"])
def test_reduced_networks_have_one_dense_stem(preset):
    """Each reduced CNN's first conv reads the 3-channel frame alone: one
    stem step, the frame shipped as row groups of the stem's stride."""
    from repro.core import compiled as C
    if preset == "yolov5s_v6":
        g = cnn.yolov5s(h=64, w=64, width=0.25)
        hw = scaled_paper_machine(4)
        rep, sched, subtasks, mapping = analyze(g, hw, num_cores=4)
        prog = lower_program(g, init_params(g, seed=1), subtasks, mapping,
                             sched, hw=hw)
    else:
        g, _, _, prog = _compiled(preset)
    assert C.plan_counts(prog)["dense_stem"] == 1
    (stem,) = [s for s in C._pallas_plan(prog) if s.mode == "stem"]
    a = stem.batch.attrs
    H, W, Cn = g.tensors["input"].shape
    assert stem.batch.in_idx[0] == prog.input_idx["input"]
    assert MK.input_layout(prog) == {
        "input": (H // a["stride"], a["stride"] * W * Cn)}


def test_segment_cores_round_robin():
    segments = [s for s in MK.plan_segments(_compiled("resnet50")[3])
                if s.emits_call]
    assert [s.core for s in segments] == [i % 4 for i in range(len(segments))]


# -- backend options / capabilities -------------------------------------------

def _deploy(preset="small_cnn", backend="pallas", **kw):
    g, shape = PRESETS[preset][0](), PRESETS[preset][1]
    hw = scaled_paper_machine(4)
    params = init_params(g, seed=1)
    dep = repro.compile(g, hw, backend=backend, params=params, **kw)
    return g, shape, params, dep


def test_backend_options_validated_at_compile_time():
    with pytest.raises(BackendError, match="does not support"):
        _deploy(backend="jax",
                backend_options=BackendOptions(max_kernels=2))


def test_interpret_false_requires_tpu():
    import jax
    if jax.default_backend() == "tpu":
        pytest.skip("native lowering legal here")
    with pytest.raises(BackendError, match="requires"):
        _deploy(backend="pallas",
                backend_options=BackendOptions(interpret=False))


def test_with_backend_validates_at_swap_time():
    """An invalid (backend, options) pair raises at `with_backend`, before
    the view ever reaches a serving loop (the PR-8 fix: it used to blow up
    on the first run)."""
    g, shape, params, dep = _deploy(
        backend="pallas", backend_options=BackendOptions(interpret=True))
    with pytest.raises(BackendError):
        dep.with_backend("nonexistent-backend")
    with pytest.raises(BackendError):
        dep.with_backend("numpy")        # numpy supports no options
    # a valid swap carries (or replaces) the options
    view = dep.with_backend("jax", options=BackendOptions())
    assert view.backend == "jax" and view.options == BackendOptions()
    x = np.random.default_rng(2).integers(-64, 64, size=shape).astype(np.int8)
    ref = reference_forward(g, params, {"input": x})
    for d in (dep, view):
        out = d.run({"input": x})
        for t in g.outputs:
            assert np.array_equal(ref[t], out[t])


def test_pallas_megakernel_off_restores_per_op_path(tmp_path):
    """An artifact saved when the pallas backend still had a per-op path
    carries ``"megakernel": false`` in its options. It loads, the retired
    key is ignored, and it runs the megakernel bit-exact."""
    g, shape, params, dep = _deploy(
        backend="pallas", backend_options=BackendOptions(interpret=True))
    p = str(tmp_path / "old.rtdep")
    dep.save(p)
    with zipfile.ZipFile(p) as z:
        manifest = json.loads(z.read("manifest.json"))
        payload = pickle.loads(z.read("payload.pkl"))
    old_options = {"interpret": True, "megakernel": False}
    payload["backend_options"] = manifest["backend_options"] = old_options
    blob = pickle.dumps(payload)
    manifest["payload_sha256"] = hashlib.sha256(blob).hexdigest()
    with zipfile.ZipFile(p, "w") as z:
        z.writestr("manifest.json", json.dumps(manifest))
        z.writestr("payload.pkl", blob)
    old = repro.Deployment.load(p, machine=dep.machine)
    assert old.options == BackendOptions(interpret=True)
    x = np.random.default_rng(2).integers(-64, 64, size=shape).astype(np.int8)
    ref = reference_forward(g, params, {"input": x})
    out = old.run({"input": x})
    for t in g.outputs:
        assert np.array_equal(ref[t], out[t])


def test_options_persist_through_save_load(tmp_path):
    opts = BackendOptions(interpret=True, max_kernels=2)
    g, shape, params, dep = _deploy(backend="pallas", backend_options=opts)
    p = str(tmp_path / "net.rtdep")
    dep.save(p)
    dep2 = repro.Deployment.load(p, machine=dep.machine)
    assert dep2.backend == "pallas" and dep2.options == opts
    x = np.random.default_rng(2).integers(-64, 64, size=shape).astype(np.int8)
    ref = reference_forward(g, params, {"input": x})
    out = dep2.run({"input": x})
    for t in g.outputs:
        assert np.array_equal(ref[t], out[t])


def test_options_manifest_round_trip_lenient():
    opts = BackendOptions(interpret=True, scratchpad_budget=1 << 16)
    assert BackendOptions.from_manifest(opts.to_manifest()) == opts
    # unknown keys from newer artifacts are ignored, absent ones default
    assert (BackendOptions.from_manifest({"interpret": True, "future": 1})
            == BackendOptions(interpret=True))
    assert BackendOptions.from_manifest(None) == BackendOptions()
    assert BackendOptions().to_manifest() == {}


def test_capabilities_of_builtins():
    assert get_backend("pallas").capabilities.requires_device == "tpu"
    assert get_backend("jax").capabilities.supports_batched_native
    assert get_backend("jax").capabilities.supports_decode
    assert not get_backend("numpy").capabilities.supports_batched_native
    assert get_backend("numpy").capabilities.supported_options == frozenset()


def test_engine_accepts_backend_options():
    """`Server(backend_options=...)` carries the options into the
    Deployment it compiles, and serves through it bit-exact."""
    from repro.serve import Server
    g, shape = PRESETS["small_cnn"][0](), PRESETS["small_cnn"][1]
    params = init_params(g, seed=1)
    opts = BackendOptions(interpret=True, max_kernels=2)
    srv = Server(scaled_paper_machine(4), backend="pallas", num_cores=4,
                 backend_options=opts)
    srv.register("cnn", g, period_s=1 / 50, slots=2, params=params)
    assert srv.executors["cnn"].options == opts
    xb = np.random.default_rng(7).integers(
        -64, 64, size=(2,) + shape).astype(np.int8)
    tickets = [srv.submit("cnn", x) for x in xb]
    srv.step()
    for b, tk in enumerate(tickets):
        ref = reference_forward(g, params, {"input": xb[b]})
        out = tk.result().output
        for t in g.outputs:
            assert np.array_equal(ref[t], out[t])


def test_server_persists_backend_options(tmp_path):
    from repro.serve.runtime import Server
    hw = scaled_paper_machine(4)
    opts = BackendOptions(interpret=True)
    srv = Server(hw, backend="pallas", backend_options=opts)
    g = cnn.small_cnn()
    srv.register("cnn", g, 0.05, 0.05, params=init_params(g, seed=1))
    assert srv._nets["cnn"].deployment.options == opts
    srv.save(str(tmp_path))
    srv2 = Server.load(str(tmp_path))
    assert srv2.backend == "pallas" and srv2.backend_options == opts
    with pytest.raises(BackendError):
        Server(hw, backend="numpy", backend_options=opts)


# -- real-device path ---------------------------------------------------------

@pytest.mark.tpu
def test_megakernel_native_mosaic_smoke():
    """Non-interpret smoke on a real TPU: the same megakernel program
    lowers through Mosaic (interpret=False) and stays bit-exact. Skipped
    on CPU CI (run with `pytest -m tpu` on a TPU host); the interpret-mode
    tests above cover the numerics everywhere else."""
    import jax
    if jax.default_backend() != "tpu":
        pytest.skip("needs a real TPU device")
    g, shape, params, prog = _compiled("small_cnn")
    x = np.random.default_rng(2).integers(-64, 64, size=shape).astype(np.int8)
    ref = reference_forward(g, params, {"input": x})
    out = MK.run_megakernel(prog, {"input": x}, interpret=False)
    for t in g.outputs:
        assert np.array_equal(ref[t], out[t])
