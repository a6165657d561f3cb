"""Compiled schedule executor: bit-exactness of both backends against the
whole-graph oracle across every CNN preset, batched (vmap) execution, the
program cache, and eventq-vs-rescan scheduler identity.

The contract under test (see repro/core/compiled.py): lowering a
StaticSchedule to fused per-op tile batches and replaying them — vectorized
numpy or one jitted+vmapped JAX function — produces bit-identical values to
``reference_forward`` and to the tile-by-tile interpreter.
"""

import numpy as np
import pytest

from repro.core import (analyze, cnn, compile_graph, execute_schedule,
                        init_params, lower_program, reference_forward,
                        run_jax, run_numpy, run_megakernel)
from repro.core import compiled as C
from repro.core import megakernel as MK
from repro.core.schedule import compute_schedule, validate_schedule
from repro.core.taskset import NetworkSpec, compile_taskset
from repro.hw import scaled_paper_machine

# all CNN presets in repro.core.cnn, at test-sized configs
PRESETS = {
    "small_cnn": (lambda: cnn.small_cnn(), (32, 32, 3)),
    "resnet50": (lambda: cnn.resnet50(h=32, w=32, width=0.25,
                                      blocks=(1, 1, 1, 1), num_classes=16),
                 (32, 32, 3)),
    "yolov5s": (lambda: cnn.yolov5s_backbone(h=64, w=64, width=0.25),
                (64, 64, 3)),
    "yolov5s_det": (lambda: cnn.yolov5s_det(h=64, w=64, width=0.25),
                    (64, 64, 3)),
}


def _compiled(preset, cores=4, seed=1):
    g, shape = PRESETS[preset][0](), PRESETS[preset][1]
    hw = scaled_paper_machine(cores)
    rep, sched, subtasks, mapping = analyze(g, hw, num_cores=cores)
    params = init_params(g, seed=seed)
    prog = lower_program(g, params, subtasks, mapping, sched, hw=hw)
    return g, shape, params, prog, (subtasks, mapping, sched)


# every compiled backend as a uniform single-sample callable
BACKENDS = {
    "numpy": lambda prog, x: run_numpy(prog, {"input": x}),
    "jax": lambda prog, x: {t: v[0] for t, v in
                            run_jax(prog, {"input": x[None]}).items()},
    "pallas": lambda prog, x: run_megakernel(prog, {"input": x},
                                             interpret=True),
}


@pytest.mark.parametrize("backend", sorted(BACKENDS))
@pytest.mark.parametrize("preset", sorted(PRESETS))
def test_backend_bit_exact(preset, backend):
    """Every compiled backend (numpy, jitted JAX, Pallas kernels in
    interpret mode) is bit-exact vs the whole-graph oracle on every CNN
    preset — the acceptance bar for the pallas lowering."""
    g, shape, params, prog, _ = _compiled(preset)
    x = np.random.default_rng(2).integers(-64, 64, size=shape).astype(np.int8)
    ref = reference_forward(g, params, {"input": x})
    out = BACKENDS[backend](prog, x)
    for t in g.outputs:
        assert np.array_equal(ref[t], out[t])


@pytest.mark.parametrize("backend", sorted(BACKENDS))
def test_backend_matches_interpreter(backend):
    """Compiled backends match the tile-by-tile schedule interpreter (the
    correctness proof chain: interpreter == oracle == compiled)."""
    g, shape, params, prog, (subtasks, mapping, sched) = _compiled(
        "small_cnn")
    x = np.random.default_rng(3).integers(-64, 64, size=shape).astype(np.int8)
    interp = execute_schedule(g, params, {"input": x}, subtasks, mapping,
                              sched)
    out = BACKENDS[backend](prog, x)
    for t in g.outputs:
        assert np.array_equal(interp[t], out[t])


@pytest.mark.parametrize("batch", [1, 4, 16])
def test_jax_batched_bit_exact_small(batch):
    g, shape, params, prog, _ = _compiled("small_cnn")
    xb = np.random.default_rng(4).integers(
        -64, 64, size=(batch,) + shape).astype(np.int8)
    out = run_jax(prog, {"input": xb})
    for b in range(batch):
        ref = reference_forward(g, params, {"input": xb[b]})
        for t in g.outputs:
            assert out[t].shape[0] == batch
            assert np.array_equal(ref[t], out[t][b])


@pytest.mark.parametrize("preset", ["resnet50", "yolov5s"])
def test_jax_batched_bit_exact_presets(preset):
    g, shape, params, prog, _ = _compiled(preset)
    xb = np.random.default_rng(5).integers(
        -64, 64, size=(4,) + shape).astype(np.int8)
    out = run_jax(prog, {"input": xb})
    for b in range(4):
        ref = reference_forward(g, params, {"input": xb[b]})
        for t in g.outputs:
            assert np.array_equal(ref[t], out[t][b])


def test_lowering_structure():
    g, shape, params, prog, (subtasks, mapping, sched) = _compiled(
        "small_cnn")
    # every compute slot became exactly one per-core instruction
    assert prog.num_instructions == len(sched.compute)
    assert len(prog.core_streams) == mapping.num_cores
    for stream in prog.core_streams:
        # per-core streams are in slot time order
        assert all(a.start <= b.start for a, b in zip(stream, stream[1:]))
    # one fused batch per op, in graph (topological) order
    assert [b.name for b in prog.batches] == [op.name for op in g.ops]
    # requant multipliers are pre-resolved
    for b in prog.batches:
        if b.kind == "requant":
            assert b.mult == np.float32(params[f"{b.name}.mult"])


def test_program_cache_keyed_by_signature():
    hw = scaled_paper_machine(4)
    g1, g2 = cnn.small_cnn(), cnn.small_cnn()
    assert C.graph_signature(g1) == C.graph_signature(g2)
    assert C.graph_signature(g1) != C.graph_signature(cnn.small_cnn(h=24,
                                                                    w=24))
    params = init_params(g1)
    p1 = compile_graph(g1, params, hw, 4)
    p2 = compile_graph(g2, params, hw, 4)    # same signature + params -> hit
    assert p1 is p2
    p3 = compile_graph(g1, params, hw, 2)    # different cores -> miss
    assert p3 is not p1


def test_eventq_identical_to_rescan_deterministic():
    """Slot-for-slot identity on a real CNN and on a released taskset
    (the hypothesis property test covers random graphs)."""
    hw = scaled_paper_machine(4)
    from repro.core.partition import Partitioner
    from repro.core.mapping import map_reverse_affinity
    g = cnn.small_cnn()
    subtasks = Partitioner(hw).partition(g)
    mapping = map_reverse_affinity(subtasks, hw)
    for wcet in (True, False):
        a = compute_schedule(subtasks, mapping, hw, wcet=wcet,
                             engine="rescan")
        b = compute_schedule(subtasks, mapping, hw, wcet=wcet,
                             engine="eventq")
        assert a.makespan == b.makespan
        assert a.dma == b.dma
        assert a.compute == b.compute
        assert a.bytes_moved == b.bytes_moved
        assert a.bytes_saved_reuse == b.bytes_saved_reuse

    specs = [NetworkSpec("a", cnn.small_cnn(), 1 / 50),
             NetworkSpec("b", cnn.small_cnn(h=24, w=24), 1 / 100)]
    ct = compile_taskset(specs, hw, 4)
    a = compute_schedule(ct.subtasks, ct.mapping, hw, release=ct.release,
                         engine="rescan")
    b = compute_schedule(ct.subtasks, ct.mapping, hw, release=ct.release,
                         engine="eventq")
    assert a.dma == b.dma and a.compute == b.compute
    validate_schedule(b, ct.subtasks, ct.mapping, release=ct.release)


def test_taskset_templates_shared_across_jobs():
    """Job instantiation reuses the per-network schedule template: transfer
    and tile structures are the *same objects* across job instances."""
    hw = scaled_paper_machine(4)
    specs = [NetworkSpec("a", cnn.small_cnn(), 1 / 100),
             NetworkSpec("b", cnn.small_cnn(h=24, w=24), 1 / 50)]
    ct = compile_taskset(specs, hw, 4)
    template, _ = ct.templates["a"]
    by_sid = {st.sid: st for st in ct.subtasks}
    jobs = ct.jobs_of("a")
    assert len(jobs) >= 2                          # H = 1/50 -> 2 releases
    for job in jobs:
        for sid, tmpl in zip(job.sids, template):
            st = by_sid[sid]
            assert st.loads is tmpl.loads          # shared, not re-derived
            assert st.store is tmpl.store
            assert st.tile is tmpl.tile
            assert sid - job.sids[0] == tmpl.sid
    # and the merged set still schedules + validates
    sched = compute_schedule(ct.subtasks, ct.mapping, hw,
                             release=ct.release)
    validate_schedule(sched, ct.subtasks, ct.mapping, release=ct.release)


def test_per_channel_requant_multipliers():
    """Lowering and both backends accept per-output-channel requant
    multipliers (what quantize.requant_multiplier produces), not just the
    scalar stand-in from init_params."""
    g = cnn.small_cnn()
    hw = scaled_paper_machine(4)
    rep, sched, subtasks, mapping = analyze(g, hw, num_cores=4)
    params = init_params(g, seed=9)
    for op in g.ops:                         # widen scalars to per-channel
        if op.kind == "requant":
            n = g.tensors[op.outputs[0]].shape[-1]
            base = float(params[f"{op.name}.mult"])
            params[f"{op.name}.mult"] = (
                base * (1 + 0.01 * np.arange(n))).astype(np.float32)
    x = np.random.default_rng(10).integers(
        -64, 64, size=(32, 32, 3)).astype(np.int8)
    ref = reference_forward(g, params, {"input": x})
    prog = lower_program(g, params, subtasks, mapping, sched)
    out_np = run_numpy(prog, {"input": x})
    out_j = run_jax(prog, {"input": x[None]})
    for t in g.outputs:
        assert np.array_equal(ref[t], out_np[t])
        assert np.array_equal(ref[t], out_j[t][0])


def test_supports_graph():
    from repro.core.graph import Graph, eltwise
    assert C.supports_graph(cnn.small_cnn())
    g = Graph("mul")
    g.add_tensor("x", (4, 8), "int8", is_input=True)
    eltwise(g, "m", "mul", ["x", "x"])
    assert not C.supports_graph(g)


# -- pallas backend specifics -------------------------------------------------

def test_pallas_plan_fuses_requant_chains():
    """Every conv -> requant chain in the CNN presets fuses into the kernel
    epilogue; fused requant batches become skip steps; fallback kinds go to
    the JAX lowering; blocks come from the program's scratchpad model. The
    conv that reads the 3-channel input is the stem step."""
    g, shape, params, prog, _ = _compiled("small_cnn")
    plan = C._pallas_plan(prog)
    modes = {s.batch.name: s.mode for s in plan}
    assert modes["conv1"] == "stem" and modes["conv1.rq"] == "skip"
    assert modes["conv2"] == "conv2d" and modes["conv2.rq"] == "skip"
    assert modes["pool1"] == "jax" and modes["gap"] == "jax"
    assert modes["fc"] == "gemm"
    for s in plan:
        if s.mode in ("conv2d", "stem"):
            assert s.mult is not None          # fused epilogue multiplier
            assert len(s.blocks) == (2 if s.mode == "conv2d" else 0)
        if s.mode == "gemm":
            assert len(s.blocks) == 3
    # a pointwise conv is planned as a gemm step carrying the conv's batch,
    # its requant fused the same way; the stem reads the input as row
    # groups; every other conv stays windowed
    _, _, _, prog, _ = _compiled("resnet50")
    plan = C._pallas_plan(prog)
    modes = {s.batch.name: s.mode for s in plan}
    assert modes["s0.b0.c1"] == "gemm" and modes["s0.b0.c1.rq"] == "skip"
    assert modes["s1.b0.ds"] == "gemm" and modes["s1.b0.ds.rq"] == "skip"
    assert modes["s0.b0.c2"] == "conv2d" and modes["stem"] == "stem"
    for s in plan:
        a = s.batch.attrs
        if s.batch.kind == "conv2d":
            pointwise = a["kh"] == a["kw"] == 1 and a["padding"] == 0
            assert s.mode == ("gemm" if pointwise else
                              "stem" if s.batch.name == "stem" else "conv2d")
            assert s.mult is not None
        if s.mode == "gemm" and s.batch.kind == "conv2d":
            oh, ow, n = prog.buffers[s.batch.out_idx][1]
            assert s.gemm == (oh * ow, a["C_in"], a["C_out"], a["stride"])
            assert n == a["C_out"] and len(s.blocks) == 3
        elif s.mode == "gemm":
            assert s.gemm == (a["M"], a["K"], a["N"], 1)
        else:
            assert s.gemm is None


def test_plan_counts_full_width_resnet50():
    """Full-width ResNet-50 (224², the benchmark's graph) on the 16-core
    paper machine: its 36 pointwise convs (c1 and c3 of 16 bottlenecks,
    4 projections) are planned as GEMMs, the 7×7 stem reads the frame as
    row groups (`dense_stem`), the 16 3×3 convs stay windowed, and the
    segmentation is untouched: 54 tiled (53 convs and the fc), 67 at the
    XLA level."""
    from repro.core import megakernel as MK
    g = cnn.resnet50()
    hw = scaled_paper_machine(16)
    rep, sched, subtasks, mapping = analyze(g, hw, num_cores=16)
    prog = lower_program(g, init_params(g, seed=0), subtasks, mapping,
                         sched, hw=hw)
    counts = C.plan_counts(prog)
    assert counts["pointwise_gemm"] == 36
    assert counts["gemm"] == 37 and counts["conv2d"] == 16
    assert counts["stem"] == counts["dense_stem"] == 1
    windowed = [s.batch.attrs for s in C._pallas_plan(prog)
                if s.mode == "conv2d"]
    assert sorted({a["kh"] for a in windowed}) == [3]
    segments = MK.plan_segments(prog)
    kinds = [s.kind for s in segments]
    assert kinds.count("tiled") == 54 and kinds.count("outside") == 67
    assert kinds.count("fused") == 0
    assert sum(s.steps[0].mode == "gemm" for s in segments
               if s.kind == "tiled") == 37


def test_plan_counts_full_width_yolov5s():
    """Full-width YOLOv5s backbone + SPPF as published (640², the
    benchmark's graph) on the 16-core paper machine: the 21 pointwise
    convs (C3 branches and fuses, SPPF's 512→256 and 1024→512) are planned
    as GEMMs, the 6×6 stem reads the frame as row groups (`dense_stem`),
    the 11 3×3 convs stay windowed; concats, SPPF's pools, shortcut adds
    and ReLUs are 48 XLA-level steps; 33 tiled segments, none fused."""
    from repro.core import megakernel as MK
    g = cnn.yolov5s()
    hw = scaled_paper_machine(16)
    rep, sched, subtasks, mapping = analyze(g, hw, num_cores=16)
    prog = lower_program(g, init_params(g, seed=0), subtasks, mapping,
                         sched, hw=hw)
    counts = C.plan_counts(prog)
    assert counts["conv2d"] == 11 and counts["pointwise_gemm"] == 21
    assert counts["gemm"] == 21 and counts["jax"] == 48
    assert counts["stem"] == counts["dense_stem"] == 1
    windowed = [s.batch.attrs for s in C._pallas_plan(prog)
                if s.mode == "conv2d"]
    assert sorted({a["kh"] for a in windowed}) == [3]
    gemms = sorted({s.gemm for s in C._pallas_plan(prog) if s.mode == "gemm"})
    assert {m for m, *_ in gemms} == {25600, 6400, 1600, 400}
    assert max(k for _, k, *_ in gemms) == 1024
    kinds = [s.kind for s in MK.plan_segments(prog)]
    assert kinds.count("tiled") == 33 and kinds.count("outside") == 48
    assert kinds.count("fused") == 0


def test_plan_counts_full_width_yolov5s_det():
    """The whole published YOLOv5s (640², the benchmark's detector graph)
    on the 16-core paper machine: every one of its 57 SiLUs is applied in
    the epilogue of its conv's kernel (none is left as a step), the two
    upsamples are steps, the stem reads the frame as row groups; the 60
    convs are 60 tiled kernels and the 25 upsamples, concats, pools and
    adds run at the XLA level. A ReLU network's plan has no SiLU and no
    upsample."""
    from repro.core import megakernel as MK
    hw = scaled_paper_machine(16)
    g = cnn.yolov5s_det()
    rep, sched, subtasks, mapping = analyze(g, hw, num_cores=16)
    prog = lower_program(g, init_params(g, seed=0), subtasks, mapping,
                         sched, hw=hw)
    counts = C.plan_counts(prog)
    assert (counts["silu_epilogue"], counts["silu_steps"],
            counts["upsample"], counts["dense_stem"]) == (57, 0, 2, 1)
    assert counts["conv2d"] == 17 and counts["gemm"] == 42
    assert counts["skip"] == 60 + 57 and counts["jax"] == 25
    plan = C._pallas_plan(prog)
    detect = [s for s in plan if s.mode != "skip"
              and s.batch.name.startswith("detect.")]
    assert [s.silu for s in detect] == [None] * 3
    assert [prog.buffers[s.out_idx][0] for s in detect] == g.outputs
    kinds = [s.kind for s in MK.plan_segments(prog)]
    assert kinds.count("tiled") == 60 and kinds.count("outside") == 25
    relu = C.plan_counts(_compiled("yolov5s")[3])
    assert (relu["silu_epilogue"], relu["silu_steps"], relu["upsample"]) \
        == (0, 0, 0)


def test_pallas_no_fusion_when_acc_is_graph_output():
    """An int32 accumulator that is itself a graph output must NOT be
    requant-fused away — and the backend stays bit-exact (the conv reads
    the 3-channel input, so it is the stem, with an int32 output)."""
    from repro.core.graph import Graph, conv2d, requant
    g = Graph("acc_out")
    g.add_tensor("input", (12, 12, 3), "int8", is_input=True)
    y = conv2d(g, "c1", "input", 8, 3)
    yq = requant(g, "c1.rq", y)
    g.mark_output(y)                           # raw int32 accumulator
    g.mark_output(yq)
    g.validate()
    hw = scaled_paper_machine(2)
    rep, sched, subtasks, mapping = analyze(g, hw, num_cores=2)
    params = init_params(g, seed=7)
    prog = lower_program(g, params, subtasks, mapping, sched, hw=hw)
    plan = C._pallas_plan(prog)
    modes = {s.batch.name: s.mode for s in plan}
    assert modes["c1"] == "stem" and modes["c1.rq"] == "jax"
    assert all(s.mult is None for s in plan)
    x = np.random.default_rng(8).integers(-64, 64,
                                          size=(12, 12, 3)).astype(np.int8)
    ref = reference_forward(g, params, {"input": x})
    out = run_megakernel(prog, {"input": x}, interpret=True)
    for t in g.outputs:
        assert np.array_equal(ref[t], out[t])


def test_pallas_per_channel_requant_fused():
    """Per-channel multipliers survive epilogue fusion bit-exactly."""
    g = cnn.small_cnn()
    hw = scaled_paper_machine(4)
    rep, sched, subtasks, mapping = analyze(g, hw, num_cores=4)
    params = init_params(g, seed=9)
    for op in g.ops:
        if op.kind == "requant":
            n = g.tensors[op.outputs[0]].shape[-1]
            base = float(params[f"{op.name}.mult"])
            params[f"{op.name}.mult"] = (
                base * (1 + 0.01 * np.arange(n))).astype(np.float32)
    prog = lower_program(g, params, subtasks, mapping, sched, hw=hw)
    x = np.random.default_rng(10).integers(
        -64, 64, size=(32, 32, 3)).astype(np.int8)
    ref = reference_forward(g, params, {"input": x})
    out = run_megakernel(prog, {"input": x}, interpret=True)
    for t in g.outputs:
        assert np.array_equal(ref[t], out[t])


@pytest.mark.parametrize("batch", [1, 3])
def test_pallas_batched_vmap(batch):
    """megakernel_batched vmaps the kernel program over a leading batch
    axis."""
    g, shape, params, prog, _ = _compiled("small_cnn")
    xb = np.random.default_rng(11).integers(
        -64, 64, size=(batch,) + shape).astype(np.int8)
    fn = MK.megakernel_batched(prog, interpret=True)
    out = {k: np.asarray(v) for k, v in fn({"input": xb}).items()}
    for b in range(batch):
        ref = reference_forward(g, params, {"input": xb[b]})
        for t in g.outputs:
            assert np.array_equal(ref[t], out[t][b])


def test_engine_pallas_backend():
    """Server(backend="pallas") in interpret mode serves a bit-exact batch
    of 2 in one runner call."""
    from repro.compiler import BackendOptions
    from repro.serve import Server
    g = cnn.small_cnn()
    params = init_params(g, seed=12)
    srv = Server(scaled_paper_machine(4), backend="pallas", num_cores=4,
                 backend_options=BackendOptions(interpret=True))
    srv.register("cnn", g, period_s=1 / 50, slots=2, params=params)
    xb = np.random.default_rng(13).integers(
        -64, 64, size=(2, 32, 32, 3)).astype(np.int8)
    tickets = [srv.submit("cnn", x) for x in xb]
    srv.step()
    for b, tk in enumerate(tickets):
        ref = reference_forward(g, params, {"input": xb[b]})
        out = tk.result().output
        for t in g.outputs:
            assert np.array_equal(ref[t], out[t])
    assert srv.metrics["runner_calls"] == 1
    assert srv.metrics["slots_filled"] == 2
