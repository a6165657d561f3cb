"""Bit-exact equivalence: schedule replay == whole-graph reference.

This is the numerical proof that partition/mapping/schedule preserve the
program: integer arithmetic end to end, so any tiling or ordering bug
produces a hard mismatch.
"""

import numpy as np
import pytest

from repro.core import (analyze, cnn, execute_schedule, init_params,
                        reference_forward)
from repro.core.mapping import map_round_robin
from repro.core.partition import Partitioner
from repro.core.schedule import compute_schedule
from repro.hw import scaled_paper_machine


@pytest.mark.parametrize("cores", [1, 3, 8])
def test_small_cnn_bit_exact(cores):
    g = cnn.small_cnn()
    hw = scaled_paper_machine(cores)
    rep, sched, subtasks, mapping = analyze(g, hw, num_cores=cores)
    params = init_params(g, seed=1)
    x = np.random.default_rng(2).integers(
        -64, 64, size=(32, 32, 3)).astype(np.int8)
    ref = reference_forward(g, params, {"input": x})
    out = execute_schedule(g, params, {"input": x}, subtasks, mapping,
                           sched)
    for t in g.outputs:
        assert np.array_equal(ref[t], out[t])


def test_round_robin_mapping_also_exact():
    g = cnn.small_cnn(h=24, w=24)
    hw = scaled_paper_machine(4)
    part = Partitioner(hw)
    subtasks = part.partition(g)
    mapping = map_round_robin(subtasks, hw)
    sched = compute_schedule(subtasks, mapping, hw)
    params = init_params(g, seed=3)
    x = np.random.default_rng(4).integers(
        -64, 64, size=(24, 24, 3)).astype(np.int8)
    ref = reference_forward(g, params, {"input": x})
    out = execute_schedule(g, params, {"input": x}, subtasks, mapping,
                           sched)
    for t in g.outputs:
        assert np.array_equal(ref[t], out[t])


def test_yolo_reduced_graph_builds_and_schedules():
    g = cnn.yolov5s_backbone(h=64, w=64, width=0.25)
    hw = scaled_paper_machine(4)
    rep, sched, subtasks, mapping = analyze(g, hw, num_cores=4)
    assert rep.wcet_total_s > 0
    params = init_params(g, seed=5)
    x = np.random.default_rng(6).integers(
        -64, 64, size=(64, 64, 3)).astype(np.int8)
    ref = reference_forward(g, params, {"input": x})
    out = execute_schedule(g, params, {"input": x}, subtasks, mapping,
                           sched)
    for t in g.outputs:
        assert np.array_equal(ref[t], out[t])


def test_replay_band_expansion_regression_16_cores():
    """Regression: the replay must expand (im2col / evaluate) only a tile's
    own input band. The seed replay cached a whole-op im2col at first touch;
    at 16 cores the schedule interleaves producer and consumer tiles enough
    that the cache snapshotted unwritten rows — first seen on full-width
    ResNet50 at 160x160 (smaller configs happen to serialize)."""
    g = cnn.resnet50(h=160, w=160, width=1.0)
    hw = scaled_paper_machine(16)
    rep, sched, subtasks, mapping = analyze(g, hw, num_cores=16,
                                            validate=False)
    params = init_params(g, seed=7)
    x = np.random.default_rng(8).integers(
        -64, 64, size=(160, 160, 3)).astype(np.int8)
    ref = reference_forward(g, params, {"input": x})
    out = execute_schedule(g, params, {"input": x}, subtasks, mapping,
                           sched)
    for t in g.outputs:
        assert np.array_equal(ref[t], out[t])


@pytest.mark.parametrize("shape,kh,kw,stride,pad",
                         [((8, 8, 3), 3, 3, 1, 1),
                          ((9, 7, 2), 3, 3, 2, 0),
                          ((16, 16, 4), 5, 5, 2, 2),
                          ((7, 7, 1), 1, 1, 1, 0),
                          ((12, 10, 3), 7, 7, 2, 3),
                          ((6, 6, 2), 2, 3, 1, 1)])
def test_im2col_vectorized_matches_reference(shape, kh, kw, stride, pad):
    """The sliding_window_view im2col is bit-identical to the original
    per-pixel loop (including non-square kernels)."""
    from repro.core.executor import im2col, im2col_reference
    x = np.random.default_rng(0).integers(
        -128, 128, size=shape).astype(np.int8)
    assert np.array_equal(im2col(x, kh, kw, stride, pad),
                          im2col_reference(x, kh, kw, stride, pad))


def test_execute_schedule_setup_is_hoisted():
    """Repeated replays of one schedule reuse a cached ScheduleReplayer
    (sorting/dict resolution paid once), and stay correct."""
    from repro.core.executor import _REPLAYERS
    g = cnn.small_cnn()
    hw = scaled_paper_machine(3)
    rep, sched, subtasks, mapping = analyze(g, hw, num_cores=3)
    params = init_params(g, seed=1)
    rng = np.random.default_rng(2)
    x1 = rng.integers(-64, 64, size=(32, 32, 3)).astype(np.int8)
    x2 = rng.integers(-64, 64, size=(32, 32, 3)).astype(np.int8)
    out1 = execute_schedule(g, params, {"input": x1}, subtasks, mapping,
                            sched)
    rp = _REPLAYERS.get(sched)
    assert rp is not None
    out2 = execute_schedule(g, params, {"input": x2}, subtasks, mapping,
                            sched)
    assert _REPLAYERS.get(sched) is rp          # reused, not rebuilt
    for x, out in ((x1, out1), (x2, out2)):
        ref = reference_forward(g, params, {"input": x})
        for t in g.outputs:
            assert np.array_equal(ref[t], out[t])


def test_resnet50_reduced_bit_exact():
    g = cnn.resnet50(h=32, w=32, width=0.25, blocks=(1, 1, 1, 1),
                     num_classes=16)
    hw = scaled_paper_machine(4)
    rep, sched, subtasks, mapping = analyze(g, hw, num_cores=4)
    params = init_params(g, seed=7)
    x = np.random.default_rng(8).integers(
        -64, 64, size=(32, 32, 3)).astype(np.int8)
    ref = reference_forward(g, params, {"input": x})
    out = execute_schedule(g, params, {"input": x}, subtasks, mapping,
                           sched)
    for t in g.outputs:
        assert np.array_equal(ref[t], out[t])


@pytest.mark.parametrize("factor", [2, 3])
def test_upsample_band_loads_its_own_input_rows(factor):
    """An upsample band of output rows [r0, r1) loads input rows
    [r0 // f, (r1 - 1) // f + 1) and their bytes alone (not the whole
    input), the bands cover the output once, and the schedule replays
    bit-exact."""
    from repro.core.graph import Graph, upsample
    H, W, C = 16, 16, 64
    g = Graph("up")
    g.add_tensor("input", (H, W, C), "int8", is_input=True)
    g.mark_output(upsample(g, "up", "input", factor))
    g.validate()
    hw = scaled_paper_machine(4, scratchpad_bytes=64 * 1024)
    subtasks = Partitioner(hw).partition(g)
    assert len(subtasks) > 1
    rows = []
    for st in subtasks:
        r0, r1 = st.tile["r0"], st.tile["r1"]
        i0, i1 = r0 // factor, (r1 - 1) // factor + 1
        (ld,) = st.loads
        assert ld.region == ("rows", i0, i1)
        assert ld.nbytes == (i1 - i0) * W * C
        rows += range(r0, r1)
    assert rows == list(range(H * factor))
    rep, sched, subtasks, mapping = analyze(g, hw, num_cores=4)
    x = np.random.default_rng(7).integers(
        -128, 128, size=(H, W, C)).astype(np.int8)
    out = execute_schedule(g, {}, {"input": x}, subtasks, mapping, sched)
    want = x.repeat(factor, axis=0).repeat(factor, axis=1)
    assert np.array_equal(out[g.outputs[0]], want)


def test_yolov5s_det_schedule_replay_bit_exact():
    """The whole published YOLOv5s (64x64, width 0.25) on 8 cores: the
    tile-by-tile schedule replay of its SiLUs, upsamples and concats
    equals the whole-graph oracle in all three Detect outputs."""
    g = cnn.yolov5s_det(h=64, w=64, width=0.25)
    assert [g.tensors[t].shape for t in g.outputs] == \
        [(8, 8, 255), (4, 4, 255), (2, 2, 255)]
    hw = scaled_paper_machine(8)
    rep, sched, subtasks, mapping = analyze(g, hw, num_cores=8)
    params = init_params(g, seed=8)
    x = np.random.default_rng(9).integers(
        -64, 64, size=(64, 64, 3)).astype(np.int8)
    ref = reference_forward(g, params, {"input": x})
    out = execute_schedule(g, params, {"input": x}, subtasks, mapping,
                           sched)
    for t in g.outputs:
        assert np.array_equal(ref[t], out[t])
