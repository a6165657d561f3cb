"""Compiles of the main path's Pallas kernels for a TPU v5e, at ResNet-50
224x224 and YOLOv5s 640x640 widths, without a chip.

The TPU compiler is installed alongside JAX and compiles for a chip that is
described (`topologies.get_topology_desc`) rather than attached, so Mosaic
refuses here what it would refuse on the chip: unaligned or strided
accesses it cannot lower, block shapes off the tiling, more VMEM than a
kernel's stated limit. Nothing runs; numerics are the interpret-mode
tests' job (tests/test_megakernel.py, tests/test_kernels.py).

All such compiles live in this one file. The topology is described inside
a module-scoped fixture — never while a module is imported — because only
one process at a time may load the TPU library: under pytest-xdist only
the worker given this file loads it.
"""

import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import repro
from repro.core import analyze, cnn, init_params, lower_program
from repro.core import megakernel as MK
from repro.hw import derive_conv_blocks, scaled_paper_machine
from repro.kernels.conv2d_im2col import conv2d_int8_pallas
from repro.kernels.gemm_int8 import gemm_int8_pallas
from repro.kernels.stem_conv import (stem_conv_pallas, stem_geometry,
                                     stem_input_shape, stem_kernel_name)

HW = scaled_paper_machine(16)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=sharding)


def _n_kernels(compiled) -> int:
    return compiled.as_text().count("custom_call_target=\"tpu_custom_call\"")


def _operands(prog, sharding, batch=()):
    """Specs of the program's weight operands (a stem's band expansion
    among them) and of its inputs in the layout the program declares,
    with a leading `batch` shape."""
    weights = {i: _spec(w.shape, w.dtype, sharding)
               for i, w in MK.host_weights(prog).items()}
    inputs = {name: _spec(batch + shape, jnp.int8, sharding)
              for name, shape in MK.input_layout(prog).items()}
    return weights, inputs


def _compile_served(prog, frame_shape, n_kernels, one_chip):
    """The served program batched over 4 frames, as the Server runs it,
    compiled for v5e: it takes the frame (`frame_shape`) in the layout the
    program declares, row groups for the stem; no fused segment holds
    more than the scratchpad, and each kernel-emitting segment is one
    Mosaic kernel (`n_kernels`)."""
    segments = MK.plan_segments(prog)
    cap = prog.hw.scratchpad_bytes
    assert all(MK.segment_footprint(prog, s) <= cap
               for s in segments if s.kind == "fused")
    H, W, C = frame_shape
    assert MK.input_layout(prog) == {"input": (H // 2, 2 * W * C)}
    fn = jax.vmap(MK.megakernel_fn(prog, interpret=False),
                  in_axes=(None, 0))
    weights, x = _operands(prog, one_chip, (4,))
    compiled = jax.jit(fn).lower(weights, x).compile()
    assert _n_kernels(compiled) == sum(s.emits_call for s in segments) \
        == n_kernels
    return compiled


@pytest.fixture(scope="module")
def resnet50_prog():
    """Full-width ResNet-50 (224x224, width 1.0, 1000 classes) compiled for
    the 16-core paper machine, sanitizer on, no suppressions."""
    g = cnn.resnet50()
    dep = repro.compile(g, HW, backend="pallas",
                        params=init_params(g, seed=0), num_cores=16)
    return dep.program


def test_resnet50_megakernel_program_compiles_for_v5e(resnet50_prog,
                                                      one_chip):
    """The whole served program — every segment kernel plus the XLA-level
    steps between them, batched as the Server runs it — compiles for v5e,
    with one Mosaic kernel per kernel-emitting segment, none of which holds
    more than the scratchpad."""
    compiled = _compile_served(resnet50_prog, (224, 224, 3), 54, one_chip)
    assert compiled.memory_analysis() is not None


def test_yolov5s_megakernel_program_compiles_for_v5e(one_chip):
    """Full-width YOLOv5s backbone + SPPF as published (640x640, the
    benchmark's graph) as the Server runs it, batched over 4 frames,
    compiles for v5e: the 6x6/2 stem over 3 input channels, the 3x3/2
    downsamples at 320² and 160², pointwise GEMMs of up to 25600 rows and
    SPPF's K = 1024 fuse, one Mosaic kernel per kernel-emitting segment,
    no fused segment above the scratchpad, and the concats and 5x5 pools
    at the XLA level."""
    g = cnn.yolov5s()
    prog = repro.compile(g, HW, backend="pallas",
                         params=init_params(g, seed=0), num_cores=16).program
    compiled = _compile_served(prog, (640, 640, 3), 33, one_chip)
    mem = compiled.memory_analysis()
    assert mem.output_size_in_bytes == 4 * 20 * 20 * 512
    assert mem.temp_size_in_bytes < 1 << 30


def _conv_case(name):
    """(x shape, kernel size, stride, padding, C_out) of a ResNet-50 conv."""
    return {
        "stem_7x7_s2_cin3": ((224, 224, 3), 7, 2, 3, 64),
        "s1_c2_3x3_s2": ((55, 55, 128), 3, 2, 1, 128),
        "s3_c1_1x1_requant": ((7, 7, 2048), 1, 1, 0, 512),
    }[name]


@pytest.mark.parametrize("name", ["stem_7x7_s2_cin3", "s1_c2_3x3_s2",
                                  "s3_c1_1x1_requant"])
def test_resnet50_conv_kernel_compiles_for_v5e(name, one_chip):
    """The tiled conv kernel with a fused requant epilogue, at the block
    shapes the 16-core paper machine's scratchpad derives."""
    (H, W, C), k, s, p, N = _conv_case(name)
    attrs = {"H": H, "W": W, "C_in": C, "C_out": N, "kh": k, "kw": k,
             "stride": s, "padding": p}
    rows_t, bn = derive_conv_blocks(HW, attrs, out_bytes=1)
    fn = functools.partial(conv2d_int8_pallas, kh=k, kw=k, stride=s,
                           padding=p, rows_t=rows_t, bn=bn)
    compiled = jax.jit(fn).lower(
        _spec((H, W, C), jnp.int8, one_chip),
        _spec((k * k * C, N), jnp.int8, one_chip),
        _spec((N,), jnp.float32, one_chip)).compile()
    assert _n_kernels(compiled) == 1


@pytest.mark.parametrize("name", ["s0_c3_1x1", "s1_ds_1x1_s2"])
def test_resnet50_pointwise_conv_gemm_route_compiles_for_v5e(name, one_chip):
    """A pointwise conv as the served program runs it: planned as a GEMM
    on the 16-core paper machine, a tiled segment subsampling by the
    stride, flattening to (oh·ow, C_in) (M = 3025 or 784, neither a
    multiple of the block), the GEMM kernel with its fused requant,
    batched over 4 frames."""
    from repro.core.graph import Graph, conv2d, requant
    from repro.kernels.gemm_int8 import gemm_kernel_name
    shape, stride, c_out = {"s0_c3_1x1": ((55, 55, 64), 1, 256),
                            "s1_ds_1x1_s2": ((55, 55, 256), 2, 512)}[name]
    g = Graph(name)
    g.add_tensor("input", shape, "int8", is_input=True)
    g.mark_output(requant(g, "pw.rq", conv2d(g, "pw", "input", c_out, 1,
                                             stride=stride)))
    g.validate()
    rep, sched, subtasks, mapping = analyze(g, HW, num_cores=16)
    prog = lower_program(g, init_params(g, seed=0), subtasks, mapping,
                         sched, hw=HW)
    (seg,) = MK.plan_segments(prog)
    assert seg.kind == "tiled" and seg.steps[0].mode == "gemm"
    M = ((shape[0] - 1) // stride + 1) ** 2
    assert seg.steps[0].gemm == (M, shape[2], c_out, stride)
    fn = jax.vmap(MK.megakernel_fn(prog, interpret=False), in_axes=(None, 0))
    weights, x = _operands(prog, one_chip, (4,))
    assert x["input"].shape == (4,) + shape
    compiled = jax.jit(fn).lower(weights, x).compile()
    assert _n_kernels(compiled) == 1
    assert gemm_kernel_name(M, shape[2], c_out) in compiled.as_text()


def test_resnet50_fc_gemm_fused_requant_compiles_for_v5e(one_chip):
    """The tiled GEMM kernel with a fused requant epilogue at the
    classifier's width (1 x 2048 @ 2048 x 1000)."""
    fn = functools.partial(gemm_int8_pallas, bm=256, bn=256, bk=256)
    compiled = jax.jit(fn).lower(
        _spec((1, 2048), jnp.int8, one_chip),
        _spec((2048, 1000), jnp.int8, one_chip),
        _spec((1000,), jnp.float32, one_chip)).compile()
    assert _n_kernels(compiled) == 1


@pytest.mark.parametrize("preset", ["resnet50_32_w025", "yolov5s_64_w025"])
def test_fused_segments_compile_for_v5e(preset, one_chip):
    """Fused segments (the megakernel body: in-kernel convs, pools, gap,
    concat, residual adds, requant epilogues) of reduced networks whose
    whole program fits a few scratchpads."""
    g, shape = {
        "resnet50_32_w025": (cnn.resnet50(h=32, w=32, width=0.25,
                                          blocks=(1, 1, 1, 1),
                                          num_classes=16), (32, 32, 3)),
        "yolov5s_64_w025": (cnn.yolov5s_backbone(h=64, w=64, width=0.25),
                            (64, 64, 3)),
    }[preset]
    hw = scaled_paper_machine(4)
    rep, sched, subtasks, mapping = analyze(g, hw, num_cores=4)
    prog = lower_program(g, init_params(g, seed=1), subtasks, mapping,
                         sched, hw=hw)
    segments = MK.plan_segments(prog)
    assert any(s.kind == "fused" for s in segments)
    weights, x = _operands(prog, one_chip)
    compiled = jax.jit(MK.megakernel_fn(prog, interpret=False)).lower(
        weights, x).compile()
    assert _n_kernels(compiled) == sum(s.emits_call for s in segments)


@pytest.mark.parametrize("frame,k,p,n", [((640, 640, 3), 6, 2, 32),
                                         ((224, 224, 3), 7, 3, 64)])
def test_stem_kernel_compiles_for_v5e(frame, k, p, n, one_chip):
    """The stem kernel at YOLOv5s's 6×6/2 (640×640×3 → 32) and
    ResNet-50's 7×7/2 (224×224×3 → 64), with its fused requant, batched
    over 4 frames as the served program runs it: row groups in (a minor
    dim of 3840 or 1344 lanes, the latter a partial 128-lane block), the
    banded weights, one Mosaic kernel under the stem's name."""
    H, W, C = frame
    geom = stem_geometry(H, W, C, n, kh=k, kw=k, stride=2, padding=p)
    fn = jax.vmap(functools.partial(stem_conv_pallas, geom=geom),
                  in_axes=(0, None, None))
    compiled = jax.jit(fn).lower(
        _spec((4,) + stem_input_shape(H, W, C, 2), jnp.int8, one_chip),
        _spec((geom.k_total, len(geom.qs) * geom.n_blk), jnp.int8,
              one_chip),
        _spec((n,), jnp.float32, one_chip)).compile()
    assert _n_kernels(compiled) == 1
    assert stem_kernel_name(geom) in compiled.as_text()
