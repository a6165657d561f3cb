"""Pallas TPU kernel: int8 conv2d with *implicit* im2col.

The paper's rule — "the actual duplication of memory is only carried out in
the scratchpad" — adapted one step further for TPU: the duplication never
materializes at all. The kernel keeps the raw NHWC input band in VMEM and
accumulates kh*kw shifted (strided) GEMMs against the corresponding
weight slabs, so HBM traffic is the raw band and VMEM holds only the raw
band + weight tile + int32 accumulator.

Grid: (output-row bands, output-channel tiles). Each band (with its halo) is
streamed per grid step; Pallas double-buffers the band transfer against the
previous step's compute (the paper's dual-ported scratchpad).

What Mosaic (v5e) accepts shapes the tap reads: a strided *value* slice is
refused, and strided loads exist only for 32-bit refs whose last dim fits
one 128-lane row. So the band is widened to int32 once into a chunk-major
VMEM scratch (`fill_window`) and every tap is a strided ref load per
128-channel chunk (`tap_windows`). Window reshapes happen on the int32
values; each window narrows to int8 right before its MXU dot. The megakernel's fused
segments window their scratchpad-resident values through the same
helpers (`conv_accumulate`), so both paths share one tap order and one
exact int8 contraction.

A network's stem, a conv that alone reads a frame of few channels, does
not come here: with 3 channels every tap would be a dot with K = 3 over a
frame padded to 128 lanes. The plan gives it the stem kernel
(`kernels.stem_conv`), which reads the frame as lane-dense row groups.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import vmem
from .gemm_int8 import _block, dot_i32_exact, requant_epilogue
from .ref import _as_channel_mult


LANES = 128


def lane_chunks(c: int) -> list[tuple[int, int]]:
    """[c0, c1) channel ranges of at most one 128-lane vreg width: Mosaic
    strided-loads only refs whose last dim fits one lane row, so windowed
    copies keep channels chunk-major."""
    return [(c0, min(c, c0 + LANES)) for c0 in range(0, c, LANES)]


def window_scratch(hp: int, wp: int, c: int):
    """The chunk-major padded int32 VMEM copy (n_chunks, hp, wp, lanes) a
    conv or pool windows over."""
    return pltpu.VMEM((len(lane_chunks(c)), hp, wp, min(c, LANES)),
                      jnp.int32)


def fill_window(src, x, pad: int, fill: int | None = None) -> None:
    """Write the int32 (H, W, C) value or int8 ref `x` into the scratch
    `src` at offset (pad, pad), chunk by chunk, the border set to `fill`
    (None: `x` covers the whole scratch)."""
    if fill is not None:
        src[...] = jnp.full(src.shape, fill, jnp.int32)
    x = x[...].astype(jnp.int32)     # whole loads: no unaligned ref slices
    H, W, C = x.shape
    for k, (c0, c1) in enumerate(lane_chunks(C)):
        src[k, pad:pad + H, pad:pad + W, :c1 - c0] = x[:, :, c0:c1]


def tap_windows(src, c: int, kh: int, kw: int, stride: int, oh: int,
                ow: int):
    """Yield ((di, dj), chunks) for every kernel tap, where chunks lists
    (c0, c1, window): the (oh, ow, c1 - c0) int32 window of channel chunk
    [c0, c1) that tap (di, dj) reads from the scratch `src`, as one
    strided ref load."""
    for di in range(kh):
        for dj in range(kw):
            yield (di, dj), [
                (c0, c1, src[k, pl.ds(di, oh, stride=stride),
                             pl.ds(dj, ow, stride=stride), :c1 - c0])
                for k, (c0, c1) in enumerate(lane_chunks(c))]


def conv_accumulate(src, w, c: int, kh: int, kw: int, stride: int, oh: int,
                    ow: int, via_f32: bool = False) -> jax.Array:
    """(oh*ow, N) exact int32 accumulator of a conv over the scratch
    `src`; `w` is the (kh*kw, C, N) int8 weight ref, tap-major — the
    (kh*kw*C, N) conv weight layout with the tap axis split off."""
    acc = None
    for (di, dj), chunks in tap_windows(src, c, kh, kw, stride, oh, ow):
        for c0, c1, win in chunks:
            x = win.reshape(oh * ow, c1 - c0).astype(jnp.int8)
            part = dot_i32_exact(x, w[di * kw + dj, c0:c1, :],
                                 via_f32=via_f32)
            acc = part if acc is None else acc + part
    return acc


def _make_kernel(kh: int, kw: int, stride: int, rows_t: int, ow: int,
                 requant: bool, via_f32: bool):
    def kernel(x_ref, w_ref, *refs):
        # x_ref: (1, in_rows, Wp, C) int8 raw band (halo included)
        # w_ref: (kh*kw, C, bn) int8
        # [m_ref: (1, bn) f32 requant multiplier, if fused]
        # o_ref: (rows_t, ow, bn) int32 (int8 if fused requant)
        # xs:    chunk-major int32 copy of the band the taps window over
        o_ref, xs = refs[-2], refs[-1]
        fill_window(xs, x_ref.at[0], 0)
        acc = conv_accumulate(xs, w_ref, x_ref.shape[-1], kh, kw, stride,
                              rows_t, ow, via_f32=via_f32)
        acc = acc.reshape(rows_t, ow, acc.shape[-1])
        if requant:
            o_ref[...] = requant_epilogue(acc, refs[0][...])
        else:
            o_ref[...] = acc
    return kernel


def _conv_geometry(H: int, W: int, kh: int, kw: int, stride: int,
                   padding: int, rows_t: int) -> tuple:
    """(oh, ow, rows_t, oh_p, in_rows, wp) of the banded conv: output
    dims, the band height clamped to oh, the padded output height, the
    input rows a band reads (halo included) and the padded input width
    (every tap in range, rounded to whole 8-row sublane tiles)."""
    oh = (H + 2 * padding - kh) // stride + 1
    ow = (W + 2 * padding - kw) // stride + 1
    rows_t = min(rows_t, oh)
    oh_p = -(-oh // rows_t) * rows_t
    in_rows = (rows_t - 1) * stride + kh
    wp = -(-max(W + 2 * padding, (ow - 1) * stride + kw) // 8) * 8
    return oh, ow, rows_t, oh_p, in_rows, wp


def conv2d_vmem_bytes(H: int, W: int, C: int, N: int, *, kh: int, kw: int,
                      stride: int, padding: int, rows_t: int, bn: int,
                      requant: bool) -> int:
    """VMEM the tiled conv kernel holds: double-buffered band, weight,
    multiplier and output blocks, the int32 band scratch, and the
    accumulator, one tap window and its partial product."""
    _, ow, rows_t, _, in_rows, wp = _conv_geometry(H, W, kh, kw, stride,
                                                   padding, rows_t)
    bn = _block(bn, N, 128)
    m = rows_t * ow
    blocks = (vmem.tile_bytes((in_rows, wp, C), 1)
              + vmem.tile_bytes((kh * kw, C, bn), 1)
              + vmem.tile_bytes((1, bn), 4)
              + vmem.tile_bytes((rows_t, ow, bn), 1 if requant else 4))
    values = (vmem.tile_bytes(window_scratch(in_rows, wp, C).shape, 4)
              + 2 * vmem.tile_bytes((m, bn), 4)
              + vmem.tile_bytes((m, C), 4) + vmem.tile_bytes((m, C), 1))
    return 2 * blocks + values


def conv2d_kernel_name(H: int, W: int, C: int, N: int, *, kh: int, kw: int,
                       stride: int, padding: int) -> str:
    """The kernel's name in the compiled program and the profiler's trace:
    its geometry, so a trace ties the kernel's time to the layers of that
    shape, and convolutions of one shape share one kernel."""
    return f"conv{kh}x{kw}s{stride}p{padding}_{H}x{W}x{C}_{N}"


@functools.partial(jax.jit, static_argnames=(
    "kh", "kw", "stride", "padding", "rows_t", "bn", "interpret"))
def conv2d_int8_pallas(x: jax.Array, w: jax.Array,
                       requant_mult: jax.Array | None = None,
                       *, kh: int, kw: int,
                       stride: int = 1, padding: int = 0,
                       rows_t: int = 8, bn: int = 128,
                       interpret: bool = False) -> jax.Array:
    """x (H,W,C) int8, w (kh*kw*C, N) int8 -> (oh, ow, N) int32.

    With `requant_mult` (scalar or per-channel (N,)) the int32 accumulator
    is folded to int8 in the kernel epilogue (`requant_epilogue` — the same
    round-half-even contract as the GEMM kernel and `kernels.ref`), so the
    int32 tensor never leaves VMEM. Block shapes (rows_t, bn) can be derived
    from a scratchpad budget with `repro.hw.derive_conv_blocks`; `bn` is
    rounded to whole 128-lane tiles (or the whole channel axis).
    """
    H, W, C = x.shape
    KKC, N = w.shape
    assert KKC == kh * kw * C
    oh, ow, rows_t, oh_p, in_rows_t, wp_ = _conv_geometry(
        H, W, kh, kw, stride, padding, rows_t)
    bn_ = _block(bn, N, 128)
    Np = -(-N // bn_) * bn_
    # pad input so every band's halo slice is in range
    need_rows = (oh_p - 1) * stride + kh
    xp = jnp.pad(x, ((padding, max(0, need_rows - H - padding)),
                     (padding, wp_ - W - padding), (0, 0)))
    wp = jnp.pad(w.reshape(kh * kw, C, N), ((0, 0), (0, 0), (0, Np - N)))
    # bands overlap by the halo; BlockSpec blocks cannot overlap, so the
    # wrapper materializes per-band views (XLA fuses the gather with the
    # HBM->VMEM stream; on the paper machine this is the raw-band DMA)
    starts = jnp.arange(oh_p // rows_t) * (rows_t * stride)
    bands = jax.vmap(
        lambda s: jax.lax.dynamic_slice(
            xp, (s, 0, 0), (in_rows_t, xp.shape[1], C)))(starts)

    fused = requant_mult is not None
    kernel = _make_kernel(kh, kw, stride, rows_t, ow, requant=fused,
                          via_f32=interpret)
    in_specs = [
        pl.BlockSpec((1, in_rows_t, xp.shape[1], C),
                     lambda i, j: (i, 0, 0, 0)),
        pl.BlockSpec((kh * kw, C, bn_), lambda i, j: (0, 0, j)),
    ]
    operands = [bands, wp]
    if fused:
        mult = _as_channel_mult(requant_mult, N)
        operands.append(jnp.pad(mult, (0, Np - N)).reshape(1, Np))
        in_specs.append(pl.BlockSpec((1, bn_), lambda i, j: (0, j)))
    out = pl.pallas_call(
        kernel,
        grid=(oh_p // rows_t, Np // bn_),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((rows_t, ow, bn_), lambda i, j: (i, 0, j)),
        out_shape=jax.ShapeDtypeStruct(
            (oh_p, ow, Np), jnp.int8 if fused else jnp.int32),
        scratch_shapes=[window_scratch(in_rows_t, xp.shape[1], C)],
        compiler_params=vmem.compiler_params(conv2d_vmem_bytes(
            H, W, C, N, kh=kh, kw=kw, stride=stride, padding=padding,
            rows_t=rows_t, bn=bn, requant=fused)),
        interpret=interpret,
        name=conv2d_kernel_name(H, W, C, N, kh=kh, kw=kw, stride=stride,
                                padding=padding),
    )(*operands)
    return out[:oh, :, :N]
