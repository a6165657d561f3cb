"""Pallas TPU kernel: a narrow-input int8 conv over lane-dense rows (the
network's stem).

A frame of 3 channels in the (H, W, C) layout spreads each pixel's 3 bytes
over the chip's 128 lanes: the host's copy must pad it out (the device's
tiled layout of the two minor dims), and the windowed conv kernel
(`conv2d_im2col`) pads it again and runs every tap as an MXU dot with K = C.
This kernel reads the frame as the C-contiguous bytes it already is, in
*row groups*: `(H / s, s·W·C)`, where row group r holds frame rows
r·s … r·s + s − 1 back to back and s is the conv's stride. That shape is a
free reshape of the host's batch, and its minor dim is lane-dense.

Output row i reads frame rows i·s − p + di (di < kh), which lie in row
groups i + q for q = ⌊(di − p) / s⌋: every output row sees the same few row
groups at the same offsets, so no strided row access is needed. Output
columns are computed in blocks of `bo` (bo·N lanes). A block's windows
touch a few 128-lane chunks of a row group; the kernel contracts those
chunks (K ≥ 128) against a banded, block-Toeplitz expansion of the weights,
one (K, nq·bo·N) matrix whose column group q holds the taps that read row
group i + q, and adds the partial product of group q to the output shifted
by q rows. Blocks whose chunks sit at the same offsets share one band; the
bands are built once on the host (`stem_bands`), when the program's
weights are put on the device.

Zero padding is what the band leaves out: columns outside the frame have
no entries, and rows outside it are the rows the shift does not reach. No
padded copy is made. The products are the conv's own int8 products,
accumulated exactly in int32, so the result is bit-identical to the
windowed kernel's; the requant epilogue is the shared `requant_epilogue`.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import vmem
from .gemm_int8 import dot_i32_exact, requant_epilogue
from .ref import _as_channel_mult

LANES = 128

# output lanes a column block aims at: bo·N of at least this many
_BLOCK_LANES = 512


def _up(n: int, m: int) -> int:
    return -(-n // m) * m


def fits_stem(H: int, C: int, kw: int, stride: int) -> bool:
    """Whether a conv's input can be read as row groups: a window row
    (`C · kw` lanes) within one 128-lane row, and whole row groups."""
    return C * kw <= LANES and H % stride == 0


@dataclasses.dataclass(frozen=True)
class StemGeometry:
    """The shapes of one stem conv on the lane-dense layout.

    rows, lanes: the input's row groups (H / s, s·W·C); lanes_p rounds
    lanes up to whole 128-lane rows. qs: the row-group offsets an output row
    reads. bo output columns make a block of n_blk output lanes; blocks[jb]
    is (lane runs, k0): the [a, b) lane ranges of a row group it contracts
    and the first row of its band among the k_total rows of the bands."""

    H: int
    W: int
    C: int
    N: int
    kh: int
    kw: int
    stride: int
    padding: int
    oh: int
    ow: int
    rows: int
    lanes: int
    lanes_p: int
    qs: tuple
    bo: int
    n_blk: int
    blocks: tuple
    k_total: int

    @property
    def out_lanes(self) -> int:
        return len(self.blocks) * self.n_blk

    @property
    def pad_top(self) -> int:
        """Rows above the partial products in their scratch: the most
        negative offset, rounded to a whole 8-row sublane tile."""
        return _up(max(0, -self.qs[0]), 8)

    @property
    def scratch_rows(self) -> int:
        return self.pad_top + _up(max(self.rows, self.oh + self.qs[-1]), 8)


def _band_index(g: dict, runs, j0: int) -> np.ndarray:
    """(K, nq, bo) weight-row index of a block's band: the row of the
    (kh·kw·C, N) weight that lane k of the block's chunks meets in column
    group q at output column j0 + jrel, or -1."""
    s, p, C, W = g["stride"], g["padding"], g["C"], g["W"]
    lane = np.concatenate([np.arange(a, b) for a, b in runs])
    h, rem = np.divmod(lane, W * C)
    col, c = np.divmod(rem, C)
    q = np.array(g["qs"])[None, :, None]
    j = j0 + np.arange(g["bo"])[None, None, :]
    di = s * q + h[:, None, None] + p
    dj = col[:, None, None] - j * s + p
    ok = ((lane < g["lanes"])[:, None, None] & (di >= 0) & (di < g["kh"])
          & (dj >= 0) & (dj < g["kw"]) & (j < g["ow"]))
    idx = (di * g["kw"] + dj) * C + c[:, None, None]
    return np.where(ok, idx, -1)


@functools.lru_cache(maxsize=None)
def _plan(H: int, W: int, C: int, N: int, kh: int, kw: int, stride: int,
          padding: int):
    s, p = stride, padding
    oh = (H + 2 * p - kh) // s + 1
    ow = (W + 2 * p - kw) // s + 1
    lanes = s * W * C
    qs = tuple(range(-p // s, (kh - 1 - p) // s + 1))
    # the fewest columns whose lanes are whole 128-lane rows, times what
    # reaches _BLOCK_LANES; one block when the row is narrower
    bo0 = math.lcm(N, LANES) // N
    bo = bo0 * max(1, _BLOCK_LANES // (bo0 * N))
    if bo >= ow:
        bo = ow
    g = dict(W=W, C=C, kh=kh, kw=kw, stride=s, padding=p, ow=ow, qs=qs,
             bo=bo, lanes=lanes)
    blocks, bands, seen, k_total = [], [], {}, 0
    for j0 in range(0, ow, bo):
        j1 = min(ow, j0 + bo)
        lo = max(0, j0 * s - p) * C
        hi = min(W, (j1 - 1) * s - p + kw) * C
        chunks = sorted({k for h in range(s) if hi > lo
                         for k in range((h * W * C + lo) // LANES,
                                        _up(h * W * C + hi, LANES) // LANES)})
        runs = []
        for k in chunks:
            if runs and runs[-1][1] == k * LANES:
                runs[-1][1] = (k + 1) * LANES
            else:
                runs.append([k * LANES, (k + 1) * LANES])
        runs = tuple(tuple(r) for r in runs)
        idx = _band_index(g, runs, j0)
        key = (idx.shape, idx.tobytes())
        if key not in seen:
            seen[key] = k_total
            k_total += idx.shape[0]
            bands.append(idx)
        blocks.append((runs, seen[key]))
    geom = StemGeometry(
        H=H, W=W, C=C, N=N, kh=kh, kw=kw, stride=s, padding=p, oh=oh, ow=ow,
        rows=H // s, lanes=lanes, lanes_p=_up(lanes, LANES), qs=qs, bo=bo,
        n_blk=_up(bo * N, LANES), blocks=tuple(blocks), k_total=k_total)
    return geom, np.concatenate(bands)


def stem_geometry(H: int, W: int, C: int, N: int, *, kh: int, kw: int,
                  stride: int, padding: int) -> StemGeometry:
    """The geometry of a stem conv (cached per shape)."""
    return _plan(H, W, C, N, kh, kw, stride, padding)[0]


def stem_input_shape(H: int, W: int, C: int, stride: int) -> tuple:
    """The per-frame shape the stem reads its (H, W, C) input in: row
    groups of `stride` frame rows, a reshape of the C-contiguous frame."""
    return (H // stride, stride * W * C)


def stem_bands(w: np.ndarray, geom: StemGeometry) -> np.ndarray:
    """The (k_total, nq·n_blk) int8 band expansion of a (kh·kw·C, N) conv
    weight, the stem kernel's weight operand: its distinct bands stacked
    along K."""
    index = _plan(geom.H, geom.W, geom.C, geom.N, geom.kh, geom.kw,
                  geom.stride, geom.padding)[1]
    w = np.asarray(w, np.int8)
    t = np.where((index >= 0)[..., None], w[np.maximum(index, 0)], 0)
    t = t.reshape(t.shape[:2] + (geom.bo * geom.N,))
    t = np.pad(t, ((0, 0), (0, 0), (0, geom.n_blk - geom.bo * geom.N)))
    return t.reshape(geom.k_total, -1).astype(np.int8)


def stem_kernel_name(geom: StemGeometry) -> str:
    """The kernel's name in the compiled program and the profiler's trace:
    the conv and the row-group shape it reads."""
    return (f"stem{geom.kh}x{geom.kw}s{geom.stride}p{geom.padding}_"
            f"{geom.rows}x{geom.lanes}_{geom.N}")


def stem_vmem_bytes(geom: StemGeometry, requant: bool) -> int:
    """VMEM the stem kernel holds: double-buffered input, band, multiplier
    and output blocks, the partial-product scratch, and a block's operand,
    product and accumulator values."""
    nq = len(geom.qs)
    k_blk = max(sum(b - a for a, b in runs) for runs, _ in geom.blocks)
    blocks = (vmem.tile_bytes((geom.rows, geom.lanes_p), 1)
              + vmem.tile_bytes((geom.k_total, nq * geom.n_blk), 1)
              + vmem.tile_bytes((1, geom.n_blk), 4)
              + vmem.tile_bytes((geom.oh, geom.out_lanes),
                                1 if requant else 4))
    values = (vmem.tile_bytes((geom.scratch_rows, nq * geom.n_blk), 4)
              + vmem.tile_bytes((geom.rows, k_blk), 1)
              + vmem.tile_bytes((geom.rows, nq * geom.n_blk), 4)
              + 2 * vmem.tile_bytes((geom.oh, geom.n_blk), 4))
    return 2 * blocks + values


def _make_kernel(geom: StemGeometry, requant: bool, via_f32: bool):
    top, rows, oh, n_blk = geom.pad_top, geom.rows, geom.oh, geom.n_blk

    def kernel(x_ref, t_ref, *refs):
        # x_ref: (rows, lanes_p) int8 row groups (lanes past `lanes` are
        #        whatever the block holds: every band row there is 0)
        # t_ref: (k_total, nq·n_blk) int8 bands
        # [m_ref: (1, n_blk) f32 requant multiplier, tiled over columns]
        # o_ref: (oh, blocks·n_blk) int8 (int32 without requant)
        # p_ref: int32 partial products, zero rows above and below
        o_ref, p_ref = refs[-2], refs[-1]
        width = p_ref.shape[1]
        if top:
            p_ref[:top, :] = jnp.zeros((top, width), jnp.int32)
        if geom.scratch_rows > top + rows:
            p_ref[top + rows:, :] = jnp.zeros(
                (geom.scratch_rows - top - rows, width), jnp.int32)
        for jb, (runs, k0) in enumerate(geom.blocks):
            parts = [x_ref[:, a:b] for a, b in runs]
            lhs = parts[0] if len(parts) == 1 else \
                jnp.concatenate(parts, axis=1)
            k = lhs.shape[1]
            p_ref[top:top + rows, :] = dot_i32_exact(
                lhs, t_ref[k0:k0 + k, :], via_f32=via_f32)
            acc = None
            for n, q in enumerate(geom.qs):
                part = p_ref[top + q:top + q + oh,
                             n * n_blk:(n + 1) * n_blk]
                acc = part if acc is None else acc + part
            out = requant_epilogue(acc, refs[0][...]) if requant else acc
            o_ref[:, jb * n_blk:(jb + 1) * n_blk] = out
    return kernel


@functools.partial(jax.jit, static_argnames=("geom", "interpret"))
def stem_conv_pallas(x: jax.Array, bands: jax.Array,
                     requant_mult: jax.Array | None = None, *,
                     geom: StemGeometry, interpret: bool = False
                     ) -> jax.Array:
    """x (H/s, s·W·C) int8 row groups, bands from `stem_bands` ->
    (oh, ow, N) int32, or int8 with `requant_mult` (scalar or per-channel
    (N,)) folded in by the epilogue."""
    assert x.shape == (geom.rows, geom.lanes), (x.shape, geom)
    fused = requant_mult is not None
    operands = [x, bands]
    in_specs = [pl.BlockSpec((geom.rows, geom.lanes_p), lambda i: (0, 0)),
                pl.BlockSpec(bands.shape, lambda i: (0, 0))]
    if fused:
        mult = _as_channel_mult(requant_mult, geom.N)
        mult = jnp.pad(jnp.tile(mult, geom.bo),
                       (0, geom.n_blk - geom.bo * geom.N))
        operands.append(mult.reshape(1, geom.n_blk))
        in_specs.append(pl.BlockSpec((1, geom.n_blk), lambda i: (0, 0)))
    out = pl.pallas_call(
        _make_kernel(geom, fused, via_f32=interpret),
        grid=(1,),       # a grid: the input's block may overhang its lanes
        in_specs=in_specs,
        out_specs=pl.BlockSpec((geom.oh, geom.out_lanes), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct(
            (geom.oh, geom.out_lanes), jnp.int8 if fused else jnp.int32),
        scratch_shapes=[pltpu.VMEM(
            (geom.scratch_rows, len(geom.qs) * geom.n_blk), jnp.int32)],
        compiler_params=vmem.compiler_params(stem_vmem_bytes(geom, fused)),
        interpret=interpret,
        name=stem_kernel_name(geom),
    )(*operands)
    return out[:, :geom.ow * geom.N].reshape(geom.oh, geom.ow, geom.N)
