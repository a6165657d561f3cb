"""Pallas TPU kernel: int8 x int8 -> int32 tiled GEMM with optional fused
per-channel requantization epilogue.

This is the paper's worker-core inner loop, re-targeted from Vicuna
(512-bit vector registers, Zve32x int8 MACs, 1 MiB scratchpad) to the TPU
MXU (128x128 systolic, int8 path at 2x bf16 rate, VMEM scratchpad):

  * BlockSpec tiling (bm, bn, bk) is the TPU analogue of the compiler's
    scratchpad GEMM tiles — HBM->VMEM streaming with double buffering is
    emitted by the Pallas grid pipeline, exactly the dual-ported-scratchpad
    DMA overlap the paper builds in hardware.
  * accumulation stays in an int32 VMEM scratch tile across the K grid
    dimension (paper: int32 accumulators in the vector registers).
  * the epilogue folds the int32 tile to int8 via the same fixed-point
    requant math as `repro.core.quantize.requantize` (bit-exact).

Block shapes default to MXU-aligned (128, 128, 128) and are rounded to
what Mosaic tiles (`_block`); the call states its scoped-VMEM limit from
the double-buffered x/w/out blocks plus the int32 accumulator
(`kernels.vmem`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import vmem
from .ref import _as_channel_mult


def _gemm_kernel(x_ref, w_ref, o_ref, acc_ref):
    """Grid (Mi, Nj, Kk); K innermost -> acc tile lives across K steps."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        x_ref[...], w_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)

    @pl.when(k == pl.num_programs(2) - 1)
    def _store():
        o_ref[...] = acc_ref[...]


# largest K for which <= K partial sums of int8 products (each <= 2^14)
# stay exactly representable in float32 (K * 2^14 <= 2^24)
_F32_EXACT_K = 1024


def dot_i32_exact(x: jax.Array, w: jax.Array, *,
                  via_f32: bool = False) -> jax.Array:
    """int8-valued (M, K) @ (K, N) -> exact int32, value-level.

    Usable inside Pallas kernel bodies (operates on values, not refs).
    With ``via_f32=False`` this is the MXU int8 contraction
    (``preferred_element_type=int32``) — the deployment path. With
    ``via_f32=True`` the contraction runs in float32, chunked along K so
    every partial sum stays exactly representable (products <= 2^14, at
    most ``_F32_EXACT_K`` summands < 2^24): the same exactness argument as
    ``repro.core.compiled.gemm_i32_exact``, but inside a kernel, where the
    f32 dot hits the fast vector path under Pallas interpret mode on CPU.
    """
    dn = (((1,), (0,)), ((), ()))
    if not via_f32:
        return jax.lax.dot_general(x, w, dn,
                                   preferred_element_type=jnp.int32)
    K = x.shape[1]
    xf = x.astype(jnp.float32)
    wf = w.astype(jnp.float32)
    if K <= _F32_EXACT_K:
        return jax.lax.dot_general(
            xf, wf, dn,
            preferred_element_type=jnp.float32).astype(jnp.int32)
    acc = jnp.zeros((x.shape[0], w.shape[1]), jnp.int32)
    for k0 in range(0, K, _F32_EXACT_K):
        k1 = min(K, k0 + _F32_EXACT_K)
        acc = acc + jax.lax.dot_general(
            xf[:, k0:k1], wf[k0:k1], dn,
            preferred_element_type=jnp.float32).astype(jnp.int32)
    return acc


def requant_epilogue(acc: jax.Array, mult: jax.Array,
                     dtype=jnp.int8) -> jax.Array:
    """int32 accumulator tile -> int8, the repo's single requant definition.

    float32 multiply + round-half-even + saturate. `jnp.round` rounds halves
    to even, so this is bit-identical to `kernels.ref.gemm_int8`'s requant
    path, `quantize.requantize`, the executor's `_requant_np` (np.round is
    also half-even), and the integer-exact `kernels.ref.round_half_even_div`
    semantics on exact-half quotients. Shared by the GEMM and conv kernels
    so the fused epilogue can never drift from the oracle. `dtype=int32`
    returns the same int8-ranged values widened, for kernel bodies that
    keep their values in 32-bit layouts.
    """
    y = jnp.round(acc.astype(jnp.float32) * mult)
    return jnp.clip(y, -128, 127).astype(dtype)


def _gemm_requant_kernel(x_ref, w_ref, m_ref, o_ref, acc_ref):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        x_ref[...], w_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)

    @pl.when(k == pl.num_programs(2) - 1)
    def _store():
        o_ref[...] = requant_epilogue(acc_ref[...], m_ref[...])


def _block(b: int, dim: int, align: int) -> int:
    """A block extent Mosaic accepts along an axis of size `dim`: the
    whole axis, or `b` rounded up to the layout tile `align`."""
    if b >= dim:
        return dim
    return min(dim, -(-b // align) * align)


def gemm_blocks(M: int, K: int, N: int, bm: int, bn: int, bk: int
                ) -> tuple[int, int, int]:
    """The (bm, bn, bk) the GEMM kernel runs for requested blocks: each
    rounded to the int8 layout tile (32 rows, 128 lanes) or the whole
    axis."""
    return _block(bm, M, 32), _block(bn, N, 128), _block(bk, K, 128)


def gemm_vmem_bytes(M: int, K: int, N: int, *, bm: int, bn: int, bk: int,
                    requant: bool) -> int:
    """VMEM the GEMM kernel holds: double-buffered x, w, multiplier and
    output blocks, the int32 accumulator scratch and one partial product."""
    bm, bn, bk = gemm_blocks(M, K, N, bm, bn, bk)
    blocks = (vmem.tile_bytes((bm, bk), 1) + vmem.tile_bytes((bk, bn), 1)
              + vmem.tile_bytes((1, bn), 4)
              + vmem.tile_bytes((bm, bn), 1 if requant else 4))
    return 2 * blocks + 2 * vmem.tile_bytes((bm, bn), 4)


def gemm_kernel_name(M: int, K: int, N: int) -> str:
    """The kernel's name in the compiled program and the profiler's trace:
    its shape, so GEMMs of one shape share one kernel and one name."""
    return f"gemm_{M}x{K}x{N}"


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def gemm_int8_pallas(x: jax.Array, w: jax.Array,
                     requant_mult: jax.Array | None = None,
                     *, bm: int = 128, bn: int = 128, bk: int = 128,
                     interpret: bool = False) -> jax.Array:
    """x (M,K) int8 @ w (K,N) int8 -> int32 (or int8 if requant_mult given).

    Shapes are padded to block multiples; padding contributes zeros to the
    accumulator so results are exact. `requant_mult` may be a scalar or a
    per-channel (N,) vector (both broadcast, as in `quantize.requantize`).
    Block shapes can be derived from a scratchpad budget with
    `repro.hw.derive_gemm_blocks` (the compiled executor's pallas backend
    does exactly that); each is rounded to the int8 layout tile (32 rows,
    128 lanes) or clamped to the whole axis.
    """
    M, K = x.shape
    K2, N = w.shape
    assert K == K2, (x.shape, w.shape)
    if requant_mult is not None:
        requant_mult = _as_channel_mult(requant_mult, N)
    bm_, bn_, bk_ = gemm_blocks(M, K, N, bm, bn, bk)
    Mp, Np, Kp = -(-M // bm_) * bm_, -(-N // bn_) * bn_, -(-K // bk_) * bk_
    xp = jnp.pad(x, ((0, Mp - M), (0, Kp - K)))
    wp = jnp.pad(w, ((0, Kp - K), (0, Np - N)))
    grid = (Mp // bm_, Np // bn_, Kp // bk_)
    params = vmem.compiler_params(gemm_vmem_bytes(
        M, K, N, bm=bm, bn=bn, bk=bk, requant=requant_mult is not None))

    if requant_mult is None:
        out = pl.pallas_call(
            _gemm_kernel,
            grid=grid,
            in_specs=[pl.BlockSpec((bm_, bk_), lambda i, j, k: (i, k)),
                      pl.BlockSpec((bk_, bn_), lambda i, j, k: (k, j))],
            out_specs=pl.BlockSpec((bm_, bn_), lambda i, j, k: (i, j)),
            out_shape=jax.ShapeDtypeStruct((Mp, Np), jnp.int32),
            scratch_shapes=[pltpu.VMEM((bm_, bn_), jnp.int32)],
            compiler_params=params,
            interpret=interpret, name=gemm_kernel_name(M, K, N),
        )(xp, wp)
    else:
        mp = jnp.pad(requant_mult.astype(jnp.float32), (0, Np - N))
        out = pl.pallas_call(
            _gemm_requant_kernel,
            grid=grid,
            in_specs=[pl.BlockSpec((bm_, bk_), lambda i, j, k: (i, k)),
                      pl.BlockSpec((bk_, bn_), lambda i, j, k: (k, j)),
                      pl.BlockSpec((1, bn_), lambda i, j, k: (0, j))],
            out_specs=pl.BlockSpec((bm_, bn_), lambda i, j, k: (i, j)),
            out_shape=jax.ShapeDtypeStruct((Mp, Np), jnp.int8),
            scratch_shapes=[pltpu.VMEM((bm_, bn_), jnp.int32)],
            compiler_params=params,
            interpret=interpret, name=gemm_kernel_name(M, K, N),
        )(xp, wp, mp.reshape(1, Np))
    return out[:M, :N]
