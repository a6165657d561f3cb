"""VMEM accounting for the Pallas kernels.

Every `pallas_call` in the package states its scoped-VMEM limit
(`pltpu.CompilerParams(vmem_limit_bytes=...)`) instead of relying on the
compiler's default. The limit is derived from what the kernel keeps in
VMEM, counted in the chip's tiled layout: an array's lane (last) dim
rounds up to 128 and its sublane (second-to-last) dim to the dtype's tile
height — 8 rows of 32-bit, 16 of 16-bit, 32 of 8-bit values. A (7, 3)
int8 block therefore occupies a whole (32, 128) tile.

The cap is the physical VMEM of the chip the kernels target
(`hw.TPU_V5E.scratchpad_bytes`, 128 MiB on a v5e); a kernel that would
need more raises instead of compiling into a spill or a refusal.
"""

from __future__ import annotations

import math

from jax.experimental.pallas import tpu as pltpu

from ..hw import TPU_V5E

CAPACITY = TPU_V5E.scratchpad_bytes

# Mosaic's own VMEM beyond the counted buffers and values: relayout
# copies, spilled vregs, the pipeline's semaphores
HEADROOM = 4 << 20


def _up(n: int, m: int) -> int:
    return -(-int(n) // m) * m


def tile_bytes(shape, itemsize: int) -> int:
    """Bytes one array of `shape` occupies in VMEM's tiled layout."""
    shape = tuple(int(d) for d in shape) or (1,)
    if len(shape) == 1:
        shape = (1,) + shape
    *lead, sub, lane = shape
    rows = 32 // itemsize if itemsize < 4 else 8
    return math.prod(lead) * _up(sub, rows) * _up(lane, 128) * itemsize


def compiler_params(nbytes: int) -> pltpu.CompilerParams:
    """Compiler params whose scoped-VMEM limit covers `nbytes` of counted
    buffers and values (blocks already doubled where the pipeline
    double-buffers them) plus half again for the in-kernel temporaries
    Mosaic materializes around them, plus `HEADROOM`."""
    if nbytes > CAPACITY:
        raise ValueError(
            f"kernel needs {nbytes} bytes of VMEM, over the "
            f"{CAPACITY}-byte VMEM of {TPU_V5E.name}")
    limit = min(CAPACITY, nbytes + nbytes // 2 + HEADROOM)
    return pltpu.CompilerParams(vmem_limit_bytes=int(limit))
