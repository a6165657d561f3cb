"""Mesh-sharded execution of a CompiledProgram (backend "mesh").

The schedule's per-core instruction streams already say which core computes
which tile of which op. `partition_streams` groups the cores into
contiguous blocks — one block per device on the mesh's **model** axis —
and this module executes exactly those per-device tile sets under
`shard_map`:

  * every device materializes the op's operands (inputs are replicated),
    computes ONLY its own tiles into a zero int32 accumulator, and a
    `lax.psum` over the model axis reconstructs the full output — the
    jax-native analogue of the paper's cores writing disjoint output tiles
    back to shared memory. The tile sets are disjoint and exactly cover
    the output (verified at lowering time), and the gemm/conv paths
    accumulate in int32, so the summed result is **bit-identical** to the
    single-device jax backend — no reduction-order caveats.
  * op kinds without tile-level parallelism (requant, pooling, add, ...)
    are replicated: every device computes them identically, which keeps
    the values consistent without communication.
  * the **data** axis shards the serving batch (`jax.vmap` inside the
    shard_map body); the runner pads a ragged batch up to a multiple of
    the axis size and slices the pad back off.

Tile bounds differ per device, but traced shapes cannot: the loop runs
over fixed-size (max-extent) index windows with validity masks, clipping
out-of-range indices and masking their contribution to zero — a masked
scatter-add of zero is exact, so padding never changes the result.

The mesh shape comes from the machine: `HardwareModel.with_mesh(data,
model)` stamps `mesh_shape` into the model (and thus its fingerprint), and
`make_host_mesh` validates it against the visible device count.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from ..core import compiled as _C
from ..core.compiled import CompiledProgram, CompileError, partition_streams
from ..core.graph import conv_out_hw
from ..launch.mesh import make_host_mesh


def mesh_axes(prog: CompiledProgram) -> tuple[int, int]:
    """The (data, model) mesh axis sizes the program was compiled for.

    Raises `CompileError` when the program's machine carries no mesh shape
    (i.e. it was compiled for single-device execution) — the backend/machine
    consistency check in `repro.compile` makes this unreachable through the
    public API, but direct callers get the same clear failure.
    """
    hw = prog.hw
    shape = getattr(hw, "mesh_shape", None) if hw is not None else None
    if shape is None:
        raise CompileError(
            "program was compiled for a machine without a mesh shape; "
            "use HardwareModel.with_mesh(data, model) to target the "
            "mesh backend")
    data, model = shape
    return int(data), int(model)


# -- per-device tile tables ---------------------------------------------------

def _stack_tiles(parts: list[dict[int, np.ndarray]],
                 op_idx: int) -> tuple[np.ndarray, np.ndarray]:
    """Stack one op's per-device tile sets into a rectangular table.

    Returns `(tiles, mask)` with shapes (n_devices, T_max, 4) and
    (n_devices, T_max): device d's real tiles occupy the first
    `mask[d].sum()` rows; the rest are zero padding the mask disables.
    """
    per = [g.get(op_idx, np.zeros((0, 4), np.int64)) for g in parts]
    t_max = max(max((len(p) for p in per), default=0), 1)
    tiles = np.zeros((len(parts), t_max, 4), np.int64)
    mask = np.zeros((len(parts), t_max), bool)
    for d, p in enumerate(per):
        tiles[d, : len(p)] = p
        mask[d, : len(p)] = True
    return tiles, mask


def _im2col_jnp(x: jax.Array, kh: int, kw: int, stride: int,
                padding: int) -> jax.Array:
    """JAX im2col matching `core.executor.im2col`'s row layout: each output
    row is the patch raveled as (kh, kw, C), i.e. column (di*kw + dj)*C + c
    — the layout the baked (K, N) conv weight matrix expects."""
    xp = jnp.pad(x, ((padding, padding), (padding, padding), (0, 0)))
    h, w, c = xp.shape
    oh = (h - kh) // stride + 1
    ow = (w - kw) // stride + 1
    cols = []
    for di in range(kh):
        for dj in range(kw):
            patch = xp[di:di + oh * stride:stride,
                       dj:dj + ow * stride:stride, :]
            cols.append(patch.reshape(oh * ow, c))
    return jnp.concatenate(cols, axis=1)


def _tiled_partial(x2d: jax.Array, w: jax.Array, tiles: jax.Array,
                   mask: jax.Array, mt: int, nt: int, m: int,
                   n: int) -> jax.Array:
    """This device's partial (m, n) int32 accumulator: the sum of its own
    (masked, fixed-max-extent) tiles' x·w products, zero elsewhere."""
    row_win = jnp.arange(mt)
    col_win = jnp.arange(nt)

    def body(i: int, acc: jax.Array) -> jax.Array:
        t = tiles[i]
        live = mask[i]
        r = t[0] + row_win
        c = t[2] + col_win
        vr = (r < t[1]) & live
        vc = (c < t[3]) & live
        rc = jnp.clip(r, 0, m - 1)
        cc = jnp.clip(c, 0, n - 1)
        part = lax.dot_general(jnp.take(x2d, rc, axis=0),
                               jnp.take(w, cc, axis=1),
                               (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.int32)
        part = part * (vr[:, None] & vc[None, :]).astype(jnp.int32)
        return acc.at[rc[:, None], cc[None, :]].add(part)

    acc0 = jnp.zeros((m, n), jnp.int32)
    return lax.fori_loop(0, tiles.shape[0], body, acc0)


# -- the traced per-shard program ---------------------------------------------

def _mesh_single_fn(prog: CompiledProgram, n_model: int):
    """The per-device single-sample function `shard_map` runs: device d of
    the model axis executes core block d's tiles; cheap ops replicate."""
    parts = partition_streams(prog, n_model)
    weights = {i: jnp.asarray(w) for i, w in prog.weights.items()}
    tables: dict[int, tuple] = {}
    for b in prog.batches:
        if b.kind not in ("gemm", "conv2d"):
            continue
        tiles, mask = _stack_tiles(parts, b.op_idx)
        mt = max(int((tiles[..., 1] - tiles[..., 0]).max()), 1)
        nt = max(int((tiles[..., 3] - tiles[..., 2]).max()), 1)
        tables[b.op_idx] = (jnp.asarray(tiles), jnp.asarray(mask), mt, nt)

    def single(inputs: dict) -> dict:
        d = lax.axis_index("model")
        vals: list = [None] * len(prog.buffers)
        for name, i in prog.input_idx.items():
            vals[i] = inputs[name]
        for b in prog.batches:
            if b.kind in ("gemm", "conv2d"):
                a = b.attrs
                tiles, mask, mt, nt = tables[b.op_idx]
                if b.kind == "gemm":
                    m, n = a["M"], a["N"]
                    x2d = vals[b.in_idx[0]].reshape(m, a["K"])
                else:
                    oh, ow = conv_out_hw(a)
                    m, n = oh * ow, a["C_out"]
                    x2d = _im2col_jnp(vals[b.in_idx[0]], a["kh"], a["kw"],
                                      a["stride"], a["padding"])
                acc = _tiled_partial(
                    x2d, weights[b.w_idx], jnp.take(tiles, d, axis=0),
                    jnp.take(mask, d, axis=0), mt, nt, m, n)
                acc = lax.psum(acc, "model")
                out = acc.astype(_C._JNP_DT[prog.buffers[b.out_idx][2]])
                if b.kind == "conv2d":
                    out = out.reshape(oh, ow, n)
                vals[b.out_idx] = out
            else:
                vals[b.out_idx] = _C._jax_op(b, vals, prog, weights)
        return {name: vals[i] for name, i in prog.output_idx.items()}

    return single


def _mesh_program(prog: CompiledProgram, batched: bool):
    """The jitted shard_map program for (prog, batched), cached on the
    program (same lifecycle as the pallas trace cache: dropped on pickle,
    rebuilt lazily after `Deployment.load`)."""
    data, model = mesh_axes(prog)
    key = ("mesh", bool(batched), (data, model))
    if key not in prog._pallas_cache:
        # partition first: a model axis that does not divide the core count
        # is a program error (CompileError) regardless of how many devices
        # this host happens to expose
        single = _mesh_single_fn(prog, model)
        mesh = make_host_mesh(data=data, model=model)
        if batched:
            fn = jax.shard_map(jax.vmap(single), mesh=mesh,
                               in_specs=(P("data"),), out_specs=P("data"),
                               check_vma=False)
        else:
            # replicated in, replicated out: every device computes the
            # same value (psum over disjoint exact tile covers)
            fn = jax.shard_map(single, mesh=mesh, in_specs=(P(),),
                               out_specs=P(), check_vma=False)
        prog._pallas_cache[key] = jax.jit(fn)
    return prog._pallas_cache[key]


# -- backend runners ----------------------------------------------------------

def mesh_single_runner(prog: CompiledProgram):
    """Single-sample runner with the uniform serving contract (numpy in,
    numpy out, graph outputs only)."""
    fn = _mesh_program(prog, batched=False)

    def run(inputs: dict) -> dict:
        out = fn({k: jnp.asarray(v) for k, v in inputs.items()})
        return {k: np.asarray(v) for k, v in out.items()}

    return run


def mesh_batched_runner(prog: CompiledProgram):
    """Batched runner: shards the leading batch axis over the data axis,
    padding a ragged batch by repeating the last sample (sliced back off),
    so any batch size serves on any data-axis size."""
    fn = _mesh_program(prog, batched=True)
    data, _ = mesh_axes(prog)

    def run(inputs: dict) -> dict:
        b = next(iter(inputs.values())).shape[0]
        pad = (-b) % data
        arrs = {}
        for k, v in inputs.items():
            v = np.asarray(v)
            if pad:
                v = np.concatenate([v, np.repeat(v[-1:], pad, axis=0)])
            arrs[k] = jnp.asarray(v)
        out = fn(arrs)
        return {k: np.asarray(v)[:b] for k, v in out.items()}

    return run
