"""Compiled schedule executor: lower a StaticSchedule once, replay it fast.

The interpreter in `repro.core.executor` replays the schedule subtask-by-
subtask through Python dict lookups — the right *oracle*, but the dominant
cost of both analysis (replay checks) and serving (one replay per job).
This module lowers a compiled network `(graph, subtasks, mapping, schedule)`
**once** into a `CompiledProgram`:

  * **per-core instruction streams** — every compute slot resolved to flat
    buffer indices, tile bounds, and (for requant) the multiplier, in core
    order: the management/worker-core programs the paper's step 7 emits,
    with no dict lookups or `sorted()` left for replay time;
  * **fused per-op tile batches** — each op's tile set, verified at lowering
    time to exactly cover the op's output. Because tiles of one op are
    independent and `Graph.validate()` guarantees topological op order,
    executing each op's whole tile batch as one fused kernel call in graph
    order computes bit-identical values to any dependency-respecting
    tile-by-tile replay (the interpreter remains the oracle that proves it).

Backends over the lowered program:

  * ``run_numpy``   — vectorized numpy replay (sliding-window im2col + one
    GEMM per op); bit-exact vs ``reference_forward`` and the interpreter.
  * ``jit_batched`` — the whole program traced as ONE jitted JAX function
    and vmapped over a batch axis: the real batched-inference step used by
    `repro.serve`. Integer paths are bit-exact; requant uses the same
    float32 round-half-even as `quantize.requantize`, and avgpool/gap use
    integer-exact round-half-even division (`kernels.ref.round_half_even_div`)
    so no x64 is needed.
  * the Pallas plan (``_pallas_plan``) — how each gemm/conv tile batch
    lowers onto the package's Pallas kernels (`kernels.gemm_int8`,
    `kernels.conv2d_im2col`; a pointwise conv is a GEMM and takes the GEMM
    kernel), with a gemm/conv -> requant chain fused into the kernel
    epilogue whenever the int32 accumulator has no other consumer, and a
    SiLU that alone reads the requant's output fused after it.
    BlockSpec tiling is derived from the program's hardware model
    scratchpad capacity (`hw.derive_gemm_blocks` /
    `hw.derive_conv_blocks`) so the kernel grid mirrors the SPM streaming
    the schedule models. Op kinds the kernels don't cover fall back to
    the JAX backend's lowering (`_jax_op`). `repro.core.megakernel` packs
    the plan into scratchpad-sized segments and emits it; tiled steps run
    through ``run_kernel_step``. On non-TPU backends the kernels run in
    Pallas interpret mode (bit-exact, CPU CI); on TPU they are the real
    Mosaic lowering.

Programs are cached per graph *signature* (structural hash) so serving
engines compile each distinct network once per process.
"""

from __future__ import annotations

import dataclasses
import hashlib
from collections import Counter, OrderedDict
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from .graph import Graph, conv_out_hw
from .mapping import Mapping, map_reverse_affinity
from .partition import Partitioner, Subtask
from .schedule import StaticSchedule, compute_schedule
from .executor import (_NP_DT, _avgpool, _maxpool, _requant_np, _sat_add,
                       _silu_np, _upsample_np, im2col)
from .quantize import silu_steps, silu_table
from ..hw import HardwareModel, derive_conv_blocks, derive_gemm_blocks
from ..kernels import ref as kref
from ..kernels.conv2d_im2col import conv2d_int8_pallas
from ..kernels.gemm_int8 import gemm_int8_pallas
from ..kernels.stem_conv import fits_stem, stem_conv_pallas, stem_geometry

_JNP_DT = {"int8": jnp.int8, "uint8": jnp.uint8, "int16": jnp.int16,
           "int32": jnp.int32, "f32": jnp.float32, "bf16": jnp.float32}


class CompileError(ValueError):
    pass


# Op kinds both backends lower; matches the executor oracle's coverage.
SUPPORTED_KINDS = frozenset({"gemm", "conv2d", "requant", "relu", "add",
                             "maxpool", "avgpool", "gap", "concat", "silu",
                             "upsample"})


def supports_graph(g: Graph) -> bool:
    """True iff every op kind has a compiled lowering (e.g. LM decode graphs
    with analysis-only kinds like "mul" are schedulable but not executable —
    same coverage as the interpreter oracle)."""
    return all(op.kind in SUPPORTED_KINDS for op in g.ops)


@dataclasses.dataclass(frozen=True)
class TileInstr:
    """One compute slot, fully pre-resolved (per-core program entry)."""

    sid: int
    core: int
    start: float
    end: float
    op_idx: int                  # position in CompiledProgram.batches
    kind: str
    bounds: tuple[int, ...]      # (m0, m1, n0, n1) | (r0, r1)


@dataclasses.dataclass
class OpBatch:
    """One op's fused tile batch: buffer indices + the full tile set."""

    op_idx: int
    name: str
    kind: str
    in_idx: tuple[int, ...]
    w_idx: int | None
    out_idx: int
    attrs: dict
    mult: np.ndarray | None      # pre-resolved requant multiplier
    tiles: np.ndarray            # (T, 4) gemm/conv | (T, 2) row ops


@dataclasses.dataclass(eq=False)
class CompiledProgram:
    """A StaticSchedule lowered for replay (see module docstring)."""

    graph: Graph
    signature: str
    num_cores: int
    makespan: float
    buffers: list[tuple[str, tuple, str]]   # (name, shape, dtype)
    index: dict[str, int]
    input_idx: dict[str, int]
    output_idx: dict[str, int]
    weights: dict[int, np.ndarray]          # buffer idx -> baked weight
    batches: list[OpBatch]                  # graph (topological) order
    core_streams: list[list[TileInstr]]
    hw: HardwareModel | None = None         # SPM model for pallas tiling
    _jax_single: object = dataclasses.field(default=None, repr=False)
    _jax_jit_single: object = dataclasses.field(default=None, repr=False)
    _jax_batched: object = dataclasses.field(default=None, repr=False)
    _pallas_cache: dict = dataclasses.field(default_factory=dict, repr=False)

    @property
    def num_instructions(self) -> int:
        return sum(len(s) for s in self.core_streams)

    # Programs are serializable (repro.compiler.Deployment.save): the jit /
    # pallas caches hold traced closures that cannot be pickled and are
    # rebuilt lazily on first use after load, so they are dropped here.
    def __getstate__(self):
        state = self.__dict__.copy()
        state["_jax_single"] = None
        state["_jax_jit_single"] = None
        state["_jax_batched"] = None
        state["_pallas_cache"] = {}
        return state


# -- signatures + cache -------------------------------------------------------

def graph_signature(g: Graph) -> str:
    """Structural hash: identical for structurally identical graphs (the
    program-cache key for serving engines)."""
    h = hashlib.sha256()
    for name, t in g.tensors.items():
        h.update(f"T|{name}|{t.shape}|{t.dtype}\n".encode())
    for op in g.ops:
        h.update(f"O|{op.name}|{op.kind}|{op.inputs}|{op.outputs}|"
                 f"{op.weights}|{sorted(op.attrs.items())}\n".encode())
    h.update(f"I|{g.inputs}|{g.outputs}\n".encode())
    return h.hexdigest()[:16]


# key -> (params, program). The params dict is kept in the entry on
# purpose: it pins the dict alive so its id() (part of the key) can never
# be recycled by a different params dict, which would otherwise make a
# fresh dict at the same address silently hit a stale program with the old
# baked weights.
_PROGRAM_CACHE: "OrderedDict[tuple, tuple[dict, CompiledProgram]]" = \
    OrderedDict()
_PROGRAM_CACHE_CAP = 64          # bounds baked-weight memory in long servers

# Dependent caches (e.g. repro.compiler's deployment cache) register a
# clearer here so `clear_program_cache()` is the single cache-reset entry
# point for the whole compile pipeline.
_CACHE_CLEAR_HOOKS: list = []


def clear_program_cache() -> None:
    """Drop every cached compiled program — and, via registered hooks, any
    dependent cache (the `repro.compile` deployment cache)."""
    _PROGRAM_CACHE.clear()
    for hook in _CACHE_CLEAR_HOOKS:
        hook()


def compile_graph(g: Graph, params: dict, hw: HardwareModel,
                  num_cores: int | None = None, *,
                  use_cache: bool = True) -> CompiledProgram:
    """Full pipeline + lowering: partition -> map -> schedule -> lower.

    Cached (LRU, bounded) on (graph signature, params identity, machine
    fingerprint, cores): a serving engine replaying many jobs of the same
    network compiles it once.
    """
    key = (graph_signature(g), id(params), hw.fingerprint(), num_cores)
    if use_cache:
        hit = _PROGRAM_CACHE.get(key)
        if hit is not None and hit[0] is params:
            _PROGRAM_CACHE.move_to_end(key)
            return hit[1]
    part = Partitioner(hw)
    subtasks = part.partition(g)
    mapping = map_reverse_affinity(subtasks, hw, num_cores)
    sched = compute_schedule(subtasks, mapping, hw)
    prog = lower_program(g, params, subtasks, mapping, sched, hw=hw)
    if use_cache:
        _PROGRAM_CACHE[key] = (params, prog)
        while len(_PROGRAM_CACHE) > _PROGRAM_CACHE_CAP:
            _PROGRAM_CACHE.popitem(last=False)
    return prog


# -- lowering -----------------------------------------------------------------

def _op_rows(g: Graph, op) -> int:
    return g.tensors[op.outputs[0]].shape[0]


def lower_program(g: Graph, params: dict, subtasks: list[Subtask],
                  mapping: Mapping, sched: StaticSchedule,
                  hw: HardwareModel | None = None) -> CompiledProgram:
    """Lower one scheduled network into a CompiledProgram.

    `hw` (optional) records the scratchpad model so the pallas backend can
    derive its block shapes; without it the kernels use their MXU-aligned
    defaults."""
    index = {name: i for i, name in enumerate(g.tensors)}
    buffers = [(t.name, t.shape, t.dtype) for t in g.tensors.values()]
    op_pos = {op.name: i for i, op in enumerate(g.ops)}
    by_id = {st.sid: st for st in subtasks}

    # per-core instruction streams in slot time order (the emitted program)
    core_streams: list[list[TileInstr]] = [[] for _ in
                                           range(mapping.num_cores)]
    tiles_of: dict[str, list[tuple[int, ...]]] = {op.name: [] for op in g.ops}
    for slot in sorted(sched.compute, key=lambda s: (s.start, s.sid)):
        st = by_id[slot.sid]
        t = st.tile
        if st.kind in ("gemm", "conv2d"):
            bounds = (t["m0"], t["m1"], t["n0"], t["n1"])
        else:
            bounds = (t["r0"], t["r1"])
        tiles_of[st.op_name].append(bounds)
        core_streams[slot.core].append(TileInstr(
            sid=st.sid, core=slot.core, start=slot.start, end=slot.end,
            op_idx=op_pos[st.op_name], kind=st.kind, bounds=bounds))

    batches: list[OpBatch] = []
    weights: dict[int, np.ndarray] = {}
    for op in g.ops:
        tiles = np.array(sorted(tiles_of[op.name]), dtype=np.int64)
        if tiles.size == 0:
            raise CompileError(f"{op.name}: no scheduled subtasks")
        # fused execution is only valid if the tile set covers the output
        if op.kind in ("gemm", "conv2d"):
            if op.kind == "gemm":
                M, N = op.attrs["M"], op.attrs["N"]
            else:
                oh, ow = conv_out_hw(op.attrs)
                M, N = oh * ow, op.attrs["C_out"]
            area = int(((tiles[:, 1] - tiles[:, 0])
                        * (tiles[:, 3] - tiles[:, 2])).sum())
            if area != M * N:
                raise CompileError(
                    f"{op.name}: tiles cover {area} of {M * N} elements")
        else:
            rows = int((tiles[:, 1] - tiles[:, 0]).sum())
            if rows != _op_rows(g, op):
                raise CompileError(
                    f"{op.name}: tiles cover {rows} of "
                    f"{_op_rows(g, op)} rows")
        w_idx = index[op.weights[0]] if op.weights else None
        if w_idx is not None:
            weights[w_idx] = params[op.weights[0]]
        # scalar or per-channel (N,) multiplier — both broadcast in requant
        mult = (np.asarray(params[f"{op.name}.mult"], np.float32)
                if op.kind == "requant" else None)
        batches.append(OpBatch(
            op_idx=op_pos[op.name], name=op.name, kind=op.kind,
            in_idx=tuple(index[t] for t in op.inputs), w_idx=w_idx,
            out_idx=index[op.outputs[0]], attrs=op.attrs, mult=mult,
            tiles=tiles))

    return CompiledProgram(
        graph=g, signature=graph_signature(g),
        num_cores=mapping.num_cores, makespan=sched.makespan,
        buffers=buffers, index=index,
        input_idx={t: index[t] for t in g.inputs},
        output_idx={t: index[t] for t in g.outputs},
        weights=weights, batches=batches, core_streams=core_streams,
        hw=hw)


# -- mesh partitioning --------------------------------------------------------

def partition_streams(prog: CompiledProgram,
                      n_groups: int) -> list[dict[int, np.ndarray]]:
    """Split the per-core instruction streams into `n_groups` contiguous
    core blocks — the mesh-model-axis decomposition `repro.cluster.mesh`
    executes (device d of the model axis runs core block d).

    Returns one `{op_idx: tiles}` dict per group, where `tiles` is the
    (T, 4) / (T, 2) bounds array of every tile the group's cores were
    scheduled to run for that op. Because the lowering already verified
    that each op's full tile set exactly covers its output, the union of
    the per-group tile sets is exact and disjoint: summing the groups'
    partial results (a `lax.psum` over the model axis) reconstructs the
    single-device value bit-for-bit for the integer accumulation paths.
    """
    if n_groups < 1:
        raise CompileError(f"n_groups must be >= 1, got {n_groups}")
    if prog.num_cores % n_groups != 0:
        raise CompileError(
            f"cannot partition {prog.num_cores} core streams into "
            f"{n_groups} mesh groups: group count must divide the "
            f"core count")
    per = prog.num_cores // n_groups
    raw: list[dict[int, list[tuple[int, ...]]]] = [
        {} for _ in range(n_groups)]
    for core, stream in enumerate(prog.core_streams):
        g = core // per
        for ins in stream:
            raw[g].setdefault(ins.op_idx, []).append(ins.bounds)
    return [{op_idx: np.array(sorted(tiles), dtype=np.int64)
             for op_idx, tiles in group.items()}
            for group in raw]


# -- numpy backend ------------------------------------------------------------

_GEMM_CHUNK = 8192               # rows per BLAS call (bounds temp memory)


def gemm_i32_exact(x: np.ndarray, w: np.ndarray) -> np.ndarray:
    """Bit-exact int8 GEMM through float BLAS.

    numpy routes integer matmul through a slow non-BLAS kernel; float matmul
    hits BLAS. For int8 operands every product is <= 2^14, so partial sums
    stay exactly representable in f32 while K * 2^14 <= 2^24 (K <= 1024) and
    in f64 always (< 2^53) — accumulation order therefore cannot change the
    result, and the round-trip is exact. Falls back to the integer path for
    non-int8 operands.
    """
    if x.dtype != np.int8 or w.dtype != np.int8:
        return x.astype(np.int32) @ w.astype(np.int32)
    K = x.shape[1]
    dt = np.float32 if K <= 1024 else np.float64
    wf = w.astype(dt)
    M = x.shape[0]
    if M <= _GEMM_CHUNK:
        return (x.astype(dt) @ wf).astype(np.int32)
    out = np.empty((M, w.shape[1]), np.int32)
    for m0 in range(0, M, _GEMM_CHUNK):
        m1 = min(M, m0 + _GEMM_CHUNK)
        out[m0:m1] = (x[m0:m1].astype(dt) @ wf).astype(np.int32)
    return out


def run_numpy(prog: CompiledProgram,
              inputs: dict[str, np.ndarray]) -> dict[str, np.ndarray]:
    """Vectorized replay: each op's fused tile batch as one kernel call.

    Bit-exact vs ``reference_forward`` and the schedule interpreter (same
    primitives: sliding-window im2col, int32 GEMM, f32 round-half-even
    requant).
    """
    vals: list = [None] * len(prog.buffers)
    for name, i in prog.input_idx.items():
        vals[i] = np.asarray(inputs[name], dtype=_NP_DT[prog.buffers[i][2]])
    for i, w in prog.weights.items():
        vals[i] = w
    for b in prog.batches:
        a = b.attrs
        if b.kind == "gemm":
            x = vals[b.in_idx[0]].reshape(a["M"], a["K"])
            acc = gemm_i32_exact(x, vals[b.w_idx])
            out = acc.astype(_NP_DT[prog.buffers[b.out_idx][2]])
        elif b.kind == "conv2d":
            cols = im2col(vals[b.in_idx[0]], a["kh"], a["kw"], a["stride"],
                          a["padding"])
            acc = gemm_i32_exact(cols, vals[b.w_idx])
            oh, ow = conv_out_hw(a)
            out = acc.reshape(oh, ow, a["C_out"])
        elif b.kind == "requant":
            out = _requant_np(vals[b.in_idx[0]], b.mult)
        elif b.kind == "relu":
            out = np.maximum(vals[b.in_idx[0]], 0)
        elif b.kind == "add":
            out = _sat_add(vals[b.in_idx[0]], vals[b.in_idx[1]],
                           _NP_DT[prog.buffers[b.out_idx][2]])
        elif b.kind == "maxpool":
            out = _maxpool(vals[b.in_idx[0]], a["k"], a["stride"],
                           a.get("padding", 0))
        elif b.kind == "avgpool":
            out = _avgpool(vals[b.in_idx[0]], a["k"], a["stride"],
                           a.get("padding", 0))
        elif b.kind == "gap":
            x = vals[b.in_idx[0]].astype(np.int32)
            m = np.round(x.mean(axis=(0, 1)))
            out = np.clip(m, -128, 127).astype(np.int8).reshape(1, -1)
        elif b.kind == "concat":
            out = np.concatenate([vals[i] for i in b.in_idx], axis=-1)
        elif b.kind == "silu":
            out = _silu_np(vals[b.in_idx[0]], a["scale"])
        elif b.kind == "upsample":
            out = _upsample_np(vals[b.in_idx[0]], a["factor"])
        else:
            raise CompileError(f"op kind {b.kind} not lowered")
        vals[b.out_idx] = out
    return {name: vals[i] for name, i in prog.index.items()
            if vals[i] is not None}


# -- JAX backend --------------------------------------------------------------

def _jax_op(b: OpBatch, vals: list, prog: CompiledProgram,
            weights: dict[int, jax.Array]):
    a = b.attrs
    if b.kind == "gemm":
        x = vals[b.in_idx[0]].reshape(a["M"], a["K"])
        acc = jax.lax.dot_general(x, weights[b.w_idx],
                                  (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.int32)
        return acc.astype(_JNP_DT[prog.buffers[b.out_idx][2]])
    if b.kind == "conv2d":
        return kref.conv2d_int8_general(
            vals[b.in_idx[0]], weights[b.w_idx], a["kh"], a["kw"],
            a["stride"], a["padding"])
    if b.kind == "requant":
        y = jnp.round(vals[b.in_idx[0]].astype(jnp.float32) * b.mult)
        return jnp.clip(y, -128, 127).astype(jnp.int8)
    if b.kind == "relu":
        return jnp.maximum(vals[b.in_idx[0]], 0)
    if b.kind == "add":
        s = (vals[b.in_idx[0]].astype(jnp.int32)
             + vals[b.in_idx[1]].astype(jnp.int32))
        dt = _JNP_DT[prog.buffers[b.out_idx][2]]
        if dt == jnp.int8:
            return jnp.clip(s, -128, 127).astype(jnp.int8)
        return s.astype(dt)
    if b.kind == "maxpool":
        x = vals[b.in_idx[0]]
        k, s, p = a["k"], a["stride"], a.get("padding", 0)
        fill = jnp.iinfo(x.dtype).min
        xp = jnp.pad(x, ((p, p), (p, p), (0, 0)), constant_values=fill)
        H, W, C = xp.shape
        oh, ow = (H - k) // s + 1, (W - k) // s + 1
        out = jnp.full((oh, ow, C), fill, dtype=x.dtype)
        for di in range(k):
            for dj in range(k):
                out = jnp.maximum(
                    out, xp[di:di + oh * s:s, dj:dj + ow * s:s, :])
        return out
    if b.kind == "avgpool":
        x = vals[b.in_idx[0]]
        k, s, p = a["k"], a["stride"], a.get("padding", 0)
        xp = jnp.pad(x, ((p, p), (p, p), (0, 0))).astype(jnp.int32)
        H, W, C = xp.shape
        oh, ow = (H - k) // s + 1, (W - k) // s + 1
        acc = jnp.zeros((oh, ow, C), jnp.int32)
        for di in range(k):
            for dj in range(k):
                acc = acc + xp[di:di + oh * s:s, dj:dj + ow * s:s, :]
        out = kref.round_half_even_div(acc, k * k)
        return jnp.clip(out, -128, 127).astype(x.dtype)
    if b.kind == "gap":
        x = vals[b.in_idx[0]].astype(jnp.int32)
        H, W = x.shape[0], x.shape[1]
        m = kref.round_half_even_div(x.sum(axis=(0, 1)), H * W)
        return jnp.clip(m, -128, 127).astype(jnp.int8).reshape(1, -1)
    if b.kind == "concat":
        return jnp.concatenate([vals[i] for i in b.in_idx], axis=-1)
    if b.kind == "silu":
        table = jnp.asarray(silu_table(a["scale"]))
        return table[vals[b.in_idx[0]].astype(jnp.int32) + 128]
    if b.kind == "upsample":
        f = a["factor"]
        return jnp.repeat(jnp.repeat(vals[b.in_idx[0]], f, axis=0), f, axis=1)
    raise CompileError(f"op kind {b.kind} not lowered")


def jax_single(prog: CompiledProgram):
    """Single-sample traced function: {input: (H,W,C)} -> {output: ...}."""
    if prog._jax_single is None:
        weights = {i: jnp.asarray(w) for i, w in prog.weights.items()}
        batches = prog.batches

        def single(inputs: dict):
            vals: list = [None] * len(prog.buffers)
            for name, i in prog.input_idx.items():
                vals[i] = inputs[name]
            for b in batches:
                vals[b.out_idx] = _jax_op(b, vals, prog, weights)
            return {name: vals[i] for name, i in prog.output_idx.items()}

        prog._jax_single = single
    return prog._jax_single


def jit_batched(prog: CompiledProgram):
    """The whole program as ONE jitted function, vmapped over a leading
    batch axis: {input: (B,H,W,C)} -> {output: (B, ...)}. Compiled once per
    (program, batch shape) by jit's own cache."""
    if prog._jax_batched is None:
        prog._jax_batched = jax.jit(jax.vmap(jax_single(prog)))
    return prog._jax_batched


def jit_single(prog: CompiledProgram):
    """Jitted single-sample program, cached on the program (a fresh jax.jit
    wrapper per call would retrace the whole network every invocation)."""
    if prog._jax_jit_single is None:
        prog._jax_jit_single = jax.jit(jax_single(prog))
    return prog._jax_jit_single


def run_jax(prog: CompiledProgram, inputs: dict[str, np.ndarray],
            batched: bool = True) -> dict[str, np.ndarray]:
    """Convenience wrapper: numpy in, numpy out, block until ready."""
    fn = jit_batched(prog) if batched else jit_single(prog)
    out = fn({k: jnp.asarray(v) for k, v in inputs.items()})
    return {k: np.asarray(v) for k, v in out.items()}


# -- Pallas backend -----------------------------------------------------------

# Op kinds with a Pallas kernel lowering; everything else falls back to the
# JAX backend's per-op lowering inside the same traced program.
PALLAS_KINDS = frozenset({"gemm", "conv2d"})


def resolve_interpret(interpret: bool | None = None) -> bool:
    """Resolve an interpret-mode request against the runtime device.

    ``None`` means auto: real Mosaic lowering on TPU, Pallas interpret mode
    everywhere else (Pallas cannot lower to the CPU XLA backend). The one
    place this decision is made — the backend registry's `BackendOptions`
    and every megakernel entry point route through it.
    """
    if interpret is None:
        return jax.default_backend() != "tpu"
    return bool(interpret)


class GemmShape(NamedTuple):
    """The contraction a "gemm" plan step runs: x (M, K) @ w (K, N). A
    pointwise conv's (H, W, K) input is subsampled by `stride` first."""

    M: int
    K: int
    N: int
    stride: int = 1


@dataclasses.dataclass(frozen=True)
class _PallasStep:
    """One op of the pallas-backend program plan.

    mode: "gemm" / "conv2d" / "stem" (Pallas kernel), "jax" (fallback),
    or "skip" (a requant or SiLU batch fused into the preceding kernel's
    epilogue). A "gemm" step's batch is a gemm or a pointwise conv; `gemm`
    gives its shape. A "stem" step is a conv that reads a graph input as
    row groups (`_is_stem`). `silu` is the fused SiLU's table as
    `quantize.silu_steps` gives it, applied after the requant.
    """

    mode: str
    batch: OpBatch
    out_idx: int                 # where the result lands (fused: last op's)
    mult: np.ndarray | None      # fused requant multiplier, else None
    blocks: tuple                # (bm, bn, bk) gemm | (rows_t, bn) conv
    gemm: GemmShape | None = None  # the contraction of a "gemm" step
    silu: tuple | None = None    # fused SiLU steps, else None


def _sole_consumer(prog: CompiledProgram, b: OpBatch,
                   kind: str) -> OpBatch | None:
    """The batch of `b`'s only consumer, if it is of `kind` and `b`'s
    output is not itself a graph output; else None."""
    out_name = prog.buffers[b.out_idx][0]
    if out_name in prog.graph.outputs:
        return None
    consumers = prog.graph.consumers_of(out_name)
    if len(consumers) != 1 or consumers[0].kind != kind:
        return None
    return next(c for c in prog.batches if c.name == consumers[0].name)


def _fusable_epilogue(prog: CompiledProgram, b: OpBatch
                      ) -> tuple[OpBatch | None, OpBatch | None]:
    """The requant batch, and after it the SiLU batch, to fold into `b`'s
    kernel epilogue, where legal.

    A requant is legal iff b's int32 output feeds exactly one consumer,
    that consumer is a requant op, and the accumulator is not itself a
    graph output — then requantization in the epilogue is observationally
    identical to running the requant batch afterwards (`requant_epilogue`
    shares the oracle's round-half-even numerics). A SiLU is legal on the
    same terms for the requant's int8 output: its table applied in the
    epilogue is the standalone op's, bit for bit (`silu_epilogue`).
    """
    rq = _sole_consumer(prog, b, "requant")
    return rq, (None if rq is None else _sole_consumer(prog, rq, "silu"))


def _gemm_shape(b: OpBatch) -> GemmShape | None:
    """The GEMM a gemm batch, or a pointwise (1×1, unpadded) conv batch,
    computes; None for every other batch."""
    a = b.attrs
    if b.kind == "gemm":
        return GemmShape(a["M"], a["K"], a["N"])
    if b.kind == "conv2d" and a["kh"] == a["kw"] == 1 and a["padding"] == 0:
        oh, ow = conv_out_hw(a)
        return GemmShape(oh * ow, a["C_in"], a["C_out"], a["stride"])
    return None


def _is_stem(prog: CompiledProgram, b: OpBatch) -> bool:
    """Whether a conv batch reads a graph input that nothing else reads,
    through a weight nothing else uses, with few enough input channels
    that a window row (`C_in · kw`) fits one 128-lane row and whole row
    groups of `stride` frame rows (`kernels.stem_conv.fits_stem`)."""
    if b.kind != "conv2d":
        return False
    a = b.attrs
    name = prog.buffers[b.in_idx[0]][0]
    return (name in prog.graph.inputs
            and len(prog.graph.consumers_of(name)) == 1
            and sum(o.w_idx == b.w_idx for o in prog.batches) == 1
            and fits_stem(a["H"], a["C_in"], a["kw"], a["stride"]))


def stem_geometry_of(b: OpBatch):
    """The stem kernel's geometry for a "stem" step's conv batch."""
    a = b.attrs
    return stem_geometry(a["H"], a["W"], a["C_in"], a["C_out"], kh=a["kh"],
                         kw=a["kw"], stride=a["stride"], padding=a["padding"])


def _pallas_plan(prog: CompiledProgram) -> list[_PallasStep]:
    """Decide, once per program, how each fused tile batch lowers onto the
    Pallas kernels: kernel vs fallback, epilogue fusion, and SPM-derived
    block shapes.

    A pointwise conv (1×1 kernel, no padding) is a GEMM: its output pixel
    (i, j) reads input pixel (i·s, j·s) alone, so the input subsampled by
    the stride and flattened to (oh·ow, C_in) contracts against the
    (C_in, C_out) weight. It is planned as a "gemm" step and runs on the
    GEMM kernel, which feeds int8 blocks straight to the MXU, instead of
    the windowed conv kernel's halo-less pad, band copies, int32 widening
    and tap loads. Same exact int32 contraction, same requant epilogue.

    A windowed conv that reads a narrow graph input alone (`_is_stem`:
    the networks' 3-channel stems) is a "stem" step: the runner ships that
    input as row groups (`megakernel.input_layout`) and the stem kernel
    (`kernels.stem_conv`) contracts them against banded weights, so the
    frame is never padded out to 128 lanes, on the host or on the device.
    Every other conv keeps the windowed kernel.

    A SiLU whose input is a fused requant's output, and which nothing
    else reads, applies its table in the same epilogue (`silu`), so the
    conv's int8 output does not make a second pass for its activation."""
    plan: list[_PallasStep] = []
    skipped: set[int] = set()
    for b in prog.batches:
        if b.op_idx in skipped:
            plan.append(_PallasStep("skip", b, b.out_idx, None, ()))
            continue
        if b.kind not in PALLAS_KINDS:
            plan.append(_PallasStep("jax", b, b.out_idx, None, ()))
            continue
        rq, act = _fusable_epilogue(prog, b)
        last = act or rq or b
        mult = rq.mult if rq is not None else None
        out_bytes = 1 if rq is not None else 4
        gemm = _gemm_shape(b)
        if gemm is not None:
            mode = "gemm"
            blocks = (derive_gemm_blocks(prog.hw, gemm.M, gemm.K, gemm.N,
                                         out_bytes)
                      if prog.hw is not None else (128, 128, 128))
        elif _is_stem(prog, b):
            mode, blocks = "stem", ()
        else:
            mode = "conv2d"
            blocks = (derive_conv_blocks(prog.hw, b.attrs, out_bytes)
                      if prog.hw is not None else (8, 128))
        skipped.update(f.op_idx for f in (rq, act) if f is not None)
        silu = silu_steps(act.attrs["scale"]) if act is not None else None
        plan.append(_PallasStep(mode, b, last.out_idx, mult, blocks, gemm,
                                silu))
    return plan


def plan_counts(prog: CompiledProgram) -> dict[str, int]:
    """Steps of the pallas plan by mode, plus `pointwise_gemm`: the convs
    planned as GEMMs; `dense_stem`: the convs that read a graph input as
    row groups (the "stem" steps); `silu_epilogue`: the kernel steps whose
    epilogue applies a SiLU; `silu_steps`: the SiLUs left as steps of
    their own; `upsample`: the upsample steps."""
    plan = _pallas_plan(prog)
    counts = dict(Counter(s.mode for s in plan))
    counts["pointwise_gemm"] = sum(
        s.mode == "gemm" and s.batch.kind == "conv2d" for s in plan)
    counts["dense_stem"] = counts.get("stem", 0)
    counts["silu_epilogue"] = sum(s.silu is not None for s in plan)
    counts["silu_steps"] = sum(s.batch.kind == "silu" and s.mode != "skip"
                               for s in plan)
    counts["upsample"] = sum(s.batch.kind == "upsample" for s in plan)
    return counts


def run_kernel_step(prog: CompiledProgram, step: _PallasStep, vals: list,
                    weights: dict, interpret: bool) -> None:
    """Run one "gemm", "conv2d" or "stem" plan step on its tiled Pallas
    kernel (grid-streamed, double-buffered) and store its result in
    `vals`. A stem's input is in row groups and its weight is the band
    expansion (`megakernel.device_weights`)."""
    b = step.batch
    mult = None if step.mult is None else jnp.asarray(step.mult)
    x = vals[b.in_idx[0]]
    if step.mode == "stem":
        vals[step.out_idx] = stem_conv_pallas(
            x, weights[b.w_idx], mult, geom=stem_geometry_of(b),
            silu=step.silu, interpret=interpret)
    elif step.mode == "gemm":
        M, K, _, s = step.gemm
        bm, bn, bk = step.blocks
        if s > 1:
            x = x[::s, ::s, :]
        out = gemm_int8_pallas(x.reshape(M, K), weights[b.w_idx], mult,
                               bm=bm, bn=bn, bk=bk, silu=step.silu,
                               interpret=interpret)
        if mult is None:
            out = out.astype(_JNP_DT[prog.buffers[step.out_idx][2]])
        vals[step.out_idx] = out.reshape(prog.buffers[step.out_idx][1])
    else:
        a = b.attrs
        rows_t, bn = step.blocks
        vals[step.out_idx] = conv2d_int8_pallas(
            x, weights[b.w_idx], mult, kh=a["kh"], kw=a["kw"],
            stride=a["stride"], padding=a["padding"], rows_t=rows_t, bn=bn,
            silu=step.silu, interpret=interpret)
