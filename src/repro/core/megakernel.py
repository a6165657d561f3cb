"""Fused per-core megakernel: the `pallas` backend's program.

The paper's machine runs every core's whole statically scheduled
instruction stream out of local scratchpad, with the DMA engine
prefetching the next tile while the core computes the current one. This
module emits the compiled program's Pallas plan (`compiled._pallas_plan`:
kernel vs fallback per op, fused requant epilogues, SPM-derived blocks)
in that structure, so a program runs as a few kernels rather than one
per op:

  1. **Segmentation** (`plan_segments`): walk `_pallas_plan`'s steps in
     program order and greedily pack them into contiguous *segments* whose
     summed working set — streamed operands counted twice on a dual-ported
     scratchpad (the i/i+1 double-buffer pair), the int32 accumulator, the
     output tile, and the padded copy plus tap window a conv or pool step
     windows over — fits the machine's scratchpad capacity
     (`hw.scratchpad_bytes`). Each segment is one core's fused stretch of
     the program and is assigned a core round-robin, so the per-core WCET
     composition of the schedule survives the fusion (ACETONE-style
     analyzability: segment boundaries are schedule-visible).
  2. **Emission**: every fused segment becomes ONE `pallas_call` whose body
     replays the segment's steps scratchpad-resident — gemms via the exact
     int8 contraction (`kernels.gemm_int8.dot_i32_exact`: MXU int8 dots on
     TPU, exactness-preserving chunked-f32 dots under interpret mode),
     convs and pools as strided tap windows over a padded int32 VMEM copy
     (`kernels.conv2d_im2col.conv_accumulate`), requantization fused into
     the epilogues exactly as the plan decided (`_PallasStep.mult`).
     In-kernel values stay int32 (Mosaic lays out and windows 32-bit
     values; int8 ones it refuses to reshape or compare) and narrow to int8
     only for the MXU and at the output store. Weights and requant
     multipliers enter as operands. A single gemm/conv whose working set
     alone exceeds the scratchpad runs on the *tiled* kernels
     (`gemm_int8_pallas` / `conv2d_int8_pallas`), whose grid streaming is
     Pallas-double-buffered — still one `pallas_call`. A pointwise (1×1,
     unpadded) conv is a GEMM, so the plan makes it a "gemm" step
     (`compiled._pallas_plan`) and a tiled one runs on the GEMM kernel:
     its input, subsampled by the stride and flattened to (oh·ow, C_in),
     reaches the MXU in int8 blocks, without the windowed kernel's
     padding, band copies, int32 widening and tap loads. (A fused body
     still windows it.) A stem step (`compiled._is_stem`: a windowed conv
     that alone reads a narrow graph input) is always a tiled segment of
     its own, on the stem kernel (`kernels.stem_conv`): the program takes
     that input as row groups, (H / s, s·W·C) a frame (`input_layout`),
     which the pallas runner ships from the host as a free reshape of the
     C-contiguous batch (`ship_inputs`), and the kernel contracts them
     against the banded weights `host_weights` builds once. Fallback-only
     steps that fit in no segment run at the XLA level between kernels
     (zero extra launches).
  3. **Kernel-count target**: the paper's shape is one program per core, so
     the planner aims at `num_cores` kernels (`max_kernels` override in
     `BackendOptions`): when a pack at a reduced budget emits more, the
     budget doubles toward the scratchpad capacity and packing reruns. The
     capacity is a hard ceiling — a program that cannot fit `num_cores`
     segments of the scratchpad emits more kernels, never a segment larger
     than the scratchpad (the sanitizer's SPM002 re-checks this).
     `count_pallas_calls` counts the kernels of the traced function.

Every `pallas_call` states its VMEM limit from the bytes it holds in the
chip's tiled layout (`kernels.vmem`).

Bit-exactness: every emission path reuses the repo's single requant
definition (`requant_epilogue`) and exact int8 contractions, so the
megakernel is bit-identical to `run_numpy` / `reference_forward` on every
supported graph.
"""

from __future__ import annotations

import dataclasses
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from . import compiled as C
from ..kernels import vmem
from ..kernels.conv2d_im2col import (conv2d_vmem_bytes, conv_accumulate,
                                     fill_window, tap_windows,
                                     window_scratch)
from ..kernels.gemm_int8 import (dot_i32_exact, gemm_vmem_bytes,
                                 requant_epilogue)
from ..kernels.ref import _as_channel_mult, round_half_even_div
from ..kernels.stem_conv import (stem_bands, stem_input_shape,
                                 stem_vmem_bytes)

_ITEM_BYTES = {"int8": 1, "uint8": 1, "int16": 2, "int32": 4,
               "f32": 4, "bf16": 2}

# fallback capacity when the program carries no hardware model: the paper
# machine's 1 MiB worker scratchpad
_DEFAULT_BUDGET = 1 << 20

_WINDOWED = ("conv2d", "maxpool", "avgpool")


@dataclasses.dataclass(frozen=True)
class Segment:
    """A contiguous run of plan steps with one execution strategy.

    kind: "fused"   — one pallas_call replaying all steps scratch-resident;
          "tiled"   — one oversized gemm/conv step on the grid-scheduled
                      double-buffered tiled kernel (one pallas_call);
          "outside" — one fallback-mode step executed at the XLA level
                      between kernels (no pallas_call).
    """

    kind: str
    steps: tuple
    core: int = 0

    @property
    def emits_call(self) -> bool:
        return self.kind in ("fused", "tiled")


def _size(shape) -> int:
    n = 1
    for d in shape:
        n *= int(d)
    return n


def _buffer_bytes(prog: C.CompiledProgram, idx: int) -> int:
    _, shape, dtype = prog.buffers[idx]
    return _size(shape) * _ITEM_BYTES[dtype]


def _window_geometry(prog: C.CompiledProgram, b) -> tuple:
    """(padded input shape, pad, (oh, ow)) of a conv or pool batch: the
    padded copy its taps window over, and the output extent."""
    H, W, Cn = prog.buffers[b.in_idx[0]][1]
    p = b.attrs.get("padding", 0)
    oh, ow = prog.buffers[b.out_idx][1][:2]
    return (H + 2 * p, W + 2 * p, Cn), p, (oh, ow)


def _work_bytes(prog: C.CompiledProgram, step) -> int:
    """In-kernel working set of a conv or pool step beyond its operands:
    the padded int32 copy its taps window over plus one int32 tap window —
    what the fused body holds in place of an im2col patch matrix."""
    b = step.batch
    if b.kind not in _WINDOWED:
        return 0
    padded, _, (oh, ow) = _window_geometry(prog, b)
    return 4 * (_size(padded) + oh * ow * padded[2])


def _step_bytes(prog: C.CompiledProgram, step, dual: bool) -> int:
    """Scratchpad residency of one step: streamed operands (inputs +
    weights, double-buffered when the scratchpad is dual-ported) + int32
    accumulator for matmul kinds + the output tile + the windowed working
    set of conv and pool steps."""
    b = step.batch
    stream = sum(_buffer_bytes(prog, i) for i in b.in_idx)
    if b.w_idx is not None:
        stream += _buffer_bytes(prog, b.w_idx)
    if dual:
        stream *= 2
    acc = 0
    if b.kind in ("gemm", "conv2d"):
        acc = 4 * _size(prog.buffers[b.out_idx][1])
    return (stream + acc + _buffer_bytes(prog, step.out_idx)
            + _work_bytes(prog, step))


def _pack(prog: C.CompiledProgram, plan, budget: int, dual: bool
          ) -> list[Segment]:
    segments: list[Segment] = []
    cur: list = []
    cur_bytes = 0

    def flush():
        nonlocal cur, cur_bytes
        if cur:
            segments.append(Segment("fused", tuple(cur)))
            cur, cur_bytes = [], 0

    for step in plan:
        if step.mode == "skip":      # requant folded into its producer
            continue
        sb = _step_bytes(prog, step, dual)
        if step.mode == "jax":
            # fallback ops ride inside a fused segment when they fit;
            # otherwise they run at the XLA level (no kernel launch)
            if cur and cur_bytes + sb <= budget:
                cur.append(step)
                cur_bytes += sb
            else:
                flush()
                segments.append(Segment("outside", (step,)))
            continue
        if sb > budget or step.mode == "stem":
            # an oversized gemm/conv, or a stem, runs on its own kernel
            flush()
            segments.append(Segment("tiled", (step,)))
            continue
        if cur_bytes + sb <= budget:
            cur.append(step)
            cur_bytes += sb
        else:
            flush()
            cur, cur_bytes = [step], sb
    flush()
    return segments


def plan_segments(prog: C.CompiledProgram, *, budget: int | None = None,
                  max_kernels: int | None = None) -> list[Segment]:
    """Partition the pallas plan into kernel-emitting segments, none of
    which holds more than the machine's scratchpad.

    `budget` (`BackendOptions.scratchpad_budget`) packs against less than
    the capacity; a larger one is clamped to it. `max_kernels` (default:
    the program's core count) is a target: while the pack emits more
    kernels and the budget is below the capacity, the budget doubles
    (capped at the capacity) and packing reruns — larger segments, fewer
    launches. At the capacity the pack stands, however many kernels it
    emits.
    """
    plan = C._pallas_plan(prog)
    hw = prog.hw
    capacity = hw.scratchpad_bytes if hw is not None else _DEFAULT_BUDGET
    cap = max_kernels if max_kernels is not None else max(1, prog.num_cores)
    b = capacity if budget is None else min(budget, capacity)
    dual = hw.dual_ported if hw is not None else True
    while True:
        segments = _pack(prog, plan, b, dual)
        if sum(s.emits_call for s in segments) <= cap or b >= capacity:
            break
        b = min(2 * b, capacity)
    cores = max(1, prog.num_cores)
    out = []
    n_call = 0
    for seg in segments:
        if seg.emits_call:
            out.append(dataclasses.replace(seg, core=n_call % cores))
            n_call += 1
        else:
            out.append(seg)
    return out


def segment_footprint(prog: C.CompiledProgram, seg: Segment,
                      dual: bool = True) -> int:
    """Scratchpad bytes a fused segment keeps resident: the sum of its
    steps' streamed operands, accumulators, output tiles and windowed
    working sets — exactly the quantity `_pack` budgets against. Public so
    the static analyzer (repro.analysis) can check the packing instead of
    trusting it."""
    return sum(_step_bytes(prog, s, dual) for s in seg.steps)


def segment_io(prog: C.CompiledProgram, seg: Segment
               ) -> tuple[list[int], list[int], list[int]]:
    """Public alias of `_segment_io` for the static analyzer: the
    (streamed-in, weight, written-out) buffer indices of a segment."""
    return _segment_io(prog, seg)


# -- emission -----------------------------------------------------------------

def _mult_of(step):
    """The requant multiplier a fused step applies, if any: its fused
    epilogue's, or a standalone requant batch's own."""
    if step.mult is not None:
        return step.mult
    return step.batch.mult if step.batch.kind == "requant" else None


def _narrow(v: jax.Array, dtype: str) -> jax.Array:
    """An int32 value cast through its buffer dtype (wrapping like the
    oracle's astype) and widened back."""
    if dtype == "int32":
        return v
    return v.astype(C._JNP_DT[dtype]).astype(jnp.int32)


def _emit_step(step, local: dict, w_refs: dict, m_refs: dict,
               s_refs: dict, prog: C.CompiledProgram, via_f32: bool) -> None:
    """Execute one plan step on in-kernel int32 values. local maps buffer
    idx -> value; w_refs maps weight buffer idx -> weight ref; m_refs and
    s_refs map step out idx -> its (1, N) multiplier ref and its window
    scratch."""
    b = step.batch
    a = b.attrs
    out_dt = prog.buffers[step.out_idx][2]
    mult = m_refs[step.out_idx][...] if step.out_idx in m_refs else None
    if b.kind in ("gemm", "conv2d"):
        x = local[b.in_idx[0]]
        if b.kind == "gemm":
            acc = dot_i32_exact(x.reshape(a["M"], a["K"]).astype(jnp.int8),
                                w_refs[b.w_idx][...], via_f32=via_f32)
        else:
            _, pad, (oh, ow) = _window_geometry(prog, b)
            src = s_refs[step.out_idx]
            fill_window(src, x, pad, 0)
            acc = conv_accumulate(src, w_refs[b.w_idx], a["C_in"], a["kh"],
                                  a["kw"], a["stride"], oh, ow,
                                  via_f32=via_f32)
            acc = acc.reshape(oh, ow, a["C_out"])
        local[step.out_idx] = (_narrow(acc, out_dt) if mult is None else
                               requant_epilogue(acc, mult, jnp.int32))
        return
    ins = [local[i] for i in b.in_idx]
    if b.kind == "requant":
        out = requant_epilogue(ins[0], mult, jnp.int32)
    elif b.kind == "relu":
        out = jnp.maximum(ins[0], 0)
    elif b.kind == "add":
        s = ins[0] + ins[1]
        out = jnp.clip(s, -128, 127) if out_dt == "int8" else \
            _narrow(s, out_dt)
    elif b.kind in ("maxpool", "avgpool"):
        padded, pad, (oh, ow) = _window_geometry(prog, b)
        k = a["k"]
        src = s_refs[step.out_idx]
        in_dt = C._JNP_DT[prog.buffers[b.in_idx[0]][2]]
        fill_window(src, ins[0], pad, int(jnp.iinfo(in_dt).min)
                    if b.kind == "maxpool" else 0)
        taps = [chunks for _, chunks in tap_windows(
            src, padded[2], k, k, a["stride"], oh, ow)]
        red = jnp.maximum if b.kind == "maxpool" else jnp.add
        out = jnp.concatenate(
            [functools.reduce(red, [t[j][2] for t in taps])
             for j in range(len(taps[0]))], axis=-1)
        if b.kind == "avgpool":
            out = jnp.clip(round_half_even_div(out, k * k), -128, 127)
    elif b.kind == "gap":
        H, W = ins[0].shape[0], ins[0].shape[1]
        m = round_half_even_div(ins[0].sum(axis=(0, 1)).reshape(1, -1),
                                H * W)
        out = jnp.clip(m, -128, 127)
    elif b.kind == "concat":
        out = jnp.concatenate(ins, axis=-1)
    else:
        raise C.CompileError(f"op kind {b.kind} not lowered")
    local[step.out_idx] = out


def _segment_io(prog: C.CompiledProgram, seg: Segment
                ) -> tuple[list[int], list[int], list[int]]:
    """(external input idxs, weight idxs, output idxs) of a fused segment.

    Outputs are the produced buffers consumed by a later step outside the
    segment or that are graph outputs."""
    produced = {s.out_idx for s in seg.steps}
    ins: list[int] = []
    wids: list[int] = []
    for s in seg.steps:
        for i in s.batch.in_idx:
            if i not in produced and i not in ins:
                ins.append(i)
        w = s.batch.w_idx
        if w is not None and w not in wids:
            wids.append(w)
    graph_outs = set(prog.graph.outputs)
    consumed_outside: set[int] = set()
    for b in prog.batches:
        if b.op_idx in {s.batch.op_idx for s in seg.steps}:
            continue
        consumed_outside.update(b.in_idx)
    outs = [i for i in sorted(produced)
            if i in consumed_outside or prog.buffers[i][0] in graph_outs]
    return ins, wids, outs


def _tiled_bytes(shape, dtype: str) -> int:
    return vmem.tile_bytes(shape, _ITEM_BYTES[dtype])


def _weight_layout(prog: C.CompiledProgram, w_idx: int, seg: Segment):
    """Shape a fused kernel receives weight buffer `w_idx` in: (K, N) for
    a gemm, tap-major (kh*kw, C, N) for a conv."""
    shape = tuple(prog.buffers[w_idx][1])
    for s in seg.steps:
        a = s.batch.attrs
        if s.batch.w_idx == w_idx and s.batch.kind == "conv2d":
            return (a["kh"] * a["kw"], a["C_in"], shape[-1])
    return shape


def _fused_vmem_bytes(prog: C.CompiledProgram, seg: Segment) -> int:
    """VMEM a fused segment's kernel holds in the chip's tiled layout: its
    operand and output blocks (doubled: the batched program pipelines them
    over the batch grid) plus every step's int32 value, accumulator and
    windowed working set."""
    ins, wids, outs = _segment_io(prog, seg)
    blocks = sum(_tiled_bytes(prog.buffers[i][1], prog.buffers[i][2])
                 for i in ins + outs)
    blocks += sum(_tiled_bytes(_weight_layout(prog, i, seg), "int8")
                  for i in wids)
    values = 0
    for s in seg.steps:
        out_shape = prog.buffers[s.out_idx][1]
        values += 2 * _tiled_bytes(out_shape, "int32")
        if _mult_of(s) is not None:
            blocks += _tiled_bytes((1, out_shape[-1]), "f32")
        if s.batch.kind in _WINDOWED:
            padded, _, (oh, ow) = _window_geometry(prog, s.batch)
            values += (vmem.tile_bytes(window_scratch(*padded).shape, 4)
                       + 2 * _tiled_bytes((oh * ow, padded[2]), "int32"))
    return 2 * blocks + values


def segment_vmem_bytes(prog: C.CompiledProgram, seg: Segment) -> int:
    """VMEM bytes the kernel a segment emits holds (0 for "outside")."""
    if seg.kind == "fused":
        return _fused_vmem_bytes(prog, seg)
    if seg.kind == "outside":
        return 0
    step = seg.steps[0]
    a = step.batch.attrs
    requant = step.mult is not None
    if step.mode == "gemm":
        bm, bn, bk = step.blocks
        M, K, N, _ = step.gemm
        return gemm_vmem_bytes(M, K, N, bm=bm, bn=bn, bk=bk, requant=requant)
    if step.mode == "stem":
        return stem_vmem_bytes(C.stem_geometry_of(step.batch), requant)
    rows_t, bn = step.blocks
    return conv2d_vmem_bytes(a["H"], a["W"], a["C_in"], a["C_out"],
                             kh=a["kh"], kw=a["kw"], stride=a["stride"],
                             padding=a["padding"], rows_t=rows_t, bn=bn,
                             requant=requant)


def segment_name(index: int, seg: Segment) -> str:
    """The stable name of segment `index` of a plan: its index and its first
    graph op. Every segment runs under it as a `jax.named_scope`, and a
    fused segment's kernel carries it as its `pallas_call` name into the
    compiled program and the profiler's trace. A tiled segment's kernel is
    named after its shape instead (`conv2d_kernel_name`,
    `gemm_kernel_name`, `stem_kernel_name`): segments of one shape share
    one traced and lowered kernel, which a name per segment would
    multiply."""
    op = re.sub(r"[^0-9A-Za-z_]", "_", seg.steps[0].batch.name)
    return f"seg{index:03d}_{op}"


def _run_fused(prog: C.CompiledProgram, seg: Segment, vals: list,
               weights: dict, interpret: bool, name: str) -> None:
    ins, wids, outs = _segment_io(prog, seg)
    mults = [(s.out_idx, _mult_of(s)) for s in seg.steps
             if _mult_of(s) is not None]
    windowed = [s for s in seg.steps if s.batch.kind in _WINDOWED]
    steps = seg.steps
    n_in, n_w, n_m, n_o = len(ins), len(wids), len(mults), len(outs)

    def kernel(*refs):
        w_refs = dict(zip(wids, refs[n_in:n_in + n_w]))
        m_refs = {o: r for (o, _), r in
                  zip(mults, refs[n_in + n_w:n_in + n_w + n_m])}
        out_refs = refs[n_in + n_w + n_m:n_in + n_w + n_m + n_o]
        s_refs = {s.out_idx: r for s, r in
                  zip(windowed, refs[n_in + n_w + n_m + n_o:])}
        local = {i: r[...].astype(jnp.int32)
                 for i, r in zip(ins, refs[:n_in])}
        for step in steps:
            _emit_step(step, local, w_refs, m_refs, s_refs, prog,
                       via_f32=interpret)
        for i, r in zip(outs, out_refs):
            r[...] = local[i].astype(r.dtype)

    out_shape = [jax.ShapeDtypeStruct(tuple(prog.buffers[i][1]),
                                      C._JNP_DT[prog.buffers[i][2]])
                 for i in outs]
    operands = ([vals[i] for i in ins]
                + [weights[i].reshape(_weight_layout(prog, i, seg))
                   for i in wids]
                + [_as_channel_mult(m, prog.buffers[o][1][-1]).reshape(1, -1)
                   for o, m in mults])
    res = pl.pallas_call(
        kernel, out_shape=out_shape,
        scratch_shapes=[window_scratch(*_window_geometry(prog, s.batch)[0])
                        for s in windowed],
        compiler_params=vmem.compiler_params(_fused_vmem_bytes(prog, seg)),
        interpret=interpret, name=name)(*operands)
    for i, r in zip(outs, res):
        vals[i] = r


def _stem_steps(prog: C.CompiledProgram) -> list:
    return [s for s in C._pallas_plan(prog) if s.mode == "stem"]


def input_layout(prog: C.CompiledProgram) -> dict:
    """The per-frame shape the program takes each graph input in. An input
    a stem step reads comes as row groups (`stem_input_shape`: (H / s,
    s·W·C), a reshape of the C-contiguous (H, W, C) frame, which the host
    copies to the device without padding its channels out to 128 lanes);
    every other input keeps its graph shape. Cached on the program."""
    if "input_layout" not in prog._pallas_cache:
        layout = {name: tuple(prog.buffers[i][1])
                  for name, i in prog.input_idx.items()}
        for step in _stem_steps(prog):
            a = step.batch.attrs
            layout[prog.buffers[step.batch.in_idx[0]][0]] = \
                stem_input_shape(a["H"], a["W"], a["C_in"], a["stride"])
        prog._pallas_cache["input_layout"] = layout
    return prog._pallas_cache["input_layout"]


def ship_inputs(prog: C.CompiledProgram, inputs: dict) -> dict:
    """Host arrays, with or without a leading batch axis, reshaped to the
    layout the program takes (`input_layout`): free for C-contiguous
    arrays."""
    layout = input_layout(prog)
    out = {}
    for name, v in inputs.items():
        v = np.asarray(v)
        lead = v.ndim - len(prog.buffers[prog.input_idx[name]][1])
        out[name] = v.reshape(v.shape[:lead] + layout[name])
    return out


def megakernel_fn(prog: C.CompiledProgram, *, interpret: bool = False,
                  budget: int | None = None,
                  max_kernels: int | None = None):
    """The megakernel program as ``fn(weights, inputs) -> outputs`` over
    the segment plan (cached per (interpret, budget, max_kernels) on the
    program). `weights` maps weight buffer idx -> array
    (`device_weights`); taking them as arguments keeps them out of the
    executable instead of baking them in as constants. Each input is
    taken in its `input_layout` shape; one in its graph shape is reshaped
    to it inside the program (correct, but a copy on the device that a
    caller shipping the layout avoids)."""
    key = ("mega_fn", bool(interpret), budget, max_kernels)
    if key not in prog._pallas_cache:
        segments = plan_segments(prog, budget=budget,
                                 max_kernels=max_kernels)
        layout = input_layout(prog)

        def fn(weights: dict, inputs: dict):
            vals: list = [None] * len(prog.buffers)
            for name, i in prog.input_idx.items():
                vals[i] = inputs[name].reshape(layout[name])
            for index, seg in enumerate(segments):
                name = segment_name(index, seg)
                with jax.named_scope(name):
                    if seg.kind == "fused":
                        _run_fused(prog, seg, vals, weights, interpret, name)
                    elif seg.kind == "tiled":
                        C.run_kernel_step(prog, seg.steps[0], vals,
                                          weights, interpret)
                    else:            # "outside": XLA-level fallback op
                        b = seg.steps[0].batch
                        vals[b.out_idx] = C._jax_op(b, vals, prog, weights)
            return {name: vals[i] for name, i in prog.output_idx.items()}

        prog._pallas_cache[key] = fn
    return prog._pallas_cache[key]


def host_weights(prog: C.CompiledProgram) -> dict:
    """The weight operands of the program's kernels, by weight buffer idx:
    each weight as lowered, but a stem's as its band expansion
    (`kernels.stem_conv.stem_bands`), built here once."""
    weights = dict(prog.weights)
    for step in _stem_steps(prog):
        b = step.batch
        weights[b.w_idx] = stem_bands(weights[b.w_idx],
                                      C.stem_geometry_of(b))
    return weights


def device_weights(prog: C.CompiledProgram) -> dict:
    """The program's weight operands (`host_weights`) as device arrays,
    put once per program."""
    if "weights" not in prog._pallas_cache:
        prog._pallas_cache["weights"] = {
            i: jnp.asarray(w) for i, w in host_weights(prog).items()}
    return prog._pallas_cache["weights"]


def megakernel_single(prog: C.CompiledProgram, *, interpret: bool = False,
                      budget: int | None = None,
                      max_kernels: int | None = None):
    """Single-sample traced function ``single(inputs)`` over the segment
    plan, weights bound. Same calling convention as
    `compiled.jax_single`; bit-exact against `compiled.run_numpy`."""
    fn = megakernel_fn(prog, interpret=interpret, budget=budget,
                       max_kernels=max_kernels)
    return functools.partial(fn, device_weights(prog))


def jit_megakernel_single(prog: C.CompiledProgram, *,
                          interpret: bool | None = None,
                          budget: int | None = None,
                          max_kernels: int | None = None):
    interpret = C.resolve_interpret(interpret)
    key = ("mega_jit_single", bool(interpret), budget, max_kernels)
    if key not in prog._pallas_cache:
        prog._pallas_cache[key] = functools.partial(
            jax.jit(megakernel_fn(prog, interpret=interpret, budget=budget,
                                  max_kernels=max_kernels)),
            device_weights(prog))
    return prog._pallas_cache[key]


def megakernel_batched(prog: C.CompiledProgram, *,
                       interpret: bool | None = None,
                       budget: int | None = None,
                       max_kernels: int | None = None):
    """The megakernel program jitted and vmapped over a leading batch axis
    of the inputs (the `pallas` backend's batched serving step)."""
    interpret = C.resolve_interpret(interpret)
    key = ("mega_batched", bool(interpret), budget, max_kernels)
    if key not in prog._pallas_cache:
        prog._pallas_cache[key] = functools.partial(
            jax.jit(jax.vmap(megakernel_fn(
                prog, interpret=interpret, budget=budget,
                max_kernels=max_kernels), in_axes=(None, 0))),
            device_weights(prog))
    return prog._pallas_cache[key]


def run_megakernel(prog: C.CompiledProgram, inputs: dict,
                   interpret: bool | None = None) -> dict:
    """Convenience wrapper: one unbatched sample; numpy in (shipped in the
    program's `input_layout`), numpy out."""
    fn = jit_megakernel_single(prog, interpret=interpret)
    out = fn({k: jnp.asarray(v) for k, v in
              ship_inputs(prog, inputs).items()})
    return {k: np.asarray(v) for k, v in out.items()}


# -- invariants ---------------------------------------------------------------

def _sub_jaxprs(v):
    """Duck-typed sub-jaxpr discovery in eqn params (pjit bodies, cond
    branches come as lists) — avoids version-fragile core imports."""
    items = v if isinstance(v, (list, tuple)) else (v,)
    for item in items:
        inner = getattr(item, "jaxpr", item)  # ClosedJaxpr -> Jaxpr
        if hasattr(inner, "eqns"):
            yield inner


def _pallas_eqn_names(jaxpr) -> list[str]:
    names = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            names.append(str(eqn.params["name"]))
        for v in eqn.params.values():
            for sub in _sub_jaxprs(v):
                names += _pallas_eqn_names(sub)
    return names


def pallas_call_names(fn, sample_inputs: dict) -> list[str]:
    """The names of the pallas_call equations in `fn`'s jaxpr (recursing
    into sub-jaxprs), in program order."""
    return _pallas_eqn_names(jax.make_jaxpr(fn)(sample_inputs).jaxpr)


def count_pallas_calls(fn, sample_inputs: dict) -> int:
    """Number of pallas_call equations in `fn`'s jaxpr — the kernel count
    the plan promises."""
    return len(pallas_call_names(fn, sample_inputs))
