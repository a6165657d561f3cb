"""The benchmark harness: one cell, one seed, one run, in one process.

Everything that belongs to one configuration, traffic mix, cell or metric
is a file that this module finds by the name `BENCHMARK.json` gives it:

  configs/<config>.json   the configuration as it is run (its `file`)
  configs/<ref>.py        its plain reference (`reference` in the file)
  traffic/<mix>.json      a traffic mix, read by `traffic.py`
  cells/<cell>.json       data of one cell, such as its fixed rate
  metrics/<metric>.py     the reader of one metric; a metric `a.b` falls
                          back to `metrics/a.py` when `a.b.py` is absent

A run builds the network and its weights from the seed, registers it with
a `repro.serve.Server` on the configuration's backend, warms up the one
program shape the mix uses, then acts as the time-triggered executive: it
submits each frame at its wall-clock due time (open loop) or keeps the
queue full (closed loop) and calls `Server.step()`. After the window it
compares served outputs with the plain reference.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import gc
import importlib
import importlib.util
import json
import math
import os
import shutil
import sys
import time

import numpy as np

import counting
import reference
import traffic

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
OUT_DIR = os.path.join(HERE, "out")
WARM_CALLS = 2           # full calls before the window: compile, then steady
DRAIN_S = 60.0           # a frame still queued this long after the close is lost
HOST_TRACER_LEVEL = 1    # user spans and JAX's own; 2 adds runtime internals
TRACE_WINDOW_S = 3.0     # the traced window, after the untraced one
TRACE_K0 = 10_000_000    # frame indices of the traced window start here


class BenchError(RuntimeError):
    """The run cannot measure: no result line is printed."""


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not os.path.exists(path):
        raise BenchError(f"no module at {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# -- the cell, from files --------------------------------------------------

@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    mix: dict
    data: dict                      # cells/<cell>.json, {} where absent
    end_to_end: list[dict]
    per_layer: list[dict]

    @property
    def rate_hz(self) -> float | None:
        return self.data.get("rate_hz")


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(workload: str) -> Cell:
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    wl = next((w for w in bench["workloads"] if w["name"] == workload), None)
    if wl is None:
        raise BenchError(f"no workload {workload!r} in BENCHMARK.json")
    entry = next(c for c in bench["configs"] if c["name"] == wl["config"])
    cell_file = os.path.join(HERE, "cells", f"{workload}.json")
    return Cell(
        name=workload, chips=int(wl["chips"]),
        config=load_json(os.path.join(ROOT, entry["file"])),
        mix=load_json(os.path.join(HERE, "traffic", f"{wl['traffic']}.json")),
        data=load_json(cell_file) if os.path.exists(cell_file) else {},
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, workload)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, workload)])


def reference_net(config: dict) -> reference.Net:
    mod = load_module(os.path.join(HERE, "configs", f"{config['reference']}.py"),
                      f"chipbench_ref_{config['reference']}")
    return mod.network(**config["kwargs"])


def metric_reader(name: str):
    for stem in (name, name.split(".")[0]):
        path = os.path.join(HERE, "metrics", f"{stem}.py")
        if os.path.exists(path):
            return load_module(path, f"chipbench_metric_{stem}").read
    raise BenchError(f"no reader for metric {name!r} under metrics/")


def peaks_for(device_kind: str) -> dict:
    table = load_json(os.path.join(HERE, "peaks.json"))
    if device_kind not in table:
        raise BenchError(f"device kind {device_kind!r} is not in peaks.json")
    return table[device_kind]


# -- weights ---------------------------------------------------------------

def make_params(net: reference.Net, seed: int, gain: float) -> dict:
    """int8 weights in [-64, 64) drawn on the device in one jitted call from
    the seed (any non-negative integer below 2**64) as one flat array, cut
    into the layers' weights on the host (views, no copy); then the requant
    multipliers. The program and the reference both take numpy."""
    import jax
    import jax.numpy as jnp
    specs = sorted(net.weights.items())
    total = sum(math.prod(shape) for _, shape in specs)

    def draw(lo, hi):
        key = jax.random.fold_in(jax.random.fold_in(jax.random.key(0), lo), hi)
        return jax.random.randint(key, (total,), -64, 64, dtype=jnp.int8)

    words = (np.uint32(seed & 0xFFFFFFFF), np.uint32((seed >> 32) & 0xFFFFFFFF))
    flat = np.asarray(jax.device_get(jax.jit(draw)(*words)))
    params, at = {}, 0
    for name, shape in specs:
        n = math.prod(shape)
        params[name] = flat[at:at + n].reshape(shape)
        at += n
    params.update(net.mult_values(gain))
    return params


# -- the system under test -------------------------------------------------

def _import(path: str):
    mod, _, attr = path.partition(":")
    return getattr(importlib.import_module(mod), attr)


def build_graph(config: dict, net: reference.Net):
    """The program's graph of the configuration, checked against the
    reference: the same weight names and shapes, the same multipliers,
    as many outputs as the reference has, the graph's i-th output of the
    shape of the reference's i-th (`Net.outputs`: the graph may name an
    output after an activation the reference folds into its layer), and
    every tensor the reference computes (`<layer>.out`) present in the
    graph with the same shape, so a program whose layers change shape
    (a pool's padding, a stride) is refused rather than measured."""
    kwargs = {k: tuple(v) if isinstance(v, list) else v
              for k, v in config["kwargs"].items()}
    g = _import(config["builder"])(**kwargs)
    want_w = {w: tuple(g.tensors[w].shape) for op in g.ops for w in op.weights}
    want_m = {f"{op.name}.mult" for op in g.ops if op.kind == "requant"}
    if want_w != net.weights or want_m != set(net.mults):
        raise BenchError(f"{config['name']}: the program's graph and the "
                         f"reference name different parameters")
    if [tuple(g.tensors[t].shape) for t in g.outputs] \
            != [net.shapes[t] for t in net.outputs]:
        raise BenchError(f"{config['name']}: the program's outputs and the "
                         f"reference's differ in number or shape")
    differ = sorted(t for t, shape in net.shapes.items()
                    if t not in g.tensors or tuple(g.tensors[t].shape) != shape)
    if differ:
        raise BenchError(f"{config['name']}: the program's graph has other "
                         f"shapes than the reference for {differ[:4]}")
    return g


def build_server(config: dict, mix: dict, graph, params: dict):
    from repro.serve import Server
    machine = _import(config["machine"]["factory"])(*config["machine"]["args"])
    srv = Server(machine, backend=config["backend"],
                 num_cores=config["num_cores"],
                 queue_capacity=mix["queue_capacity"])
    srv.register(config["name"], graph, period_s=config["period_s"],
                 params=params, slots=mix["slots"])
    return srv


# -- one window ------------------------------------------------------------

@dataclasses.dataclass
class Frame:
    k: int
    due: float
    step_start: float | None = None
    done: float | None = None
    status: str = "queued"

    @property
    def latency_s(self) -> float:
        """From due time to output on the host; inf where none came."""
        return self.done - self.due if self.status == "done" else math.inf


@dataclasses.dataclass
class Step:
    start: float
    end: float
    frames: int
    runner_s: float
    in_window: bool


@dataclasses.dataclass
class Record:
    """What a run saw; the metric readers read it."""

    frames: list[Frame]
    steps: list[Step]
    window: tuple[float, float]
    setup_s: float
    net: reference.Net
    peaks: dict
    trace: dict | None = None
    trace_steps: list[Step] = dataclasses.field(default_factory=list)

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    @property
    def window_steps(self) -> list[Step]:
        return [s for s in self.steps if s.in_window]


def _spans(on: bool):
    if not on:
        return lambda name: contextlib.nullcontext()
    import jax
    return jax.profiler.TraceAnnotation


class Executive:
    """Submits frames and steps the Server, recording every frame and step.
    Every output of each served frame is kept, by frame index, as a list
    in the order of the graph's outputs, for the check."""

    def __init__(self, srv, name: str, frames: traffic.Frames, span,
                 k0: int = 0):
        from repro.serve import BackpressureError
        self.srv, self.name, self.frames, self.span = srv, name, frames, span
        self.k0 = k0                     # index of this executive's first frame
        self.refused = BackpressureError
        self.pending: collections.deque = collections.deque()
        self.records: list[Frame] = []
        self.steps: list[Step] = []
        self.outputs: dict[int, list[np.ndarray]] = {}
        self.out_names = list(srv.specs[0].graph.outputs)
        self.in_window = True

    @property
    def queued(self) -> int:
        """Frames waiting in the Server's queue for this network."""
        return self.srv.queue_depths()[self.name]

    def submit(self, k: int, due: float, frame: np.ndarray) -> None:
        rec = Frame(k, due)
        self.records.append(rec)
        with self.span("chipbench.submit"):
            try:
                self.pending.append((rec, self.srv.submit(self.name, frame)))
            except self.refused:
                rec.status = "refused"

    def step(self) -> None:
        clock = time.perf_counter
        with self.span("chipbench.step"):
            s = clock()
            self.srv.step()
            e = clock()
        n, runner_s, waiting = 0, 0.0, collections.deque()
        for rec, t in self.pending:
            if not t.terminal:
                waiting.append((rec, t))
                continue
            rec.step_start, rec.status = s, t.status
            if t.status == "done":
                rec.done = e
                res = t.result()
                runner_s = res.latency_s
                self.outputs[rec.k] = [res.output[t] for t in self.out_names]
                n += 1
        self.pending = waiting
        self.steps.append(Step(s, e, n, runner_s, self.in_window))

    def drain(self, limit_s: float) -> None:
        """Step until no frame is queued, for at most `limit_s` seconds;
        a frame whose ticket is still not terminal then is lost."""
        self.in_window = False
        stop = time.perf_counter() + limit_s
        while self.queued and time.perf_counter() < stop:
            self.step()


def wait_until(t: float) -> None:
    """Sleep to within half a millisecond of t, then spin."""
    while True:
        left = t - time.perf_counter()
        if left <= 0:
            return
        if left > 6e-4:
            time.sleep(left - 5e-4)


def drive_open(d: Executive, offsets: np.ndarray, seconds: float
               ) -> tuple[float, float]:
    """Open loop: frame i is submitted at t0 + offsets[i]; after each
    submission the executive steps until the queue is empty."""
    n = len(offsets)
    nxt = d.frames(d.k0)
    with d.span("chipbench.window"):
        t0 = time.perf_counter()
        dues = t0 + offsets
        i = 0
        while i < n:
            if dues[i] > time.perf_counter():
                with d.span("chipbench.wait"):
                    wait_until(dues[i])
            while i < n and dues[i] <= time.perf_counter():
                d.submit(d.k0 + i, dues[i], nxt)
                i += 1
                if i < n:
                    nxt = d.frames(d.k0 + i)
            while d.queued:
                d.step()
        with d.span("chipbench.wait"):
            wait_until(t0 + seconds)
        t1 = time.perf_counter()
    return t0, t1


def drive_closed(d: Executive, depth: int, seconds: float
                 ) -> tuple[float, float]:
    """Closed loop: before each step the queue is topped up to `depth`
    frames; the window ends with the last step started before t0+seconds."""
    k = d.k0
    with d.span("chipbench.window"):
        t0 = time.perf_counter()
        end = t0 + seconds
        while time.perf_counter() < end:
            while d.queued < depth:
                d.submit(k, time.perf_counter(), d.frames(k))
                k += 1
            d.step()
        t1 = time.perf_counter()
    return t0, t1


def warm_up(srv, name: str, frames: traffic.Frames, slots: int) -> None:
    """WARM_CALLS full calls at the cell's one shape (slots frames each)."""
    d = Executive(srv, name, frames, _spans(False))
    for c in range(WARM_CALLS):
        for j in range(slots):
            d.submit(-1 - c * slots - j, time.perf_counter(), frames(j))
        d.drain(DRAIN_S)
        if any(r.status != "done" for r in d.records):
            raise BenchError("warm-up frames were not served")


# -- the check -------------------------------------------------------------

def compare(net: reference.Net, params: dict, frames: traffic.Frames,
            outputs: dict, records: list[Frame], seed: int, sample: int,
            arith: str = "int8") -> dict:
    """Every frame of the window must come back ("lost_frames"); a sample
    of the served ones, drawn from the seed with the first and the last
    served frame in it, must equal the reference bit for bit in every
    output: a frame counts once in "mismatched_frames" if any of its
    outputs differs, or if it has another number of outputs, and
    "max_abs_diff" is the largest gap over all outputs. `outputs` holds
    each served frame's outputs as a list in the order of `net.outputs`.
    Returns ({name: (value, limit)}, number of frames compared).
    `arith` computes the reference itself in another precision (the
    control reads its gap from the int8 reference instead of the program).
    """
    lost = sum(r.status != "done" for r in records)
    served = sorted(outputs)
    rng = np.random.default_rng([seed, 0x636865636B])
    picks = set(rng.choice(served, size=min(sample, len(served)),
                           replace=False).tolist()) if served else set()
    picks |= {served[0], served[-1]} if served else set()
    mism, gap = 0, 0
    for k in sorted(picks):
        want = list(reference.forward(net, params, frames(k)).values())
        got = list(outputs[k]) if arith == "int8" else list(
            reference.forward(net, params, frames(k), arith).values())
        bad = len(got) != len(want)
        for g, w in zip(got, want):
            diff = np.abs(np.asarray(g, np.int64).reshape(w.shape)
                          - w.astype(np.int64))
            bad |= bool(diff.any())
            gap = max(gap, int(diff.max()))
        mism += int(bad)
    return {"lost_frames": (lost, 0), "mismatched_frames": (mism, 0),
            "max_abs_diff": (gap, 0)}, len(picks)


# -- a run -----------------------------------------------------------------

class CompileLog:
    """Counts backend compiles and persistent-cache hits from JAX's
    monitoring events, so a compile inside the window shows."""

    def __init__(self):
        import jax.monitoring as mon
        self.compiles = 0
        self.compile_s = 0.0
        self.hits = 0
        mon.register_event_duration_secs_listener(self._duration)
        mon.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += secs

    def _event(self, event: str, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1


def log(msg: str) -> None:
    print(f"chipbench: {msg}", file=sys.stderr, flush=True)


def enable_cache() -> None:
    """JAX's persistent compilation cache at a fixed path in the checkout,
    for every program however short its compile."""
    import jax
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def device_info(chips: int, require_tpu: bool = True) -> dict:
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise BenchError(f"JAX's platform is {devs[0].platform!r}, not "
                         f"'tpu': the benchmark measures the chip only")
    if len(devs) < chips:
        raise BenchError(f"the cell asks for {chips} chips, JAX sees "
                         f"{len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


@dataclasses.dataclass
class Session:
    """A cell set up for measuring: the warmed-up Server and what the check
    and the readers need beside it."""

    cell: Cell
    device: dict
    peaks: dict
    net: reference.Net
    params: dict
    frames: traffic.Frames
    srv: object
    compiles: CompileLog
    phases: dict


def set_up(cell: Cell, seed: int, t_start: float,
           require_tpu: bool = True) -> Session:
    """Everything before the window; `require_tpu=False` skips the look for
    a chip (tests on the CPU) and takes the first peaks of the table."""
    enable_cache()
    device = device_info(cell.chips, require_tpu)
    peaks = peaks_for(device["kind"]) if require_tpu else \
        next(iter(load_json(os.path.join(HERE, "peaks.json")).values()))
    compiles = CompileLog()
    phases = {"start": time.perf_counter() - t_start}
    t = time.perf_counter()

    def phase(name: str) -> None:
        nonlocal t
        phases[name] = time.perf_counter() - t
        t = time.perf_counter()

    import jax
    jax.block_until_ready(jax.device_put(np.zeros(8, np.int8)))
    phase("runtime")                  # the chip's runtime starts on first use
    cfg, mix = cell.config, cell.mix
    net = reference_net(cfg)
    graph = build_graph(cfg, net)
    phase("graph")
    params = make_params(net, seed, cfg["requant_gain"])
    phase("weights")
    frames = traffic.Frames(net.shapes["input"], mix["frame_pool"], seed)
    phase("frames")
    srv = build_server(cfg, mix, graph, params)
    phase("register")
    warm_up(srv, cfg["name"], frames, mix["slots"])
    phase("warm_up")
    return Session(cell, device, peaks, net, params, frames, srv, compiles,
                   phases)


def _trace_runner(srv, name: str) -> None:
    """Wrap the network's runner in a `chipbench.runner` span (the Server
    has no public hook for it yet)."""
    import jax
    st = srv._nets[name]
    inner = st.runner

    def runner(batch):
        with jax.profiler.TraceAnnotation("chipbench.runner"):
            return inner(batch)
    st.runner = runner


def window(ses: Session, seconds: float, trace: bool,
           rate_hz: float | None = None, k0: int = 0) -> tuple[Executive, tuple]:
    """Drive one measured window; the caller drains what is left."""
    mix, name = ses.cell.mix, ses.cell.config["name"]
    d = Executive(ses.srv, name, ses.frames, _spans(trace), k0)
    gc.collect()
    gc.freeze()
    if mix["loop"] == "open":
        offsets = traffic.open_loop_offsets(
            mix, rate_hz or ses.cell.rate_hz, seconds)
        bounds = drive_open(d, offsets, seconds)
    else:
        bounds = drive_closed(d, mix["depth"], seconds)
    return d, bounds


def run(cell: Cell, seed: int, seconds: float, trace: bool, t_start: float,
        require_tpu: bool = True) -> dict:
    """One run of one cell; returns the result line as a dict.

    The measured window runs with the profiler off. With `trace`, a second,
    traced window of TRACE_WINDOW_S follows at the same load: the host-clock
    readers read the first window, the device readers the second (the
    profiler slows each call on the host several-fold, so host times taken
    under it would measure the profiler). Both windows' frames are checked.
    """
    import jax
    ses = set_up(cell, seed, t_start, require_tpu)
    compiles_before = ses.compiles.compiles
    setup_s = time.perf_counter() - t_start
    d, bounds = window(ses, seconds, False)
    window_compiles = ses.compiles.compiles - compiles_before
    d.drain(DRAIN_S)
    rec = Record(d.records, d.steps, bounds, setup_s, ses.net, ses.peaks)
    checked, outputs = list(d.records), dict(d.outputs)
    device, extra = dict(ses.device), {}
    if trace:
        import trace_reduce
        trace_dir = os.path.join(OUT_DIR, f"trace-{cell.name}")
        shutil.rmtree(trace_dir, ignore_errors=True)
        _trace_runner(ses.srv, cell.config["name"])
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = HOST_TRACER_LEVEL
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        td, _ = window(ses, min(seconds, TRACE_WINDOW_S), True, k0=TRACE_K0)
        td.drain(DRAIN_S)
        jax.profiler.stop_trace()
        t = time.perf_counter()
        rec.trace = trace_reduce.reduce_dir(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        log(f"trace reduced in {time.perf_counter() - t:.1f} s; device "
            f"clock offset {rec.trace['clock_offset_s'] * 1e3:.3f} ms")
        rec.trace_steps = [s for s in td.steps if s.in_window]
        checked += td.records
        outputs.update(td.outputs)
        device["busy_s"] = rec.trace["busy_s"]
        device["window_s"] = rec.trace["window_s"]
        extra["breakdown"] = {"device_ops": rec.trace["device_ops"],
                              "idle_gaps": rec.trace["idle_gaps"]}
        td = None
    device["memory_peak_bytes"] = int(max(
        dev.memory_stats().get("peak_bytes_in_use", 0)
        for dev in jax.devices()[:cell.chips]) if require_tpu else 0)
    ses.srv = d = None                   # the program's state, before the check
    t = time.perf_counter()
    checks, n_checked = compare(ses.net, ses.params, ses.frames, outputs,
                                checked, seed, cell.mix["check_sample"])
    check_s = time.perf_counter() - t

    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        v = metric_reader(m["name"])(rec)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    log("setup phases (s): " + ", ".join(f"{k} {v:.3f}"
                                         for k, v in ses.phases.items()))
    log(f"compiles: {ses.compiles.compiles} ({ses.compiles.compile_s:.2f} s, "
        f"{ses.compiles.hits} persistent-cache hits), {window_compiles} "
        f"inside the window")
    latencies = [f.latency_s for f in rec.frames]
    log(f"window {rec.window_s:.3f} s, {len(rec.frames)} frames, "
        f"{sum(s.frames for s in rec.window_steps)} served in "
        f"{len(rec.window_steps)} window steps, latency samples "
        f"{len(latencies)} ({sum(map(math.isinf, latencies))} missing), "
        f"{n_checked} compared with the reference in {check_s:.1f} s")
    correct = all(v <= lim for v, lim in checks.values())
    failed = checks["lost_frames"][0] + checks["mismatched_frames"][0]
    for name, (v, lim) in checks.items():
        log(f"check {name}: {v} (limit {lim})")
    return {"correct": correct, "attempted": len(checked),
            "failed": failed, "metrics": metrics, "device": device, **extra,
            "checks": {k: {"value": v, "limit": lim}
                       for k, (v, lim) in checks.items()}}
