"""The span recorder (`repro.tracing`) and the spans and counters of the
serving path: `Server` (submit, queue, step, batch, call, account) and the
pallas runner (h2d, launch, fetch); the kernel names of the megakernel."""

import gc

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import tracing
from repro.core import cnn
from repro.core import megakernel as MK
from repro.hw import scaled_paper_machine
from repro.models.config import ModelConfig
from repro.serve import Server

HW = scaled_paper_machine(4)


@pytest.fixture
def rec():
    tracing.disable()
    yield tracing
    tracing.disable()


def _frame(seed=0):
    return np.random.default_rng(seed).integers(
        -64, 64, (32, 32, 3)).astype(np.int8)


def _cnn_server(backend, slots=2):
    srv = Server(HW, backend=backend, num_cores=4)
    srv.register("cnn", cnn.small_cnn(), period_s=1 / 50, slots=slots)
    return srv


def _serve_one_job(srv, frames):
    tickets = [srv.submit("cnn", f) for f in frames]
    while not all(t.terminal for t in tickets):
        srv.step()
    return tickets


def _names(spans, idx):
    return sorted(spans[i].name for i in idx)


def _children(spans, parent):
    return [i for i, s in enumerate(spans) if s.parent == parent]


def _ancestors(spans, i):
    out = []
    while spans[i].parent is not None:
        i = spans[i].parent
        out.append(spans[i].name)
    return out


# -- the recorder -------------------------------------------------------------

def test_off_returns_the_shared_no_op_and_records_nothing(rec):
    assert rec._active is None
    a, b = rec.span("x"), rec.span("y", ref=3)
    assert a is b is rec._NO_SPAN
    with a as got:
        assert got is None
    srv = _cnn_server("jax")
    _serve_one_job(srv, [_frame()])
    assert rec.drain() == [] and rec.dropped_spans() == 0
    rec.enable(capacity=8)
    assert rec.drain() == []          # nothing of the served job was kept


def test_spans_nest_and_carry_their_parent_index(rec):
    rec.enable(capacity=16)
    with rec.span("a", ref=1):
        with rec.span("b"):
            pass
        rec.record("c", 5, 7, ref="t")
    spans = rec.drain()
    assert [s.name for s in spans] == ["b", "c", "a"]
    a = spans[2]
    assert a.parent is None and a.ref == 1 and a.end_ns >= a.start_ns
    assert spans[0].parent == 2 and spans[1].parent == 2
    assert (spans[1].start_ns, spans[1].end_ns, spans[1].ref) == (5, 7, "t")
    assert rec.drain() == []


def test_a_full_store_counts_dropped_spans_and_does_not_grow(rec):
    gc.disable()                      # no repro.gc span takes a slot
    try:
        rec.enable(capacity=3)
        for i in range(5):
            with rec.span("s", ref=i):
                pass
        assert rec.dropped_spans() == 2
        spans = rec.drain()
    finally:
        gc.enable()
    assert [s.ref for s in spans] == [0, 1, 2]
    assert len(rec._active.slots) == 3


def test_enable_replaces_the_recorder_and_disable_unhooks_gc(rec):
    rec.enable(capacity=4)
    rec.enable(capacity=4)
    hooks = [cb for cb in gc.callbacks
             if getattr(cb, "__self__", None).__class__ is tracing.Recorder]
    assert len(hooks) == 1
    rec.disable()
    assert not any(getattr(cb, "__self__", None).__class__ is tracing.Recorder
                   for cb in gc.callbacks)
    with pytest.raises(ValueError):
        rec.enable(capacity=0)


def test_a_compile_inside_a_span_is_recorded_under_it(rec):
    rec.enable(capacity=64)
    with rec.span("outer"):
        jax.jit(lambda x: x * 3 + 1)(jnp.ones((3, 7, 5)))
    spans = rec.drain()
    outer = next(i for i, s in enumerate(spans) if s.name == "outer")
    compiles = [s for s in spans if s.name == "repro.compile"]
    assert compiles and all(s.parent == outer for s in compiles)
    assert "backend_compile_duration" in {s.ref for s in compiles}


def test_annotate_records_the_same_spans(rec):
    rec.enable(capacity=8, annotate=True)
    with rec.span("annotated", ref=9):
        pass
    (s,) = [s for s in rec.drain() if s.name == "annotated"]
    assert s.ref == 9 and s.parent is None


# -- the serving path ---------------------------------------------------------

def test_server_and_runner_spans_nest_per_job(rec):
    srv = _cnn_server("pallas")
    _serve_one_job(srv, [_frame()])          # compile outside the recording
    rec.enable(capacity=256)
    tickets = _serve_one_job(srv, [_frame(1), _frame(2)])
    spans = rec.drain()
    steps = [i for i, s in enumerate(spans) if s.name == "repro.server.step"
             and "repro.server.batch" in _names(spans, _children(spans, i))]
    assert len(steps) == 1
    (step,) = steps
    kids = _children(spans, step)
    assert _names(spans, kids) == [
        "repro.server.account", "repro.server.batch", "repro.server.call",
        "repro.server.queue", "repro.server.queue"]
    seq = spans[step].ref
    for i in kids:
        if spans[i].name != "repro.server.queue":
            assert spans[i].ref == seq
    call = next(i for i in kids if spans[i].name == "repro.server.call")
    assert _names(spans, _children(spans, call)) == [
        "repro.runner.fetch", "repro.runner.h2d", "repro.runner.launch"]
    runner = [spans[i] for i in _children(spans, call)]
    assert all(spans[call].start_ns <= s.start_ns <= s.end_ns
               <= spans[call].end_ns for s in runner)
    assert [s.name for s in sorted(runner, key=lambda s: s.start_ns)] == [
        "repro.runner.h2d", "repro.runner.launch", "repro.runner.fetch"]
    queue = [spans[i] for i in kids if spans[i].name == "repro.server.queue"]
    assert sorted(s.ref for s in queue) == sorted(t.tid for t in tickets)
    batch = next(spans[i] for i in kids
                 if spans[i].name == "repro.server.batch")
    assert all(s.end_ns == batch.start_ns for s in queue)
    submits = [s for s in spans if s.name == "repro.server.submit"]
    assert sorted(s.ref for s in submits) == sorted(t.tid for t in tickets)
    assert {s.start_ns for s in submits} == {s.start_ns for s in queue}
    # the call span holds the interval the ticket's latency_s measures
    call_s = (spans[call].end_ns - spans[call].start_ns) / 1e9
    assert 0 < tickets[0].result().latency_s <= call_s
    assert rec.dropped_spans() == 0


def test_a_staged_call_copies_its_input_in_the_step_before(rec):
    srv = _cnn_server("pallas")
    _serve_one_job(srv, [_frame()])          # compile outside the recording
    rec.enable(capacity=256)
    tickets = _serve_one_job(srv, [_frame(k) for k in range(4)])
    spans = rec.drain()
    assert srv.metrics["staged_calls"] == 1
    first, second = [i for i, s in enumerate(spans)
                     if s.name == "repro.server.step"]
    calls = [next(i for i in _children(spans, step)
                  if spans[i].name == "repro.server.call")
             for step in (first, second)]
    # the first call copies its own input, launches, copies the second
    # call's input while its program runs, then fetches
    kids = sorted(_children(spans, calls[0]), key=lambda i: spans[i].start_ns)
    assert [spans[i].name for i in kids] == [
        "repro.runner.h2d", "repro.runner.launch", "repro.server.stage",
        "repro.runner.fetch"]
    assert spans[kids[2]].ref == spans[first].ref
    assert _names(spans, _children(spans, kids[2])) == ["repro.runner.h2d"]
    # the staged call launches and fetches, and copies nothing
    kids = sorted(_children(spans, calls[1]), key=lambda i: spans[i].start_ns)
    assert [spans[i].name for i in kids] == [
        "repro.runner.launch", "repro.runner.fetch"]
    assert "repro.runner.h2d" not in {
        s.name for s in spans if s.start_ns >= spans[second].start_ns}
    # latency_s is the whole call, the copy staged inside it included
    for call, t in zip(calls, (tickets[0], tickets[2])):
        call_s = (spans[call].end_ns - spans[call].start_ns) / 1e9
        assert 0 < t.result().latency_s <= call_s
    stage_s = sum(spans[i].end_ns - spans[i].start_ns
                  for i in _children(spans, calls[0])
                  if spans[i].name == "repro.server.stage") / 1e9
    assert 0 < stage_s < tickets[0].result().latency_s
    assert rec.dropped_spans() == 0


def test_a_short_batch_counts_padded_slots(rec):
    srv = _cnn_server("jax", slots=4)
    _serve_one_job(srv, [_frame(0)])
    _serve_one_job(srv, [_frame(k) for k in range(4)])
    m = srv.metrics
    assert (m["runner_calls"], m["slots_filled"], m["slots_padded"]) \
        == (2, 5, 3)
    assert srv.telemetry()["metrics"]["slots_padded"] == 3
    assert "runner_calls=2 (slots filled 5, padded 3)" in srv.summary()


def test_a_collection_inside_a_step_is_a_child_of_the_step(rec):
    cfg = ModelConfig(name="tiny_lm", family="dense", num_layers=2,
                      d_model=128, num_heads=4, num_kv_heads=4, d_ff=256,
                      vocab_size=512, act="swiglu")
    srv = Server(HW, backend="numpy", num_cores=4)
    srv.register("lm", cfg, period_s=1 / 25, cache_len=64,
                 step_fn=lambda tok: gc.collect())
    rec.enable(capacity=256)
    t = srv.submit("lm", 1)
    while not t.terminal:
        srv.step()
    spans = rec.drain()
    forced = [i for i, s in enumerate(spans)
              if s.name == "repro.gc" and s.ref == 2]
    assert forced
    assert all(_ancestors(spans, i)[:2]
               == ["repro.server.call", "repro.server.step"] for i in forced)


def test_every_megakernel_pallas_call_has_a_stable_name():
    """A fused kernel carries its segment's name (unique in the program); a
    tiled kernel its shape, shared by the segments of one shape: a GEMM's
    (M, K, N), pointwise convs included, or a windowed conv's geometry. A
    64 KiB scratchpad splits the reduced ResNet-50 into fused, tiled and
    XLA-level segments."""
    from repro.core import analyze, init_params, lower_program
    from repro.kernels.conv2d_im2col import conv2d_kernel_name
    from repro.kernels.gemm_int8 import gemm_kernel_name

    def expected(prog, index, seg):
        if seg.kind == "fused":
            return MK.segment_name(index, seg)
        step = seg.steps[0]
        b = step.batch
        a = b.attrs
        if step.mode == "gemm":
            return gemm_kernel_name(step.gemm.M, step.gemm.K, step.gemm.N)
        H, W, C = prog.buffers[b.in_idx[0]][1]
        return conv2d_kernel_name(H, W, C, prog.buffers[b.out_idx][1][-1],
                                  kh=a["kh"], kw=a["kw"],
                                  stride=a["stride"], padding=a["padding"])

    g = cnn.resnet50(h=32, w=32, width=0.25, blocks=(1, 1, 1, 1),
                     num_classes=16)
    for hw in (HW, scaled_paper_machine(4, scratchpad_bytes=64 * 1024)):
        rep, sched, subtasks, mapping = analyze(g, hw, num_cores=4)
        prog = lower_program(g, init_params(g, seed=1), subtasks, mapping,
                             sched, hw=hw)
        fn = MK.megakernel_single(prog, interpret=True)
        names = MK.pallas_call_names(
            fn, {"input": jnp.zeros((32, 32, 3), jnp.int8)})
        segments = MK.plan_segments(prog)
        assert names == [expected(prog, i, s) for i, s in enumerate(segments)
                         if s.emits_call]
        fused = [n for n, s in zip(names, [s for s in segments
                                           if s.emits_call])
                 if s.kind == "fused"]
        assert len(set(fused)) == len(fused)
    assert {s.kind for s in segments} == {"fused", "tiled", "outside"}
    assert names[0] == "seg000_stem"
    assert "conv3x3s2p1_4x4x64_64" in names
    assert "gemm_4x128x256" in names           # the stride-2 projection
    assert not [n for n in names if n.startswith("conv1x1")]
    assert all(n.replace("_", "").isalnum() for n in names)
