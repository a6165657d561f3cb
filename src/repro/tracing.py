"""In-process span recorder for the serving path.

The serving layers (`repro.serve.Server`, the deployment runners of
`repro.compiler.backends`) mark what they do with named spans:

    with tracing.span("repro.server.step", ref=seq):
        ...

The recorder is off by default. Off, `span()` returns one shared no-op
context manager after a single module-global check: it allocates nothing
and reads no clock. An operator turns it on around the stretch to look at
and drains it afterwards:

    tracing.enable(capacity=1 << 16)
    ... serve ...
    spans = tracing.drain()          # [Span(name, start_ns, end_ns, parent, ref)]
    tracing.disable()

Each span holds `time.perf_counter_ns()` times, the index in the drained
list of the span that enclosed it (`parent`, None at the top) and a
reference (`ref`: the ticket id, the job sequence number, a GC
generation). The recorder keeps at most `capacity` spans between drains;
beyond that it counts them in `dropped_spans()` and does not grow.

While it is on, Python's garbage collector records each collection as a
`repro.gc` span (ref: the generation) and JAX's compile events as
`repro.compile` spans (ref: the event's name), each under the span that
was open, so a step that collected or compiled shows it. With
`annotate=True` every span also opens a `jax.profiler.TraceAnnotation` of
its name, which puts it on the profiler's host timeline while a trace is
being taken.

Spans nest on the thread that serves (the Server is single-threaded).
Drain between steps: a span still open at a `drain()` lands in the next
one, without its link to a parent or to its children.
"""

from __future__ import annotations

import gc
import itertools
import time
from typing import NamedTuple

COMPILE_EVENTS = "/jax/core/compile/"


class Span(NamedTuple):
    name: str
    start_ns: int
    end_ns: int
    parent: int | None
    ref: object


class _NoSpan:
    """The span of a recorder that is off: enters and exits, nothing else."""

    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


_NO_SPAN = _NoSpan()


class _Span:
    __slots__ = ("rec", "name", "ref", "seq", "parent", "start", "note")

    def __init__(self, rec: "Recorder", name: str, ref):
        self.rec, self.name, self.ref = rec, name, ref

    def __enter__(self):
        rec = self.rec
        self.seq = next(rec.seqs)
        self.parent = rec.stack[-1] if rec.stack else None
        rec.stack.append(self.seq)
        self.note = rec.annotation(self.name) if rec.annotation else None
        if self.note is not None:
            self.note.__enter__()
        self.start = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        end = time.perf_counter_ns()
        if self.note is not None:
            self.note.__exit__(*exc)
        self.rec.stack.pop()
        self.rec.put(self.name, self.start, end, self.parent, self.ref,
                     self.seq)
        return False


class Recorder:
    """A bounded store of finished spans and the stack of open ones."""

    def __init__(self, capacity: int, annotate: bool):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.capacity = capacity
        self.slots: list = [None] * capacity
        self.n = 0
        self.dropped = 0
        self.seqs = itertools.count()
        self.stack: list[int] = []
        self.gc_start: int | None = None
        self.annotation = None
        if annotate:
            import jax
            self.annotation = jax.profiler.TraceAnnotation

    def put(self, name: str, start: int, end: int, parent, ref,
            seq: int | None = None) -> None:
        # the row is built before the check: building it may run a garbage
        # collection, whose own span then takes a slot first
        row = (name, start, end, parent, ref,
               next(self.seqs) if seq is None else seq)
        if self.n >= self.capacity:
            self.dropped += 1
            return
        self.slots[self.n] = row
        self.n += 1

    def record(self, name: str, start: int, end: int, ref) -> None:
        """A finished span under the span open now."""
        self.put(name, start, end, self.stack[-1] if self.stack else None,
                 ref)

    def drain(self) -> list[Span]:
        rows, self.slots = self.slots[:self.n], [None] * self.capacity
        self.n = 0
        index = {row[5]: i for i, row in enumerate(rows)}
        return [Span(name, s, e, index.get(parent), ref)
                for name, s, e, parent, ref, _ in rows]

    def on_gc(self, phase: str, info: dict) -> None:
        if phase == "start":
            self.gc_start = time.perf_counter_ns()
        elif self.gc_start is not None:
            self.record("repro.gc", self.gc_start, time.perf_counter_ns(),
                        info.get("generation"))
            self.gc_start = None


_active: Recorder | None = None
_listening = False


def _on_jax_duration(event: str, secs: float, **_) -> None:
    rec = _active
    if rec is not None and event.startswith(COMPILE_EVENTS):
        end = time.perf_counter_ns()
        rec.record("repro.compile", end - int(secs * 1e9), end,
                   event[len(COMPILE_EVENTS):])


def enable(capacity: int = 1 << 16, annotate: bool = False) -> None:
    """Start recording into a fresh store of `capacity` spans (replacing
    one that is on, and what it held)."""
    global _active, _listening
    disable()
    if not _listening:
        import jax.monitoring
        jax.monitoring.register_event_duration_secs_listener(_on_jax_duration)
        _listening = True
    _active = Recorder(capacity, annotate)
    gc.callbacks.append(_active.on_gc)


def disable() -> None:
    """Stop recording; what was not drained is discarded."""
    global _active
    if _active is not None:
        gc.callbacks.remove(_active.on_gc)
        _active = None


def span(name: str, ref=None):
    """A context manager that records one span while the recorder is on."""
    rec = _active
    if rec is None:
        return _NO_SPAN
    return _Span(rec, name, ref)


def record(name: str, start_ns: int, end_ns: int, ref=None) -> None:
    """Record a span whose times were taken elsewhere, under the span open
    now (as the Server does for a ticket's wait in its queue)."""
    rec = _active
    if rec is not None:
        rec.record(name, start_ns, end_ns, ref)


def drain() -> list[Span]:
    """The spans finished since the last drain, in the order they ended
    (children before their parent); empties the store."""
    return _active.drain() if _active is not None else []


def dropped_spans() -> int:
    """Spans refused since `enable()` because the store was full."""
    return _active.dropped if _active is not None else 0
