"""Reduction of a profiler trace (`.xplane.pb`) to the benchmark's numbers.

The benchmark marks its own host activity with `TraceAnnotation` spans on
the thread that drives the window: `chipbench.window` around the measured
window, and inside it `chipbench.wait` (pacing until a frame is due),
`chipbench.submit`, `chipbench.step` (one `Server.step()`) and
`chipbench.runner` (the network's runner call inside the step). Device
operations are the events of the `XLA Ops` line of each `/device:TPU:<n>`
plane, named by their HLO instruction (the text before " = ").

The device's clock in the trace is offset from the host's (by about a
millisecond on a v5e host: a program shows up on the device before the
host launched it). `clock_offset_ns` estimates the offset as the least
gap between the start of a program on the device (`XLA Modules`) and the
host's launch of it (`TpuLoadedExecutable::ExecuteLaunch`), paired in
order: each gap is the offset plus that call's launch latency, which is
never negative. Device times are shifted by it before they meet host
times.

`reduce_trace` gives, within the window:

  window_s      the window span's length;
  busy_s        the length of the union of device operations, averaged over
                the device planes;
  spans_s       per benchmark span name, its total length in the window;
  device_ops    the ten operations with the most device time;
  idle_gaps     device idle time (the window minus the busy union, on the
                first device) grouped by what the driving thread was doing
                at the middle of each gap (its innermost event there); the
                ten labels with the most idle time.
"""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

WINDOW = "chipbench.window"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
LAUNCH = "TpuLoadedExecutable::ExecuteLaunch"


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def op_name(name: str) -> str:
    return name.split(" = ", 1)[0].lstrip("%")


def load(path: str) -> dict:
    """The trace as plain lists: `devices` (per device plane, its ops as
    (name, start_ns, end_ns)), `modules` (program starts on the first
    device), `launches` (host launch starts) and `thread` (events of the
    host thread that holds the window span)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices, modules, launches, host_lines = [], [], [], []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            ops = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops = [(op_name(e.name), e.start_ns, e.end_ns)
                           for e in line.events]
                elif line.name == MODULES_LINE and not devices:
                    modules = [e.start_ns for e in line.events]
            devices.append(ops)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                events = [(e.name, e.start_ns, e.end_ns) for e in line.events]
                launches += [s for n, s, _ in events if n == LAUNCH]
                host_lines.append(events)
    thread = next((ev for ev in host_lines
                   if any(n == WINDOW for n, _, _ in ev)), [])
    return {"devices": devices, "modules": sorted(modules),
            "launches": sorted(launches), "thread": thread}


def clock_offset_ns(modules: list[float], launches: list[float]) -> float:
    """Device clock minus host clock, from programs paired with launches in
    order; 0 where the counts differ and no pairing is sound."""
    if not modules or len(modules) != len(launches):
        return 0.0
    return min(m - h for m, h in zip(modules, launches))


def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def reduce_trace(trace: dict, top: int = 10) -> dict:
    thread = trace["thread"]
    windows = [(s, e) for n, s, e in thread if n == WINDOW]
    if not windows or not trace["devices"]:
        raise ValueError("trace holds no window span or no device plane")
    w0, w1 = windows[0]
    shift = clock_offset_ns(trace["modules"], trace["launches"])
    devices = [[(n, s - shift, e - shift) for n, s, e in ops]
               for ops in trace["devices"]]
    merged = [union([(max(s, w0), min(e, w1)) for _, s, e in ops
                     if e > w0 and s < w1]) for ops in devices]
    busy_ns = sum(sum(e - s for s, e in m) for m in merged) / len(merged)

    spans: dict[str, float] = defaultdict(float)
    for n, s, e in thread:
        if n.startswith("chipbench.") and e > w0 and s < w1:
            spans[n] += (min(e, w1) - max(s, w0)) / 1e9

    op_time: dict[str, float] = defaultdict(float)
    for ops in devices:
        for n, s, e in ops:
            if e > w0 and s < w1:
                op_time[n] += (min(e, w1) - max(s, w0)) / 1e9 / len(devices)

    return {"window_s": (w1 - w0) / 1e9, "busy_s": busy_ns / 1e9,
            "clock_offset_s": shift / 1e9, "spans_s": dict(spans),
            "device_ops": _ranked(op_time, top),
            "idle_gaps": _ranked(_label(_gaps(merged[0], w0, w1), thread),
                                 top)}


def _gaps(merged, w0: float, w1: float) -> list[tuple[float, float]]:
    """(midpoint, seconds) of each gap between busy intervals in [w0, w1)."""
    out, edge = [], w0
    for s, e in merged + [(w1, w1)]:
        if s > edge:
            out.append(((edge + s) / 2, (s - edge) / 1e9))
        edge = max(edge, e)
    return out


def _label(gaps: list[tuple[float, float]], thread: list[tuple]) -> dict:
    """Idle seconds per innermost event of the driving thread covering each
    gap's midpoint (one sweep through time)."""
    events = sorted((s, e, n) for n, s, e in thread if e > s and n != WINDOW)
    out: dict[str, float] = defaultdict(float)
    active: list[tuple] = []
    i = 0
    for mid, length in gaps:
        while i < len(events) and events[i][0] <= mid:
            active.append(events[i])
            i += 1
        active = [ev for ev in active if ev[1] > mid]
        label = min(active, key=lambda ev: ev[1] - ev[0])[2] if active \
            else WINDOW
        out[label] += length
    return out


def _ranked(d: dict, top: int) -> list[list]:
    return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])][:top]


def reduce_dir(log_dir: str) -> dict:
    return reduce_trace(load(find_xplane(log_dir)))
