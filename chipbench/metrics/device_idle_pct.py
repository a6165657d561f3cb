"""device_idle_pct: the share of the runner calls' host time in which no
operation runs on the device, in percent: 1 - (device busy time per call
x calls) / (summed runner time). The device busy time per call is read
from the traced window (busy time over the calls it served; every call
runs the same program), the calls and their runner time from the
untraced window, because the profiler stretches each call's host time
several-fold while the device work stays the same."""


def read(rec):
    traced = [s for s in rec.trace_steps if s.frames]
    steps = [s for s in rec.window_steps if s.frames]
    runner = sum(s.runner_s for s in steps)
    if rec.trace is None or not traced or not runner:
        return None
    per_call = rec.trace["busy_s"] / len(traced)
    return 100 * (1 - per_call * len(steps) / runner)
