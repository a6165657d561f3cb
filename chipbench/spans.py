"""The program's own spans in one cell: where the host time of a call goes.

    python3 chipbench/spans.py --workload resnet50_224.periodic_b1 \
        --seed 7 --seconds 10

Sets the cell up as run.py does, then drives four windows at the cell's
load, one after the other:

  1. untraced (`--seconds`): `runner_ms` as run.py reads it, the recorder
     and the profiler off;
  2. recorder (`--seconds`): `repro.tracing` on, the profiler off; the
     readers below read its spans;
  3. untraced again (`--seconds`), so that the recorder's cost is read
     against windows on both sides of it;
  4. profiler (`--profile-seconds`, 0 skips it): the recorder on with
     `annotate=True`, so the spans are on the trace's host timeline, and
     device idle time is grouped by the innermost `repro.*` span over
     each gap (`idle_by_span`).

Every frame of the windows is checked against the reference as run.py
checks its own. Prints one JSON object: the per-call means of the
runner's spans (`runner_h2d_ms`, `runner_launch_ms`, `runner_fetch_ms`),
the Server's `server_batch_ms` and `server_queue_ms` (p95), the mean
`repro.server.call` against the untraced windows' `runner_ms` (the
recorder's cost when on), `dropped_spans`, the Server's counters, `stalls` (the five
longest steps, also on standard error) and, with the profiler window,
`idle_by_span` and the trace's `device_ops`. It measures the chip only,
as run.py does, and needs a program that has `repro.tracing`.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import collections  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))

import harness  # noqa: E402
import stats  # noqa: E402
import trace_reduce as T  # noqa: E402

CAPACITY = 1 << 18       # spans a recorder window may hold (about 15 a call)
REC_K0 = 20_000_000      # frame indices of the recorder window start here,
AFTER_K0 = 30_000_000    # of the second untraced window here
PROF_K0 = 40_000_000     # and of the profiler window here
OUTSIDE = ("repro.server.queue",)   # spans that start before their parent


# -- readers of a drained span list ---------------------------------------

def durations_ms(spans, name: str) -> list[float]:
    return [(s.end_ns - s.start_ns) / 1e6 for s in spans if s.name == name]


def mean_ms(spans, name: str) -> float | None:
    return stats.mean(durations_ms(spans, name))


def p95_ms(spans, name: str) -> float | None:
    return stats.percentile(durations_ms(spans, name), 95)


def children(spans) -> dict[int, list[int]]:
    out = collections.defaultdict(list)
    for i, s in enumerate(spans):
        if s.parent is not None:
            out[s.parent].append(i)
    return out


def self_ms(spans, kids: dict, i: int) -> float:
    """A span's time less the time of its children (those inside it)."""
    inner = sum(spans[k].end_ns - spans[k].start_ns for k in kids.get(i, ())
                if spans[k].name not in OUTSIDE)
    return (spans[i].end_ns - spans[i].start_ns - inner) / 1e6


def stalls(spans, n: int = 5) -> list[dict]:
    """The n longest `repro.server.step` spans, longest first: each with its
    time, the self time of each span name under it (the step's own under
    its name), and each `repro.gc` / `repro.compile` span under it."""
    kids = children(spans)
    steps = sorted((i for i, s in enumerate(spans)
                    if s.name == "repro.server.step"),
                   key=lambda i: spans[i].start_ns - spans[i].end_ns)[:n]
    out = []
    for i in steps:
        by_name: dict[str, float] = collections.defaultdict(float)
        events, todo = [], [i]
        while todo:
            j = todo.pop()
            s = spans[j]
            if s.name in OUTSIDE:
                continue
            by_name[s.name] += self_ms(spans, kids, j)
            if s.name in ("repro.gc", "repro.compile"):
                events.append([s.name, s.ref, (s.end_ns - s.start_ns) / 1e6])
            todo += kids.get(j, ())
        out.append({"ref": spans[i].ref,
                    "ms": (spans[i].end_ns - spans[i].start_ns) / 1e6,
                    "self_ms": dict(sorted(by_name.items(),
                                           key=lambda kv: -kv[1])),
                    "events": events})
    return out


def recorder_readings(spans) -> dict:
    """The per-call readings of a recorder window."""
    out = {"runner_h2d_ms": mean_ms(spans, "repro.runner.h2d"),
           "runner_launch_ms": mean_ms(spans, "repro.runner.launch"),
           "runner_fetch_ms": mean_ms(spans, "repro.runner.fetch"),
           "server_batch_ms": mean_ms(spans, "repro.server.batch"),
           "server_queue_ms": p95_ms(spans, "repro.server.queue"),
           "server_call_ms": mean_ms(spans, "repro.server.call"),
           "server_step_ms": mean_ms(spans, "repro.server.step"),
           "calls": len(durations_ms(spans, "repro.server.call")),
           "gc_ms": sum(durations_ms(spans, "repro.gc")),
           "compiles": len(durations_ms(spans, "repro.compile"))}
    parts = [out[k] for k in ("runner_h2d_ms", "runner_launch_ms",
                              "runner_fetch_ms")]
    if None not in parts and out["server_call_ms"]:
        out["split_over_call"] = sum(parts) / out["server_call_ms"]
    return out


# -- a profiler trace with the spans on it --------------------------------

def idle_by_span(trace: dict, top: int = 10) -> list[list]:
    """Device idle time in the window (on the first device, its clock
    shifted onto the host's as `trace_reduce` shifts it), grouped by the
    innermost `repro.*` span of the driving thread over each gap's
    midpoint, else by its innermost event, as `idle_gaps` labels it."""
    thread = trace["thread"]
    windows = [(s, e) for n, s, e in thread if n == T.WINDOW]
    if not windows or not trace["devices"]:
        raise ValueError("trace holds no window span or no device plane")
    w0, w1 = windows[0]
    shift = T.clock_offset_ns(trace["modules"], trace["launches"])
    busy = T.union([(max(s - shift, w0), min(e - shift, w1))
                    for _, s, e in trace["devices"][0]
                    if e - shift > w0 and s - shift < w1])
    events = sorted((s, e, n) for n, s, e in thread
                    if e > s and n != T.WINDOW)
    out: dict[str, float] = collections.defaultdict(float)
    active: list[tuple] = []
    i = 0
    for mid, length in T._gaps(busy, w0, w1):
        while i < len(events) and events[i][0] <= mid:
            active.append(events[i])
            i += 1
        active = [ev for ev in active if ev[1] > mid]
        pick = [ev for ev in active if ev[2].startswith("repro.")] or active
        label = min(pick, key=lambda ev: ev[1] - ev[0])[2] if pick \
            else T.WINDOW
        out[label] += length
    return T._ranked(out, top)


# -- a run ----------------------------------------------------------------

def untraced(ses, seconds: float, k0: int):
    """An untraced window, drained; and its mean runner time a call."""
    d, _ = harness.window(ses, seconds, False, k0=k0)
    d.drain(harness.DRAIN_S)
    return d, stats.mean([s.runner_s * 1e3 for s in d.steps
                          if s.in_window and s.frames])


def measure(ses, seconds: float, profile_s: float, seed: int,
            trace_dir: str | None = None) -> dict:
    from repro import tracing
    d, runner_ms = untraced(ses, seconds, 0)
    before = dict(ses.srv.metrics)

    tracing.enable(CAPACITY)
    rd, _ = harness.window(ses, seconds, False, k0=REC_K0)
    spans = tracing.drain()
    dropped = tracing.dropped_spans()
    tracing.disable()
    counters = {k: ses.srv.metrics[k] - before[k]
                for k in ("jobs", "idle_jobs", "runner_calls",
                          "slots_filled", "slots_padded")}
    rd.drain(harness.DRAIN_S)
    ad, runner_after_ms = untraced(ses, seconds, AFTER_K0)
    records = d.records + rd.records + ad.records
    outputs = {**d.outputs, **rd.outputs, **ad.outputs}
    rec = recorder_readings(spans)
    rec["runner_ms_untraced"] = [runner_ms, runner_after_ms]
    if rec["server_call_ms"] and runner_ms and runner_after_ms:
        off = (runner_ms + runner_after_ms) / 2
        rec["on_cost_pct"] = 100 * (rec["server_call_ms"] / off - 1)
    result = {"recorder": rec, "dropped_spans": dropped,
              "spans": len(spans), "counters": counters,
              "stalls": stalls(spans)}

    if profile_s > 0:
        import jax
        trace_dir = trace_dir or os.path.join(harness.OUT_DIR, "spans-trace")
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = harness.HOST_TRACER_LEVEL
        tracing.enable(CAPACITY, annotate=True)
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        pd, _ = harness.window(ses, profile_s, True, k0=PROF_K0)
        pd.drain(harness.DRAIN_S)
        jax.profiler.stop_trace()
        tracing.disable()
        trace = T.load(T.find_xplane(trace_dir))
        reduced = T.reduce_trace(trace)
        result["profiler"] = {
            "window_s": reduced["window_s"], "busy_s": reduced["busy_s"],
            "idle_by_span": idle_by_span(trace),
            "idle_gaps": reduced["idle_gaps"],
            "device_ops": reduced["device_ops"]}
        records += pd.records
        outputs.update(pd.outputs)

    ses.srv = d = rd = ad = None
    checks, n = harness.compare(ses.net, ses.params, ses.frames, outputs,
                                records, seed, ses.cell.mix["check_sample"])
    result.update(correct=all(v <= lim for v, lim in checks.values()),
                  attempted=len(records), compared=n,
                  checks={k: {"value": v, "limit": lim}
                          for k, (v, lim) in checks.items()})
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--profile-seconds", type=float, default=3.0)
    ap.add_argument("--trace-dir", default=None,
                    help="keep the profiler trace here (default: removed)")
    args = ap.parse_args(argv)
    try:
        cell = harness.load_cell(args.workload)
        ses = harness.set_up(cell, args.seed, T_START)
        result = measure(ses, args.seconds, args.profile_seconds, args.seed,
                         args.trace_dir)
    except (harness.BenchError, ImportError, OSError) as e:
        print(f"spans: cannot measure: {type(e).__name__}: {e}",
              file=sys.stderr, flush=True)
        return 1
    if args.trace_dir is None and args.profile_seconds > 0:
        shutil.rmtree(os.path.join(harness.OUT_DIR, "spans-trace"),
                      ignore_errors=True)
    for s in result["stalls"]:
        harness.log(f"stall: step {s['ref']} {s['ms']:.3f} ms; self "
                    + ", ".join(f"{k} {v:.3f}" for k, v in s["self_ms"].items())
                    + "".join(f"; {n} ({r}) {ms:.3f} ms"
                              for n, r, ms in s["events"]))
    print(json.dumps({"workload": cell.name, "seed": args.seed,
                      "device": ses.device, **result}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
