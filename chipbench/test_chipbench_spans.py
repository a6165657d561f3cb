"""The readers of the program's spans (`spans.py`) on hand-made span lists
and traces, and one recorder window at a small size on the CPU."""

import time

import pytest

import harness
import spans as S
import trace_reduce as T
from repro.tracing import Span
from test_chipbench_faults import small_cell

MS = 1_000_000   # ns


def _step(ref, t0, fetch_ms=3.0, gc_ms=0.0):
    """One job of a batch-2 runner as the Server and runner record it, in
    the order spans end: (name, start ms, end ms, parent's row, ref); a
    collection of `gc_ms` inside the fetch when given."""
    f = t0 + 3 + fetch_ms
    rows = [("repro.server.queue", t0 - 3, t0, "step", 100 + ref),
            ("repro.server.batch", t0, t0 + 1, "step", ref),
            ("repro.runner.h2d", t0 + 1, t0 + 2, "call", None),
            ("repro.runner.launch", t0 + 2, t0 + 3, "call", None),
            ("repro.runner.fetch", t0 + 3, f, "call", None),
            ("repro.server.call", t0 + 1, f + 0.5, "step", ref),
            ("repro.server.account", f + 0.5, f + 1, "step", ref),
            ("repro.server.step", t0, f + 1.5, None, ref)]
    if gc_ms:
        rows.insert(4, ("repro.gc", t0 + 4, t0 + 4 + gc_ms,
                        "repro.runner.fetch", 2))
    return rows


def _spans(*jobs):
    """Span lists with parents as indices, from `_step` rows."""
    out = []
    for rows in jobs:
        base = len(out)
        at = {name.rsplit(".", 1)[-1]: base + k
              for k, (name, *_) in enumerate(rows)}
        at["repro.runner.fetch"] = at["fetch"]
        for name, s, e, parent, ref in rows:
            out.append(Span(name, int(s * MS), int(e * MS),
                            None if parent is None else at[parent], ref))
    return out


def test_recorder_readings_are_per_call_means():
    spans = _spans(_step(0, 10), _step(1, 30))
    r = S.recorder_readings(spans)
    assert r["runner_h2d_ms"] == pytest.approx(1.0)
    assert r["runner_launch_ms"] == pytest.approx(1.0)
    assert r["runner_fetch_ms"] == pytest.approx(3.0)
    assert r["server_batch_ms"] == pytest.approx(1.0)
    assert r["server_queue_ms"] == pytest.approx(3.0)
    assert r["server_call_ms"] == pytest.approx(5.5)
    assert r["split_over_call"] == pytest.approx(5 / 5.5)
    assert r["calls"] == 2 and r["compiles"] == 0
    assert S.recorder_readings([])["runner_h2d_ms"] is None


def test_stalls_list_the_longest_steps_with_child_self_times():
    spans = _spans(_step(0, 10), _step(1, 30, fetch_ms=13, gc_ms=4))
    assert spans[12].name == "repro.gc" and spans[12].parent == 13
    (first, second) = S.stalls(spans, n=2)
    assert first["ref"] == 1 and first["ms"] == pytest.approx(17.5)
    assert second["ref"] == 0 and second["ms"] == pytest.approx(7.5)
    self_ms = first["self_ms"]
    assert list(self_ms)[0] == "repro.runner.fetch"
    assert self_ms["repro.runner.fetch"] == pytest.approx(9.0)
    assert self_ms["repro.gc"] == pytest.approx(4.0)
    assert self_ms["repro.server.call"] == pytest.approx(0.5)
    assert self_ms["repro.server.step"] == pytest.approx(0.5)
    assert "repro.server.queue" not in self_ms
    assert sum(self_ms.values()) == pytest.approx(first["ms"])
    assert first["events"] == [["repro.gc", 2, pytest.approx(4.0)]]
    assert second["events"] == []


def test_idle_by_span_labels_gaps_by_the_innermost_program_span():
    # host thread: window 0-100; a step 10-60 holding a call 20-60 that
    # holds a fetch 30-60 and JAX's own np.asarray event 30-60; a wait
    # 60-100. The device's clock runs 5 ns behind the host's: busy 35-50
    # and 70-80 on the host's clock.
    thread = [("chipbench.window", 0, 100), ("chipbench.step", 10, 60),
              ("repro.server.step", 10, 60), ("repro.server.call", 20, 60),
              ("repro.runner.fetch", 30, 60), ("np.asarray", 30, 60),
              ("chipbench.wait", 60, 100)]
    trace = {"thread": thread, "devices": [[("k", 30, 45), ("k", 65, 75)]],
             "modules": [30, 65], "launches": [35, 70]}
    assert T.clock_offset_ns(trace["modules"], trace["launches"]) == -5
    got = dict(S.idle_by_span(trace))
    # gaps 0-35 (mid 17.5: the step, not chipbench.step), 50-70 (mid 60:
    # the wait), 80-100 (mid 90: the wait)
    assert got == pytest.approx({"repro.server.step": 35e-9,
                                 "chipbench.wait": 40e-9})
    trace.update(devices=[[("k", 0, 10), ("k", 40, 100)]],
                 modules=[0, 40], launches=[5, 45])
    got = dict(S.idle_by_span(trace))
    # gaps 0-5 (mid 2.5: the window alone), 15-45 (mid 30: the fetch, not
    # np.asarray, the call or the step)
    assert got == pytest.approx({"chipbench.window": 5e-9,
                                 "repro.runner.fetch": 30e-9})
    with pytest.raises(ValueError):
        S.idle_by_span({"thread": [], "devices": [], "modules": [],
                        "launches": []})


def test_a_recorder_window_at_a_small_size(monkeypatch):
    monkeypatch.setattr(harness, "enable_cache", lambda: None)
    monkeypatch.setattr(harness, "DRAIN_S", 5.0)
    cell = small_cell("backlog_b4")
    cell.config.update(backend="pallas")
    seed = 2**33 + 11
    ses = harness.set_up(cell, seed, time.perf_counter(), require_tpu=False)
    r = S.measure(ses, 0.5, 0.0, seed)
    assert r["correct"] and r["dropped_spans"] == 0
    rec = r["recorder"]
    assert rec["calls"] == r["counters"]["runner_calls"] > 0
    assert r["counters"]["slots_filled"] == 4 * rec["calls"]
    assert r["counters"]["slots_padded"] == 0
    for k in ("runner_h2d_ms", "runner_launch_ms", "runner_fetch_ms",
              "server_batch_ms", "server_queue_ms"):
        assert rec[k] > 0
    assert len(rec["runner_ms_untraced"]) == 2 and "on_cost_pct" in rec
    assert 0.9 < rec["split_over_call"] <= 1.0
    assert len(r["stalls"]) == 5
    assert all("repro.server.call" in s["self_ms"] for s in r["stalls"])
    assert "profiler" not in r
