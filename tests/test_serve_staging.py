"""Double-buffered input: while a job's program runs, `Server.step` copies
the batch of the network's next job to the device (`runner.stage`), so
that job launches without a copy of its own.

The contract under test:

  * staging engages only when the runner can stage, the next job of the
    static program is the same network's and its queue holds a full batch;
  * staged tickets stay queued until their job pops them: they count in
    `queue_depths()`, and every path that resolves queued tickets (an
    executor failure, a retry, a breaker's skip, `shed`, a drop, a mode
    switch) resolves them the same way; none is lost;
  * outputs are those of the unstaged run, bit for bit and in order;
  * `metrics["staged_calls"]` counts the runner calls whose input was
    copied during the previous job.
"""

import numpy as np
import pytest

from repro.core import cnn
from repro.hw import scaled_paper_machine
from repro.serve import (BreakerPolicy, Mode, ModeNetwork, RetryPolicy,
                         ServeError, Server)

HW = scaled_paper_machine(4)


def _frame(seed=0):
    return np.random.default_rng(seed).integers(
        -64, 64, (32, 32, 3)).astype(np.int8)


class _Staged(dict):
    """A staged batch, as the fake runner hands it back."""


class _Staging:
    """A runner with the pallas runner's `stage` and `launch` around the
    numpy runner. It logs whether each call's input was staged and fails
    the fetch of the calls (counted from 1) listed in `fail_fetch`."""

    def __init__(self, inner, fail_fetch=()):
        self.inner, self.fail_fetch = inner, set(fail_fetch)
        self.calls: list[bool] = []
        self.stages = 0

    def stage(self, inputs):
        self.stages += 1
        return _Staged(inputs)

    def launch(self, inputs):
        self.calls.append(isinstance(inputs, _Staged))
        n = len(self.calls)

        def fetch():
            if n in self.fail_fetch:
                raise RuntimeError("device fault at fetch")
            return self.inner(dict(inputs))
        return fetch

    def __call__(self, inputs):
        return self.launch(inputs)()


def _server(slots=4, staging=True, two_nets=False, fail_fetch=(), **kw):
    """One CNN "a" (with a second network "b" of half its rate, the job
    order a, b, a) on the numpy backend; "a"'s runner can stage."""
    srv = Server(HW, backend="numpy", num_cores=4, speed_ratio=1e9, **kw)
    srv.register("a", cnn.small_cnn(), period_s=1 / 50, slots=slots,
                 criticality=2)
    if two_nets:
        srv.register("b", cnn.small_cnn(), period_s=1 / 25, slots=slots)
    st = srv._nets["a"]
    if staging:
        st.runner = _Staging(st.runner, fail_fetch)
    return srv, st


def _reference(srv, frames):
    dep = srv._nets["a"].deployment
    return [dep.run(f) for f in frames]


def _assert_served(tickets, want):
    for t, w in zip(tickets, want):
        assert t.done
        for k in w:
            np.testing.assert_array_equal(t.result().output[k], w[k])


def _serve(srv, tickets):
    while not all(t.terminal for t in tickets):
        srv.step()


def test_a_full_queue_stages_the_next_batch_and_outputs_match():
    frames = [_frame(k) for k in range(12)]
    outputs = {}
    for staging in (True, False):
        srv, st = _server(staging=staging)
        tickets = [srv.submit("a", f) for f in frames]
        _serve(srv, tickets)
        outputs[staging] = [t.result().output for t in tickets]
        assert srv.metrics["runner_calls"] == 3
        assert srv.metrics["staged_calls"] == (2 if staging else 0)
        if staging:
            assert st.runner.calls == [False, True, True]
            assert st.runner.stages == 2
    for got, want in zip(outputs[True], outputs[False]):
        for k in want:
            np.testing.assert_array_equal(got[k], want[k])
    _assert_served(tickets, _reference(srv, frames))


def test_fewer_than_slots_queued_stages_nothing():
    srv, st = _server()
    frames = [_frame(k) for k in range(6)]
    tickets = [srv.submit("a", f) for f in frames]
    _serve(srv, tickets)
    assert st.runner.calls == [False, False] and st.runner.stages == 0
    assert srv.metrics["staged_calls"] == 0
    assert srv.metrics["slots_padded"] == 2
    _assert_served(tickets, _reference(srv, frames))


def test_another_networks_job_next_stages_nothing():
    srv, st = _server(slots=2, two_nets=True)
    assert [j.network for j in srv.compiled.jobs] == ["a", "b", "a"]
    frames = [_frame(k) for k in range(8)]
    tickets = [srv.submit("a", f) for f in frames]
    srv.step()                       # a, with b next: no staging
    assert st.runner.stages == 0 and st.staged_batch is None
    srv.step()                       # b, idle
    srv.step()                       # a, with a next (the wrap): staged
    assert st.runner.stages == 1
    assert [t.tid for t in st.staged_batch[0]] == [t.tid for t in
                                                   tickets[4:6]]
    _serve(srv, tickets)
    assert st.runner.calls == [False, False, True, False]
    assert srv.metrics["staged_calls"] == 1
    _assert_served(tickets, _reference(srv, frames))


def test_a_runner_without_stage_is_called_whole():
    srv, st = _server(staging=False)
    inner, calls = st.runner, []

    def runner(batch):               # a wrapper, as a profiler would add
        calls.append(batch)
        return inner(batch)
    st.runner = runner
    frames = [_frame(k) for k in range(12)]
    tickets = [srv.submit("a", f) for f in frames]
    _serve(srv, tickets)
    assert len(calls) == 3 and srv.metrics["staged_calls"] == 0
    assert all(isinstance(v, np.ndarray) for b in calls for v in b.values())
    _assert_served(tickets, _reference(srv, frames))


def test_queue_depths_count_staged_tickets():
    srv, st = _server()
    tickets = [srv.submit("a", _frame(k)) for k in range(12)]
    srv.step()
    assert srv.queue_depths() == {"a": 8}
    staged = st.staged_batch[0]
    assert staged == tickets[4:8]
    assert all(t.status == "queued" for t in tickets[4:])
    assert srv.network_status("a")["queue_depth"] == 8
    tel = srv.telemetry()
    assert tel["queue_depths"] == {"a": 8}
    assert tel["metrics"]["staged_calls"] == 0
    srv.step()
    assert srv.queue_depths() == {"a": 4}
    assert srv.telemetry()["metrics"]["staged_calls"] == 1
    assert "runner_calls=2 (slots filled 8, padded 0), staged_calls=1" \
        in srv.summary()


def test_a_closed_loop_and_its_drain_lose_no_frame():
    """The benchmark's closed loop: top the queue up to `depth` before each
    step, then step until nothing is queued."""
    srv, st = _server()
    depth, frames, tickets = 8, [], []
    for _ in range(10):
        while srv.queue_depths()["a"] < depth:
            frames.append(_frame(len(frames)))
            tickets.append(srv.submit("a", frames[-1]))
        srv.step()
    while srv.queue_depths()["a"]:
        srv.step()
    assert all(t.done for t in tickets) and len(tickets) == 44
    assert srv.metrics["runner_calls"] == 11
    assert srv.metrics["staged_calls"] == 10      # all but the first call
    assert st.staged_batch is None
    _assert_served(tickets, _reference(srv, frames))


def test_a_failed_call_leaves_the_batch_it_staged_queued():
    srv, st = _server(fail_fetch={2})
    frames = [_frame(k) for k in range(12)]
    tickets = [srv.submit("a", f) for f in frames]
    srv.step()
    with pytest.raises(RuntimeError, match="fetch"):
        srv.step()                   # staged call; stages the third batch
    assert all(t.status == "failed" for t in tickets[4:8])
    assert all(t.status == "queued" for t in tickets[8:])
    assert srv.queue_depths() == {"a": 4}
    srv.step()
    assert st.runner.calls == [False, True, True]
    assert srv.metrics["staged_calls"] == 2
    _assert_served(tickets[:4] + tickets[8:],
                   _reference(srv, frames[:4] + frames[8:]))


def test_a_retried_call_stages_once():
    srv, st = _server(fail_fetch={2})
    srv.enable_resilience(retry=RetryPolicy(max_retries=1))
    frames = [_frame(k) for k in range(12)]
    tickets = [srv.submit("a", f) for f in frames]
    _serve(srv, tickets)
    assert srv.metrics["retries"] == 1
    # the retry runs the staged input again and stages nothing more
    assert st.runner.calls == [False, True, True, True]
    assert st.runner.stages == 2 and srv.metrics["staged_calls"] == 2
    assert all(t.result().latency_s > 0 for t in tickets)
    _assert_served(tickets, _reference(srv, frames))


def test_a_breaker_skip_degrades_staged_tickets_and_drops_the_copy():
    srv, st = _server(fail_fetch={1})
    srv.enable_resilience(retry=RetryPolicy(max_retries=0),
                          breaker=BreakerPolicy(threshold=1,
                                                cooldown_jobs=3))
    tickets = [srv.submit("a", _frame(k)) for k in range(12)]
    srv.step()                       # fails, stages 4..8, trips the breaker
    assert st.staged_batch is not None
    assert all(t.status == "degraded" for t in tickets[:4])
    srv.step()                       # skip: the staged tickets degrade
    assert all(t.status == "degraded" for t in tickets[:8])
    srv.step()                       # skip: the rest degrade
    assert all(t.status == "degraded" for t in tickets)
    assert srv.queue_depths() == {"a": 0}
    srv.step()                       # half-open, nothing queued
    late = [srv.submit("a", _frame(k)) for k in (20, 21)]
    srv.step()                       # the probe: a fresh, unstaged batch
    assert srv.telemetry()["breakers"]["a"] == "closed"
    assert st.runner.calls == [False, False]
    assert srv.metrics["staged_calls"] == 0 and st.staged_batch is None
    _assert_served(late, _reference(srv, [_frame(20), _frame(21)]))


def test_shed_and_a_drop_resolve_staged_tickets():
    srv, st = _server(slots=2, two_nets=True)
    tickets = [srv.submit("a", _frame(k)) for k in range(6)]
    for _ in range(3):
        srv.step()                   # a, b, a: the last stages 4..6
    assert st.staged_batch[0] == tickets[4:]
    srv.shed("a")
    assert all(t.done for t in tickets[:4])
    assert all(t.status == "degraded" for t in tickets[4:])
    assert st.staged_batch is None and srv.queue_depths()["a"] == 0

    srv, st = _server(queue_policy="drop-oldest", queue_capacity=8)
    frames = [_frame(k) for k in range(13)]
    tickets = [srv.submit("a", f) for f in frames[:8]]
    srv.step()                       # stages 4..8
    tickets += [srv.submit("a", f) for f in frames[8:]]   # evicts ticket 4
    assert tickets[4].status == "dropped"
    _serve(srv, tickets)
    assert st.runner.calls == [False, False, True]   # 5..9 restacked
    kept = [k for k in range(13) if k != 4]
    _assert_served([tickets[k] for k in kept],
                   _reference(srv, [frames[k] for k in kept]))


@pytest.mark.parametrize("stays", [True, False])
def test_a_mode_switch_resolves_staged_tickets(stays):
    srv, st = _server(slots=2)
    frames = [_frame(k) for k in range(6)]
    tickets = [srv.submit("a", f) for f in frames]
    want = _reference(srv, frames)
    srv.step()                       # stages 2..4; the cursor is at 0 again
    assert st.staged_batch is not None
    nets = (ModeNetwork("park", cnn.small_cnn(), period_s=1 / 25, slots=2),)
    if stays:
        nets += (ModeNetwork("a", cnn.small_cnn(), period_s=1 / 50,
                             slots=2),)
    srv.switch_mode(Mode("next", nets))
    assert srv.mode_name == "next"
    if stays:                        # the queue carries over, unstaged
        assert srv.queue_depths()["a"] == 4
        _serve(srv, tickets)
        _assert_served(tickets, want)
        assert srv.metrics["staged_calls"] == 0
    else:
        assert all(t.status == "dropped" for t in tickets[2:])
        _assert_served(tickets[:2], want[:2])


def test_a_payload_that_cannot_be_stacked_fails_in_its_own_job():
    srv, st = _server(slots=2)
    good = [srv.submit("a", _frame(k)) for k in range(2)]
    bad = [srv.submit("a", {"nope": _frame(k)}) for k in range(2)]
    srv.step()                       # staging the bad pair fails quietly
    assert all(t.done for t in good) and st.staged_batch is None
    with pytest.raises(ServeError, match="missing input"):
        srv.step()
    assert all(t.status == "failed" for t in bad)


def test_the_pallas_runner_stages_and_serves_bit_exact():
    frames = [_frame(k) for k in range(6)]
    outs = {}
    for backend in ("pallas", "numpy"):
        srv = Server(HW, backend=backend, num_cores=4)
        srv.register("a", cnn.small_cnn(), period_s=1 / 50, slots=2)
        tickets = [srv.submit("a", f) for f in frames]
        _serve(srv, tickets)
        outs[backend] = [t.result().output for t in tickets]
        assert srv.metrics["staged_calls"] == (2 if backend == "pallas"
                                               else 0)
    for got, want in zip(outs["pallas"], outs["numpy"]):
        for k in want:
            np.testing.assert_array_equal(got[k], want[k])


def test_the_pallas_runner_stages_the_stems_row_groups():
    """The pallas runner's `stage` puts the frames on the device in the
    layout the program declares: small_cnn's first conv (3×3, stride 1)
    reads the 3-channel input alone, so 4 frames of 32×32×3 cross as
    (4, 32, 96) row groups. A launch of the staged arrays and a call of
    the runner with the numpy batch give the numpy backend's outputs."""
    import jax
    frames = [_frame(k) for k in range(4)]
    batch = {"input": np.stack(frames)}
    outs = {}
    for backend in ("pallas", "numpy"):
        srv = Server(HW, backend=backend, num_cores=4)
        srv.register("a", cnn.small_cnn(), period_s=1 / 50, slots=4)
        outs[backend] = srv._nets["a"].runner
    runner = outs["pallas"]
    staged = runner.stage(batch)
    assert isinstance(staged["input"], jax.Array)
    assert staged["input"].shape == (4, 32, 96)
    assert staged["input"].dtype == np.int8
    want = outs["numpy"](batch)
    for got in (runner.launch(staged)(), runner(batch)):
        for k in want:
            np.testing.assert_array_equal(got[k], want[k])
