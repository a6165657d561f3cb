"""The unified serving runtime (`repro.serve.Server`) — ISSUE 5 contract:

  * admission accept/reject is atomic (unschedulable additions AND compile
    errors roll the server back to the previously admitted set);
  * bounded request queues apply backpressure per policy (reject raises,
    drop-oldest evicts the stalest ticket);
  * tickets carry per-request deadline verdicts, deterministic under a
    pinned speed ratio;
  * release-order execution is correct across multiple hyperperiods;
  * `Server.save`/`Server.load` round-trips a whole serving configuration
    and serves bit-exact results;
  * the historical engines are thin wrappers: `PredictableEngine` counts
    per-step checks AND misses, `MultiModelEngine.admit_model` admits LM
    architectures through the same atomic path.
"""

import numpy as np
import pytest

from repro.core import cnn
from repro.hw import scaled_paper_machine
from repro.models.config import ModelConfig
from repro.serve import (AdmissionError, BackpressureError, DeadlineMonitor,
                         MultiModelEngine, RequestQueue, ServeError, Server,
                         Ticket)

HW = scaled_paper_machine(4)


def _frame(seed=0, h=32, w=32):
    return np.random.default_rng(seed).integers(
        -64, 64, (h, w, 3)).astype(np.int8)


def _lm_cfg(layers=2):
    # swiglu gates emit "mul" ops, which have no compiled lowering -> the
    # decode graph is genuinely analysis-only (schedulable, not executable)
    return ModelConfig(name="tiny_lm", family="dense", num_layers=layers,
                       d_model=128, num_heads=4, num_kv_heads=4, d_ff=256,
                       vocab_size=512, act="swiglu")


def _mixed_server(backend="numpy", **kw):
    """1 CNN graph + 1 LM decode network (analysis-only, step_fn-served)."""
    srv = Server(HW, backend=backend, num_cores=4, **kw)
    srv.register("cnn", cnn.small_cnn(), period_s=1 / 50, slots=2)
    srv.register("lm", _lm_cfg(), period_s=1 / 25, cache_len=64,
                 step_fn=lambda tok: np.int64(tok) * 3 + 1)
    return srv


# -- admission ---------------------------------------------------------------

def test_register_returns_verdict_and_is_schedulable():
    srv = _mixed_server()
    assert srv.report is not None and srv.report.schedulable
    v = srv.report.verdict_of("cnn")
    assert v.schedulable and v.response_bound_s > 0
    assert srv.report.bound("cnn") == v.response_bound_s
    assert set(srv.report.response_bounds) == {"cnn", "lm"}
    with pytest.raises(KeyError, match="nope"):
        srv.report.bound("nope")


def test_admission_reject_is_atomic():
    srv = _mixed_server()
    report_before = srv.report
    nets_before = list(srv.networks)
    # same rate as "cnn" but an impossible deadline -> analyzable, rejected
    with pytest.raises(AdmissionError) as ei:
        srv.register("greedy", cnn.small_cnn(), period_s=1 / 50,
                     deadline_s=1e-9)
    assert ei.value.report is not None            # analyzed, unschedulable
    assert not ei.value.report.schedulable
    assert srv.networks == nets_before
    assert srv.report is report_before            # analysis restored intact
    # the surviving set still serves
    t = srv.submit("cnn", _frame())
    srv.run(hyperperiods=1)
    assert t.done


def test_admission_error_rollback():
    srv = _mixed_server()
    nets_before = list(srv.networks)
    with pytest.raises(ServeError):               # duplicate name
        srv.register("cnn", cnn.small_cnn(), period_s=1 / 10)
    with pytest.raises(TypeError):                # not a Graph/ModelConfig
        srv.register("junk", object(), period_s=1 / 10)
    assert srv.networks == nets_before and srv.report.schedulable


def test_register_refuses_unexecutable_graph_without_step_fn():
    """A Graph with op kinds the backend cannot lower gets no silent
    analysis-only registration: without a step_fn it has no executor."""
    from repro.core.lmgraph import lm_decode_graph
    srv = _mixed_server()
    nets_before = list(srv.networks)
    with pytest.raises(ServeError, match="no executable lowering"):
        srv.register("lm_graph", lm_decode_graph(_lm_cfg(), 1, 64),
                     period_s=1 / 25)
    assert srv.networks == nets_before


# -- queues ------------------------------------------------------------------

def test_queue_reject_policy_backpressure():
    srv = _mixed_server(queue_capacity=2, queue_policy="reject")
    x = _frame()
    srv.submit("cnn", x)
    srv.submit("cnn", x)
    with pytest.raises(BackpressureError):
        srv.submit("cnn", x)
    assert srv.queue_depths()["cnn"] == 2


def test_queue_drop_oldest_policy():
    srv = _mixed_server(queue_capacity=2, queue_policy="drop-oldest")
    t1 = srv.submit("cnn", _frame(1))
    t2 = srv.submit("cnn", _frame(2))
    t3 = srv.submit("cnn", _frame(3))
    # the evicted ticket resolves TERMINALLY: result() answers with a
    # met=False "dropped" verdict instead of hanging (or raising) forever
    assert t1.status == "dropped" and t1.terminal
    r1 = t1.result()
    assert r1.output is None
    assert r1.verdict.outcome == "dropped" and not r1.verdict.met
    srv.run(hyperperiods=1)
    assert t2.done and t3.done
    tele = srv.telemetry()
    assert tele["dropped"]["cnn"] == 1
    assert tele["metrics"]["dropped"] == 1
    assert tele["events"]["cnn"]["dropped"] == 1


def test_request_queue_validation():
    with pytest.raises(ValueError):
        RequestQueue("x", capacity=0)
    with pytest.raises(ValueError):
        RequestQueue("x", policy="fifo?")
    q = RequestQueue("x", capacity=1, policy="drop-oldest")
    q.push(Ticket(0, "x", None))
    evicted = q.push(Ticket(1, "x", None))
    assert evicted is not None and evicted.status == "dropped"


def test_submit_unknown_or_unserveable_network():
    srv = _mixed_server()
    with pytest.raises(ServeError, match="unknown network"):
        srv.submit("ghost", _frame())
    srv2 = Server(HW, backend="numpy", num_cores=4)
    srv2.register("lm_only", _lm_cfg(), period_s=1 / 25, cache_len=64)
    with pytest.raises(ServeError, match="no executor"):
        srv2.submit("lm_only", 3)                 # analysis-only, no step_fn
    srv2.attach("lm_only", lambda tok: tok + 1)
    t = srv2.submit("lm_only", 3)
    srv2.run(hyperperiods=1)
    assert t.result().output == 4


# -- tickets + deadline verdicts ---------------------------------------------

def test_ticket_verdicts_pinned_generous_ratio():
    srv = _mixed_server(speed_ratio=1e12)         # everything meets
    t1 = srv.submit("cnn", _frame(5))
    t2 = srv.submit("lm", 7)
    srv.run(hyperperiods=1)
    for t in (t1, t2):
        r = t.result()
        assert r.deadline_met and r.verdict.met
        assert r.latency_s > 0 and r.response_bound_s > 0
        assert r.verdict.budget_s > r.latency_s
    assert t2.result().output == 22
    assert srv.monitor.misses == {}


def test_ticket_verdicts_pinned_tiny_ratio_miss():
    srv = _mixed_server(speed_ratio=1e-12)        # nothing can meet
    t = srv.submit("cnn", _frame(5))
    srv.run(hyperperiods=1)
    r = t.result()
    assert not r.deadline_met
    assert srv.monitor.misses["cnn"] == 1
    assert srv.monitor.miss_rate("cnn") == 1.0
    snap = srv.monitor.snapshot()
    assert snap["networks"]["cnn"]["miss_rate"] == 1.0
    assert sum(snap["networks"]["cnn"]["histogram"].values()) == 1


def test_per_request_deadline_overrides_network_deadline():
    srv = _mixed_server(speed_ratio=1.0)          # budget == model deadline
    tight = srv.submit("cnn", _frame(1), deadline_s=1e-12)
    loose = srv.submit("cnn", _frame(2), deadline_s=1e6)
    srv.run(hyperperiods=1)
    # both rode the same serving job (same batch, same latency) but carry
    # different verdicts: the deadline is per-request
    assert tight.result().latency_s == loose.result().latency_s
    assert not tight.result().deadline_met
    assert loose.result().deadline_met


def test_failed_job_marks_popped_tickets_failed():
    srv = Server(HW, backend="numpy", num_cores=4)
    srv.register("cnn", cnn.small_cnn(), period_s=1 / 50, slots=2)
    good = srv.submit("cnn", _frame())
    bad = srv.submit("cnn", {"wrong_key": _frame()})   # co-batched, malformed
    with pytest.raises(ServeError, match="missing input"):
        srv.run(hyperperiods=1)
    # popped tickets are never silently lost: both carry the failure
    assert good.status == "failed" and bad.status == "failed"
    with pytest.raises(ServeError, match="failed.*missing input"):
        good.result()
    t = srv.submit("cnn", _frame())                    # server still serves
    srv.run(hyperperiods=1)
    assert t.done


def test_autorun_network_refuses_submissions():
    eng = MultiModelEngine(hw=HW, num_cores=4)
    eng.add_graph("a", cnn.small_cnn(), period_s=1 / 50, step_fn=lambda: 1)
    with pytest.raises(ServeError, match="free-runs"):
        eng.server.submit("a", _frame())


def test_pending_ticket_has_no_result():
    srv = _mixed_server()
    t = srv.submit("cnn", _frame())
    with pytest.raises(ServeError, match="queued"):
        t.result()


# -- release-order execution ---------------------------------------------------

def test_release_order_across_hyperperiods():
    srv = Server(HW, backend="numpy", num_cores=4)
    seen = []
    srv.register("fast", cnn.small_cnn(), period_s=1 / 100,
                 step_fn=lambda p: seen.append(("fast", p)) or p)
    srv.register("slow", cnn.small_cnn(h=24, w=24), period_s=1 / 50,
                 step_fn=lambda p: seen.append(("slow", p)) or p)
    H = srv.compiled.hyperperiod_s
    assert H == pytest.approx(1 / 50)
    n_hp = 3
    for hp in range(n_hp):
        for k in range(2):
            srv.submit("fast", (hp, k))
        srv.submit("slow", (hp, 0))
    tel = srv.run(hyperperiods=n_hp)
    # per hyperperiod: fast releases at 0 and H/2, slow at 0; release order
    # interleaves fast/slow at t=0 (sid order: fast first), fast alone later
    per_hp = [("fast", ), ("slow", ), ("fast", )]
    expected = [kind for _ in range(n_hp) for (kind,) in per_hp]
    assert [k for k, _ in seen] == expected
    # payloads drained FIFO per network across hyperperiod boundaries
    assert [p for k, p in seen if k == "fast"] == \
        [(0, 0), (0, 1), (1, 0), (1, 1), (2, 0), (2, 1)]
    assert tel["hyperperiods_completed"] == n_hp
    assert tel["metrics"]["tickets"] == 9
    assert srv.monitor.checks == {"fast": 6, "slow": 3}


def test_ticket_release_times_accumulate():
    srv = _mixed_server()
    releases = []
    for hp in range(3):
        t = srv.submit("lm", hp)
        srv.run(hyperperiods=1)
        releases.append(t.result().release_s)
    H = srv.compiled.hyperperiod_s
    assert releases == pytest.approx([0.0, H, 2 * H])


def test_step_serves_in_static_batch_slots():
    srv = Server(HW, backend="numpy", num_cores=4)
    srv.register("cnn", cnn.small_cnn(), period_s=1 / 50, slots=2)
    x1, x2, x3 = _frame(1), _frame(2), _frame(3)
    tickets = [srv.submit("cnn", x) for x in (x1, x2, x3)]
    srv.run(hyperperiods=1)                       # 1 cnn job -> 2 served
    assert [t.done for t in tickets] == [True, True, False]
    srv.run(hyperperiods=1)                       # next job drains the third
    assert tickets[2].done
    # padded short batch must not perturb the real row
    solo = Server(HW, backend="numpy", num_cores=4)
    solo.register("cnn", cnn.small_cnn(), period_s=1 / 50, slots=2)
    ts = solo.submit("cnn", x3)
    solo.run(hyperperiods=1)
    a, b = tickets[2].result().output, ts.result().output
    for k in a:
        assert np.array_equal(a[k], b[k])


def test_yolov5s_served_on_pallas_equals_the_oracle(monkeypatch):
    """The published YOLOv5s backbone + SPPF (64x64, width 0.25) served as
    the chip benchmark serves it, on the pallas megakernel (interpret mode
    here) with 4 slots: 5 frames take two calls, the second with 3 padded
    slots. Every output equals the numpy oracle bit for bit; with the
    recorder off no span is built."""
    from repro import tracing
    from repro.core import init_params, reference_forward

    def no_span(*a, **k):
        raise AssertionError("a span was built with the recorder off")

    tracing.disable()
    monkeypatch.setattr(tracing, "_Span", no_span)
    monkeypatch.setattr(tracing.Recorder, "record", no_span)
    g = cnn.yolov5s(h=64, w=64, width=0.25)
    params = init_params(g, seed=5)
    srv = Server(scaled_paper_machine(16), backend="pallas", num_cores=16)
    srv.register("yolo", g, period_s=0.2, params=params, slots=4)
    frames = [_frame(k, 64, 64) for k in range(5)]
    tickets = [srv.submit("yolo", f) for f in frames]
    while not all(t.terminal for t in tickets):
        srv.step()
    (out,) = g.outputs
    for f, t in zip(frames, tickets):
        want = reference_forward(g, params, {"input": f})[out]
        got = t.result().output[out]
        assert got.dtype == want.dtype and np.array_equal(got, want)
    m = srv.metrics
    assert (m["runner_calls"], m["slots_filled"], m["slots_padded"]) \
        == (2, 5, 3)
    assert tracing.drain() == [] and tracing.dropped_spans() == 0


# -- save / load ----------------------------------------------------------------

@pytest.mark.parametrize("backend", ["numpy", "jax"])
def test_server_save_load_roundtrip_bit_exact(tmp_path, backend):
    srv = _mixed_server(backend=backend)
    path = str(tmp_path / "fleet")
    srv.save(path)
    srv2 = Server.load(path, step_fns={"lm": lambda tok: np.int64(tok) * 3
                                       + 1})
    assert srv2.backend == backend
    assert srv2.report.schedulable
    assert srv2.report.response_bounds == srv.report.response_bounds
    frames = [_frame(11), _frame(12)]
    outs = []
    for s in (srv, srv2):
        ts = [s.submit("cnn", f) for f in frames]
        tl = s.submit("lm", 5)
        s.run(hyperperiods=3)
        assert all(t.done for t in ts) and tl.result().output == 16
        outs.append([t.result().output for t in ts])
    for a, b in zip(*outs):
        for k in a:
            assert np.array_equal(a[k], b[k])


def test_server_load_refuses_wrong_machine(tmp_path):
    from repro.compiler import ArtifactError
    srv = _mixed_server()
    path = str(tmp_path / "fleet")
    srv.save(path)
    with pytest.raises(ArtifactError):
        Server.load(path, machine=scaled_paper_machine(8))


def test_save_bundle_detects_corruption(tmp_path):
    import json
    from repro.compiler import ArtifactError, load_bundle
    srv = _mixed_server()
    path = str(tmp_path / "fleet")
    srv.save(path)
    with open(path + "/objects.pkl", "ab") as f:
        f.write(b"tamper")
    with pytest.raises(ArtifactError, match="hash mismatch"):
        load_bundle(path)
    with open(path + "/bundle.json") as f:
        manifest = json.load(f)
    manifest["format"] = 99
    with open(path + "/bundle.json", "w") as f:
        json.dump(manifest, f)
    with pytest.raises(ArtifactError, match="unsupported bundle format"):
        load_bundle(path)


# -- monitor ----------------------------------------------------------------

def test_monitor_per_step_accounting():
    mon = DeadlineMonitor(speed_ratio=1.0, slack_factor=1.0)
    for lat in (0.5, 2.0, 3.0):                  # bound 1.0 -> 2 misses
        mon.check("n", lat, 1.0)
    assert mon.checks["n"] == 3 and mon.misses["n"] == 2
    assert mon.miss_rate("n") == pytest.approx(2 / 3)
    snap = mon.snapshot()["networks"]["n"]
    assert snap["p50_s"] == 2.0 and snap["max_s"] == 3.0
    mon.reset()
    assert mon.checks == {} and mon.speed_ratio == 1.0


def test_monitor_calibrates_once():
    mon = DeadlineMonitor()
    v = mon.check("n", 0.02, 0.01)               # calibration step: meets
    assert v.met and mon.speed_ratio == pytest.approx(2.0)
    v2 = mon.check("n", 0.05, 0.01)              # 0.05 > 0.01*2*1.5
    assert not v2.met
    mon.reset(recalibrate=True)
    assert mon.speed_ratio is None


# -- wrappers ------------------------------------------------------------------

def test_predictable_engine_counts_misses_per_step():
    jax = pytest.importorskip("jax")
    from repro.configs import get_config
    from repro.models import init_params
    from repro.serve import PredictableEngine, Request
    cfg = get_config("smollm-135m", reduced=True)
    params = init_params(cfg, jax.random.PRNGKey(0))
    eng = PredictableEngine(cfg, params, batch_size=2, max_len=64,
                            hw=scaled_paper_machine(4), speed_ratio=1e-12)
    done = eng.generate([Request(rid=0, prompt=[1, 2], max_new_tokens=6)])
    assert done[0].out
    # the old aggregate accounting capped misses at 1 per generate() call;
    # with a hopeless pinned ratio every individual step must miss
    assert eng.deadline_checks == 5
    assert eng.deadline_misses == eng.deadline_checks


def test_multi_model_engine_admit_model():
    eng = MultiModelEngine(hw=HW, num_cores=4)
    assert eng.admit_graph("det", cnn.small_cnn(), period_s=1 / 50)
    assert eng.admit_model("lm", _lm_cfg(), period_s=1 / 25, cache_len=64)
    assert {s.name for s in eng.specs} == {"det", "lm"}
    assert eng.report.schedulable
    # an LM model with an impossible deadline is rejected atomically
    assert not eng.admit_model("lm2", _lm_cfg(), period_s=1 / 25,
                               cache_len=64, deadline_s=1e-9)
    assert {s.name for s in eng.specs} == {"det", "lm"}
    assert eng.report.schedulable
    stats = eng.run_hyperperiod(speed_ratio=1e12)
    assert stats["speed_ratio"] == 1e12
    # "det" has no step_fn yet: executed for ordering, never checked
    assert "det" not in stats["checks"]
