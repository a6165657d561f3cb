"""A whole run at a small size on the CPU, with and without faults planted
in the timed path: `correct` must hold for the sound program and fail for
each fault a cell can have.

The run skips the look for a chip (`require_tpu=False`) and serves through
the `jax` backend, which is bit-exact with the Pallas program and fast
here; everything else is the run the chip gets. Faults are planted by
wrapping the network's runner (or the Server's intake) after set-up, so
the warm-up calls stay sound and only the window's calls are broken.
"""

import os
import time

import numpy as np
import pytest

import harness

SMALL = {"h": 32, "w": 32, "num_classes": 10, "width": 0.125,
         "blocks": [1, 1, 1, 1]}
END_TO_END = [{"name": n, "unit": u} for n, u in (
    ("frame_latency_p50_ms", "ms"), ("frame_latency_p95_ms", "ms"),
    ("frames_per_s", "frames/s"), ("setup_s", "s"))]


def small_cell(mix_name: str) -> harness.Cell:
    cfg = harness.load_json(os.path.join(harness.HERE, "configs",
                                         "resnet50_224.json"))
    cfg.update(kwargs=SMALL, backend="jax")
    mix = harness.load_json(os.path.join(harness.HERE, "traffic",
                                         f"{mix_name}.json"))
    mix.update(check_sample=6)
    return harness.Cell(f"small.{mix_name}", 1, cfg, mix, {"rate_hz": 100.0},
                        END_TO_END, [])


@pytest.fixture(autouse=True)
def no_persistent_cache(monkeypatch):
    monkeypatch.setattr(harness, "enable_cache", lambda: None)
    monkeypatch.setattr(harness, "DRAIN_S", 0.3)


def plant(monkeypatch, wrap_runner=None, wrap_submit=None):
    """Break the runner (or the intake) of the server the run builds, from
    its first call after the warm-up on."""
    build = harness.build_server

    def faulty(config, mix, graph, params):
        srv = build(config, mix, graph, params)
        st = srv._nets[config["name"]]
        inner, calls = st.runner, [0]

        def runner(batch):
            calls[0] += 1
            out = inner(batch)
            if calls[0] <= harness.WARM_CALLS or wrap_runner is None:
                return out
            return wrap_runner({k: np.array(v) for k, v in out.items()})
        st.runner = runner
        if wrap_submit is not None:
            srv.submit = wrap_submit(srv.submit, srv)
        return srv
    monkeypatch.setattr(harness, "build_server", faulty)


def run(mix_name: str, seed: int = 2**33 + 7) -> dict:
    return harness.run(small_cell(mix_name), seed, 0.4, False,
                       time.perf_counter(), require_tpu=False)


@pytest.mark.parametrize("mix_name", ["periodic_b1", "backlog_b4"])
def test_sound_program_is_correct(monkeypatch, mix_name):
    plant(monkeypatch)
    r = run(mix_name)
    assert r["correct"] and r["failed"] == 0 and r["attempted"] > 0
    assert list(r)[-1] == "checks"
    assert all(c["value"] <= c["limit"] for c in r["checks"].values())
    key = "frame_latency_p95_ms" if mix_name == "periodic_b1" \
        else "frames_per_s"
    assert r["metrics"][key]["value"] > 0
    assert r["metrics"]["setup_s"]["value"] > 0


def altered_answer(out):
    for v in out.values():
        v.reshape(v.shape[0], -1)[:, 0] += 1
    return out


def half_batch_left_out(out):
    for v in out.values():
        v[v.shape[0] // 2:] = 0
    return out


@pytest.mark.parametrize("mix_name,fault", [
    ("periodic_b1", altered_answer),
    ("backlog_b4", altered_answer),
    ("backlog_b4", half_batch_left_out),
])
def test_a_fault_in_the_timed_path_is_not_correct(monkeypatch, mix_name,
                                                  fault):
    plant(monkeypatch, wrap_runner=fault)
    r = run(mix_name)
    assert not r["correct"]
    assert r["checks"]["mismatched_frames"]["value"] > 0


def test_an_answer_that_never_comes_is_not_correct(monkeypatch):
    from repro.serve import Ticket

    def losing(submit, srv):
        n = [0]

        def sub(name, payload, deadline_s=None):
            n[0] += 1
            if n[0] % 5 == 0 and n[0] > 2 * harness.WARM_CALLS:
                return Ticket(tid=-n[0], network=name, payload=payload)
            return submit(name, payload, deadline_s)
        return sub
    plant(monkeypatch, wrap_submit=losing)
    r = run("periodic_b1")
    assert not r["correct"]
    assert r["checks"]["lost_frames"]["value"] > 0
