"""The whole published YOLOv5s configuration (`configs/yolov5s_det_640.json`)
at sizes a test run holds (CPU only): the program served through the
benchmark's own path (`harness.build_graph`, `harness.build_server`,
`Server.step`) on the numpy, jax and pallas (interpret mode) backends
equals the plain reference in all three Detect outputs, bit for bit; and
the reference one precision step down (int4) fails the comparison that the
program passes."""

import os

import numpy as np
import pytest

import harness
import reference
import traffic


def setup(seed: int, h: int = 64, width: float = 0.25):
    cfg = harness.load_json(os.path.join(harness.HERE, "configs",
                                         "yolov5s_det_640.json"))
    cfg["kwargs"] = dict(cfg["kwargs"], h=h, w=h, width=width)
    net = harness.reference_net(cfg)
    params = harness.make_params(net, seed, cfg["requant_gain"])
    return cfg, net, params, traffic.Frames(net.shapes["input"], 4, seed)


def test_configuration_is_the_published_network():
    """At 640x640 the reference is the published YOLOv5s: 60 convs, 57
    SiLUs at scale 1/16, 2 upsamples, 13 concats, three Detect maps of 255
    channels; the program's graph has the same parameters and shapes."""
    cfg, net, _, _ = setup(1, 640, 1.0)
    ops = [ly.op for ly in net.layers]
    assert (ops.count("conv"), ops.count("silu"), ops.count("upsample"),
            ops.count("concat")) == (60, 57, 2, 13)
    assert {ly.attrs["scale"] for ly in net.layers if ly.op == "silu"} \
        == {0.0625}
    assert [net.shapes[t] for t in net.outputs] == \
        [(80, 80, 255), (40, 40, 255), (20, 20, 255)]
    g = harness.build_graph(cfg, net)
    assert g.outputs == [f"detect.m{i}.rq.out" for i in range(3)]
    assert cfg["reduced"] == ["detect_decode"]


@pytest.mark.parametrize("backend", ["numpy", "jax", "pallas"])
@pytest.mark.parametrize("h,width", [(64, 0.25), (128, 0.5)])
def test_served_program_matches_the_reference(h, width, backend):
    cfg, net, params, frames = setup(3, h, width)
    cfg["backend"] = backend
    graph = harness.build_graph(cfg, net)
    mix = {"queue_capacity": 8, "slots": 2}
    srv = harness.build_server(cfg, mix, graph, params)
    tickets = [srv.submit(cfg["name"], frames(k)) for k in range(2)]
    while not all(t.terminal for t in tickets):
        srv.step()
    for k, t in enumerate(tickets):
        want = reference.forward(net, params, frames(k))
        got = t.result().output
        assert len(want) == len(graph.outputs) == 3
        for g_out, w in zip(graph.outputs, want.values()):
            np.testing.assert_array_equal(got[g_out], w)
            assert np.mean(w != 0) > 0.5


@pytest.mark.parametrize("seed", [1, 2, 2**33 + 3])
def test_int4_control_fails_the_comparison(seed):
    _, net, params, frames = setup(seed)
    records = [harness.Frame(k, 0.0, 0.0, 0.0, "done") for k in range(6)]
    sound = {k: list(reference.forward(net, params, frames(k)).values())
             for k in range(6)}
    checks, n = harness.compare(net, params, frames, sound, records, seed, 4)
    assert n >= 4 and all(v <= lim for v, lim in checks.values())
    control, _ = harness.compare(net, params, frames, sound, records, seed,
                                 4, arith="int4")
    assert control["mismatched_frames"][0] == n
    assert control["max_abs_diff"][0] > 0
