"""The published YOLOv5s configuration (`configs/yolov5s_640_v6.json`) at
sizes a test run holds (CPU only): its reference agrees with the
program's numpy oracle, and the reference one precision step down (int4)
fails the comparison that the program passes."""

import os

import numpy as np
import pytest

import harness
import reference
import traffic


def setup(seed: int, h: int = 64, width: float = 0.5):
    cfg = harness.load_json(os.path.join(harness.HERE, "configs",
                                         "yolov5s_640_v6.json"))
    cfg["kwargs"] = {"h": h, "w": h, "width": width}
    net = harness.reference_net(cfg)
    params = harness.make_params(net, seed, cfg["requant_gain"])
    return cfg, net, params, traffic.Frames(net.shapes["input"], 4, seed)


@pytest.mark.parametrize("width", [0.25, 1.0])
def test_reference_matches_the_program_oracle(width):
    from repro.core import reference_forward
    cfg, net, params, frames = setup(3, width=width)
    graph = harness.build_graph(cfg, net)
    c = net.shapes["c3_4.cv3.out"][-1]          # SPPF: c -> c/2, 4 x c/2 -> c
    assert net.weights["sppf.cv1.w"] == (c, c // 2)
    assert net.shapes["sppf.cat.out"][-1] == 2 * c
    assert net.weights["sppf.cv2.w"] == (2 * c, c)
    for k in range(2):
        want = reference_forward(graph, params, {"input": frames(k)})
        got = reference.forward(net, params, frames(k))
        np.testing.assert_array_equal(got, want[graph.outputs[0]])
        assert np.mean(got != 0) > 0.2


@pytest.mark.parametrize("seed", [1, 2, 2**33 + 3])
def test_int4_control_fails_the_comparison(seed):
    _, net, params, frames = setup(seed)
    records = [harness.Frame(k, 0.0, 0.0, 0.0, "done") for k in range(6)]
    sound = {k: reference.forward(net, params, frames(k)) for k in range(6)}
    checks, n = harness.compare(net, params, frames, sound, records, seed, 4)
    assert n >= 4 and all(v <= lim for v, lim in checks.values())
    control, _ = harness.compare(net, params, frames, sound, records, seed,
                                 4, arith="int4")
    assert control["mismatched_frames"][0] == n
    assert control["max_abs_diff"][0] > 0
