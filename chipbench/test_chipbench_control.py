"""The control of the comparison, at sizes a test run holds (CPU only).

The plain reference computed one precision step down (int4 for the
configurations' int8), put in the program's place, must fail the check
that the program passes; and the reference itself must agree with the
program's own numpy oracle on both networks, at reduced sizes.
"""

import os

import numpy as np
import pytest

import harness
import reference
import traffic

CASES = {      # configuration: (its file, reduced sizes)
    "resnet50_224": ("resnet50_224.json",
                     {"h": 64, "w": 64, "num_classes": 16, "width": 0.25,
                      "blocks": [1, 1, 1, 1]}),
    "yolov5s_640": ("yolov5s_640_v6.json",
                    {"h": 128, "w": 128, "width": 0.5}),
}


def setup(config_name: str, seed: int):
    file, kwargs = CASES[config_name]
    cfg = harness.load_json(os.path.join(harness.HERE, "configs", file))
    cfg["kwargs"] = kwargs
    net = harness.reference_net(cfg)
    rng = np.random.default_rng(seed)
    params = {n: rng.integers(-64, 64, s).astype(np.int8)
              for n, s in net.weights.items()}
    params.update(net.mult_values(cfg["requant_gain"]))
    return cfg, net, params, traffic.Frames(net.shapes["input"], 4, seed)


@pytest.mark.parametrize("config_name", sorted(CASES))
def test_reference_matches_the_program_oracle(config_name):
    from repro.core import reference_forward
    cfg, net, params, frames = setup(config_name, 3)
    graph = harness.build_graph(cfg, net)
    for k in range(2):
        want = reference_forward(graph, params, {"input": frames(k)})
        (got,) = reference.forward(net, params, frames(k)).values()
        np.testing.assert_array_equal(got, want[graph.outputs[0]])


@pytest.mark.parametrize("config_name", sorted(CASES))
@pytest.mark.parametrize("seed", [1, 2, 2**33 + 3])
def test_int4_control_fails_the_comparison(config_name, seed):
    _, net, params, frames = setup(config_name, seed)
    records = [harness.Frame(k, 0.0, 0.0, 0.0, "done") for k in range(6)]
    sound = {k: list(reference.forward(net, params, frames(k)).values())
             for k in range(6)}
    checks, n = harness.compare(net, params, frames, sound, records, seed, 4)
    assert n >= 4 and all(v <= lim for v, lim in checks.values())
    control, _ = harness.compare(net, params, frames, sound, records, seed,
                                 4, arith="int4")
    assert control["mismatched_frames"][0] == n
    assert control["max_abs_diff"][0] > 0
