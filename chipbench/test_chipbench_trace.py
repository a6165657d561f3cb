"""The trace reduction against hand counts (CPU only).

`testdata/resnet_tiny_v5e.xplane.pb` is a profiler trace recorded on one
TPU v5e by `run.py --trace 1` over a 50 ms window of a reduced ResNet-50
(32x32 input, width 0.125, one block per stage) at 100 frames/s: five
frames, five launches of one program, 330 device operations. The
expected numbers below were read off its raw events by hand.
"""

import os

import pytest

import trace_reduce as T

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "testdata",
                    "resnet_tiny_v5e.xplane.pb")


@pytest.fixture(scope="module")
def tiny():
    return T.load(DATA)


def test_planes_and_lines_are_found(tiny):
    assert len(tiny["devices"]) == 1 and len(tiny["devices"][0]) == 330
    assert len(tiny["modules"]) == 5 and len(tiny["launches"]) == 5
    assert sum(n == "chipbench.runner" for n, _, _ in tiny["thread"]) == 5


def test_device_clock_offset_is_the_least_launch_gap(tiny):
    # program start minus host launch, per frame (ns):
    # -1037437, -891216, -1074434, -1055589, -1113081 -> least -1113081
    assert T.clock_offset_ns(tiny["modules"], tiny["launches"]) == -1113081
    assert T.clock_offset_ns([1.0, 2.0], [0.5]) == 0.0


def test_reduction_of_the_recorded_window(tiny):
    r = T.reduce_trace(tiny)
    assert r["window_s"] == pytest.approx(50008015e-9, abs=1e-12)
    # once shifted, every op lies in the window: busy = union of all ops
    assert r["busy_s"] == pytest.approx(41222e-9, abs=1e-12)
    # five runner spans: 2248140 + 1883269 + 1737450 + 1790120 + 1838120 ns
    assert r["spans_s"]["chipbench.runner"] == pytest.approx(9497099e-9,
                                                             abs=1e-12)
    # the network's kernel, 4434 + 4433 + 4435 + 4436 + 4434 ns
    assert r["device_ops"][0] == ["vmap__.1", pytest.approx(22172e-9)]
    assert r["idle_gaps"][0][0] == "chipbench.wait"
    idle = sum(v for _, v in r["idle_gaps"])
    assert idle <= r["window_s"] - r["busy_s"] + 1e-12


def test_gaps_are_labelled_by_the_innermost_host_event():
    trace = {
        "devices": [[("a", 10, 20), ("b", 15, 30), ("c", 50, 60),
                     ("a", 95, 120)]],
        "modules": [], "launches": [],
        "thread": [("chipbench.window", 0, 100), ("chipbench.step", 5, 40),
                   ("chipbench.runner", 12, 35), ("chipbench.wait", 40, 90),
                   ("x", 41, 45)],
    }
    r = T.reduce_trace(trace)
    # busy: [10,30] + [50,60] + [95,100] of the window [0,100]
    assert r["busy_s"] == pytest.approx(35e-9)
    assert r["device_ops"][0] == ["a", pytest.approx(15e-9)]
    # gaps [0,10] (mid 5: step), [30,50] (mid 40: wait), [60,95] (wait)
    assert dict(r["idle_gaps"]) == {"chipbench.step": pytest.approx(10e-9),
                                    "chipbench.wait": pytest.approx(55e-9)}
    assert r["spans_s"]["chipbench.runner"] == pytest.approx(23e-9)


def test_op_names_are_the_hlo_instruction():
    assert T.op_name("%fusion.22 = s8[3025,64]{1,0} fusion(...)") == \
        "fusion.22"
