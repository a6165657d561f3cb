"""The plain reference's upsample, int8 SiLU and several outputs, and the
harness's handling of a network with several outputs (CPU only); and the
numbers today's configurations read, pinned."""

import hashlib
import os
import time
import types

import numpy as np
import pytest

import counting
import harness
import reference
import test_chipbench_faults
import traffic

HERE = os.path.dirname(os.path.abspath(__file__))


# -- upsample and SiLU -----------------------------------------------------

@pytest.mark.parametrize("factor", [2, 3])
def test_upsample_repeats_each_pixel(factor):
    net = reference.Net("up", (3, 4, 5))
    y = net.upsample("up", "input", factor)
    assert net.shapes[y] == (3 * factor, 4 * factor, 5)
    frame = traffic.Frames((3, 4, 5), 1, 11)(0)
    want = np.empty((3 * factor, 4 * factor, 5), np.int8)
    for i in range(3 * factor):
        for j in range(4 * factor):
            want[i, j] = frame[i // factor, j // factor]
    got = reference.forward(net, {}, frame)[y]
    np.testing.assert_array_equal(got, want)
    assert got.dtype == np.int8
    assert counting.layer_ops(net, net.layers[0]) == 0


def test_silu_table_at_scale_one_sixteenth():
    t = reference.silu_table(1 / 16)
    at = {q: int(t[q + 128]) for q in range(-128, 128)}
    # worked out by hand in float64: q * sigmoid(q / 16), rounded
    assert at[0] == 0
    assert at[64] == 63          # 64 * 0.98201 = 62.85
    assert at[100] == 100        # 100 * 0.99807 = 99.81
    assert at[127] == 127        # 127 * 0.99964 = 126.95
    assert at[-20] == -4         # -20 * 0.22270 = -4.45
    assert min(at.values()) == -4
    # where sigmoid lowers q by half a step or more: a table that skips
    # the sigmoid (or takes it at another scale) fails here
    assert all(at[q] < q for q in range(2, 82))
    assert at[82] == 82 and at[1] == 1
    assert all(at[q] <= 0 for q in range(-128, 1))


def test_silu_layer_applies_the_table_and_keeps_the_scale():
    net = reference.Net("silu", (4, 4, 16))
    y = net.silu("act", "input", 1 / 16)
    assert net.shapes[y] == (4, 4, 16) and net.dtypes[y] == "int8"
    frame = np.arange(-128, 128, dtype=np.int16).astype(np.int8)
    frame = frame.reshape(4, 4, 16)
    got = reference.forward(net, {}, frame)[y]
    np.testing.assert_array_equal(got.reshape(-1),
                                  reference.silu_table(1 / 16))
    assert counting.layer_ops(net, net.layers[0]) == 0
    with pytest.raises(ValueError, match="not int8"):
        fc = reference.Net("fc", (1, 8))
        fc.silu("act", fc.fc("fc", "input", 4), 1 / 16)


# -- a network with two outputs ---------------------------------------------

def two_output_net() -> reference.Net:
    """conv -> SiLU -> upsample -> concat with an earlier tensor -> conv,
    and the SiLU's output as a second output."""
    net = reference.Net("two_out", (8, 8, 3))
    c0 = net.conv("c0", "input", 8, 3)
    c1 = net.conv("c1", "input", 8, 3, stride=2, relu=False)
    s1 = net.silu("c1.silu", c1, 1 / 16)
    up = net.upsample("up", s1, 2)
    cat = net.concat("cat", [up, c0])
    c2 = net.conv("c2", cat, 12, 1)
    net.mark_output(c2)
    net.mark_output(s1)
    return net


def two_output_params(net: reference.Net, seed: int) -> dict:
    rng = np.random.default_rng(seed)
    params = {n: rng.integers(-64, 64, net.weights[n]).astype(np.int8)
              for n in sorted(net.weights)}
    params.update(net.mult_values(0.3))
    return params


def test_forward_returns_every_marked_output_in_order():
    net = two_output_net()
    assert net.outputs == ["c2.out", "c1.silu.out"]
    out = reference.forward(net, two_output_params(net, 1),
                            traffic.Frames((8, 8, 3), 1, 1)(0))
    assert list(out) == net.outputs
    assert out["c2.out"].shape == (8, 8, 12)
    assert out["c1.silu.out"].shape == (4, 4, 8)
    plain = reference.Net("one", (8, 8, 3))
    last = plain.conv("c", "input", 4, 1)
    assert plain.outputs == [last]


def test_frame_io_bytes_sums_every_output():
    net = two_output_net()
    assert counting.frame_io_bytes(net) == 8 * 8 * 3 + 8 * 8 * 12 + 4 * 4 * 8


def fake_graph(variant: str = "sound"):
    """A graph as `build_graph` reads it (ops with weights and requants,
    tensors with shapes, outputs), drawn from the reference net; the
    variant changes its outputs."""
    net = two_output_net()
    ops = []
    for ly in net.layers:
        if ly.op == "conv":
            ops.append(types.SimpleNamespace(name=ly.name, kind="conv2d",
                                             weights=[f"{ly.name}.w"]))
            ops.append(types.SimpleNamespace(name=f"{ly.name}.rq",
                                             kind="requant", weights=[]))
    tensors = {t: types.SimpleNamespace(shape=s)
               for t, s in {**net.shapes, **net.weights}.items()}
    outputs = list(net.outputs)
    if variant == "renamed":       # named after an activation, same shapes
        tensors["c2.relu.out"] = tensors["c2.out"]
        outputs[0] = "c2.relu.out"
    elif variant == "one_output":
        outputs = outputs[:1]
    elif variant == "extra_output":
        outputs.append("cat.out")
    elif variant == "other_shape":
        outputs[1] = "up.out"
    elif variant == "swapped":
        outputs.reverse()
    return types.SimpleNamespace(ops=ops, tensors=tensors, outputs=outputs)


def fake_config(variant: str) -> dict:
    return {"name": "two_out", "builder": f"{__name__}:fake_graph",
            "kwargs": {"variant": variant}}


@pytest.mark.parametrize("variant", ["sound", "renamed"])
def test_build_graph_takes_the_reference_outputs(variant):
    g = harness.build_graph(fake_config(variant), two_output_net())
    assert len(g.outputs) == 2


@pytest.mark.parametrize("variant", ["one_output", "extra_output",
                                     "other_shape", "swapped"])
def test_build_graph_refuses_other_outputs(variant):
    with pytest.raises(harness.BenchError, match="outputs"):
        harness.build_graph(fake_config(variant), two_output_net())


def served_two_output(seed: int, frames_n: int = 4):
    net = two_output_net()
    params = two_output_params(net, seed)
    frames = traffic.Frames(net.shapes["input"], 2, seed)
    outputs = {k: list(reference.forward(net, params, frames(k)).values())
               for k in range(frames_n)}
    records = [harness.Frame(k, 0.0, 0.0, 0.0, "done")
               for k in range(frames_n)]
    return net, params, frames, outputs, records


def test_compare_counts_a_frame_once_over_its_outputs():
    net, params, frames, outputs, records = served_two_output(5)
    checks, n = harness.compare(net, params, frames, outputs, records, 5, 4)
    assert n == 4 and all(v == 0 for v, _ in checks.values())
    # frame 1: its second output alone is off by 3 in one value
    second = outputs[1][1].astype(np.int16)
    second[2, 3, 4] += 3 if second[2, 3, 4] < 100 else -3
    outputs[1][1] = second
    checks, _ = harness.compare(net, params, frames, outputs, records, 5, 4)
    assert checks["mismatched_frames"] == (1, 0)
    assert checks["max_abs_diff"] == (3, 0)
    # frame 2: both outputs off, by 1 and 7: still one frame more
    outputs[2] = [outputs[2][0].astype(np.int16) + 1,
                  outputs[2][1].astype(np.int16) - 7]
    checks, _ = harness.compare(net, params, frames, outputs, records, 5, 4)
    assert checks["mismatched_frames"] == (2, 0)
    assert checks["max_abs_diff"] == (7, 0)
    # frame 3: an output missing
    outputs[3] = outputs[3][:1]
    checks, _ = harness.compare(net, params, frames, outputs, records, 5, 4)
    assert checks["mismatched_frames"] == (3, 0)


@pytest.mark.parametrize("seed", [1, 2, 2**33 + 3])
def test_int4_control_fails_on_two_outputs(seed):
    net, params, frames, outputs, records = served_two_output(seed, 6)
    checks, n = harness.compare(net, params, frames, outputs, records,
                                seed, 4)
    assert n >= 4 and all(v <= lim for v, lim in checks.values())
    control, _ = harness.compare(net, params, frames, outputs, records,
                                 seed, 4, arith="int4")
    assert control["mismatched_frames"][0] == n
    assert control["max_abs_diff"][0] > 0


# -- a whole run of a two-output program on the CPU --------------------------

def two_output_program():
    """The program's graph of `two_output_net` without its SiLU and
    upsample, which the program lacks: conv -> relu, max-pool, conv."""
    from repro.core.graph import Graph, conv2d, eltwise, pool2d, requant
    g = Graph("two_out_program")
    g.add_tensor("input", (16, 16, 3), "int8", is_input=True)
    a = eltwise(g, "c0.relu", "relu",
                [requant(g, "c0.rq", conv2d(g, "c0", "input", 8, 3))])
    p = pool2d(g, "pool", "maxpool", a, 2, 2)
    b = eltwise(g, "c1.relu", "relu",
                [requant(g, "c1.rq", conv2d(g, "c1", p, 16, 1))])
    g.mark_output(b)
    g.mark_output(a)
    g.validate()
    return g


def two_output_program_net(config=None) -> reference.Net:
    net = reference.Net("two_out_program", (16, 16, 3))
    a = net.conv("c0", "input", 8, 3)
    b = net.conv("c1", net.maxpool("pool", a, 2, 2), 16, 1)
    net.mark_output(b)
    net.mark_output(a)
    return net


def run_two_output(monkeypatch, fault=None) -> dict:
    cfg = harness.load_json(os.path.join(HERE, "configs",
                                         "resnet50_224.json"))
    cfg.update(name="two_out", builder=f"{__name__}:two_output_program",
               kwargs={}, backend="jax", requant_gain=0.5)
    mix = harness.load_json(os.path.join(HERE, "traffic", "backlog_b4.json"))
    mix.update(check_sample=6)
    cell = harness.Cell("two_out.backlog_b4", 1, cfg, mix, {},
                        [{"name": "frames_per_s", "unit": "frames/s"}], [])
    monkeypatch.setattr(harness, "enable_cache", lambda: None)
    monkeypatch.setattr(harness, "DRAIN_S", 0.3)
    monkeypatch.setattr(harness, "reference_net", two_output_program_net)
    test_chipbench_faults.plant(monkeypatch, wrap_runner=fault)
    return harness.run(cell, 2**33 + 9, 0.4, False, time.perf_counter(),
                       require_tpu=False)


def test_a_run_checks_every_output(monkeypatch):
    r = run_two_output(monkeypatch)
    assert r["correct"] and r["attempted"] > 0
    assert r["metrics"]["frames_per_s"]["value"] > 0


def test_a_run_with_its_second_output_altered_is_not_correct(monkeypatch):
    def second_altered(out):
        v = out["c0.relu.out"]
        v.reshape(v.shape[0], -1)[:, 0] ^= 1
        return out
    r = run_two_output(monkeypatch, second_altered)
    assert not r["correct"]
    assert r["checks"]["mismatched_frames"]["value"] > 0
    assert r["checks"]["max_abs_diff"]["value"] >= 1


# -- today's configurations, pinned -----------------------------------------

# sha256 over two frames of (dtype, shape, bytes) of the reference's output
# at 64x64, width 0.25, weights and frames from seed 3, and the counts at
# the configurations' own sizes, as the reference gave them when it knew
# one output only; the counts feed `program_roofline` and `step_mfu_pct`.
PINNED = {
    "resnet50_224.json": {
        "int8": "17acbddd35d9a0322402bfba296ea714"
                "54fa63b809ed59d9a789612c4a564fd2",
        "int4": "6179358d730630a44d1d541504f290be"
                "41834c58382080a8e28a6e484d39719f",
        "counts": (8123809792, 25502912, 154528)},
    "yolov5s_640_v6.json": {
        "int8": "a67ae80ce5aed0d4cbc78581be3015da"
                "7a9e0b6d4ccbd34c35e6d13f6105456d",
        "int4": "a7748c762e5fc628e4d76e320709005f"
                "01a07147b53aff1adc9209fba02d6167",
        "counts": (10354688000, 4160896, 1433600)},
}


@pytest.mark.parametrize("config_file", sorted(PINNED))
def test_configurations_read_the_pinned_numbers(config_file):
    cfg = harness.load_json(os.path.join(HERE, "configs", config_file))
    net = harness.reference_net(cfg)
    assert (counting.frame_ops(net), counting.weight_bytes(net),
            counting.frame_io_bytes(net)) == PINNED[config_file]["counts"]
    cfg["kwargs"] = {**cfg["kwargs"], "h": 64, "w": 64, "width": 0.25}
    net = harness.reference_net(cfg)
    assert len(net.outputs) == 1
    rng = np.random.default_rng(3)
    params = {n: rng.integers(-64, 64, net.weights[n]).astype(np.int8)
              for n in sorted(net.weights)}
    params.update(net.mult_values(cfg["requant_gain"]))
    frames = traffic.Frames(net.shapes["input"], 2, 3)
    for arith in ("int8", "int4"):
        h = hashlib.sha256()
        for k in range(2):
            y = reference.forward(net, params, frames(k), arith)[net.output]
            h.update(str((y.dtype.str, y.shape)).encode())
            h.update(np.ascontiguousarray(y).tobytes())
        assert h.hexdigest() == PINNED[config_file][arith], arith
