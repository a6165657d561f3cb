"""The benchmark's arithmetic against hand counts (CPU only)."""

import math
import os

import pytest

import counting
import harness
import stats
import traffic

HERE = os.path.dirname(os.path.abspath(__file__))


def load_ref_module(name):
    return harness.load_module(os.path.join(HERE, "configs", f"{name}.py"),
                               f"test_ref_{name}")


def test_percentiles_count_failed_frames_as_missing():
    lat = [0.001 * i for i in range(1, 21)]           # 1..20 ms
    assert stats.percentile(lat, 50) == pytest.approx(0.010)
    assert stats.percentile(lat, 95) == pytest.approx(0.019)
    # a failed frame is infinitely late: it shifts the ranks
    assert stats.percentile(lat + [math.inf], 50) == pytest.approx(0.011)
    assert stats.percentile(lat + [math.inf], 95) == pytest.approx(0.020)
    # a percentile landing on a missing frame has no finite value
    assert stats.percentile([0.001] * 18 + [math.inf] * 2, 95) is None
    assert stats.percentile([], 50) is None


def test_latency_is_measured_from_the_due_time():
    f = harness.Frame(k=0, due=10.0, step_start=10.004, done=10.007,
                      status="done")
    assert f.latency_s == pytest.approx(0.007)
    assert harness.Frame(k=1, due=10.0, status="refused").latency_s == math.inf


def test_open_loop_schedule_is_fixed_by_rate_and_window():
    off = traffic.open_loop_offsets({"frames_per_trigger": 1}, 100.0, 2.0)
    assert len(off) == 200 and off[0] == 0 and off[-1] == pytest.approx(1.99)
    four = traffic.open_loop_offsets({"frames_per_trigger": 4}, 10.0, 1.0)
    assert len(four) == 40 and (four[:4] == 0).all()


def test_frames_repeat_only_after_256_pool_lengths():
    fr = traffic.Frames((4, 4, 3), pool=2, seed=2**33 + 1)
    seen = {fr(k).tobytes() for k in range(2 * 256)}
    assert len(seen) == 2 * 256
    assert fr(0).tobytes() == fr(2 * 256).tobytes()
    again = traffic.Frames((4, 4, 3), pool=2, seed=2**33 + 1)
    assert again(7).tobytes() == fr(7).tobytes()


def test_resnet50_stem_and_fc_counts():
    net = load_ref_module("resnet").network()
    by = {ly.name: ly for ly in net.layers}
    # stem: 112x112 outputs, 7*7*3 taps, 64 channels, 2 ops per MAC
    assert counting.layer_ops(net, by["stem"]) == 2 * 112 * 112 * 147 * 64
    assert net.weights["stem.w"] == (147, 64)
    # fc: 2048 -> 1000
    assert counting.layer_ops(net, by["fc"]) == 2 * 2048 * 1000
    assert net.weights["fc.w"] == (2048, 1000)
    # conv and fc only: the elementwise ops in the program's 8.18 GOP are not
    # matrix work
    assert counting.frame_ops(net) == pytest.approx(8.124e9, rel=1e-3)
    assert counting.weight_bytes(net) == pytest.approx(25.5e6, rel=2e-3)
    # input 224*224*3 int8, output 1000 int32 logits
    assert counting.frame_io_bytes(net) == 224 * 224 * 3 + 4 * 1000


def test_call_floor_is_memory_bound_at_batch_1_and_compute_bound_at_4():
    net = load_ref_module("resnet").network()
    peaks = harness.load_json(os.path.join(HERE, "peaks.json"))["TPU v5 lite"]
    w = counting.weight_bytes(net)
    io = counting.frame_io_bytes(net)
    one = counting.call_floor_s(net, 1, peaks)
    assert one == pytest.approx((w + io) / 819e9)
    four = counting.call_floor_s(net, 4, peaks)
    assert four == pytest.approx(4 * counting.frame_ops(net) / 393e12)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(harness.BenchError):
        harness.peaks_for("TPU v99")
    assert harness.peaks_for("TPU v5 lite")["int8_ops_per_s"] == 393e12


def test_benchmark_json_names_a_file_for_everything():
    bench = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert callable(harness.metric_reader(m["name"]))
    for w in bench["workloads"]:
        cell = harness.load_cell(w["name"])
        assert cell.mix["slots"] >= 1
        if cell.mix["loop"] == "open":
            assert cell.rate_hz > 0
        net = harness.reference_net(cell.config)
        graph = harness.build_graph(cell.config, net)
        assert graph.outputs


def test_a_program_graph_of_other_shapes_is_refused():
    cfg = harness.load_json(os.path.join(HERE, "configs", "resnet50_224.json"))
    cfg["kwargs"] = {"h": 32, "w": 32, "num_classes": 10, "width": 0.125,
                     "blocks": [1, 1, 1, 1]}
    net = harness.reference_net(cfg)
    assert harness.build_graph(cfg, net).outputs
    # a stem pool padded by 1, as in the paper's Table 1, makes stage 1 one
    # row and one column larger than the program's unpadded pool
    h, w, c = net.shapes["stem.pool.out"]
    net.shapes["stem.pool.out"] = (h + 1, w + 1, c)
    with pytest.raises(harness.BenchError, match="stem.pool.out"):
        harness.build_graph(cfg, net)
