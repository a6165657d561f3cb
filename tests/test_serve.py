"""Serving: engine generation, decode WCET analysis and deadline checks
through `Server`, quantized LM decode graph pipeline."""

import jax
import pytest

from repro.configs import get_config
from repro.core.lmgraph import lm_decode_graph
from repro.core.wcet import analyze
from repro.hw import PAPER_RISCV, TPU_V5E, scaled_paper_machine
from repro.models import init_params
from repro.serve import Server
from repro.serve.engine import Request, ServeEngine
from repro.serve.predictable import analyze_decode


def test_engine_generates():
    cfg = get_config("smollm-135m", reduced=True)
    params = init_params(cfg, jax.random.PRNGKey(0))
    eng = ServeEngine(cfg, params, batch_size=4, max_len=64)
    reqs = [Request(rid=i, prompt=[1 + i, 2, 3], max_new_tokens=6)
            for i in range(3)]
    done = eng.generate(reqs)
    assert len(done) == 3
    for r in done:
        assert len(r.out) == 6
        assert all(0 <= t < cfg.vocab_size for t in r.out)
    assert eng.metrics["decode_steps"] == 5


def test_engine_greedy_deterministic():
    cfg = get_config("smollm-135m", reduced=True)
    params = init_params(cfg, jax.random.PRNGKey(0))
    eng = ServeEngine(cfg, params, batch_size=2, max_len=64)
    r1 = eng.generate([Request(rid=0, prompt=[5, 6, 7],
                               max_new_tokens=8)])[0]
    r2 = eng.generate([Request(rid=0, prompt=[5, 6, 7],
                               max_new_tokens=8)])[0]
    assert r1.out == r2.out


@pytest.mark.parametrize("arch", ["smollm-135m", "rwkv6-1.6b",
                                  "zamba2-1.2b", "mixtral-8x22b"])
def test_lm_decode_graph_wcet(arch):
    """The paper pipeline produces a valid schedule + WCET for LM decode."""
    cfg = get_config(arch)
    g = lm_decode_graph(cfg, batch=8, cache_len=2048, layers=2)
    report, sched, subtasks, mapping = analyze(g, TPU_V5E, num_cores=8)
    assert report.wcet_total_s > 0
    assert report.num_subtasks == len(subtasks)
    assert report.dma_utilization <= 1.0 + 1e-9
    assert report.compute_utilization <= 1.0 + 1e-9


def test_analyze_decode_scales_layers():
    cfg = get_config("smollm-135m")
    rep = analyze_decode(cfg, batch=8, cache_len=1024, hw=TPU_V5E,
                         max_layers=2)
    assert rep.layers_modeled == 2
    assert rep.scaled_to_layers == 30
    assert rep.per_token_wcet_s > rep.wcet.wcet_total_s  # scaled up


def test_predictable_engine_runs_with_deadlines():
    """Decode through `Server.register_decode` with the machine-speed
    ratio calibrated on the first step: tokens come out, every decode step
    is checked, and the request gets a deadline verdict."""
    cfg = get_config("smollm-135m", reduced=True)
    params = init_params(cfg, jax.random.PRNGKey(0))
    srv = Server(scaled_paper_machine(4))
    srv.register_decode("lm", cfg, period_s=1 / 50, params=params, slots=2,
                        prompt_len=2, max_new_tokens=4, max_len=64)
    t = srv.submit("lm", [1, 2])
    while not t.done:
        srv.step()
    r = t.result()
    assert r.output and r.verdict is not None
    assert srv.monitor.speed_ratio is not None
    assert srv.monitor.checks["lm"] > 0


def test_wcet_scales_down_with_cores():
    """More worker cores => lower (or equal) WCET — the paper's scaling
    argument for its multicore design."""
    cfg = get_config("smollm-135m")
    g = lm_decode_graph(cfg, batch=8, cache_len=1024, layers=2)
    w = {}
    for cores in (1, 4, 16):
        rep, _, _, _ = analyze(g, PAPER_RISCV, num_cores=cores)
        w[cores] = rep.wcet_total_s
    assert w[4] < w[1] * 0.7
    assert w[16] <= w[4] * 1.02
