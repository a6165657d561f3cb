"""Predictable LM serving: per-token WCET bounds computed by the paper's
compiler pipeline for production-scale configs on the TPU-v5e machine
model, and the continuous-batching decode loop (`Server.register_decode`)
serving mixed-length traffic, every decode step checked against its
bound and every request given a deadline verdict.

    PYTHONPATH=src python examples/serve_predictable.py
"""

import jax
import numpy as np

from repro.configs import get_config
from repro.hw import TPU_V5E, scaled_paper_machine
from repro.models import init_params
from repro.serve import Server
from repro.serve.predictable import analyze_decode


def main():
    print("=" * 72)
    print("Per-token WCET bounds for the full-size archs (paper pipeline)")
    print("=" * 72)
    for arch in ("smollm-135m", "rwkv6-1.6b", "mixtral-8x22b"):
        cfg = get_config(arch)
        rep = analyze_decode(cfg, batch=16, cache_len=2048, hw=TPU_V5E,
                             num_cores=16, max_layers=2)
        print(f"{arch:<16} {rep.per_token_wcet_s*1e3:8.3f} ms/token  "
              f"({rep.wcet.dominant_term()})")

    print()
    print("=" * 72)
    print("Continuous batching: requests enter/leave the batch mid-decode")
    print("=" * 72)
    cfg = get_config("smollm-135m", reduced=True)
    params = init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    srv = Server(scaled_paper_machine(4), speed_ratio=1e6)
    verdict = srv.register_decode(
        "lm", cfg, period_s=1 / 50, params=params, slots=4, prompt_len=8,
        max_new_tokens=16, max_len=96, prefill_per_step=2,
        arrival_rps=20.0, tokens_per_request=10.0)  # sustained-occupancy check
    print(f"admitted: step bound {verdict.response_bound_s * 1e3:.3f} ms, "
          f"occupancy {srv.telemetry()['sustained']['lm']['occupancy']:.0%}")
    # mixed trace: short and long generations, arrivals interleaved with
    # decode — short requests finish and free their slot while long ones
    # keep decoding (no batch-to-completion head-of-line blocking)
    tickets = []
    for i in range(6):
        tickets.append(srv.submit(
            "lm", {"prompt": list(rng.integers(1, cfg.vocab_size, 4)),
                   "max_new_tokens": 4 if i % 2 == 0 else 16}))
        srv.step()
    while not all(t.done for t in tickets):
        srv.step()
    for t in tickets[:4]:
        r = t.result()
        print(f"  ticket {t.tid}: {len(r.output)} tokens, "
              f"{'met' if r.verdict.met else 'MISSED'} its deadline")
    cont = srv.telemetry()["continuous"]["lm"]
    print(f"continuous metrics: {cont['decode_steps']} decode steps, "
          f"{cont['tokens']} tokens, {cont['evictions']} evictions")
    print(srv.monitor.summary())


if __name__ == "__main__":
    main()
