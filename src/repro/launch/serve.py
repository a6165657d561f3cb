"""Serving launcher: LM decode through `Server`, with its WCET report.

    PYTHONPATH=src python -m repro.launch.serve --arch smollm-135m \
        --reduced --requests 8 --max-new 16

Builds the model, prints the paper-pipeline WCET report for the decode
step, serves synthetic prompts through `Server.register_decode` (every
decode step checked against its bound), and prints the deadline checks
and misses. `--analyze-only` prints the report without running.
"""

from __future__ import annotations

import argparse

import jax
import numpy as np

from ..configs import ARCH_IDS, get_config
from ..models import init_params
from ..serve import Server
from ..serve.predictable import analyze_decode
from ..hw import TPU_V5E, PAPER_RISCV


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=ARCH_IDS)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--hw", default="tpu", choices=["tpu", "paper"])
    ap.add_argument("--analyze-only", action="store_true",
                    help="print the WCET analysis without running")
    args = ap.parse_args(argv)

    cfg = get_config(args.arch, reduced=args.reduced)
    hw = TPU_V5E if args.hw == "tpu" else PAPER_RISCV

    rep = analyze_decode(cfg, args.batch, args.max_len, hw)
    print(rep.summary())
    if args.analyze_only:
        return

    params = init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prompts = [list(rng.integers(1, cfg.vocab_size, rng.integers(4, 12)))
               for _ in range(args.requests)]
    srv = Server(hw, queue_capacity=max(1, args.requests))
    # one decode step per period, with the per-token bound's headroom
    srv.register_decode("lm", cfg, period_s=2 * rep.per_token_wcet_s,
                        params=params, slots=args.batch,
                        prompt_len=max(map(len, prompts), default=1),
                        max_new_tokens=args.max_new, max_len=args.max_len)
    tickets = [srv.submit("lm", {"prompt": p,
                                 "max_new_tokens": args.max_new})
               for p in prompts]
    while not all(t.terminal for t in tickets):
        srv.step()
    for t in tickets[:4]:
        out = t.result().output
        print(f"req {t.tid}: {len(out)} tokens -> {out[:8]}...")
    cont = srv.telemetry()["continuous"]["lm"]
    mon = srv.monitor
    print(f"metrics: {cont['decode_steps']} decode steps, "
          f"{cont['tokens']} tokens; deadline misses "
          f"{mon.misses.get('lm', 0)}/{mon.checks.get('lm', 0)}")


if __name__ == "__main__":
    main()
