"""WCET analysis of one LM decode step (the paper pipeline).

`analyze_decode` lowers a `ModelConfig` to one decode step
(`repro.core.lmgraph.lm_decode_graph`), bounds it on a machine model, and
scales the bound to the full depth. `repro.launch.serve` prints it, and
`Server.register_decode` admits and serves decode networks against the
same analysis, with every decode step checked by the server's
`DeadlineMonitor`.
"""

from __future__ import annotations

import dataclasses

from ..core.lmgraph import lm_decode_graph
from ..core.wcet import WCETReport, analyze
from ..hw import HardwareModel, TPU_V5E
from ..models.config import ModelConfig


@dataclasses.dataclass
class PredictableServeReport:
    wcet: WCETReport
    per_token_wcet_s: float
    layers_modeled: int
    scaled_to_layers: int

    def summary(self) -> str:
        return (f"{self.wcet.summary()}\n"
                f"  per-token WCET (scaled x"
                f"{self.scaled_to_layers}/{self.layers_modeled} layers): "
                f"{self.per_token_wcet_s * 1e3:.3f} ms")


def analyze_decode(cfg: ModelConfig, batch: int, cache_len: int,
                   hw: HardwareModel = TPU_V5E,
                   num_cores: int | None = None,
                   max_layers: int = 4,
                   arbitration: str = "static") -> PredictableServeReport:
    """WCET bound for one decode step of `cfg` on `hw`.

    Deep archs are analyzed on a representative truncated stack and scaled
    linearly (sound: per-layer structure is identical, the schedule is
    periodic; the lm_head is included in the truncated graph so the
    non-recurring part is not scaled)."""
    L = min(cfg.num_layers, max_layers)
    g = lm_decode_graph(cfg, batch, cache_len, layers=L)
    report, sched, subtasks, mapping = analyze(
        g, hw, num_cores=num_cores, arbitration=arbitration)
    scale = cfg.num_layers / L
    per_token = report.wcet_total_s * scale
    return PredictableServeReport(report, per_token, L, cfg.num_layers)
