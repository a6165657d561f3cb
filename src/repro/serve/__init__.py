"""Serving substrate — fronted by ONE runtime: `repro.serve.Server`.

    srv = Server(machine, backend="jax")
    srv.register("net", graph, period_s=1/30)      # admission-controlled
    ticket = srv.submit("net", frame)
    srv.run(hyperperiods=3)
    ticket.result()        # output + latency + WCET bound + deadline verdict

All deadline accounting lives in `DeadlineMonitor`, all multi-network
execution in `Server`. LM decode traffic is served *continuously*
(`repro.serve.continuous`): `Server.register_decode` installs a
slot-indexed `ContinuousEngine` where requests enter and leave the batch
mid-stream, each decode step checked against its WCET bound
(`analyze_decode`). `ServeEngine` runs LM batches to completion; it is
the oracle the continuous loop is tested against. See docs/serving.md.

Degraded operation is first-class (docs/serving.md, "Failure modes &
degraded operation"): mixed-criticality overload shedding
(`OverloadPolicy`), atomic hyperperiod-boundary mode changes
(`repro.serve.modes.Mode` / `Server.switch_mode`), and seeded fault
injection with bounded retries and per-network circuit breaking
(`repro.serve.faults` / `Server.enable_resilience`).
"""

from .continuous import (ContinuousEngine, ContinuousRequest, DecodeState,
                         LMBackend, ResultTokens, SlotError, StepInfo,
                         ToyBackend)
from .engine import Request, ServeEngine
from .faults import (BreakerPolicy, CircuitBreaker, FaultInjector,
                     FaultPlan, InjectedFailure, InjectedTimeout,
                     RetryPolicy, StragglerWatchdog)
from .modes import Mode, ModeChangeError, ModeNetwork
from .monitor import DeadlineMonitor, DeadlineVerdict
from .predictable import PredictableServeReport, analyze_decode
from .runtime import (AdmissionError, BackpressureError, OverloadPolicy,
                      RequestQueue, ServeError, Server, Ticket,
                      TicketResult)

__all__ = ["Server", "Ticket", "TicketResult", "RequestQueue",
           "ServeError", "AdmissionError", "BackpressureError",
           "DeadlineMonitor", "DeadlineVerdict",
           "OverloadPolicy", "Mode", "ModeNetwork", "ModeChangeError",
           "FaultPlan", "FaultInjector", "InjectedFailure",
           "InjectedTimeout", "RetryPolicy", "BreakerPolicy",
           "CircuitBreaker", "StragglerWatchdog",
           "Request", "ServeEngine", "PredictableServeReport",
           "analyze_decode",
           "ContinuousEngine", "ContinuousRequest", "DecodeState",
           "LMBackend", "ResultTokens", "SlotError", "StepInfo",
           "ToyBackend"]
