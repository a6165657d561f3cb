"""runner_ms: mean `TicketResult.latency_s` per call in the window: the
deployment runner's host time around one batched call, input copy to the
device, the program and the output copy back included."""

import stats


def read(rec):
    return stats.mean([s.runner_s * 1e3 for s in rec.window_steps
                       if s.frames])
