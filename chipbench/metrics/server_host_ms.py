"""server_host_ms: mean over the window's serving steps of the wall time
of `Server.step()` less the runner's own time (`TicketResult.latency_s`):
the Server's host work per job (queue, batching, padding, bookkeeping)."""

import stats


def read(rec):
    return stats.mean([(s.end - s.start - s.runner_s) * 1e3
                       for s in rec.window_steps if s.frames])
