"""queue_wait_ms: 95th percentile over the frames served of the time from
a frame's due time until the start of the `Server.step()` that served it,
on the benchmark's clock. It holds the generator's own lateness too."""

import stats


def read(rec):
    waits = [f.step_start - f.due for f in rec.frames
             if f.step_start is not None and f.status == "done"]
    v = stats.percentile(waits, 95)
    return None if v is None else v * 1e3
