"""frame_latency_p50_ms: median over every frame due in the window of the
time from its due time until its output is on the host; a frame that
failed or was refused counts as missing (infinitely late)."""

import stats


def read(rec):
    v = stats.percentile([f.latency_s for f in rec.frames], 50)
    return None if v is None else v * 1e3
