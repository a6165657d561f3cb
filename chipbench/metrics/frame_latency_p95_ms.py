"""frame_latency_p95_ms: the 95th percentile (nearest rank) of the same
sample as frame_latency_p50_ms."""

import stats


def read(rec):
    v = stats.percentile([f.latency_s for f in rec.frames], 95)
    return None if v is None else v * 1e3
