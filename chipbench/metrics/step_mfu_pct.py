"""step_mfu_pct: matrix work of the frames served in the window (conv and
fully-connected multiply-accumulates, 2 operations each, counted from the
reference's shapes; padded slots do not count) over the runners' summed
time at the chip's int8 peak, in percent."""

import counting


def read(rec):
    steps = [s for s in rec.window_steps if s.frames]
    busy = sum(s.runner_s for s in steps)
    if not busy:
        return None
    ops = sum(s.frames for s in steps) * counting.frame_ops(rec.net)
    return 100 * ops / (busy * rec.peaks["int8_ops_per_s"])
