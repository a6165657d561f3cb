"""program_roofline: the least device time the traced window's calls could
take (`counting.call_floor_s` of each call, from the frames it served)
over the device's busy time in that window from the profiler trace, in
percent."""

import counting


def read(rec):
    if rec.trace is None or not rec.trace["busy_s"]:
        return None
    floor = sum(counting.call_floor_s(rec.net, s.frames, rec.peaks)
                for s in rec.trace_steps if s.frames)
    return 100 * floor / rec.trace["busy_s"]
