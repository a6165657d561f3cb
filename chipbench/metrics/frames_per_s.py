"""frames_per_s: frames completed by the steps of the window over the
window's length (from its opening to the end of the last step started in
it)."""


def read(rec):
    return sum(s.frames for s in rec.window_steps) / rec.window_s
