"""Ratio of engine counters' growth inside the window:
sum(num) / (sum(den) * engine_setting) * scale.  None when the
denominator did not move."""


def read(run, num, den, scale=1.0, den_setting=None):
    if run.window is None:
        return None
    d = sum(run.window.delta(c) for c in den)
    if den_setting:
        d *= float(run.engine_settings[den_setting])
    if d == 0:
        return None
    return scale * sum(run.window.delta(c) for c in num) / d
