"""Share of the traced window in which no operation ran on the device."""


def read(r):
    t = r.trace
    return (1.0 - t.busy_s / t.window_s) * 100.0 if t.window_s > 0 else None
