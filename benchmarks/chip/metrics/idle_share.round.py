"""Device idle share of the traced window of round steps:
1 - (union of the device's op intervals) / window."""


def read(m):
    if m.kind != "round":
        return None
    return 100.0 * m.trace.idle_s / m.trace.window_s
