"""Device idle share of the traced window of arrivals:
1 - (union of the device's op intervals) / window."""


def read(m):
    if m.kind != "arrival":
        return None
    return 100.0 * m.trace.idle_s / m.trace.window_s
