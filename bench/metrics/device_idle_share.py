"""Share of the traced window in which no operation ran on the device:
1 - (union of the device's operation intervals / window)."""


def read(m):
    if m.trace is None or m.trace.window_s <= 0:
        return None
    return 100.0 * m.trace.idle_share
