"""Share of the traced window in which a chip of the deployment ran no
operation, the mean over the cell's chips of 1 - (union of the chip's
operation intervals / window): the time the chips of faster stages wait
behind the slowest."""


def read(m):
    if not m.traces or m.traces[0].window_s <= 0:
        return None
    return 100.0 * sum(t.idle_share for t in m.traces) / len(m.traces)
