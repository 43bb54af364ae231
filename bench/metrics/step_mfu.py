"""The whole step's share of the chip's bf16 peak while the device works:
the FLOPs the window's steps require over the device's busy time in the
trace, over the peak.  It bounds what any one kernel's roofline can add."""


def read(m):
    if m.trace is None or m.trace.busy_s <= 0:
        return None
    return 100.0 * m.steps * m.flops_per_step["total"] / m.trace.busy_s \
        / m.peak["bf16_flops_per_s"]
