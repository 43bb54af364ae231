"""The whole step's share of the chips' bf16 peak while they work: the FLOPs
the window's steps require over the devices' busy time in the trace,
summed over the cell's chips, over the peak.  It bounds what any one
kernel's roofline can add."""


def read(m):
    busy = sum(t.busy_s for t in m.traces or ())
    if busy <= 0:
        return None
    return 100.0 * m.steps * m.flops_per_step["total"] / busy \
        / m.peak["bf16_flops_per_s"]
