"""The whole step's share of the chips' bf16 peak over the window: the FLOPs
a step requires of every stage the cell runs (the family's ``step_flops``,
no recomputation) times the steps completed, over the window, over the
chips times the peak (host clock)."""


def read(m):
    return 100.0 * m.steps * m.flops_per_step["total"] / m.window_s \
        / (m.chips * m.peak["bf16_flops_per_s"])
