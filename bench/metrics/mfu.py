"""The whole step's share of the chip's bf16 peak over the window: the FLOPs
a step requires (bench.model.step_flops, no recomputation) times the steps
completed, over the window, over the peak (host clock)."""


def read(m):
    return 100.0 * m.steps * m.flops_per_step["total"] / m.window_s \
        / m.peak["bf16_flops_per_s"]
