"""The Pallas SIL-MSE kernel's share of its roofline: the least time its
calls could take (bytes needed: activations read, one float32 table row
gathered per token, the float32 gradient written; bench.model.sil_mse_cost)
over the device time of its events, found by the kernel's name in the trace.
The label-major transpose of the table outside the kernel is not counted
here; it shows in the breakdown."""
from bench import model as bm

KERNEL = "sil_mse_fwd_tpu"


def read(m):
    if m.trace is None:
        return None
    calls, secs = m.trace.kernel(KERNEL)
    if calls == 0 or secs <= 0:
        return None
    flops, nbytes = bm.sil_mse_cost(
        m.cfg, m.tokens_per_step // m.traffic.seq, m.traffic.seq)
    least = max(flops / m.peak["bf16_flops_per_s"],
                nbytes / m.peak["hbm_bytes_per_s"])
    return 100.0 * calls * least / secs
