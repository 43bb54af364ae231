"""The Pallas flash-attention forward's share of its roofline: the least
time its calls could take on this chip (the larger of the causal FLOPs over
the peak and the Q, K, V and O bytes over the bandwidth, per call, from
bench.model.flash_forward_cost) over the device time of its events, found by
the kernel's name in the trace."""
from bench import model as bm

KERNEL = "flash_attention_tpu"


def read(m):
    if m.trace is None:
        return None
    calls, secs = m.trace.kernel(KERNEL)
    if calls == 0 or secs <= 0:
        return None
    flops, nbytes = bm.flash_forward_cost(
        m.cfg, m.tokens_per_step // m.traffic.seq, m.traffic.seq)
    least = max(flops / m.peak["bf16_flops_per_s"],
                nbytes / m.peak["hbm_bytes_per_s"])
    return 100.0 * calls * least / secs
