"""The Pallas flash-attention forward's share of its roofline: the least
time its calls could take on this chip (the larger of the causal FLOPs over
the peak and the Q, K, V and O bytes over the bandwidth, per call, from the
family's ``kernel_costs``) over the device time of its events, found by the
kernel's name in the trace (``bench.trace.roofline``)."""
from bench.trace import roofline

KERNEL = "flash_attention_tpu"


def read(m):
    return roofline(m, KERNEL)
