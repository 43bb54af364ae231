"""The Pallas SIL-MSE kernel's share of its roofline: the least time its
calls could take (bytes needed: activations read, one float32 table row
gathered per token, the float32 gradient written; the family's
``kernel_costs``) over the device time of its events, found by the
kernel's name in the trace (``bench.trace.roofline``).  The label-major
transpose of the table outside the kernel is not counted here; it shows in
the breakdown."""
from bench.trace import roofline

KERNEL = "sil_mse_fwd_tpu"


def read(m):
    return roofline(m, KERNEL)
