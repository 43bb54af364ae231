"""Device ms per step of the attention sub-blocks: the operations whose
scope path holds ``attention`` (norm1, the projections, RoPE, the residual
add, and the ``flash`` core within), forward, remat and backward."""
from bench import scopes


def read(m):
    return scopes.ms_per_step(m, lambda names, _: "attention" in names)
