"""Device ms per step of the optimizer update: the operations whose scope
path holds ``optimizer`` (AdamW and the loss-scale bookkeeping)."""
from bench import scopes


def read(m):
    return scopes.ms_per_step(m, lambda names, _: "optimizer" in names)
