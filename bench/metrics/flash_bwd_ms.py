"""Device ms per step of the attention core's backward: the operations
whose scope path holds ``flash`` on the backward pass (on the chip, the
custom VJP that differentiates the chunked jnp reference)."""
from bench import scopes


def read(m):
    return scopes.ms_per_step(
        m, lambda names, pass_: "flash" in names and pass_ == "backward")
