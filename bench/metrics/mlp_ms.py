"""Device ms per step of the FFN sub-blocks: the operations whose scope
path holds ``mlp`` (norm2, the SwiGLU matmuls, the residual add), all
passes."""
from bench import scopes


def read(m):
    return scopes.ms_per_step(m, lambda names, _: "mlp" in names)
