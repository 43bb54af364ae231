"""Device ms per step of the stage's loss head: the operations whose scope
path holds ``sil_mse`` (an interior stage's SIL-MSE) or ``ce`` (the last
stage's tied unembedding and chunked cross-entropy), with their
gradients."""
from bench import scopes


def read(m):
    return scopes.ms_per_step(
        m, lambda names, _: "sil_mse" in names or "ce" in names)
