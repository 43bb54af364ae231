"""Losses: stable cross-entropy, the SIL-MSE stage loss, and the combined
training objective (CE + MoE auxiliaries)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.sil_mse import sil_mse


# tokens per chunk of the chunked objective: 2048 x a 152k vocab is 1.2 GB
# of fp32 logits live at once, against 20 GB for a (8, 4096) batch whole
LOSS_CHUNK = 2048


def token_nll(logits, labels, vocab_size=None):
    """Per-token negative log-likelihood in fp32. logits (..., V)."""
    lf = logits.astype(jnp.float32)
    if vocab_size is not None and vocab_size < lf.shape[-1]:
        pad_mask = jnp.arange(lf.shape[-1]) < vocab_size
        lf = jnp.where(pad_mask, lf, -1e30)
    lse = jax.nn.logsumexp(lf, axis=-1)
    ll = jnp.take_along_axis(lf, labels[..., None], axis=-1)[..., 0]
    return lse - ll


def cross_entropy(logits, labels, mask=None, vocab_size=None):
    """Mean token CE. logits (..., V) any float dtype; labels int (...).

    vocab_size: real vocab when logits carry padded columns (masked out)."""
    nll = token_nll(logits, labels, vocab_size)
    if mask is not None:
        m = mask.astype(jnp.float32)
        return (nll * m).sum() / jnp.maximum(m.sum(), 1.0)
    return nll.mean()


def accuracy(logits, labels, mask=None):
    pred = jnp.argmax(logits, axis=-1)
    hit = (pred == labels).astype(jnp.float32)
    if mask is not None:
        m = mask.astype(jnp.float32)
        return (hit * m).sum() / jnp.maximum(m.sum(), 1.0)
    return hit.mean()


def sil_stage_loss(boundary_act, sil, labels):
    """Paper's left-partition loss: MSE(boundary, SIL[:, y]).

    boundary_act: (..., d); labels: int (...) matching leading dims.
    Tokens are flattened; goes through the fused kernel path.
    """
    with jax.named_scope("sil_mse"):
        d = boundary_act.shape[-1]
        act = boundary_act.reshape(-1, d)
        lab = labels.reshape(-1)
        return sil_mse(act, sil, lab)


def train_objective(cfg, logits, labels, aux, mask=None):
    """CE + MoE auxiliary losses (coefficients from the MoE config)."""
    loss = cross_entropy(logits, labels, mask,
                         vocab_size=getattr(cfg, "vocab_size", None))
    return _with_aux(cfg, loss, aux)


def chunked_train_objective(cfg, logits_fn, x, labels, aux, mask=None,
                            chunk=None):
    """``train_objective`` of ``logits_fn(x)`` without the whole logits.

    x: (..., d) final hidden states; logits_fn maps (c, d) -> (c, V).  The
    tokens are scanned in chunks of ``chunk`` and the chunk body is
    rematerialised, so one chunk's (c, V) logits are live at a time in the
    forward and in the backward pass — at a 152k vocabulary the whole
    (tokens, V) array is what keeps a 4k-token step off a 16 GB chip.
    ``chunk`` defaults to ``LOSS_CHUNK``.

    Inputs that fit one chunk take ``train_objective`` unchanged, so they
    keep bit parity with the whole-logits objective the reference
    trainers use: a one-chunk scan rounds its matmul differently, and four
    AdamW steps amplify that to a 3e-4 relative loss difference on the CPU
    (``test_fig5_phase_list_reproduces_parallel_lm``)."""
    chunk = LOSS_CHUNK if chunk is None else chunk
    d = x.shape[-1]
    if x.size // d <= chunk:
        return train_objective(cfg, logits_fn(x), labels, aux, mask)
    xf = x.reshape(-1, d)
    lf = labels.reshape(-1)
    mf = jnp.ones(lf.shape, jnp.float32) if mask is None \
        else mask.reshape(-1).astype(jnp.float32)
    c = chunk
    pad = (-xf.shape[0]) % c
    if pad:
        xf = jnp.pad(xf, ((0, pad), (0, 0)))
        lf = jnp.pad(lf, (0, pad))
        mf = jnp.pad(mf, (0, pad))
    vocab = getattr(cfg, "vocab_size", None)

    def body(total, xs):
        xc, lc, mc = xs
        return total + (token_nll(logits_fn(xc), lc, vocab) * mc).sum(), None

    total, _ = jax.lax.scan(jax.checkpoint(body), jnp.zeros((), jnp.float32),
                            (xf.reshape(-1, c, d), lf.reshape(-1, c),
                             mf.reshape(-1, c)))
    return _with_aux(cfg, total / jnp.maximum(mf.sum(), 1.0), aux)


def _with_aux(cfg, loss, aux):
    metrics = {"ce": loss}
    if cfg.moe is not None:
        loss = loss + cfg.moe.load_balance_loss * aux["lb_loss"] \
            + cfg.moe.router_z_loss * aux["z_loss"]
        metrics["lb"] = aux["lb_loss"]
        metrics["z"] = aux["z_loss"]
    metrics["loss"] = loss
    return loss, metrics
