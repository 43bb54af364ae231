"""Fused SIL-MSE loss with custom VJP; Pallas on TPU, jnp reference elsewhere
(``REPRO_FORCE_REF=1`` pins the reference on TPU).  Activations may be in
the policy's compute dtype — both backends difference and reduce in fp32 and
return a fp32 scalar; the activation gradient comes back in the activation's
dtype.  On the Pallas path the gradient is the kernel's own fused output,
saved by the forward; the reference path computes it in jnp.  Either way the
saved gradient is the unscaled fp32 ``2/(T*d) * diff``, and is cast to the
activation dtype only after the cotangent (which carries any loss scale)
multiplies it: at LM widths the unscaled values sit below fp16's normal
range and would flush before the loss scale could lift them."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.dispatch import decide

from . import ref


def sil_mse(act, sil, labels):
    return _sil_mse(jnp.dtype(act.dtype), act, sil, labels)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _sil_mse(dtype, act, sil, labels):
    return _fwd_impl(act, sil, labels)


def _pallas(act):
    return decide("sil_mse", act.shape, act.dtype).use_pallas


def _fwd_impl(act, sil, labels):
    if _pallas(act):
        from .kernel import sil_mse_tpu
        return sil_mse_tpu(act, sil, labels)
    return ref.sil_mse(act, sil, labels)


def _fwd(dtype, act, sil, labels):
    if _pallas(act):
        from .kernel import sil_mse_fwd_tpu
        loss, gact = sil_mse_fwd_tpu(act, sil, labels)
    else:
        loss = ref.sil_mse(act, sil, labels)
        gact = ref.sil_mse_grad_act(act, sil, labels)
    return loss, (gact, sil, labels)


def _bwd(dtype, res, g):
    gact, sil, labels = res
    # SIL is a frozen random table (not trained) and labels are ints.
    gsil = jnp.zeros_like(sil)
    glab = jnp.zeros(labels.shape, dtype=jax.dtypes.float0)
    return (gact * g).astype(dtype), gsil, glab


_sil_mse.defvjp(_fwd, _bwd)
