"""Pallas TPU fused SIL-MSE loss (+ activation gradient).

For LM-scale PNN the synthetic target ``SIL[:, y_t]`` per token is a gathered
column of a (d_model, vocab) table; materializing the gathered (T, d) target
in HBM costs a full activation tensor.  This kernel gathers the targets
straight into VMEM: the table is passed label-major as (M, 1, d) and left in
HBM, and grid step (it, id) issues one row DMA
``sil_t[labels[it*BT + i], 0, id*BD:(id+1)*BD]`` per token of its (BT, BD)
activation block, driven by the scalar-prefetched labels — the gathered
target never exists in HBM.  The unit middle axis puts the label on an
untiled dimension: the TPU compiler refuses a one-row slice of an
(8, 128)-tiled array, in HBM or in VMEM.  Every VMEM block is tile aligned
or spans its whole dimension.  The label-major copy of the table is one
transpose per call (a frozen table could keep that layout instead).

Outputs: per-(token-block, column-block) partial loss sums (summed outside
the call) and the activation gradient, fused in one pass.  The gradient is
written in fp32 whatever the activation dtype: it is the unscaled
``2/(T*d) * diff``, which at LM widths lies below fp16's normal range, and
the custom VJP casts it only after the loss-scaled cotangent multiplies it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.plan import (BlockPlan, KernelPlan, ScalarPrefetchPlan,
                                ScratchPlan, as_block_spec, as_scratch)

DEFAULT_BT = 128
DEFAULT_BD = 512
LANES = 128


def plan(t, d, m, *, bt=DEFAULT_BT, bd=DEFAULT_BD,
         dtype="float32") -> KernelPlan:
    """Launch geometry for ``sil_mse_fwd_tpu``: act:(t,d), sil:(d,m),
    labels:(t,) int in [0, m).  Token blocks are multiples of 8 rows; the
    feature dimension is padded to whole 128-lane tiles and cut into
    ``bd``-wide column blocks (``bd`` a multiple of 128 on the chip)."""
    bt_ = min(bt, t + (-t) % 8)
    bd_ = min(bd, d + (-d) % LANES)
    t_p = t + (-t) % bt_
    d_p = d + (-d) % bd_
    nt = t_p // bt_
    nd = d_p // bd_
    return KernelPlan(
        family="sil_mse", entry="sil_mse",
        grid=(nt, nd),
        scalar_prefetch=(
            ScalarPrefetchPlan("labels", (t_p,), "int32", max_value=m - 1),
        ),
        inputs=(
            BlockPlan("act", (bt_, bd_), lambda it, idd, lab_ref: (it, idd),
                      (t_p, d_p), dtype),
            BlockPlan("sil_t", (m, 1, d_p),
                      lambda it, idd, lab_ref: (0, 0, 0),
                      (m, 1, d_p), "float32", memory_space="any"),
        ),
        outputs=(
            BlockPlan("partial_loss", (1, 1, 1, LANES),
                      lambda it, idd, lab_ref: (it, idd, 0, 0),
                      (nt, nd, 1, LANES), "float32"),
            BlockPlan("grad", (bt_, bd_), lambda it, idd, lab_ref: (it, idd),
                      (t_p, d_p), "float32"),
        ),
        scratch=(
            ScratchPlan("target", (bt_, 1, bd_), "float32"),
            ScratchPlan("dma", (1,), "dma_semaphore"),
        ),
    )


def _sil_kernel(lab_ref, act_ref, silt_hbm, loss_ref, grad_ref, tgt_ref,
                sem, *, bt, bd, t_total, scale):
    it = pl.program_id(0)
    idd = pl.program_id(1)

    def row_copy(i):
        return pltpu.make_async_copy(
            silt_hbm.at[lab_ref[it * bt + i], :, pl.ds(idd * bd, bd)],
            tgt_ref.at[i], sem.at[0])

    def start(i, carry):
        row_copy(i).start()
        return carry

    def wait(i, carry):
        row_copy(i).wait()
        return carry

    jax.lax.fori_loop(0, bt, start, 0)
    jax.lax.fori_loop(0, bt, wait, 0)

    rows = it * bt + jax.lax.broadcasted_iota(jnp.int32, (bt, bd), 0)
    diff = jnp.where(rows < t_total,
                     act_ref[...].astype(jnp.float32)
                     - tgt_ref[...].reshape(bt, bd), 0.0)
    loss_ref[...] = jnp.full(loss_ref.shape, jnp.sum(diff * diff),
                             jnp.float32)
    grad_ref[...] = scale * diff


@functools.partial(jax.jit, static_argnames=("bt", "bd", "interpret"))
def sil_mse_fwd_tpu(act, sil, labels, *, bt=DEFAULT_BT, bd=DEFAULT_BD,
                    interpret=None):
    """act: (T, d); sil: (d, M); labels: (T,) -> (mean loss, dloss/dact in
    fp32)."""
    t, d = act.shape
    m = sil.shape[1]
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    kp = plan(t, d, m, bt=bt, bd=bd, dtype=str(act.dtype))
    bt_, bd_ = kp.inputs[0].block_shape
    t_p, d_p = kp.inputs[0].array_shape
    pad_t, pad_d = t_p - t, d_p - d
    a = jnp.pad(act, ((0, pad_t), (0, pad_d))) if (pad_t or pad_d) else act
    # label-major (M, 1, d) table in one transpose: a row per label
    s_t = sil[None].transpose(2, 0, 1).astype(jnp.float32)
    if pad_d:
        s_t = jnp.pad(s_t, ((0, 0), (0, 0), (0, pad_d)))
    lab = labels.astype(jnp.int32)
    if pad_t:
        lab = jnp.pad(lab, (0, pad_t))

    kernel = functools.partial(_sil_kernel, bt=bt_, bd=bd_, t_total=t,
                               scale=2.0 / (t * d))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(kp.scalar_prefetch),
        grid=kp.grid,
        in_specs=[as_block_spec(bp) for bp in kp.inputs],
        out_specs=[as_block_spec(bp) for bp in kp.outputs],
        scratch_shapes=[as_scratch(sp) for sp in kp.scratch],
    )
    partial_loss, grad = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct(kp.outputs[0].array_shape, jnp.float32),
            jax.ShapeDtypeStruct(a.shape, jnp.float32),
        ],
        interpret=interpret,
    )(lab, a, s_t)
    loss = partial_loss[..., 0].sum() / (t * d)
    return loss, grad[:t, :d]


@functools.partial(jax.jit, static_argnames=("bt", "bd", "interpret"))
def sil_mse_tpu(act, sil, labels, *, bt=DEFAULT_BT, bd=DEFAULT_BD,
                interpret=None):
    loss, _ = sil_mse_fwd_tpu(act, sil, labels, bt=bt, bd=bd,
                              interpret=interpret)
    return loss
