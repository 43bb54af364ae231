"""Pallas TPU flash attention (forward) with causal + sliding-window masking.

Blocking: grid (batch, q_heads, Sq/BQ, Sk/BK); the KV axis is the minor-most
grid dim, iterated sequentially per TPU core, so the online-softmax running
state (m, l, acc) lives in VMEM scratch across KV steps.  Q/K/V blocks are
(BQ, D) / (BK, D) VMEM tiles.  ``plan()`` chooses the tiles from the
shapes: 512 rows, the sequence padded to a multiple of 512, or one tile
over a shorter sequence.

Causal and windowed calls visit only the KV blocks some row of the q block
can see.  The K/V index maps clamp the KV step into that range, so a
skipped step keeps the block index of the step before it and the pipeline
issues no DMA for it; the kernel body skips its compute.  Only blocks that
cross the diagonal, the window's edge or the padding apply a mask.
Non-causal calls (encoder and cross attention, Sq != Sk) run the full grid.
A causal q is aligned to the end of k, as in ``ref.py``.

The MXU takes the operands in the input dtype (bf16 under a bf16 policy)
with fp32 results; the scale applies to the fp32 scores, and the scores,
softmax statistics, accumulator and the final divide stay fp32.  ``m`` and
``l`` are kept lane-replicated as (BQ, 128), so no step relayouts a 1-D
vector.

GQA is handled in the index map: query head h reads KV head h // (H // KV) —
KV is never materialized per-Q-head.  Validated against ref.py in
interpret mode (tests/test_kernels.py sweeps shapes and dtypes).

``decode_attention_tpu`` is the single-token serving variant: grid
(batch, kv_head, Lc/BK), one program per KV head attending all of its G
query heads at once (the (G, D) q tile rides along the whole cache sweep),
with the per-request position vector prefetched into SMEM so ragged
continuous batches mask their own history.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.plan import (BlockPlan, KernelPlan, ScalarPrefetchPlan,
                                ScratchPlan, as_block_spec, as_scratch)

DEFAULT_BK = 128      # the decode kernels' cache tile
NEG_INF = -1e30
LANES = 128
MAX_BLOCK = 512       # the flash forward's largest sequence tile


def _seq_block(s, align):
    """The flash tile along a sequence axis of length ``s``: ``MAX_BLOCK``,
    or the whole axis padded to ``align`` where it is shorter.  On a v5e a
    512 x 512 step takes about twice the time of a 128 x 128 one for 16
    times the work, so padding a sequence to a multiple of 512 costs less
    than cutting it into smaller tiles."""
    return min(MAX_BLOCK, s + (-s) % align)


def _kv_bounds(iq, *, bq, bk, nk, off, causal, window):
    """First and last KV block that some row of q block ``iq`` sees; q row
    r sits at key position r + ``off``.  Python ints in (the static
    checker's grid corners), concrete values out; traced ids in, traced
    values out."""
    lo, hi = 0, nk - 1
    if window:
        lo = jnp.maximum(iq * bq + off - window + 1, 0) // bk
    if causal:
        hi = jnp.minimum(((iq + 1) * bq - 1 + off) // bk, nk - 1)
    return lo, hi


def plan(b, sq, sk, h, kv, d, *, bq=None, bk=None, causal=True, window=0,
         dtype="float32") -> KernelPlan:
    """Launch geometry for ``flash_attention_tpu`` over logical shapes
    q:(b,sq,h,d), k/v:(b,sk,kv,d).  Arrays are transposed to head-major and
    padded to block multiples before the call; the plan describes those
    padded layouts.  ``bq``/``bk`` default to ``_seq_block``'s tiles; the
    K/V maps clamp the KV step into ``_kv_bounds``, so skipped steps fetch
    nothing new."""
    g = h // kv
    bq_ = min(bq, sq) if bq else _seq_block(sq, 8)
    bk_ = min(bk, sk) if bk else _seq_block(sk, LANES)
    sq_p = sq + (-sq) % bq_
    sk_p = sk + (-sk) % bk_
    nq = sq_p // bq_
    nk = sk_p // bk_
    bounds = functools.partial(_kv_bounds, bq=bq_, bk=bk_, nk=nk,
                               off=sk - sq, causal=causal, window=window)

    def kv_map(b_, h_, iq, ik):
        lo, hi = bounds(iq)
        return b_, h_ // g, jnp.maximum(jnp.minimum(ik, hi), lo), 0

    return KernelPlan(
        family="flash_attention", entry="flash_attention",
        grid=(b, h, nq, nk),
        inputs=(
            BlockPlan("q", (1, 1, bq_, d),
                      lambda b_, h_, iq, ik: (b_, h_, iq, 0),
                      (b, h, sq_p, d), dtype),
            BlockPlan("k", (1, 1, bk_, d), kv_map, (b, kv, sk_p, d), dtype),
            BlockPlan("v", (1, 1, bk_, d), kv_map, (b, kv, sk_p, d), dtype),
        ),
        outputs=(
            BlockPlan("o", (1, 1, bq_, d),
                      lambda b_, h_, iq, ik: (b_, h_, iq, 0),
                      (b, h, sq_p, d), dtype),
        ),
        scratch=(
            ScratchPlan("m", (bq_, LANES), "float32", accumulator=True),
            ScratchPlan("l", (bq_, LANES), "float32", accumulator=True),
            ScratchPlan("acc", (bq_, d), "float32", accumulator=True),
        ),
    )


def decode_plan(b, lc, h, kv, d, *, bk=DEFAULT_BK,
                dtype="float32") -> KernelPlan:
    """Launch geometry for ``decode_attention_tpu``: q:(b,1,h,d) over a
    (b,lc,kv,d) cache, with the per-request position vector in SMEM."""
    g = h // kv
    bk_ = min(bk, lc)
    lc_p = lc + (-lc) % bk_
    nk = lc_p // bk_
    return KernelPlan(
        family="flash_attention", entry="decode_attention",
        grid=(b, kv, nk),
        scalar_prefetch=(
            ScalarPrefetchPlan("pos", (b,), "int32", max_value=2 ** 31 - 1),
        ),
        inputs=(
            BlockPlan("q", (1, 1, g, d),
                      lambda b_, kv_, ik, pos_ref: (b_, kv_, 0, 0),
                      (b, kv, g, d), dtype),
            BlockPlan("k", (1, 1, bk_, d),
                      lambda b_, kv_, ik, pos_ref: (b_, kv_, ik, 0),
                      (b, kv, lc_p, d), dtype),
            BlockPlan("v", (1, 1, bk_, d),
                      lambda b_, kv_, ik, pos_ref: (b_, kv_, ik, 0),
                      (b, kv, lc_p, d), dtype),
        ),
        outputs=(
            BlockPlan("o", (1, 1, g, d),
                      lambda b_, kv_, ik, pos_ref: (b_, kv_, 0, 0),
                      (b, kv, g, d), dtype),
        ),
        scratch=(
            ScratchPlan("m", (g,), "float32", accumulator=True),
            ScratchPlan("l", (g,), "float32", accumulator=True),
            ScratchPlan("acc", (g, d), "float32", accumulator=True),
        ),
    )


def paged_decode_plan(b, nb, bs, h, kv, d, *, n_blocks,
                      dtype="float32") -> KernelPlan:
    """Launch geometry for ``paged_decode_attention_tpu``: q:(b,1,h,d) over
    (n_blocks, bs, kv, d) physical K/V blocks, gathered through a
    scalar-prefetched (b, nb) block table.

    The grid's minor axis walks the request's nb LOGICAL blocks; the K/V
    index maps read the prefetched table to aim each DMA at the mapped
    PHYSICAL block — the gathered logical cache never exists in HBM.  The
    static checker bounds the maps with the table filled at 0 and at
    ``n_blocks - 1`` (the garbage block and the last physical block)."""
    g = h // kv
    return KernelPlan(
        family="flash_attention", entry="paged_decode_attention",
        grid=(b, kv, nb),
        scalar_prefetch=(
            ScalarPrefetchPlan("block_tables", (b, nb), "int32",
                               max_value=n_blocks - 1),
            ScalarPrefetchPlan("pos", (b,), "int32", max_value=2 ** 31 - 1),
        ),
        inputs=(
            BlockPlan("q", (1, 1, g, d),
                      lambda b_, kv_, ik, bt_ref, pos_ref: (b_, kv_, 0, 0),
                      (b, kv, g, d), dtype),
            BlockPlan("k", (1, 1, bs, d),
                      lambda b_, kv_, ik, bt_ref, pos_ref:
                      (bt_ref[b_, ik], kv_, 0, 0),
                      (n_blocks, kv, bs, d), dtype),
            BlockPlan("v", (1, 1, bs, d),
                      lambda b_, kv_, ik, bt_ref, pos_ref:
                      (bt_ref[b_, ik], kv_, 0, 0),
                      (n_blocks, kv, bs, d), dtype),
        ),
        outputs=(
            BlockPlan("o", (1, 1, g, d),
                      lambda b_, kv_, ik, bt_ref, pos_ref: (b_, kv_, 0, 0),
                      (b, kv, g, d), dtype),
        ),
        scratch=(
            ScratchPlan("m", (g,), "float32", accumulator=True),
            ScratchPlan("l", (g,), "float32", accumulator=True),
            ScratchPlan("acc", (g, d), "float32", accumulator=True),
        ),
    )


def _lanes(x, n):
    """A (rows, 128) lane-replicated column as (rows, n), without a
    cross-lane broadcast where n is a multiple of 128 or below it."""
    if n == LANES:
        return x
    if n % LANES == 0:
        return jnp.tile(x, (1, n // LANES))
    if n < LANES:
        return x[:, :n]
    return jnp.broadcast_to(x[:, :1], (x.shape[0], n))


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  scale, causal, window, bq, bk, sk, off):
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def step(masked):
        s = jax.lax.dot_general(q_ref[0, 0], k_ref[0, 0],
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if masked:
            qpos = (iq * bq + off
                    + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0))
            kpos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            mask = kpos < sk
            if causal:
                mask &= kpos <= qpos
            if window:
                mask &= kpos > qpos - window
            s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]                                   # (BQ, 128)
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - _lanes(m_new, bk))
        if masked:
            p = jnp.where(mask, p, 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=1, keepdims=True)
        v = v_ref[0, 0]
        acc_ref[...] = (acc_ref[...] * _lanes(corr, acc_ref.shape[1])
                        + jax.lax.dot(p.astype(v.dtype), v,
                                      preferred_element_type=jnp.float32))
        m_ref[...] = m_new

    lo, hi = _kv_bounds(iq, bq=bq, bk=bk, nk=nk, off=off, causal=causal,
                       window=window)
    run = (ik >= lo) & (ik <= hi)
    # every row sees every key of the block: no mask to apply
    whole = (ik + 1) * bk <= sk
    if causal:
        whole &= (ik + 1) * bk - 1 <= iq * bq + off
    if window:
        whole &= ik * bk > (iq + 1) * bq - 1 + off - window
    pl.when(run & whole)(lambda: step(masked=False))
    pl.when(run & jnp.logical_not(whole))(lambda: step(masked=True))

    @pl.when(ik == nk - 1)
    def _finish():
        denom = _lanes(jnp.maximum(l_ref[...], 1e-30), acc_ref.shape[1])
        o_ref[0, 0] = (acc_ref[...] / denom).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "bq", "bk",
                                             "interpret"))
def flash_attention_tpu(q, k, v, *, causal=True, window=0, bq=None, bk=None,
                        interpret=None):
    """q: (B, Sq, H, D); k, v: (B, Sk, KV, D) -> (B, Sq, H, D).  ``bq``/``bk``
    override ``plan()``'s tiles."""
    b, sq, h, d = q.shape
    sk, kv = k.shape[1], k.shape[2]
    scale = d ** -0.5
    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    kp = plan(b, sq, sk, h, kv, d, bq=bq, bk=bk, causal=causal,
              window=window, dtype=str(q.dtype))
    bq_ = kp.inputs[0].block_shape[2]
    bk_ = kp.inputs[1].block_shape[2]
    pad_q = kp.inputs[0].array_shape[2] - sq
    pad_k = kp.inputs[1].array_shape[2] - sk

    qt = q.transpose(0, 2, 1, 3)     # (B, H, Sq, D)
    kt = k.transpose(0, 2, 1, 3)     # (B, KV, Sk, D)
    vt = v.transpose(0, 2, 1, 3)
    if pad_q:
        qt = jnp.pad(qt, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        kt = jnp.pad(kt, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, pad_k), (0, 0)))

    kernel = functools.partial(_flash_kernel, scale=scale, causal=causal,
                               window=window, bq=bq_, bk=bk_, sk=sk,
                               off=sk - sq)
    out = pl.pallas_call(
        kernel,
        grid=kp.grid,
        in_specs=[as_block_spec(bp) for bp in kp.inputs],
        out_specs=as_block_spec(kp.outputs[0]),
        out_shape=jax.ShapeDtypeStruct(kp.outputs[0].array_shape, q.dtype),
        scratch_shapes=[as_scratch(sp) for sp in kp.scratch],
        interpret=interpret,
    )(qt, kt, vt)
    out = out[:, :, :sq]
    return out.transpose(0, 2, 1, 3)


def _decode_kernel(pos_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
                   acc_ref, *, scale, bk, lc):
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32) * scale          # (G, D)
    k = k_ref[0, 0].astype(jnp.float32)                  # (BK, D)
    v = v_ref[0, 0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (G, BK)

    # valid slots: arange(lc) <= pos (ring caches: every written slot is
    # valid once pos >= lc — same contract as ref.decode_attention)
    slot = ik * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    mask = (slot < lc) & (slot <= pos_ref[pl.program_id(0)])
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1))
    p = jnp.exp(s - m_new[:, None])
    p = jnp.where(mask, p, 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + p.sum(axis=1)
    acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot(
        p.astype(v.dtype), v, preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(ik == pl.num_programs(2) - 1)
    def _finish():
        denom = jnp.maximum(l_ref[...], 1e-30)[:, None]
        o_ref[0, 0] = (acc_ref[...] / denom).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("window", "bk", "interpret"))
def decode_attention_tpu(q, k_cache, v_cache, pos, *, window=0,
                         bk=DEFAULT_BK, interpret=None):
    """Single-token decode over a (possibly ring-buffered) KV cache.

    q: (B, 1, H, D); caches: (B, Lc, KV, D); pos: scalar int32 or per-request
    (B,) vector.  `window` only affects the cache LAYOUT (ring), not the
    validity mask, so it is accepted for signature parity with the ref.
    `bk` is the cache tile: the online softmax folds the cache in blocks of
    `bk` slots, so at equal `bk` this kernel and the paged one (whose tile
    is its block size) visit the same blocks in the same order and agree
    bitwise.  Returns (B, 1, H, D).
    """
    b, _, h, d = q.shape
    lc, kv = k_cache.shape[1], k_cache.shape[2]
    g = h // kv
    scale = d ** -0.5
    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    kp = decode_plan(b, lc, h, kv, d, bk=bk, dtype=str(q.dtype))
    bk_ = kp.inputs[1].block_shape[2]
    pad = kp.inputs[1].array_shape[2] - lc

    pos_b = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
    qt = q[:, 0].reshape(b, kv, g, d)                    # (B, KV, G, D)
    kt = k_cache.transpose(0, 2, 1, 3)                   # (B, KV, Lc, D)
    vt = v_cache.transpose(0, 2, 1, 3)
    if pad:
        kt = jnp.pad(kt, ((0, 0), (0, 0), (0, pad), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, pad), (0, 0)))

    kernel = functools.partial(_decode_kernel, scale=scale, bk=bk_, lc=lc)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(kp.scalar_prefetch),
        grid=kp.grid,
        in_specs=[as_block_spec(bp) for bp in kp.inputs],
        out_specs=as_block_spec(kp.outputs[0]),
        scratch_shapes=[as_scratch(sp) for sp in kp.scratch],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(kp.outputs[0].array_shape, q.dtype),
        interpret=interpret,
    )(pos_b, qt, kt, vt)
    return out.reshape(b, 1, h, d)


def _paged_decode_kernel(bt_ref, pos_ref, q_ref, k_ref, v_ref, o_ref,
                         m_ref, l_ref, acc_ref, *, scale, bs, lc):
    """Block-paged decode: identical online-softmax math to
    ``_decode_kernel`` — the block table is consumed entirely by the K/V
    index maps (scalar prefetch), so the kernel body only needs the grid's
    logical-block step to reconstruct slot ids."""
    del bt_ref  # routing happened in the index maps
    _decode_kernel(pos_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
                   acc_ref, scale=scale, bk=bs, lc=lc)


@functools.partial(jax.jit, static_argnames=("logical_len", "window",
                                             "interpret"))
def paged_decode_attention_tpu(q, k_pages, v_pages, block_tables, pos, *,
                               logical_len, window=0, interpret=None):
    """Single-token decode over a block-paged KV cache.

    q: (B, 1, H, D); k/v_pages: (NB_phys, BS, KV, D); block_tables: (B, nb)
    int32 physical block ids (garbage-padded); logical_len: true logical
    cache length (the validity mask `slot < logical_len` covers both the
    block pad and the ring modulus).  The table is scalar-prefetched so the
    per-block DMAs gather physical blocks directly — the contiguous logical
    view never materializes.  `window` only affects cache LAYOUT (ring),
    not the mask — signature parity with the ref.  Returns (B, 1, H, D).
    """
    b, _, h, d = q.shape
    n_blocks, bs, kv = k_pages.shape[0], k_pages.shape[1], k_pages.shape[2]
    nb = block_tables.shape[1]
    g = h // kv
    scale = d ** -0.5
    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    kp = paged_decode_plan(b, nb, bs, h, kv, d, n_blocks=n_blocks,
                           dtype=str(q.dtype))
    pos_b = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
    qt = q[:, 0].reshape(b, kv, g, d)                    # (B, KV, G, D)
    kt = k_pages.transpose(0, 2, 1, 3)                   # (NB, KV, BS, D)
    vt = v_pages.transpose(0, 2, 1, 3)

    kernel = functools.partial(_paged_decode_kernel, scale=scale, bs=bs,
                               lc=logical_len)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(kp.scalar_prefetch),
        grid=kp.grid,
        in_specs=[as_block_spec(bp) for bp in kp.inputs],
        out_specs=as_block_spec(kp.outputs[0]),
        scratch_shapes=[as_scratch(sp) for sp in kp.scratch],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(kp.outputs[0].array_shape, q.dtype),
        interpret=interpret,
    )(block_tables.astype(jnp.int32), pos_b, qt, kt, vt)
    return out.reshape(b, 1, h, d)
