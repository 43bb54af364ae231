"""Dispatching wrapper: Pallas flash attention on TPU, chunked-jnp elsewhere.

The dry-run lowers on the CPU backend (512 host devices), where pallas_call
has no lowering path — so model code always goes through this wrapper.  Both
the training/prefill path and the single-token decode path dispatch the same
way; ``REPRO_FORCE_REF=1`` pins the reference implementation even on TPU so
the serving engine is testable against both.
"""
from __future__ import annotations

import functools

import jax

from repro.kernels.dispatch import decide

from . import ref


def flash_attention(q, k, v, *, causal=True, window=0, chunk=512):
    """Training/prefill attention. q:(B,S,H,D) k,v:(B,S,KV,D).

    Inputs may be any float dtype (bf16/fp16 under a reduced-precision
    policy); both backends accumulate scores and the softmax in fp32 and
    return the input dtype."""
    with jax.named_scope("flash"):
        if decide("flash_attention", q.shape, q.dtype).use_pallas:
            return _flash_pallas(q, k, v, causal, window, chunk)
        return ref.chunked_attention(q, k, v, causal=causal, window=window,
                                     chunk=chunk)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash_pallas(q, k, v, causal, window, chunk):
    """Pallas forward; the backward is the VJP of ``ref.chunked_attention``.

    There is no Pallas backward kernel yet: the gradient recomputes the
    forward in chunked jnp (one KV chunk of scores live at a time, the scan
    body rematerialised) and differentiates that."""
    from .kernel import flash_attention_tpu
    return flash_attention_tpu(q, k, v, causal=causal, window=window)


def _flash_fwd(q, k, v, causal, window, chunk):
    return _flash_pallas(q, k, v, causal, window, chunk), (q, k, v)


def _flash_bwd(causal, window, chunk, res, g):
    with jax.named_scope("flash"):
        _, vjp = jax.vjp(functools.partial(ref.chunked_attention,
                                           causal=causal, window=window,
                                           chunk=chunk), *res)
        return vjp(g)


_flash_pallas.defvjp(_flash_fwd, _flash_bwd)


def decode_attention(q, k_cache, v_cache, pos, *, window=0, block=None):
    """Single-token decode over a KV cache (ring-buffered if window>0).

    ``block``: the Pallas kernel's cache tile (its default when None).  The
    pool that owns the cache chooses it: a serving engine passes its paged
    block size so its contiguous and paged pools fold the softmax in the
    same blocks and decode the same tokens."""
    if decide("flash_attention", k_cache.shape, q.dtype).use_pallas:
        from .kernel import DEFAULT_BK, decode_attention_tpu
        return decode_attention_tpu(q, k_cache, v_cache, pos, window=window,
                                    bk=block or DEFAULT_BK)
    return ref.decode_attention(q, k_cache, v_cache, pos, window=window)


def paged_decode_attention(q, k_pages, v_pages, block_tables, pos, *,
                           logical_len, window=0):
    """Single-token decode gathering K/V through a per-request block table.

    k/v_pages: (NB_phys, BS, KV, D); block_tables: (B, nb) int32.  The Pallas
    path scalar-prefetches the table so each K/V block DMA reads the physical
    block directly; the ref path gathers the logical view and defers to
    ``decode_attention``."""
    if decide("flash_attention", k_pages.shape, q.dtype).use_pallas:
        from .kernel import paged_decode_attention_tpu
        return paged_decode_attention_tpu(
            q, k_pages, v_pages, block_tables, pos,
            logical_len=logical_len, window=window)
    return ref.paged_decode_attention(
        q, k_pages, v_pages, block_tables, pos,
        logical_len=logical_len, window=window)
