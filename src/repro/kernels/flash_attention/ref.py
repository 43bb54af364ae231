"""Pure-jnp oracle for the flash attention kernel.

Two entry points:

* ``chunked_attention`` — training/prefill attention (Sq == Sk), causal with an
  optional sliding window, GQA-aware, O(S * chunk) score memory.  This is also
  what model code runs on non-TPU backends (the dry-run lowers this HLO).
* ``naive_attention`` — O(S^2) direct softmax; ground truth for tests only.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def _gqa_expand(q, kv_heads):
    """Reshape q (B,S,H,D) -> (B,S,KV,G,D) where G = H // KV."""
    b, s, h, d = q.shape
    g = h // kv_heads
    return q.reshape(b, s, kv_heads, g, d)


def naive_attention(q, k, v, *, causal=True, window=0, scale=None):
    """Direct attention. q:(B,Sq,H,D) k,v:(B,Sk,KV,D). Returns (B,Sq,H,D)."""
    b, sq, h, d = q.shape
    kvh = k.shape[2]
    scale = scale if scale is not None else d ** -0.5
    qh = _gqa_expand(q, kvh).astype(jnp.float32)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    scores = jnp.einsum("bskgd,btkd->bkgst", qh, kf) * scale
    sk = k.shape[1]
    qpos = jnp.arange(sq)[:, None] + (sk - sq)  # q aligned to the end of k
    kpos = jnp.arange(sk)[None, :]
    mask = jnp.ones((sq, sk), dtype=bool)
    if causal:
        mask &= kpos <= qpos
    if window:
        mask &= kpos > qpos - window
    scores = jnp.where(mask[None, None, None], scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgst,btkd->bskgd", p, vf)
    return out.reshape(b, sq, h, d).astype(q.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "chunk"))
def chunked_attention(q, k, v, *, causal=True, window=0, chunk=512, scale=None):
    """Flash-style attention: scan over KV chunks with running (m, l, acc).

    q: (B, Sq, H, D); k, v: (B, Sk, KV, D); Sq == Sk or q aligned to end of k.
    Score memory is O(Sq * chunk) instead of O(Sq * Sk), in the forward
    and in its VJP.
    """
    b, sq, h, d = q.shape
    sk, kvh = k.shape[1], k.shape[2]
    g = h // kvh
    scale = scale if scale is not None else d ** -0.5
    chunk = min(chunk, sk)
    # pad Sk to a multiple of chunk (padded keys masked off)
    pad = (-sk) % chunk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    n_chunks = k.shape[1] // chunk

    qh = _gqa_expand(q, kvh).astype(jnp.float32) * scale
    kc = k.reshape(b, n_chunks, chunk, kvh, d).astype(jnp.float32)
    vc = v.reshape(b, n_chunks, chunk, kvh, d).astype(jnp.float32)
    qpos = jnp.arange(sq) + (sk - sq)

    def body(carry, xs):
        m, l, acc = carry
        kb, vb, c_idx = xs
        kpos = c_idx * chunk + jnp.arange(chunk)
        s = jnp.einsum("bskgd,btkd->bkgst", qh, kb)
        mask = kpos[None, :] < sk  # padding
        if causal:
            mask &= kpos[None, :] <= qpos[:, None]
        if window:
            mask &= kpos[None, :] > qpos[:, None] - window
        s = jnp.where(mask[None, None, None], s, -jnp.inf)
        m_new = jnp.maximum(m, s.max(axis=-1))
        # guard all -inf rows
        m_safe = jnp.where(jnp.isinf(m_new), 0.0, m_new)
        p = jnp.exp(s - m_safe[..., None])
        p = jnp.where(mask[None, None, None], p, 0.0)
        corr = jnp.exp(jnp.where(jnp.isinf(m), -jnp.inf, m - m_safe))
        corr = jnp.where(jnp.isnan(corr), 0.0, corr)
        l_new = l * corr + p.sum(axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum("bkgst,btkd->bkgsd", p, vb)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, kvh, g, sq), -jnp.inf, dtype=jnp.float32)
    l0 = jnp.zeros((b, kvh, g, sq), dtype=jnp.float32)
    acc0 = jnp.zeros((b, kvh, g, sq, d), dtype=jnp.float32)
    kc_t = jnp.moveaxis(kc, 1, 0)
    vc_t = jnp.moveaxis(vc, 1, 0)
    # the rematerialised body keeps the VJP's residuals to the carries: one
    # chunk of scores is live at a time in the backward too
    (m, l, acc), _ = jax.lax.scan(
        jax.checkpoint(body), (m0, l0, acc0),
        (kc_t, vc_t, jnp.arange(n_chunks)))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    out = jnp.moveaxis(out, 3, 1).reshape(b, sq, h, d)
    return out.astype(q.dtype)


def paged_decode_attention(q, k_pages, v_pages, block_tables, pos, *,
                           logical_len, window=0, scale=None):
    """Single-token decode over a block-paged KV cache.

    q: (B, 1, H, D); k/v_pages: (NB_phys, BS, KV, D) physical token blocks;
    block_tables: (B, nb) int32 physical ids per logical block (garbage-
    padded past the allocation); logical_len: the true per-request cache
    length (the ring modulus when window > 0 — storage pads up to whole
    blocks, and this slice masks the pad).  Gathers the logical view and
    reuses ``decode_attention``'s exact masking math, so paged == contiguous
    is bitwise on the gathered values.
    """
    b = q.shape[0]
    nb = block_tables.shape[1]
    bs = k_pages.shape[1]
    kc = k_pages[block_tables].reshape(
        b, nb * bs, *k_pages.shape[2:])[:, :logical_len]
    vc = v_pages[block_tables].reshape(
        b, nb * bs, *v_pages.shape[2:])[:, :logical_len]
    return decode_attention(q, kc, vc, pos, window=window, scale=scale)


def decode_attention(q, k_cache, v_cache, pos, *, window=0, scale=None):
    """Single-token decode attention over a (possibly ring-buffered) cache.

    q: (B, 1, H, D); caches: (B, Lc, KV, D); pos: int32 absolute position of
    the current token — scalar or per-request (B,) vector (ragged batches).
    Valid slots are arange(Lc) <= pos (when the cache is a ring of length
    Lc < full seq, every written slot is valid once pos >= Lc).
    """
    b, _, h, d = q.shape
    lc, kvh = k_cache.shape[1], k_cache.shape[2]
    scale = scale if scale is not None else d ** -0.5
    qh = _gqa_expand(q, kvh)[:, 0].astype(jnp.float32) * scale  # (B,KV,G,D)
    s = jnp.einsum("bkgd,btkd->bkgt", qh, k_cache.astype(jnp.float32))
    pos_b = jnp.broadcast_to(jnp.asarray(pos), (b,))
    valid = jnp.arange(lc)[None, :] <= pos_b[:, None]           # (B, Lc)
    s = jnp.where(valid[:, None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgt,btkd->bkgd", p, v_cache.astype(jnp.float32))
    return out.reshape(b, 1, h, d).astype(q.dtype)
