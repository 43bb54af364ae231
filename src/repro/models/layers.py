"""Neural network blocks (pure-functional, params-as-pytrees).

Covers every block family the assigned architectures need:

* norms (RMSNorm / LayerNorm)
* GQA attention with (partial) RoPE, optional QKV bias, sliding window,
  KV-cache decode, and cross-attention (whisper)
* SwiGLU / GELU MLPs
* token-choice MoE with capacity-based gather/scatter dispatch (GShard-style
  capacity, but gather-based so dispatch FLOPs stay proportional to expert
  compute rather than T*E*C*d einsums)
* Mamba-1 selective-SSM block (chunked scan; see kernels/selective_scan)
* xLSTM blocks: chunkwise mLSTM (bounded sigmoid gating — see DESIGN.md for
  the deviation from exponential gating) and recurrent sLSTM (exponential
  gating with the max-stabilizer)

All `apply` functions are shape-polymorphic over batch/seq and jit-safe.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention import (flash_attention, decode_attention,
                                           paged_decode_attention)
from repro.kernels.selective_scan import selective_scan, selective_scan_step


# --------------------------------------------------------------------------
# init helpers
# --------------------------------------------------------------------------

def _dense_init(key, d_in, d_out, dtype, scale=None):
    scale = scale if scale is not None else 1.0 / math.sqrt(d_in)
    return (jax.random.normal(key, (d_in, d_out), jnp.float32) * scale).astype(dtype)


def dense_init(key, d_in, d_out, dtype, bias=False, scale=None):
    p = {"w": _dense_init(key, d_in, d_out, dtype, scale)}
    if bias:
        p["b"] = jnp.zeros((d_out,), dtype)
    return p


def dense(p, x):
    """Promote-at-boundary matmul: the weight is cast to the activation's
    (compute) dtype right at the op — params keep their storage dtype, the
    cast is never persisted (repro.precision policy contract)."""
    y = x @ p["w"].astype(x.dtype)
    if "b" in p:
        y = y + p["b"].astype(x.dtype)
    return y


def residual_add(x, out):
    """Residual adds accumulate in fp32 and round once back to the compute
    dtype (PrecisionPolicy.accum_dtype contract).  For a single binary add
    this matches hardware behavior bit-for-bit; it guards the chained
    attention+cross+ffn adds against double rounding under bf16/fp16."""
    if x.dtype == jnp.float32:
        return x + out
    return (x.astype(jnp.float32) + out.astype(jnp.float32)).astype(x.dtype)


# --------------------------------------------------------------------------
# norms
# --------------------------------------------------------------------------

def norm_init(kind, d, dtype):
    p = {"scale": jnp.ones((d,), dtype)}
    if kind == "layernorm":
        p["bias"] = jnp.zeros((d,), dtype)
    return p


def norm_apply(p, x, eps=1e-5):
    xf = x.astype(jnp.float32)
    if "bias" in p:  # layernorm
        mu = xf.mean(-1, keepdims=True)
        var = ((xf - mu) ** 2).mean(-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + eps)
        y = y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    else:  # rmsnorm
        ms = (xf * xf).mean(-1, keepdims=True)
        y = xf * jax.lax.rsqrt(ms + eps) * p["scale"].astype(jnp.float32)
    return y.astype(x.dtype)


# --------------------------------------------------------------------------
# RoPE (supports partial rotary fraction, e.g. chatglm3 / stablelm)
# --------------------------------------------------------------------------

def rope_dim(head_dim: int, fraction: float) -> int:
    r = int(head_dim * fraction)
    return max(2, r - (r % 2))


def rope_tables(positions, head_dim, fraction, theta):
    """positions: (S,) int -> cos/sin tables (S, rot/2)."""
    rot = rope_dim(head_dim, fraction)
    inv = 1.0 / (theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot))
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    return jnp.cos(ang), jnp.sin(ang)


def rope_apply(x, cos, sin, *, per_batch=False):
    """x: (B, S, H, D); cos/sin: (S, rot/2), or (B, rot/2) with
    per_batch=True (ragged decode: one position per request, S == 1).
    Rotates the first `rot` dims."""
    rot2 = cos.shape[-1]
    xr, xp = x[..., : 2 * rot2], x[..., 2 * rot2:]
    x1, x2 = xr[..., 0::2], xr[..., 1::2]
    if per_batch:
        c = cos[:, None, None, :].astype(jnp.float32)
        s = sin[:, None, None, :].astype(jnp.float32)
    else:
        c = cos[None, :, None, :].astype(jnp.float32)
        s = sin[None, :, None, :].astype(jnp.float32)
    x1f, x2f = x1.astype(jnp.float32), x2.astype(jnp.float32)
    o1 = x1f * c - x2f * s
    o2 = x2f * c + x1f * s
    out = jnp.stack([o1, o2], axis=-1).reshape(xr.shape).astype(x.dtype)
    return jnp.concatenate([out, xp], axis=-1) if xp.shape[-1] else out


# --------------------------------------------------------------------------
# attention
# --------------------------------------------------------------------------

def attention_init(key, cfg, dtype, cross=False):
    d, h, kv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    ks = jax.random.split(key, 4)
    return {
        "wq": dense_init(ks[0], d, h * hd, dtype, bias=cfg.qkv_bias),
        "wk": dense_init(ks[1], d, kv * hd, dtype, bias=cfg.qkv_bias),
        "wv": dense_init(ks[2], d, kv * hd, dtype, bias=cfg.qkv_bias),
        "wo": dense_init(ks[3], h * hd, d, dtype,
                         scale=1.0 / math.sqrt(h * hd * max(cfg.n_layers, 1))),
    }


def _split_heads(x, n):
    b, s, _ = x.shape
    return x.reshape(b, s, n, -1)


def attention_apply(p, x, cfg, *, rope_cs=None, causal=True, window=0,
                    kv_override=None):
    """Full-sequence attention (train / prefill / encoder / cross).

    kv_override: (keys_src,) — cross-attention attends to this sequence
    (non-causal) instead of x.
    Returns (out, (k, v)) so callers can build caches.
    """
    h, kv = cfg.n_heads, cfg.n_kv_heads
    q = _split_heads(dense(p["wq"], x), h)
    src = kv_override if kv_override is not None else x
    k = _split_heads(dense(p["wk"], src), kv)
    v = _split_heads(dense(p["wv"], src), kv)
    if rope_cs is not None and kv_override is None:
        cos, sin = rope_cs
        q = rope_apply(q, cos, sin)
        k = rope_apply(k, cos, sin)
    if cfg.context_sharding is not None and kv_override is None:
        # sequence-parallel attention: Q (and the per-token output) stay
        # seq-sharded over the model axis; only the (narrow, GQA) K/V get
        # gathered.  Pure sharding hints — the math is unchanged.
        from jax.sharding import PartitionSpec as P
        ent = cfg.context_sharding
        bent = ent if len(ent) > 1 else ent[0]
        q = jax.lax.with_sharding_constraint(q, P(bent, "model", None, None))
        k = jax.lax.with_sharding_constraint(k, P(bent, None, None, None))
        v = jax.lax.with_sharding_constraint(v, P(bent, None, None, None))
    out = flash_attention(q, k, v, causal=causal and kv_override is None,
                          window=window)
    if cfg.context_sharding is not None and kv_override is None:
        from jax.sharding import PartitionSpec as P
        ent = cfg.context_sharding
        bent = ent if len(ent) > 1 else ent[0]
        out = jax.lax.with_sharding_constraint(
            out, P(bent, "model", None, None))
    return dense(p["wo"], out.reshape(*x.shape[:2], -1)), (k, v)


def attention_decode(p, x, cfg, cache_kv, pos, *, rope_cs=None, window=0,
                     cross_kv=None, paged=None, kv_block=None):
    """One-token decode. x: (B,1,d). cache_kv: (k,v) each (B,Lc,KV,hd) —
    or, when ``paged`` is set, physical block pools (NB,BS,KV,hd).

    pos: scalar int32 OR per-request (B,) vector (ragged batches — each
    request writes its own cache slot and masks its own history).
    paged: optional ``(block_tables, logical_len)`` — block_tables (B,nb)
    int32, logical_len the static logical cache length (the ring modulus
    when window>0; free/pad table entries point at the garbage block, which
    is written but never read thanks to the ``slot < logical_len`` mask).
    kv_block: the decode kernel's tile over a contiguous cache (see
    ``ops.decode_attention``); a paged cache's tile is its block size.
    Returns (out, new_cache_kv). For cross attention pass cross_kv
    (precomputed encoder k/v) and cache_kv=None.
    """
    h, kv = cfg.n_heads, cfg.n_kv_heads
    b = x.shape[0]
    q = _split_heads(dense(p["wq"], x), h)
    if cross_kv is not None:
        ck, cv = cross_kv
        out = decode_attention(q, ck, cv, ck.shape[1] - 1)  # all slots valid
        return dense(p["wo"], out.reshape(*x.shape[:2], -1)), None
    k = _split_heads(dense(p["wk"], x), kv)
    v = _split_heads(dense(p["wv"], x), kv)
    if rope_cs is not None:
        cos, sin = rope_cs  # tables for the current position(s)
        per_batch = cos.ndim == 2 and cos.shape[0] == b and jnp.ndim(pos) == 1
        q = rope_apply(q, cos, sin, per_batch=per_batch)
        k = rope_apply(k, cos, sin, per_batch=per_batch)
    kc, vc = cache_kv
    pos_b = jnp.broadcast_to(jnp.asarray(pos), (b,))
    if paged is not None:
        bt, lc = paged
        bs = kc.shape[1]
        slot = (pos_b % lc) if window else jnp.minimum(pos_b, lc - 1)
        phys = bt[jnp.arange(b), slot // bs]
        off = slot % bs
        kc = kc.at[phys, off].set(k[:, 0].astype(kc.dtype))
        vc = vc.at[phys, off].set(v[:, 0].astype(vc.dtype))
        out = paged_decode_attention(q, kc, vc, bt, pos,
                                     logical_len=lc, window=window)
        return dense(p["wo"], out.reshape(*x.shape[:2], -1)), (kc, vc)
    lc = kc.shape[1]
    slot = (pos_b % lc) if window else jnp.minimum(pos_b, lc - 1)
    kc = kc.at[jnp.arange(b), slot].set(k[:, 0].astype(kc.dtype))
    vc = vc.at[jnp.arange(b), slot].set(v[:, 0].astype(vc.dtype))
    out = decode_attention(q, kc, vc, pos, window=window, block=kv_block)
    return dense(p["wo"], out.reshape(*x.shape[:2], -1)), (kc, vc)


# --------------------------------------------------------------------------
# MLPs
# --------------------------------------------------------------------------

def mlp_init(key, d, ff, kind, dtype):
    ks = jax.random.split(key, 3)
    if kind == "swiglu":
        return {"wg": dense_init(ks[0], d, ff, dtype),
                "wu": dense_init(ks[1], d, ff, dtype),
                "wd": dense_init(ks[2], ff, d, dtype)}
    return {"w1": dense_init(ks[0], d, ff, dtype, bias=True),
            "w2": dense_init(ks[1], ff, d, dtype, bias=True)}


def mlp_apply(p, x):
    if "wg" in p:
        return dense(p["wd"], jax.nn.silu(dense(p["wg"], x)) * dense(p["wu"], x))
    return dense(p["w2"], jax.nn.gelu(dense(p["w1"], x)))


# --------------------------------------------------------------------------
# MoE (token-choice top-k, capacity-bounded, gather/scatter dispatch)
# --------------------------------------------------------------------------

def moe_init(key, cfg, dtype):
    d, ff, m = cfg.d_model, cfg.d_ff, cfg.moe
    ks = jax.random.split(key, 4)
    e = m.num_experts
    p = {"router": _dense_init(ks[0], d, e, jnp.float32)}
    if cfg.mlp_type == "swiglu":
        p["wg"] = jax.random.normal(ks[1], (e, d, ff), jnp.float32).astype(dtype) / math.sqrt(d)
        p["wu"] = jax.random.normal(ks[2], (e, d, ff), jnp.float32).astype(dtype) / math.sqrt(d)
        p["wd"] = jax.random.normal(ks[3], (e, ff, d), jnp.float32).astype(dtype) / math.sqrt(ff)
    else:
        p["w1"] = jax.random.normal(ks[1], (e, d, ff), jnp.float32).astype(dtype) / math.sqrt(d)
        p["w2"] = jax.random.normal(ks[2], (e, ff, d), jnp.float32).astype(dtype) / math.sqrt(ff)
    return p


def moe_capacity(tokens: int, moe_cfg) -> int:
    c = math.ceil(moe_cfg.capacity_factor * tokens * moe_cfg.top_k
                  / moe_cfg.num_experts)
    return max(8, c + (-c) % 8)


def _gather_expert_weights(p, gather: bool):
    """Constrain expert weights to (data-)gathered form before the matmuls.

    With FSDP sharding the contracted d dim, every expert matmul psums its
    (E, C, ff) hidden activations — far larger than the weights themselves
    (EXPERIMENTS.md §Perf, grok iteration).  Gathering the weight shard
    (keeping the ff model-shard: ~hundreds of MB transient) replaces TBs of
    activation all-reduces with GBs of weight all-gathers.
    """
    if not gather:
        return p
    from jax.sharding import PartitionSpec as P
    try:
        out = dict(p)
        for k in ("wg", "wu", "w1"):
            if k in out:
                out[k] = jax.lax.with_sharding_constraint(
                    out[k], P(None, None, "model"))
        for k in ("wd", "w2"):
            if k in out:
                out[k] = jax.lax.with_sharding_constraint(
                    out[k], P(None, "model", None))
        return out
    except Exception:  # no mesh context (single-device tests): no-op
        return p


def _moe_dispatch_one(p, xt, moe_cfg, c):
    """Token-choice dispatch+compute+combine for one token group.

    xt: (T, d).  Returns (out (T, d) fp32, lb_loss, z_loss).
    """
    t, d = xt.shape
    e, k = moe_cfg.num_experts, moe_cfg.top_k
    logits = (xt.astype(jnp.float32) @ p["router"])  # (T, E) fp32 routing
    probs = jax.nn.softmax(logits, axis=-1)
    gate, eid = jax.lax.top_k(probs, k)                      # (T, K)
    gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)

    flat_e = eid.reshape(-1)                                  # (T*K,)
    onehot = jax.nn.one_hot(flat_e, e, dtype=jnp.int32)
    pos = jnp.cumsum(onehot, axis=0) - 1
    pos_in_e = jnp.take_along_axis(pos, flat_e[:, None], axis=1)[:, 0]
    keep = pos_in_e < c
    flat_t = jnp.repeat(jnp.arange(t), k)
    # scatter token ids into (E, C) slots; kicked-out tokens -> slot C (drop)
    slot_tok = jnp.full((e, c), t, dtype=jnp.int32)
    slot_tok = slot_tok.at[flat_e, jnp.where(keep, pos_in_e, c)].set(
        flat_t, mode="drop")
    x_pad = jnp.concatenate([xt, jnp.zeros((1, d), xt.dtype)], axis=0)
    ein = x_pad[slot_tok]                                     # (E, C, d)

    if "wg" in p:
        hgate = jax.nn.silu(jnp.einsum("ecd,edf->ecf", ein,
                                       p["wg"].astype(ein.dtype)))
        hup = jnp.einsum("ecd,edf->ecf", ein, p["wu"].astype(ein.dtype))
        eout = jnp.einsum("ecf,efd->ecd", hgate * hup,
                          p["wd"].astype(ein.dtype))
    else:
        hmid = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", ein,
                                      p["w1"].astype(ein.dtype)))
        eout = jnp.einsum("ecf,efd->ecd", hmid, p["w2"].astype(ein.dtype))

    # combine back: each (t, k) reads its slot (if kept) weighted by its gate
    safe_pos = jnp.minimum(pos_in_e, c - 1)
    out_flat = eout[flat_e, safe_pos]                         # (T*K, d)
    w = (keep.astype(jnp.float32) * gate.reshape(-1))[:, None]
    out = (out_flat.astype(jnp.float32) * w).reshape(t, k, d).sum(axis=1)

    # aux losses (switch-style load balance + router z-loss)
    me = probs.mean(axis=0)                                   # (E,)
    ce = onehot.reshape(t, k, e).sum(axis=1).astype(jnp.float32).mean(axis=0)
    lb = e * jnp.sum(me * ce) / k
    z = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
    return out, lb, z


def moe_apply(p, x, moe_cfg, *, capacity=None, groups: int = 1,
              gather_weights: bool = False):
    """x: (B, S, d) -> (out, aux) with aux = {lb_loss, z_loss}.

    Gather/scatter dispatch: tokens routed to (expert, slot) pairs bounded by
    `capacity`; overflow tokens are dropped (standard token-choice MoE).

    groups > 1 ("locality-grouped dispatch", EXPERIMENTS.md §Perf): tokens
    are split into `groups` independent dispatch groups with per-group
    capacity.  When `groups` equals the data-parallel shard count and the
    group dim is sharded over it, every cumsum/scatter/gather in the dispatch
    is chip-local — GSPMD no longer gathers all tokens to every chip.
    Per-group capacity is how production MoE systems bound hotspots anyway.
    """
    b, s, d = x.shape
    t = b * s
    xt = x.reshape(t, d)
    p = _gather_expert_weights(p, gather_weights)
    if groups > 1 and t % groups == 0:
        tg = t // groups
        cg = capacity if capacity is not None else moe_capacity(tg, moe_cfg)
        out, lb, z = jax.vmap(
            lambda xg: _moe_dispatch_one(p, xg, moe_cfg, cg))(
                xt.reshape(groups, tg, d))
        out = out.reshape(t, d)
        lb, z = lb.mean(), z.mean()
    else:
        c = capacity if capacity is not None else moe_capacity(t, moe_cfg)
        out, lb, z = _moe_dispatch_one(p, xt, moe_cfg, c)
    aux = {"lb_loss": lb, "z_loss": z}
    return out.reshape(b, s, d).astype(x.dtype), aux


# --------------------------------------------------------------------------
# Mamba-1 block
# --------------------------------------------------------------------------

def mamba_dims(cfg):
    ssm = cfg.ssm
    d_in = ssm.expand * cfg.d_model
    dt_rank = ssm.dt_rank or -(-cfg.d_model // 16)
    return d_in, dt_rank, ssm.d_state, ssm.d_conv


def mamba_init(key, cfg, dtype):
    d = cfg.d_model
    d_in, dt_rank, n, d_conv = mamba_dims(cfg)
    ks = jax.random.split(key, 6)
    a = jnp.tile(jnp.arange(1, n + 1, dtype=jnp.float32)[None], (d_in, 1))
    return {
        "in_proj": dense_init(ks[0], d, 2 * d_in, dtype),
        "conv_w": (jax.random.normal(ks[1], (d_conv, d_in), jnp.float32)
                   / math.sqrt(d_conv)).astype(dtype),
        "conv_b": jnp.zeros((d_in,), dtype),
        "x_proj": dense_init(ks[2], d_in, dt_rank + 2 * n, dtype),
        "dt_proj": dense_init(ks[3], dt_rank, d_in, dtype, bias=True,
                              scale=dt_rank ** -0.5),
        "A_log": jnp.log(a),
        "D": jnp.ones((d_in,), jnp.float32),
        "out_proj": dense_init(ks[4], d_in, d, dtype),
    }


def _causal_conv(x, w, b):
    """Depthwise causal conv. x: (B,S,Di), w: (K,Di)."""
    k = w.shape[0]
    w = w.astype(x.dtype)
    out = jnp.zeros_like(x)
    for i in range(k):
        shift = k - 1 - i
        xi = jnp.pad(x, ((0, 0), (shift, 0), (0, 0)))[:, : x.shape[1]]
        out = out + xi * w[i][None, None]
    return out + b.astype(x.dtype)[None, None]


def mamba_apply(p, x, cfg, *, state=None):
    """Full-sequence mamba. x: (B,S,d). Returns (out, final_state).

    final_state = (conv_state (B, K-1, Di), ssm_state (B, Di, N)).
    """
    d_in, dt_rank, n, d_conv = mamba_dims(cfg)
    xz = dense(p["in_proj"], x)
    xi, z = jnp.split(xz, 2, axis=-1)
    h0 = None
    if state is not None:
        conv_st, h0 = state
        xi_ext = jnp.concatenate([conv_st.astype(xi.dtype), xi], axis=1)
    else:
        xi_ext = xi
    xc = _causal_conv(xi_ext, p["conv_w"], p["conv_b"])[:, -xi.shape[1]:]
    xc = jax.nn.silu(xc)
    xdb = dense(p["x_proj"], xc)
    dt_r, bmat, cmat = jnp.split(xdb, [dt_rank, dt_rank + n], axis=-1)
    dt = jax.nn.softplus(dense(p["dt_proj"], dt_r).astype(jnp.float32))
    a = -jnp.exp(p["A_log"])
    y, h_last = selective_scan(xc, dt, a, bmat.astype(jnp.float32),
                               cmat.astype(jnp.float32), p["D"], h0=h0)
    y = y * jax.nn.silu(z)
    out = dense(p["out_proj"], y)
    # next conv state = last (d_conv - 1) raw inputs (front-padded for short S)
    padded = jnp.concatenate(
        [jnp.zeros((xi.shape[0], d_conv - 1, d_in), xi.dtype), xi_ext], axis=1)
    new_conv = padded[:, -(d_conv - 1):]
    return out, (new_conv, h_last)


def mamba_decode(p, x, cfg, state):
    """One-token decode. x: (B,1,d); state from mamba_apply/init_cache."""
    d_in, dt_rank, n, d_conv = mamba_dims(cfg)
    conv_st, h = state  # (B, K-1, Di), (B, Di, N)
    xz = dense(p["in_proj"], x)
    xi, z = jnp.split(xz, 2, axis=-1)          # (B,1,Di)
    window = jnp.concatenate([conv_st.astype(xi.dtype), xi], axis=1)  # (B,K,Di)
    xc = jnp.einsum("bkd,kd->bd", window, p["conv_w"].astype(xi.dtype)) \
        + p["conv_b"].astype(xi.dtype)[None]
    xc = jax.nn.silu(xc)                        # (B, Di)
    xdb = xc @ p["x_proj"]["w"].astype(xc.dtype)
    dt_r, bvec, cvec = jnp.split(xdb, [dt_rank, dt_rank + n], axis=-1)
    dt = jax.nn.softplus(
        (dt_r @ p["dt_proj"]["w"].astype(xc.dtype)
         + p["dt_proj"]["b"].astype(xc.dtype)).astype(jnp.float32))
    a = -jnp.exp(p["A_log"])
    y, h_new = selective_scan_step(xc.astype(jnp.float32), dt, a,
                                   bvec.astype(jnp.float32),
                                   cvec.astype(jnp.float32), p["D"], h)
    y = (y[:, None] * jax.nn.silu(z)).astype(x.dtype)
    out = dense(p["out_proj"], y)
    new_conv = window[:, 1:]
    return out, (new_conv, h_new)


def _pin_batch(cfg, x, batch_dim=0):
    """Pin a recurrent tensor to batch-only sharding (perf knob; see
    ModelConfig.recurrent_sharding).  No-op when the knob is unset."""
    if cfg.recurrent_sharding is None:
        return x
    from jax.sharding import PartitionSpec as P
    ent = cfg.recurrent_sharding
    ent = ent if len(ent) > 1 else ent[0]
    spec = [None] * x.ndim
    spec[batch_dim] = ent
    return jax.lax.with_sharding_constraint(x, P(*spec))


def _pin_tree(cfg, tree, batch_dim=0):
    return jax.tree_util.tree_map(
        lambda t: _pin_batch(cfg, t, batch_dim), tree)


# --------------------------------------------------------------------------
# xLSTM blocks
# --------------------------------------------------------------------------

def mlstm_init(key, cfg, dtype):
    d = cfg.d_model
    x = cfg.xlstm
    d_up = int(x.proj_factor * d)
    ks = jax.random.split(key, 7)
    return {
        "up": dense_init(ks[0], d, 2 * d_up, dtype),
        "wq": dense_init(ks[1], d_up, d_up, dtype),
        "wk": dense_init(ks[2], d_up, d_up, dtype),
        "wv": dense_init(ks[3], d_up, d_up, dtype),
        "w_i": dense_init(ks[4], d, cfg.n_heads, jnp.float32, bias=True),
        "w_f": dense_init(ks[5], d, cfg.n_heads, jnp.float32, bias=True),
        "down": dense_init(ks[6], d_up, d, dtype),
    }


def _mlstm_chunk(q, k, v, i_g, f_g, state, nstate):
    """One chunk of the gated-linear-attention recurrence.

    q,k,v: (B,c,H,dh); i_g,f_g: (B,c,H) in (0,1);
    state: (B,H,dh,dh); nstate: (B,H,dh). Returns (h, state', nstate').
    """
    logf = jnp.log(f_g + 1e-9)
    cf = jnp.cumsum(logf, axis=1)                      # (B,c,H)
    # inter-chunk: decay from chunk start
    dec0 = jnp.exp(cf)                                 # (B,c,H)
    h_inter = jnp.einsum("bchd,bhde->bche", q * dec0[..., None], state)
    n_inter = jnp.einsum("bchd,bhd->bch", q * dec0[..., None], nstate)
    # intra-chunk
    c = q.shape[1]
    rel = cf[:, :, None] - cf[:, None, :]              # (B,c_t,c_j,H)
    mask = (jnp.arange(c)[:, None] >= jnp.arange(c)[None, :])
    # mask BEFORE exp: exp of masked (positive) entries would overflow and
    # poison the backward pass with 0 * inf = NaN
    rel = jnp.where(mask[None, :, :, None], rel, -jnp.inf)
    w = jnp.exp(rel)
    w = w * i_g[:, None, :, :]                         # gate at source j
    s = jnp.einsum("bthd,bjhd->btjh", q, k)            # (B,c,c,H)
    sw = s * w
    h_intra = jnp.einsum("btjh,bjhd->bthd", sw, v)
    n_intra = jnp.einsum("btjh->bth", sw)              # sum of weights
    h = h_inter + h_intra
    n = n_inter + n_intra
    denom = jnp.maximum(jnp.abs(n), 1.0)[..., None]
    h = h / denom
    # state update to end of chunk
    decT = jnp.exp(cf[:, -1])                          # (B,H) total decay
    src_dec = jnp.exp(cf[:, -1:, :] - cf)              # (B,c,H) decay j->end
    kv = jnp.einsum("bchd,bche->bhde", k * (i_g * src_dec)[..., None], v)
    state = state * decT[:, :, None, None] + kv
    nstate = nstate * decT[:, :, None] + \
        jnp.einsum("bchd->bhd", k * (i_g * src_dec)[..., None])
    return h, state, nstate


def mlstm_apply(p, x, cfg, *, state=None):
    """Chunkwise mLSTM. x: (B,S,d) -> (out, (C_state, n_state))."""
    b, s, d = x.shape
    hn = cfg.n_heads
    xc = cfg.xlstm
    up = dense(p["up"], x)
    xin, z = jnp.split(up, 2, axis=-1)                 # (B,S,d_up)
    d_up = xin.shape[-1]
    dh = d_up // hn
    q = dense(p["wq"], xin).reshape(b, s, hn, dh) * dh ** -0.5
    k = dense(p["wk"], xin).reshape(b, s, hn, dh) * dh ** -0.5
    v = dense(p["wv"], xin).reshape(b, s, hn, dh)
    i_g = jax.nn.sigmoid(dense(p["w_i"], x.astype(jnp.float32)))
    f_g = jax.nn.sigmoid(dense(p["w_f"], x.astype(jnp.float32)))

    chunk = min(xc.chunk_size, s)
    pad = (-s) % chunk

    def padseq(t, value=0.0):
        if not pad:
            return t
        widths = ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2)
        return jnp.pad(t, widths, constant_values=value)

    # padded steps: f=1 (no decay), i=0 (no write) — state unaffected
    qp = padseq(q).astype(jnp.float32)
    kp = padseq(k).astype(jnp.float32)
    vp = padseq(v).astype(jnp.float32)
    ip = padseq(i_g, 0.0)
    fp = padseq(f_g, 1.0)
    nc = (s + pad) // chunk

    def chunk_fold(t):
        folded = t.reshape(b, nc, chunk, *t.shape[2:]).swapaxes(0, 1)
        return _pin_batch(cfg, folded, 1)  # (nc, B, c, ...): batch dim 1

    if state is None:
        st = jnp.zeros((b, hn, dh, dh), jnp.float32)
        nst = jnp.zeros((b, hn, dh), jnp.float32)
    else:
        st, nst = state
    st = _pin_batch(cfg, st, 0)
    nst = _pin_batch(cfg, nst, 0)

    def body(carry, xs):
        st, nst = carry
        qc, kc, vc, ic, fc = xs
        h, st, nst = _mlstm_chunk(qc, kc, vc, ic, fc, st, nst)
        return (_pin_batch(cfg, st, 0), _pin_batch(cfg, nst, 0)), h

    (st, nst), hs = jax.lax.scan(
        body, (st, nst), (chunk_fold(qp), chunk_fold(kp), chunk_fold(vp),
                          chunk_fold(ip), chunk_fold(fp)))
    h = hs.swapaxes(0, 1).reshape(b, s + pad, hn, dh)[:, :s]
    h = h.reshape(b, s, d_up).astype(x.dtype)
    out = dense(p["down"], h * jax.nn.sigmoid(z))
    return out, (st, nst)


def mlstm_decode(p, x, cfg, state):
    """One-token mLSTM decode via the same chunk math with c=1."""
    out, new_state = mlstm_apply(p, x, cfg, state=state)
    return out, new_state


def slstm_init(key, cfg, dtype):
    d = cfg.d_model
    hn = cfg.n_heads
    dh = d // hn
    ks = jax.random.split(key, 3)
    return {
        "w_in": dense_init(ks[0], d, 4 * d, dtype, bias=True),
        # block-diagonal recurrent weights, one (dh, 4dh) block per head
        "r": (jax.random.normal(ks[1], (hn, dh, 4 * dh), jnp.float32)
              / math.sqrt(dh)).astype(dtype),
        "out": dense_init(ks[2], d, d, dtype),
    }


def _slstm_step(p, cfg, xt, state):
    """xt: (B, 4d) pre-projected input; state: (h, c, n, m) each (B, d)."""
    hn = cfg.n_heads
    b = xt.shape[0]
    d = xt.shape[-1] // 4
    dh = d // hn
    h, c, n, m = state
    hr = h.reshape(b, hn, dh)
    rec = jnp.einsum("bhd,hde->bhe", hr.astype(jnp.float32),
                     p["r"].astype(jnp.float32)).reshape(b, 4 * d)
    zifo = xt.astype(jnp.float32) + rec
    z_t, i_t, f_t, o_t = jnp.split(zifo, 4, axis=-1)
    z_t = jnp.tanh(z_t)
    o_t = jax.nn.sigmoid(o_t)
    m_new = jnp.maximum(f_t + m, i_t)          # log-space stabilizer
    i_p = jnp.exp(i_t - m_new)
    f_p = jnp.exp(f_t + m - m_new)
    c_new = f_p * c + i_p * z_t
    n_new = f_p * n + i_p
    h_new = o_t * c_new / jnp.maximum(n_new, 1e-6)
    return h_new, c_new, n_new, m_new


def slstm_apply(p, x, cfg, *, state=None):
    """Recurrent sLSTM over the sequence. x: (B,S,d) -> (out, state).

    Chunked: the lax.scan iterates over chunks of `cfg.xlstm.chunk_size`
    timesteps with the inner steps unrolled — 64x fewer loop iterations means
    64x fewer per-iteration gradient all-reduces for the (replicated)
    recurrent weights, and better TPU loop overhead.
    """
    b, s, d = x.shape
    xin = dense(p["w_in"], x)                   # (B,S,4d)
    if state is None:
        z = jnp.zeros((b, d), jnp.float32)
        state = (z, z, z, jnp.full((b, d), -1e9, jnp.float32))
    state = _pin_tree(cfg, state, 0)
    xin = _pin_batch(cfg, xin, 0)

    # NOTE (§Perf, refuted hypothesis): unrolling 64-step chunks inside the
    # scan body converts the per-step gradient all-reduces into the same
    # volume of all-to-alls (no byte win) and inflates compile time ~10x —
    # reverted to the per-step scan.  See EXPERIMENTS.md §Perf iteration 2.
    def body(st, xt):
        st = _slstm_step(p, cfg, xt, st)
        return _pin_tree(cfg, st, 0), st[0]

    state, hs = jax.lax.scan(body, state, xin.swapaxes(0, 1))
    out = dense(p["out"], hs.swapaxes(0, 1).astype(x.dtype))
    return out, state


def slstm_decode(p, x, cfg, state):
    xin = dense(p["w_in"], x)[:, 0]
    st = _slstm_step(p, cfg, xin, state)
    out = dense(p["out"], st[0][:, None].astype(x.dtype))
    return out, st
