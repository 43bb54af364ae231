"""Model assembly: scan-over-layer-groups transformers for every assigned family.

Layers are stacked into *groups* — the smallest repeating pattern of block
kinds (1 for uniform dense/MoE stacks, 2 for xLSTM 'ms', 8 for jamba's
attn:mamba 1:7 interleave) — and the stack of groups is driven by
``jax.lax.scan`` so compile time is independent of depth (88-layer models
lower as fast as 2-layer ones).

PNN stages (core/partition.py) cut the model at *group* boundaries: stage k
runs groups [g_k, g_{k+1}).  Stage 0 owns the embedding (+ encoder/frontend),
the last stage owns the final norm + unembedding.
"""
from __future__ import annotations

import contextlib
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers as L


# --------------------------------------------------------------------------
# group structure
# --------------------------------------------------------------------------

def group_size(cfg: ModelConfig) -> int:
    """Smallest g dividing n_layers such that (kind, is_moe) repeats mod g."""
    pattern = [(cfg.block_kind(l), cfg.layer_is_moe(l)) for l in range(cfg.n_layers)]
    for g in range(1, cfg.n_layers + 1):
        if cfg.n_layers % g:
            continue
        if all(pattern[l] == pattern[l % g] for l in range(cfg.n_layers)):
            return g
    return cfg.n_layers


def slot_spec(cfg: ModelConfig):
    """[(kind, is_moe, has_ffn)] for each slot inside a group."""
    g = group_size(cfg)
    out = []
    for l in range(g):
        kind = cfg.block_kind(l)
        has_ffn = kind in ("attn", "mamba") and cfg.d_ff > 0
        out.append((kind, cfg.layer_is_moe(l) and has_ffn, has_ffn))
    return out


def n_groups(cfg: ModelConfig) -> int:
    return cfg.n_layers // group_size(cfg)


# --------------------------------------------------------------------------
# init
# --------------------------------------------------------------------------

def _slot_init(key, cfg, kind, is_moe, has_ffn, dtype, cross=False):
    ks = jax.random.split(key, 6)
    p: Dict[str, Any] = {"norm1": L.norm_init(cfg.norm, cfg.d_model, dtype)}
    if kind == "attn":
        p["attn"] = L.attention_init(ks[0], cfg, dtype)
    elif kind == "mamba":
        p["mamba"] = L.mamba_init(ks[0], cfg, dtype)
    elif kind == "mlstm":
        p["mlstm"] = L.mlstm_init(ks[0], cfg, dtype)
    elif kind == "slstm":
        p["slstm"] = L.slstm_init(ks[0], cfg, dtype)
    if cross:
        p["norm_x"] = L.norm_init(cfg.norm, cfg.d_model, dtype)
        p["cross"] = L.attention_init(ks[1], cfg, dtype)
    if has_ffn:
        p["norm2"] = L.norm_init(cfg.norm, cfg.d_model, dtype)
        if is_moe:
            p["moe"] = L.moe_init(ks[2], cfg, dtype)
        else:
            p["mlp"] = L.mlp_init(ks[2], cfg.d_model, cfg.d_ff, cfg.mlp_type, dtype)
    return p


def init_params(cfg: ModelConfig, key) -> Dict[str, Any]:
    dtype = jnp.dtype(cfg.param_dtype)
    keys = jax.random.split(key, 8)
    slots = slot_spec(cfg)
    g = n_groups(cfg)

    def stack_groups(base_key):
        gkeys = jax.random.split(base_key, g)

        def one_group(k):
            sk = jax.random.split(k, len(slots))
            return {
                f"slot_{i}": _slot_init(sk[i], cfg, kind, is_moe, has_ffn, dtype,
                                        cross=cfg.enc_dec)
                for i, (kind, is_moe, has_ffn) in enumerate(slots)
            }
        return jax.vmap(one_group)(gkeys)

    params: Dict[str, Any] = {
        "tok_embed": (jax.random.normal(keys[0],
                                        (cfg.vocab_padded, cfg.d_model),
                                        jnp.float32) * 0.02).astype(dtype),
        "final_norm": L.norm_init(cfg.norm, cfg.d_model, dtype),
        "groups": stack_groups(keys[1]),
    }
    if not cfg.tie_embeddings:
        params["unembed"] = (jax.random.normal(
            keys[2], (cfg.d_model, cfg.vocab_padded), jnp.float32)
            / math.sqrt(cfg.d_model)).astype(dtype)
    if cfg.enc_dec:
        ekeys = jax.random.split(keys[3], cfg.enc_layers)

        def enc_group(k):
            return {"slot_0": _slot_init(k, cfg, "attn", False, cfg.d_ff > 0,
                                         dtype, cross=False)}
        params["encoder"] = jax.vmap(enc_group)(ekeys)
        params["enc_norm"] = L.norm_init(cfg.norm, cfg.d_model, dtype)
        params["dec_pos"] = (jax.random.normal(
            keys[4], (cfg.max_seq, cfg.d_model), jnp.float32) * 0.02).astype(dtype)
    if cfg.frontend == "vision":
        params["img_proj"] = L.dense_init(keys[5], cfg.d_model, cfg.d_model, dtype)
    return params


# --------------------------------------------------------------------------
# embeddings / frontends
# --------------------------------------------------------------------------

def sinusoidal(seq, d):
    pos = jnp.arange(seq, dtype=jnp.float32)[:, None]
    div = jnp.exp(jnp.arange(0, d, 2, dtype=jnp.float32) * (-math.log(10000.0) / d))
    ang = pos * div[None, :]
    pe = jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)
    return pe[:, :d]


def embed_tokens(cfg, params, tokens, dtype):
    with jax.named_scope("embed"):
        return params["tok_embed"].astype(dtype)[tokens]


def encode_audio(cfg, params, frames):
    """Whisper encoder over precomputed (stub-frontend) frames (B, T_enc, d)."""
    dtype = cfg.activation_dtype()
    x = frames.astype(dtype) + sinusoidal(frames.shape[1], cfg.d_model).astype(dtype)

    def body(carry, pgroup):
        x, = carry
        sp = pgroup["slot_0"]
        h = L.norm_apply(sp["norm1"], x)
        out, _ = L.attention_apply(sp["attn"], h, cfg, rope_cs=None, causal=False)
        x = L.residual_add(x, out)
        if "norm2" in sp:
            x = L.residual_add(
                x, L.mlp_apply(sp["mlp"], L.norm_apply(sp["norm2"], x)))
        return (x,), None

    (x,), _ = jax.lax.scan(body, (x,), params["encoder"])
    return L.norm_apply(params["enc_norm"], x)


def embed_inputs(cfg, params, batch):
    """Returns (x (B,S,d), enc_out or None, n_prefix) for training/prefill."""
    dtype = cfg.activation_dtype()
    enc_out = None
    n_prefix = 0
    tokens = batch["tokens"]
    x = embed_tokens(cfg, params, tokens, dtype)
    if cfg.enc_dec:
        enc_out = encode_audio(cfg, params, batch["frames"])
        s = tokens.shape[1]
        x = x + params["dec_pos"].astype(dtype)[None, :s]
    elif cfg.frontend == "vision":
        img = L.dense(params["img_proj"], batch["image_embeds"].astype(dtype))
        x = jnp.concatenate([img, x], axis=1)
        n_prefix = img.shape[1]
    return x, enc_out, n_prefix


# --------------------------------------------------------------------------
# block application (shared by train / prefill / decode)
# --------------------------------------------------------------------------

def _apply_slot_full(cfg, sp, kind, is_moe, has_ffn, x, rope_cs, enc_out,
                     collect_cache):
    """Full-sequence slot application. Returns (x, aux, cache_slot or None)."""
    aux = {"lb_loss": 0.0, "z_loss": 0.0}
    cache = {}
    # the attention and FFN sub-blocks run under the step's named regions
    # (repro.obs.scopes); the recurrent mixers take none
    mixer = jax.named_scope("attention") if kind == "attn" \
        else contextlib.nullcontext()
    with mixer:
        h = L.norm_apply(sp["norm1"], x)
        window = cfg.sliding_window
        if kind == "attn":
            out, (k, v) = L.attention_apply(sp["attn"], h, cfg,
                                            rope_cs=rope_cs, causal=True,
                                            window=window)
            if collect_cache:
                cache["k"], cache["v"] = k, v
        elif kind == "mamba":
            out, st = L.mamba_apply(sp["mamba"], h, cfg)
            if collect_cache:
                cache["conv"], cache["ssm"] = st
        elif kind == "mlstm":
            out, st = L.mlstm_apply(sp["mlstm"], h, cfg)
            if collect_cache:
                cache["C"], cache["n"] = st
        elif kind == "slstm":
            out, st = L.slstm_apply(sp["slstm"], h, cfg)
            if collect_cache:
                cache["h"], cache["c"], cache["sn"], cache["m"] = st
        x = L.residual_add(x, out)
    if cfg.enc_dec and enc_out is not None:
        with jax.named_scope("attention"):
            hx = L.norm_apply(sp["norm_x"], x)
            outx, (ck, cv) = L.attention_apply(sp["cross"], hx, cfg,
                                               kv_override=enc_out)
            x = L.residual_add(x, outx)
        if collect_cache:
            cache["cross_k"], cache["cross_v"] = ck, cv
    if has_ffn:
        with jax.named_scope("mlp"):
            h2 = L.norm_apply(sp["norm2"], x)
            if is_moe:
                out2, a = L.moe_apply(sp["moe"], h2, cfg.moe,
                                      groups=cfg.moe_dispatch_groups or 1,
                                      gather_weights=cfg.moe_gather_weights)
                aux = {k2: aux[k2] + a[k2] for k2 in aux}
            else:
                out2 = L.mlp_apply(sp["mlp"], h2)
            x = L.residual_add(x, out2)
    return x, aux, (cache if collect_cache else None)


def _apply_slot_decode(cfg, sp, kind, is_moe, has_ffn, x, rope_cs, pos,
                       cache_slot, paged=None, kv_block=None):
    """One-token slot application with cache update.

    paged: optional ``(block_tables, logical_len)`` routing the attention
    K/V through a block-paged pool (see ``layers.attention_decode``); all
    other slot kinds stay slot-resident and ignore it, as they do
    ``kv_block``, the decode tile over a contiguous attention cache.
    """
    h = L.norm_apply(sp["norm1"], x)
    window = cfg.sliding_window
    new_cache = dict(cache_slot)
    if kind == "attn":
        out, (kc, vc) = L.attention_decode(
            sp["attn"], h, cfg, (cache_slot["k"], cache_slot["v"]), pos,
            rope_cs=rope_cs, window=window, paged=paged, kv_block=kv_block)
        new_cache["k"], new_cache["v"] = kc, vc
    elif kind == "mamba":
        out, st = L.mamba_decode(sp["mamba"], h, cfg,
                                 (cache_slot["conv"], cache_slot["ssm"]))
        new_cache["conv"], new_cache["ssm"] = st
    elif kind == "mlstm":
        out, st = L.mlstm_decode(sp["mlstm"], h, cfg,
                                 (cache_slot["C"], cache_slot["n"]))
        new_cache["C"], new_cache["n"] = st
    elif kind == "slstm":
        out, st = L.slstm_decode(
            sp["slstm"], h, cfg,
            (cache_slot["h"], cache_slot["c"], cache_slot["sn"], cache_slot["m"]))
        new_cache["h"], new_cache["c"], new_cache["sn"], new_cache["m"] = st
    x = L.residual_add(x, out)
    if cfg.enc_dec:
        hx = L.norm_apply(sp["norm_x"], x)
        outx, _ = L.attention_decode(
            sp["cross"], hx, cfg, None, pos,
            cross_kv=(cache_slot["cross_k"], cache_slot["cross_v"]))
        x = L.residual_add(x, outx)
    if has_ffn:
        h2 = L.norm_apply(sp["norm2"], x)
        if is_moe:
            out2, _ = L.moe_apply(sp["moe"], h2, cfg.moe,
                                  groups=cfg.moe_dispatch_groups or 1,
                                  gather_weights=cfg.moe_gather_weights)
        else:
            out2 = L.mlp_apply(sp["mlp"], h2)
        x = L.residual_add(x, out2)
    return x, new_cache


# --------------------------------------------------------------------------
# full-sequence forward over a group range (train / prefill / PNN stages)
# --------------------------------------------------------------------------

def forward_groups(cfg, groups_params, x, *, rope_cs, enc_out=None,
                   g0=0, g1=None, collect_cache=False, remat=True,
                   shard_x=None):
    """Runs groups [g0, g1) over x. Returns (x, aux, cache or None).

    shard_x: optional callable applied to the residual stream at every group
    boundary (sequence-parallel sharding constraint — see launch/steps.py).
    """
    slots = slot_spec(cfg)
    g1 = n_groups(cfg) if g1 is None else g1

    def body(carry, pgroup):
        x, lb, z = carry
        if shard_x is not None:
            x = shard_x(x)
        cache_g = {}
        for i, (kind, is_moe, has_ffn) in enumerate(slots):
            x, aux, cache = _apply_slot_full(
                cfg, pgroup[f"slot_{i}"], kind, is_moe, has_ffn, x, rope_cs,
                enc_out, collect_cache)
            lb = lb + aux["lb_loss"]
            z = z + aux["z_loss"]
            if collect_cache:
                cache_g[f"slot_{i}"] = cache
        return (x, lb, z), (cache_g if collect_cache else None)

    if remat:
        body = jax.checkpoint(body, prevent_cse=False)
    with jax.named_scope("layers"):
        sub = jax.tree_util.tree_map(lambda a: a[g0:g1], groups_params)
        zero = jnp.zeros((), jnp.float32)
        (x, lb, z), caches = jax.lax.scan(body, (x, zero, zero), sub)
    return x, {"lb_loss": lb, "z_loss": z}, caches


def rope_for(cfg, positions):
    if cfg.enc_dec:
        return None  # whisper uses learned positions
    return L.rope_tables(positions, cfg.hd, cfg.rope_fraction, cfg.rope_theta)


def forward(cfg, params, batch, *, remat=True, shard_x=None):
    """Training forward: returns (logits, aux)."""
    x, enc_out, n_prefix = embed_inputs(cfg, params, batch)
    s = x.shape[1]
    rope_cs = rope_for(cfg, jnp.arange(s))
    x, aux, _ = forward_groups(cfg, params["groups"], x, rope_cs=rope_cs,
                               enc_out=enc_out, remat=remat, shard_x=shard_x)
    x = L.norm_apply(params["final_norm"], x)
    logits = unembed(cfg, params, x)
    aux["n_prefix"] = n_prefix
    return logits, aux


def norm_apply_final(cfg, params, x):
    return L.norm_apply(params["final_norm"], x)


def unembed(cfg, params, x):
    dtype = x.dtype
    if cfg.tie_embeddings:
        w = params["tok_embed"].astype(dtype).T
    else:
        w = params["unembed"].astype(dtype)
    return x @ w


# --------------------------------------------------------------------------
# caches / prefill / decode
# --------------------------------------------------------------------------

def init_cache(cfg, batch_size, cache_len):
    """Zero cache pytree (stacked over groups)."""
    dtype = cfg.activation_dtype()
    slots = slot_spec(cfg)
    g = n_groups(cfg)
    kv, hd = cfg.n_kv_heads, cfg.hd
    lc = min(cache_len, cfg.sliding_window) if cfg.sliding_window else cache_len
    cache = {}
    for i, (kind, _, _) in enumerate(slots):
        c = {}
        if kind == "attn":
            c["k"] = jnp.zeros((g, batch_size, lc, kv, hd), dtype)
            c["v"] = jnp.zeros((g, batch_size, lc, kv, hd), dtype)
            if cfg.enc_dec:
                c["cross_k"] = jnp.zeros((g, batch_size, cfg.enc_seq, kv, hd), dtype)
                c["cross_v"] = jnp.zeros((g, batch_size, cfg.enc_seq, kv, hd), dtype)
        elif kind == "mamba":
            d_in, _, n, d_conv = L.mamba_dims(cfg)
            c["conv"] = jnp.zeros((g, batch_size, d_conv - 1, d_in), dtype)
            c["ssm"] = jnp.zeros((g, batch_size, d_in, n), jnp.float32)
        elif kind == "mlstm":
            d_up = int(cfg.xlstm.proj_factor * cfg.d_model)
            dh = d_up // cfg.n_heads
            c["C"] = jnp.zeros((g, batch_size, cfg.n_heads, dh, dh), jnp.float32)
            c["n"] = jnp.zeros((g, batch_size, cfg.n_heads, dh), jnp.float32)
        elif kind == "slstm":
            d = cfg.d_model
            c["h"] = jnp.zeros((g, batch_size, d), jnp.float32)
            c["c"] = jnp.zeros((g, batch_size, d), jnp.float32)
            c["sn"] = jnp.zeros((g, batch_size, d), jnp.float32)
            c["m"] = jnp.full((g, batch_size, d), -1e9, jnp.float32)
        cache[f"slot_{i}"] = c
    return cache


def _ring_pack(k, lc, window):
    """Pack full-seq keys (B,S,KV,hd) into a cache of length lc.

    With a window, key at absolute pos p lands at slot p % lc (ring layout
    consistent with decode); otherwise the first lc keys land at their pos.
    """
    s = k.shape[1]
    if s <= lc:
        pad = ((0, 0), (0, lc - s), (0, 0), (0, 0))
        return jnp.pad(k, pad)
    tail = k[:, -lc:]
    if not window:
        return tail
    slots = (jnp.arange(s - lc, s)) % lc
    out = jnp.zeros((k.shape[0], lc) + k.shape[2:], k.dtype)
    return out.at[:, slots].set(tail)


def repack_prefill_cache(cfg, caches, cache_len):
    """Repack full-seq prefill kv into fixed cache slots (ring layout when a
    sliding window is set); carry states pass through unchanged."""
    lc = min(cache_len, cfg.sliding_window) if cfg.sliding_window else cache_len
    slots = slot_spec(cfg)
    cache = {}
    for i, (kind, _, _) in enumerate(slots):
        c = dict(caches[f"slot_{i}"]) if caches[f"slot_{i}"] else {}
        if kind == "attn":
            c["k"] = jax.vmap(lambda kk: _ring_pack(kk, lc, cfg.sliding_window))(c["k"])
            c["v"] = jax.vmap(lambda vv: _ring_pack(vv, lc, cfg.sliding_window))(c["v"])
        cache[f"slot_{i}"] = c
    return cache


def prefill(cfg, params, batch, cache_len):
    """Forward over the prompt, building the decode cache.

    Returns (last_token_logits (B,V), cache, next_pos scalar).
    """
    x, enc_out, n_prefix = embed_inputs(cfg, params, batch)
    s = x.shape[1]
    rope_cs = rope_for(cfg, jnp.arange(s))
    x, _, caches = forward_groups(cfg, params["groups"], x, rope_cs=rope_cs,
                                  enc_out=enc_out, collect_cache=True,
                                  remat=False)
    cache = repack_prefill_cache(cfg, caches, cache_len)
    xl = L.norm_apply(params["final_norm"], x[:, -1:])
    logits = unembed(cfg, params, xl)[:, 0]
    return logits, cache, jnp.int32(s)


def decode_embed(cfg, params, token, pos):
    """Embed the current token(s) for decode; returns (x (B,1,d), rope_cs).

    `params` needs only the embedding-owning keys (stage 0 under a
    PartitionPlan)."""
    dtype = cfg.activation_dtype()
    x = embed_tokens(cfg, params, token[:, None], dtype)
    if cfg.enc_dec:
        pe = params["dec_pos"].astype(dtype)[pos]  # (d,) or (B, d)
        x = x + (pe[None, None] if jnp.ndim(pos) == 0 else pe[:, None])
        rope_cs = None
    else:
        rope_cs = L.rope_tables(pos[None] if jnp.ndim(pos) == 0 else pos,
                                cfg.hd, cfg.rope_fraction, cfg.rope_theta)
    return x, rope_cs


def decode_groups(cfg, groups_params, cache, x, rope_cs, pos, paged=None,
                  kv_block=None):
    """One decode step over a (sub)stack of layer groups.

    groups_params / cache are stacked over the same leading group dim (the
    whole model, or one PartitionPlan stage's slice).  With ``paged``, the
    attention K/V leaves are (G, NB, BS, KV, hd) physical block pools and
    the one block table (a scan constant, shared across groups) routes each
    request's reads/writes.  Returns (x, new_cache).
    """
    slots = slot_spec(cfg)

    def body(x, xs):
        pgroup, cache_g = xs
        new_cache_g = {}
        for i, (kind, is_moe, has_ffn) in enumerate(slots):
            x, nc = _apply_slot_decode(cfg, pgroup[f"slot_{i}"], kind, is_moe,
                                       has_ffn, x, rope_cs, pos,
                                       cache_g[f"slot_{i}"], paged=paged,
                                       kv_block=kv_block)
            new_cache_g[f"slot_{i}"] = nc
        return x, new_cache_g

    return jax.lax.scan(body, x, (groups_params, cache))


def decode_step(cfg, params, cache, token, pos, paged=None, kv_block=None):
    """One decode step. token: (B,) int32; pos: scalar int32 OR per-request
    (B,) int32 vector (ragged batches: each request at its own position).

    paged: optional ``(block_tables, logical_len)`` for block-paged K/V;
    kv_block: optional decode tile over a contiguous K/V cache.
    Returns (logits (B,V), new_cache).
    """
    x, rope_cs = decode_embed(cfg, params, token, pos)
    x, new_cache = decode_groups(cfg, params["groups"], cache, x, rope_cs,
                                 pos, paged=paged, kv_block=kv_block)
    x = L.norm_apply(params["final_norm"], x)
    logits = unembed(cfg, params, x)[:, 0]
    return logits, new_cache
