"""The plain reference of a stage step, and its lower-precision control.

Straightforward ``jax.numpy`` in float32 at ``Precision.HIGHEST``, written
from the published descriptions of these dense decoders: RMSNorm, grouped-
query attention with (partial) rotary embeddings and QKV bias, a SwiGLU MLP,
then either the SIL-MSE loss against SIL_k[:, y] (paper Eq. 1) or the
cross-entropy through the frozen tied unembedding, and the AdamW update.
It imports nothing of the program and is given nothing the program made:
weights, tables and batches are made again here from the seed.

To fit beside nothing else on one chip it runs layer by layer (each layer
rematerialised), attention in blocks of query rows, and the unembedding in
blocks of tokens.  ``precision="fp8"`` is the control: every matmul operand,
forward and backward, rounded to float8 e4m3 with a per-tensor scale, the
step below the bf16 compute that the configurations state.
"""
from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp

from bench import model as bm

HIGHEST = jax.lax.Precision.HIGHEST
Q_BLOCK = 512        # query rows of attention scores live at once
TOKEN_BLOCK = 1024   # tokens of unembedding logits live at once


def _f8(x):
    """x rounded to float8 e4m3 under a per-tensor scale, back in float32."""
    amax = jnp.max(jnp.abs(x))
    s = jnp.where(amax > 0, amax / 448.0, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _make_dot(precision: str):
    """einsum(eq, a, b) in float32 at HIGHEST; for "fp8" both operands of
    the forward and of each backward product are rounded to fp8 first."""
    if precision == "fp32":
        def dot(eq, a, b):
            return jnp.einsum(eq, a, b, precision=HIGHEST,
                              preferred_element_type=jnp.float32)
        return dot
    if precision != "fp8":
        raise ValueError(f"reference precision {precision!r}")

    @functools.lru_cache(maxsize=None)
    def op(eq):
        @jax.custom_vjp
        def f(a, b):
            return jnp.einsum(eq, _f8(a), _f8(b), precision=HIGHEST)

        def fwd(a, b):
            return f(a, b), (a, b)

        def bwd(res, g):
            a, b = res
            _, vjp = jax.vjp(lambda x, y: jnp.einsum(eq, x, y,
                                                     precision=HIGHEST),
                             _f8(a), _f8(b))
            ga, gb = vjp(_f8(g))
            return ga, gb
        f.defvjp(fwd, bwd)
        return f

    def dot(eq, a, b):
        return op(eq)(a.astype(jnp.float32), b.astype(jnp.float32))
    return dot


def rms_norm(x, scale, eps):
    ms = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(ms + eps) * scale


def rope(x, cfg: bm.ModelCfg, seq: int):
    """Rotary embedding on the first ``rope_dim`` of each head's dims."""
    rot = cfg.rope_dim
    inv = 1.0 / (cfg.rope_theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32)
                                    / rot))
    ang = jnp.arange(seq, dtype=jnp.float32)[:, None] * inv[None, :]
    c, s = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    xr, xp = x[..., :rot], x[..., rot:]
    if cfg.rope_pairs == "adjacent":
        x1, x2 = xr[..., 0::2], xr[..., 1::2]
        out = jnp.stack([x1 * c - x2 * s, x2 * c + x1 * s], -1).reshape(xr.shape)
    elif cfg.rope_pairs == "halves":
        x1, x2 = xr[..., :rot // 2], xr[..., rot // 2:]
        out = jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)
    else:
        raise ValueError(f"rope_pairs {cfg.rope_pairs!r}")
    return jnp.concatenate([out, xp], -1)


def attention(q, k, v, dot):
    """Causal grouped-query attention. q (B,S,H,hd), k/v (B,S,KV,hd); query
    head h reads key/value head h // (H / KV).  Softmax in float32 over all
    keys, one block of query rows at a time."""
    b, s, h, hd = q.shape
    kv = k.shape[2]
    g = h // kv
    rows = min(Q_BLOCK, s)
    qb = q.reshape(b, s // rows, rows, kv, g, hd) * hd ** -0.5
    kpos = jnp.arange(s)

    @jax.checkpoint
    def block(args):
        qi, i = args                                     # (B, Qb, KV, G, hd)
        sc = dot("bqkgd,btkd->bkgqt", qi, k)
        qpos = i * rows + jnp.arange(rows)
        sc = jnp.where(kpos[None, :] <= qpos[:, None], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        return dot("bkgqt,btkd->bqkgd", p, v)
    out = jax.lax.map(block, (jnp.moveaxis(qb, 1, 0), jnp.arange(s // rows)))
    return jnp.moveaxis(out, 0, 1).reshape(b, s, h, hd)


def layer(x, p, cfg: bm.ModelCfg, dot):
    b, s, d = x.shape
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim

    def proj(y, w):
        out = dot("bsd,de->bse", y, w["w"])
        return out + w["b"] if "b" in w else out
    a = p["attn"]
    y = rms_norm(x, p["norm1"]["scale"], cfg.norm_eps)
    q = rope(proj(y, a["wq"]).reshape(b, s, h, hd), cfg, s)
    k = rope(proj(y, a["wk"]).reshape(b, s, kv, hd), cfg, s)
    v = proj(y, a["wv"]).reshape(b, s, kv, hd)
    x = x + proj(attention(q, k, v, dot).reshape(b, s, h * hd), a["wo"])
    m = p["mlp"]
    y = rms_norm(x, p["norm2"]["scale"], cfg.norm_eps)
    u = jax.nn.silu(proj(y, m["wg"])) * proj(y, m["wu"])
    return x + proj(u, m["wd"])


def stage_loss(cfg: bm.ModelCfg, k: int, train, frozen, tables, tokens,
               labels, dot):
    """Stage k's loss on one batch: float32 throughout."""
    if k == 0:
        x = train["tok_embed"][tokens]
    else:
        x = jnp.moveaxis(tables[k - 1][:, labels], 0, -1)

    def body(x, p):
        return jax.checkpoint(lambda x_, p_: layer(x_, p_, cfg, dot))(x, p), None
    x, _ = jax.lax.scan(body, x, train["groups"]["slot_0"])
    if not cfg.is_last(k):
        target = jnp.moveaxis(tables[k][:, labels], 0, -1)
        return jnp.mean(jnp.square(x - target))
    x = rms_norm(x, train["final_norm"]["scale"], cfg.norm_eps)
    if "unembed" in train:
        w = train["unembed"]
    else:
        emb = frozen["tied_unembed"] if "tied_unembed" in frozen \
            else train["tok_embed"]
        w = jax.lax.stop_gradient(emb).T
    w = w[:, :cfg.vocab_size]
    rows = min(TOKEN_BLOCK, labels.size)
    xt = x.reshape(-1, rows, x.shape[-1])
    lt = labels.reshape(-1, rows)

    @jax.checkpoint
    def nll(args):
        xc, lc = args
        logits = dot("td,dv->tv", xc, w)
        lse = jax.nn.logsumexp(logits, axis=-1)
        return jnp.sum(lse - jnp.take_along_axis(logits, lc[:, None], -1)[:, 0])
    return jnp.sum(jax.lax.map(nll, (xt, lt))) / labels.size


def split_frozen(params):
    train = {k: v for k, v in params.items() if k not in bm.FROZEN}
    frozen = {k: v for k, v in params.items() if k in bm.FROZEN}
    return train, frozen


def leaf_norms(tree) -> Dict[str, jax.Array]:
    """{name: L2 norm} of each leaf; stacked layer leaves give one norm per
    layer ("layer3/attn/wq/w").  Used on both sides of the comparison."""
    out = {}
    for path, leaf in bm.flat_paths(tree):
        name = "/".join(path[2:] if path[0] == "groups" else path)
        if path[0] == "groups":
            norms = jnp.sqrt(jnp.sum(jnp.square(leaf.astype(jnp.float32)),
                                     axis=tuple(range(1, leaf.ndim))))
            for i in range(leaf.shape[0]):
                out[f"layer{i}/{name}"] = norms[i]
        else:
            out[name] = jnp.sqrt(jnp.sum(jnp.square(leaf.astype(jnp.float32))))
    return out


def adamw(cfg: bm.ModelCfg, p, g, m, v, count):
    """One AdamW step (Loshchilov and Hutter), moments in float32."""
    c = count + 1
    m = jax.tree_util.tree_map(lambda m_, g_: cfg.b1 * m_ + (1 - cfg.b1) * g_,
                               m, g)
    v = jax.tree_util.tree_map(
        lambda v_, g_: cfg.b2 * v_ + (1 - cfg.b2) * g_ * g_, v, g)
    bc1, bc2 = 1 - cfg.b1 ** c, 1 - cfg.b2 ** c

    def upd(p_, m_, v_):
        step = (m_ / bc1) / (jnp.sqrt(v_ / bc2) + cfg.eps)
        return p_ - cfg.lr * (step + cfg.weight_decay * p_)
    return jax.tree_util.tree_map(upd, p, m, v), m, v


def make_step(cfg: bm.ModelCfg, k: int, precision: str):
    dot = _make_dot(precision)

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def step(train, m, v, count, frozen, tables, tokens, labels):
        loss, g = jax.value_and_grad(stage_loss, argnums=2)(
            cfg, k, train, frozen, tables, tokens, labels, dot)
        train, m, v = adamw(cfg, train, g, m, v, count)
        return train, m, v, loss, leaf_norms(g)
    return step


def readings(cfg: bm.ModelCfg, k: int, batches, seed: int, n_steps: int,
             precision: str = "fp32") -> dict:
    """Three numbers' raw readings of the reference over the first
    ``n_steps`` steps: each step's loss, the per-leaf norms of the first
    gradient, and the per-leaf norms of the parameters' change."""
    kp, kt = bm.seed_keys(seed)
    init = jax.jit(functools.partial(bm.init_stage, cfg, k))
    train, frozen = split_frozen(init(jax.random.fold_in(kp, k)))
    tables = jax.jit(functools.partial(bm.make_tables, cfg, k))(kt)
    m = jax.tree_util.tree_map(jnp.zeros_like, train)
    v = jax.tree_util.tree_map(jnp.zeros_like, train)
    step = make_step(cfg, k, precision)
    losses, grads = [], None
    for i in range(n_steps):
        b = batches.host(i)
        train, m, v, loss, gn = step(train, m, v, float(i), frozen, tables,
                                     b["tokens"], b["labels"])
        losses.append(loss)
        if i == 0:
            grads = gn
    del m, v, tables
    change = change_norms(cfg, k, train, kp)
    out = {"losses": [float(x) for x in jax.device_get(losses)],
           "grad": {n: float(x) for n, x in jax.device_get(grads).items()},
           "change": {n: float(x) for n, x in jax.device_get(change).items()}}
    del train, frozen
    return out


def change_norms(cfg: bm.ModelCfg, k: int, params, key_params):
    """Per-leaf norms of (params - their initial values), the initial values
    made again from the key inside the same program."""
    @jax.jit
    def f(p, key):
        p0, _ = split_frozen(bm.init_stage(cfg, k, jax.random.fold_in(key, k)))
        p, _ = split_frozen(p)
        return leaf_norms(jax.tree_util.tree_map(
            lambda a, b: a.astype(jnp.float32) - b, p, p0))
    return f(params, key_params)
