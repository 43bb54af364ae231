"""What every model family of the benchmark shares: the keys a seed gives,
the walk over a parameter tree, the SIL tables (paper Eq. 1), the plain
reference's float32 and float8 products, RMSNorm, AdamW and per-leaf norms,
and the reading of the program's AdamW state and compiled step.

A family module (``bench/families/<family>.py``) holds what belongs to one
kind of model; nothing here knows a layer's layout.  The config object a
family's ``parse`` returns has at least ``name``, ``stages``, ``d_model``
(the width of the stage boundary, the tables' rows), ``vocab_size``,
``kappa``, ``lr``, ``b1``, ``b2``, ``eps`` and ``weight_decay``.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


# --------------------------------------------------------------------------
# seeds and trees
# --------------------------------------------------------------------------

def seed_keys(seed: int):
    """(params key, tables key) from a seed of any size: the seed is spread
    over two 32-bit words, so seeds past 2**32 stay distinct."""
    import numpy as np
    words = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
    root = jax.random.wrap_key_data(words, impl="threefry2x32")
    return jax.random.fold_in(root, 1), jax.random.fold_in(root, 2)


def flat_paths(tree, prefix=()) -> List[Tuple[Tuple[str, ...], object]]:
    """(path, leaf) pairs of a nested dict, in sorted key order."""
    out = []
    for key in sorted(tree):
        v = tree[key]
        if isinstance(v, dict):
            out += flat_paths(v, prefix + (key,))
        else:
            out.append((prefix + (key,), v))
    return out


def set_path(tree, path, value):
    for key in path[:-1]:
        tree = tree.setdefault(key, {})
    tree[path[-1]] = value


# --------------------------------------------------------------------------
# SIL tables (paper Eq. 1)
# --------------------------------------------------------------------------

def table_ids(cfg, k: int) -> Tuple[int, ...]:
    """The SIL tables stage k reads: SIL_{k-1} as its input (k > 0) and
    SIL_k as its target (all but the last stage)."""
    ids = []
    if k > 0:
        ids.append(k - 1)
    if k != cfg.stages - 1:
        ids.append(k)
    return tuple(ids)


def make_table(cfg, j: int, key):
    """SIL_j: kappa * U(0, 1), shape (d_model, vocab) in float32 (paper
    Eq. 1), the same from ``key`` whichever stage or chip makes it."""
    return cfg.kappa * jax.random.uniform(
        jax.random.fold_in(key, j), (cfg.d_model, cfg.vocab_size),
        jnp.float32)


def make_tables(cfg, k: int, key):
    """{j: SIL_j} for the tables stage k reads.  Jit it."""
    return {j: make_table(cfg, j, key) for j in table_ids(cfg, k)}


# --------------------------------------------------------------------------
# the plain reference's arithmetic
# --------------------------------------------------------------------------

def _f8(x):
    """x rounded to float8 e4m3 under a per-tensor scale, back in float32."""
    amax = jnp.max(jnp.abs(x))
    s = jnp.where(amax > 0, amax / 448.0, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def make_dot(precision: str):
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


def adamw(cfg, p, g, m, v, count):
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


def leaf_norms(tree) -> Dict[str, jax.Array]:
    """{name: L2 norm} of each leaf; stacked layer leaves give one norm per
    layer ("layer3/attn/wq/w").  Used on both sides of the comparison."""
    out = {}
    for path, leaf in flat_paths(tree):
        name = "/".join(path[2:] if path[0] == "groups" else path)
        if path[0] == "groups":
            norms = jnp.sqrt(jnp.sum(jnp.square(leaf.astype(jnp.float32)),
                                     axis=tuple(range(1, leaf.ndim))))
            for i in range(leaf.shape[0]):
                out[f"layer{i}/{name}"] = norms[i]
        else:
            out[name] = jnp.sqrt(jnp.sum(jnp.square(leaf.astype(jnp.float32))))
    return out


# --------------------------------------------------------------------------
# readings of the program's state and compiled step
# --------------------------------------------------------------------------

def _find_first_moment(state):
    """AdamW's first-moment tree inside the optimizer state, wherever a
    wrapper nests it."""
    if isinstance(state, dict):
        if "m" in state and "v" in state:
            return state["m"]
        for v in state.values():
            found = _find_first_moment(v)
            if found is not None:
                return found
    return None


def first_grad_norms(opt_state, b1: float):
    """Per-leaf norms of the first gradient as AdamW received it: its first
    moment after one step over 1 - b1."""
    m = _find_first_moment(opt_state)
    if m is None:
        raise RuntimeError("no AdamW first moment in the optimizer state")
    return jax.jit(lambda m_: {n: v / (1 - b1) for n, v in
                               leaf_norms(m_).items()})(m)


def _jitted_of(call):
    """The jitted program behind a step builder's closure, for its
    ``memory_analysis``; None where the closure holds none."""
    for cell in getattr(call, "__closure__", None) or ():
        fn = cell.cell_contents
        if hasattr(fn, "lower") and hasattr(fn, "trace"):
            return fn
    return None


def temp_bytes(call, args) -> int:
    """``temp_size_in_bytes`` of the step builder's own jitted program,
    already compiled, lowered again at ``args`` (arrays or shape structs
    with their shardings).  A builder that hides its program is an error:
    the metric has no other source."""
    jitted = _jitted_of(call)
    if jitted is None:
        raise RuntimeError("the step builder's closure holds no jitted "
                           "program to read memory_analysis from")
    mem = jitted.lower(*args).compile().memory_analysis()
    if mem is None:
        raise RuntimeError("the backend gives no memory_analysis")
    return int(mem.temp_size_in_bytes)


def host_readings(losses, grads, change) -> dict:
    """The three raw readings of one side of the comparison, on the host:
    each step's loss, and the per-leaf norms of the first gradient and of
    the parameters' change."""
    losses, grads, change = jax.device_get((list(losses), grads, change))
    return {"losses": [float(x) for x in losses],
            "grad": {n: float(v) for n, v in grads.items()},
            "change": {n: float(v) for n, v in change.items()}}
