"""The benchmark's own model arithmetic: configurations, the stage's
parameter layout and weights, its SIL tables, and the FLOP and byte counts
of the step and of each kernel, all computed from shapes.

Nothing here imports the program.  A configuration file holds the published
``config.json`` keys under their published names; its ``keys`` object maps
the names used here onto them, so that a family with other key names needs
only a file of its own.  A published key that the program cannot run as
published keeps its number; ``departs`` gives the one the run takes.
"""
from __future__ import annotations

import json
from dataclasses import dataclass
from typing import Dict, List, Tuple

# the generic names a configuration file's ``keys`` object maps
GENERIC = ("n_layers", "d_model", "n_heads", "n_kv_heads", "head_dim", "d_ff",
           "vocab_size", "qkv_bias", "rope_theta", "rope_fraction", "norm_eps",
           "tie_embeddings", "rope_pairs")


@dataclass(frozen=True)
class ModelCfg:
    name: str
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab_size: int
    qkv_bias: bool
    rope_theta: float
    rope_fraction: float
    norm_eps: float
    tie_embeddings: bool
    rope_pairs: str          # "adjacent" (x[0::2], x[1::2]) or "halves"
    stages: int              # K stages, one per chip
    precision: str           # the program's precision preset
    optimizer: str
    lr: float
    b1: float
    b2: float
    eps: float
    weight_decay: float
    kappa: float             # SIL tables are kappa * U(0, 1) (paper Eq. 1)
    init_std: float

    @property
    def vocab_padded(self) -> int:
        """Embedding rows: the vocabulary padded to a multiple of 128."""
        return -(-self.vocab_size // 128) * 128

    @property
    def rope_dim(self) -> int:
        r = int(self.head_dim * self.rope_fraction)
        return max(2, r - r % 2)

    def stage_bounds(self) -> List[Tuple[int, int]]:
        """Layer ranges [l0, l1) of the K stages: the uniform contiguous cut."""
        base, rem = divmod(self.n_layers, self.stages)
        out, s = [], 0
        for k in range(self.stages):
            e = s + base + (1 if k < rem else 0)
            out.append((s, e))
            s = e
        return out

    def stage_layers(self, k: int) -> int:
        l0, l1 = self.stage_bounds()[k]
        return l1 - l0

    def is_last(self, k: int) -> bool:
        return k == self.stages - 1

    def layer_matmul_params(self) -> int:
        """Weights of one layer that enter a matmul (biases and norms not)."""
        d, hd = self.d_model, self.head_dim
        attn = d * hd * (self.n_heads + 2 * self.n_kv_heads) \
            + self.n_heads * hd * d
        return attn + 3 * d * self.d_ff          # SwiGLU: gate, up, down


def load_config(path: str) -> ModelCfg:
    with open(path) as f:
        raw = json.load(f)
    keys = raw["keys"]
    missing = [g for g in GENERIC if g not in keys]
    if missing:
        raise ValueError(f"{path}: 'keys' lacks {missing}")
    # a published key the program cannot run as published: the run and the
    # reference take ``run_at``, and the published number stays in the file
    run_at = {k: d["run_at"] for k, d in raw.get("departs", {}).items()}
    vals = {g: run_at.get(keys[g], raw[keys[g]]) for g in GENERIC}
    if vals["head_dim"] is None:
        vals["head_dim"] = vals["d_model"] // vals["n_heads"]
    tr = raw["training"]
    return ModelCfg(name=raw["name"], stages=raw["deployment"]["stages"],
                    precision=tr["precision"], optimizer=tr["optimizer"],
                    lr=tr["lr"], b1=tr["b1"], b2=tr["b2"], eps=tr["eps"],
                    weight_decay=tr["weight_decay"], kappa=tr["kappa"],
                    init_std=tr["init_std"], **vals)


# --------------------------------------------------------------------------
# parameter layout and weights (the pytree the program's stage step takes)
# --------------------------------------------------------------------------

def stage_shapes(cfg: ModelCfg, k: int) -> Dict:
    """Nested dict of shapes of stage k's parameters.  Layers are stacked on
    a leading axis under ``groups/slot_0``; stage 0 owns the embedding, the
    last stage the final norm and the unembedding (a frozen copy of the
    embedding, ``tied_unembed``, when embeddings are tied)."""
    n, d, hd, ff = cfg.stage_layers(k), cfg.d_model, cfg.head_dim, cfg.d_ff
    h, kv = cfg.n_heads, cfg.n_kv_heads

    def dense(d_in, d_out, bias):
        p = {"w": (n, d_in, d_out)}
        if bias:
            p["b"] = (n, d_out)
        return p
    slot = {
        "norm1": {"scale": (n, d)},
        "attn": {"wq": dense(d, h * hd, cfg.qkv_bias),
                 "wk": dense(d, kv * hd, cfg.qkv_bias),
                 "wv": dense(d, kv * hd, cfg.qkv_bias),
                 "wo": dense(h * hd, d, False)},
        "norm2": {"scale": (n, d)},
        "mlp": {"wg": dense(d, ff, False), "wu": dense(d, ff, False),
                "wd": dense(ff, d, False)},
    }
    out = {"groups": {"slot_0": slot}}
    if k == 0:
        out["tok_embed"] = (cfg.vocab_padded, d)
    if cfg.is_last(k):
        out["final_norm"] = {"scale": (d,)}
        if not cfg.tie_embeddings:
            out["unembed"] = (d, cfg.vocab_padded)
        elif k != 0:
            out["tied_unembed"] = (cfg.vocab_padded, d)
    return out


FROZEN = ("tied_unembed",)


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


def _set(tree, path, value):
    for key in path[:-1]:
        tree = tree.setdefault(key, {})
    tree[path[-1]] = value


def init_stage(cfg: ModelCfg, k: int, key):
    """Stage k's float32 parameters from ``key``: weights and embeddings
    N(0, init_std), biases 0, norm scales 1 (the published initialiser of
    these families).  Jit it: one program makes every leaf on the device."""
    import jax
    import jax.numpy as jnp
    leaves = flat_paths(stage_shapes(cfg, k))
    keys = jax.random.split(key, len(leaves))
    out: Dict = {}
    for (path, shape), lk in zip(leaves, keys):
        if path[-1] == "scale":
            v = jnp.ones(shape, jnp.float32)
        elif path[-1] == "b":
            v = jnp.zeros(shape, jnp.float32)
        else:
            v = cfg.init_std * jax.random.normal(lk, shape, jnp.float32)
        _set(out, path, v)
    return out


def table_ids(cfg: ModelCfg, k: int) -> Tuple[int, ...]:
    """The SIL tables stage k reads: SIL_{k-1} as its input (k > 0) and
    SIL_k as its target (all but the last stage)."""
    ids = []
    if k > 0:
        ids.append(k - 1)
    if not cfg.is_last(k):
        ids.append(k)
    return tuple(ids)


def make_tables(cfg: ModelCfg, k: int, key):
    """{j: SIL_j} for the tables stage k reads: kappa * U(0, 1), shape
    (d_model, vocab) in float32 (paper Eq. 1).  Jit it."""
    import jax
    import jax.numpy as jnp
    return {j: cfg.kappa * jax.random.uniform(
        jax.random.fold_in(key, j), (cfg.d_model, cfg.vocab_size),
        jnp.float32) for j in table_ids(cfg, k)}


def seed_keys(seed: int):
    """(params key, tables key) from a seed of any size: the seed is spread
    over two 32-bit words, so seeds past 2**32 stay distinct."""
    import jax
    import numpy as np
    words = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
    root = jax.random.wrap_key_data(words, impl="threefry2x32")
    return jax.random.fold_in(root, 1), jax.random.fold_in(root, 2)


# --------------------------------------------------------------------------
# operation and byte counts
# --------------------------------------------------------------------------

def step_flops(cfg: ModelCfg, k: int, batch: int, seq: int) -> Dict[str, int]:
    """FLOPs one stage-k step requires: forward and backward matmuls of the
    stage's layers (6 per weight per token), causal attention counted as the
    algorithm needs it (half of S^2; forward 2, backward 4 B*H*S^2*hd per
    layer), and for the last stage the unembedding forward and its input
    gradient only (4 per weight per token: it is frozen).  Recomputation
    for memory is not counted; gathers and elementwise work are not."""
    n = cfg.stage_layers(k)
    tokens = batch * seq
    out = {
        "layers": 6 * tokens * cfg.layer_matmul_params() * n,
        "attention": 6 * batch * cfg.n_heads * seq * seq * cfg.head_dim * n,
        "unembed": 4 * tokens * cfg.d_model * cfg.vocab_size
        if cfg.is_last(k) else 0,
    }
    out["total"] = sum(out.values())
    return out


def flash_forward_cost(cfg: ModelCfg, batch: int, seq: int,
                       itemsize: int = 2) -> Tuple[int, int]:
    """(FLOPs, bytes) one causal flash-attention forward call needs: the
    unmasked half of QK^T and PV, and Q, K, V read once and O written once."""
    flops = 2 * batch * cfg.n_heads * seq * seq * cfg.head_dim
    elems = batch * seq * cfg.head_dim * (2 * cfg.n_heads + 2 * cfg.n_kv_heads)
    return flops, elems * itemsize


def sil_mse_cost(cfg: ModelCfg, batch: int, seq: int,
                 act_itemsize: int = 2) -> Tuple[int, int]:
    """(FLOPs, bytes) one SIL-MSE kernel call needs: the activations read,
    one float32 table row gathered per token, the float32 gradient written
    and the labels read; a subtract, square, add and scale per element."""
    t, d = batch * seq, cfg.d_model
    return 4 * t * d, t * d * (act_itemsize + 4 + 4) + t * 4
