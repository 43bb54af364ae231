"""The dense decoder family: RMSNorm, grouped-query attention with (partial)
rotary embeddings and QKV bias, a SwiGLU MLP (qwen2, chatglm3).

A family module is the whole contract between the harness and one kind of
model; the harness loads ``bench/families/<family>.py`` by path, named by a
configuration file's ``"family"``.  It provides:

* parse: ``parse(raw, path)``, the configuration file to the config object
  (``bench/common.py`` lists the attributes every family's object has);
* state: ``init_stage(cfg, k, key)``, stage k's parameters, and
  ``stage_params(cfg, k, key_params, device)``, the same made on a chip
  (the SIL tables and seed keys are ``bench/common.py``'s);
* the program adapter: ``program(cfg, batch_fn)``, the program's backend,
  optimizers and stage specs; ``step_inputs``/``step_args``, what stage
  k's step is fed and what its jitted program takes; ``StageRun``, one
  stage on one chip;
* the plain reference: ``readings(cfg, k, batches, seed, n, precision)``
  and ``change_norms(cfg, k, params, key_params)``;
* the counts: ``step_flops(cfg, k, batch, seq)``, the FLOPs a step
  requires, and ``kernel_costs(cfg, k, batch, seq)``, the (FLOPs, bytes)
  one call of each kernel the step runs needs, keyed by the kernel's name
  in the trace.

The adapter is where the benchmark imports the program: this module
(``program``, ``StageRun``), ``bench/deployment.py`` (``repro.dist``'s
executor and placement) and ``bench/scopes.py`` (``repro.obs.scopes``).
Stage 0 runs ``LMBackend.build_stage_step(0, opt, SIL_0)`` fed real token
batches; a stage k > 0 runs ``build_parallel_stage_step(k, opt, SIL_{k-1},
SIL_k or None)`` fed labels only, as ``dist.StageExecutor`` drives them in
``ParallelSilPhase``.  The plan is ``make_plan(cfg, K)``, the optimizer
``make_optimizer_for`` of an AdamW ``StageSpec``, the precision preset the
configuration's.

The reference is straightforward ``jax.numpy`` in float32 at
``Precision.HIGHEST``, written from the published descriptions of these
decoders, then either the SIL-MSE loss against SIL_k[:, y] (paper Eq. 1)
or the cross-entropy through the frozen tied unembedding, and the AdamW
update.  It imports nothing of the program and is given nothing the
program made: weights, tables and batches are made again from the seed.
To fit beside nothing else on one chip it runs layer by layer (each layer
rematerialised), attention in blocks of query rows, and the unembedding in
blocks of tokens.  ``precision="fp8"`` is the control: every matmul
operand, forward and backward, rounded to float8 e4m3 with a per-tensor
scale, the step below the bf16 compute that the configurations state.

A configuration file holds the published ``config.json`` keys under their
published names; its ``keys`` object maps the names used here onto them.
A published key that the program cannot run as published keeps its
number; ``departs`` gives the one the run takes.
"""
from __future__ import annotations

import functools
import time
from dataclasses import dataclass
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp

from bench import common
from bench.traffic import Batches, Traffic

# the generic names a configuration file's ``keys`` object maps
GENERIC = ("n_layers", "d_model", "n_heads", "n_kv_heads", "head_dim", "d_ff",
           "vocab_size", "qkv_bias", "rope_theta", "rope_fraction", "norm_eps",
           "tie_embeddings", "rope_pairs")
FROZEN = ("tied_unembed",)
UNBOUNDED = 2 ** 31 - 1   # steps of a stage spec: the window sets the end
Q_BLOCK = 512        # query rows of attention scores live at once
TOKEN_BLOCK = 1024   # tokens of unembedding logits live at once


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


def parse(raw: dict, path: str) -> ModelCfg:
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
# state: parameter layout and weights (the pytree the program's step takes)
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


def init_stage(cfg: ModelCfg, k: int, key):
    """Stage k's float32 parameters from ``key``: weights and embeddings
    N(0, init_std), biases 0, norm scales 1 (the published initialiser of
    these families).  Jit it: one program makes every leaf on the device."""
    leaves = common.flat_paths(stage_shapes(cfg, k))
    keys = jax.random.split(key, len(leaves))
    out: Dict = {}
    for (path, shape), lk in zip(leaves, keys):
        if path[-1] == "scale":
            v = jnp.ones(shape, jnp.float32)
        elif path[-1] == "b":
            v = jnp.zeros(shape, jnp.float32)
        else:
            v = cfg.init_std * jax.random.normal(lk, shape, jnp.float32)
        common.set_path(out, path, v)
    return out


def stage_params(cfg: ModelCfg, k: int, key_params, device=None):
    """Stage k's parameters made on ``device`` (the default device when
    None) in one jitted call."""
    key = jax.random.fold_in(key_params, k)
    if device is not None:
        key = jax.device_put(key, device)
    return jax.jit(functools.partial(init_stage, cfg, k))(key)


def split_frozen(params):
    train = {k: v for k, v in params.items() if k not in FROZEN}
    frozen = {k: v for k, v in params.items() if k in FROZEN}
    return train, frozen


# --------------------------------------------------------------------------
# the program adapter
# --------------------------------------------------------------------------

def program_config(cfg: ModelCfg):
    from repro.configs.base import ModelConfig
    return ModelConfig(
        name=cfg.name, family="dense", n_layers=cfg.n_layers,
        d_model=cfg.d_model, n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
        d_ff=cfg.d_ff, vocab_size=cfg.vocab_size, head_dim=cfg.head_dim,
        qkv_bias=cfg.qkv_bias, rope_theta=cfg.rope_theta,
        rope_fraction=cfg.rope_fraction, norm="rmsnorm", mlp_type="swiglu",
        tie_embeddings=cfg.tie_embeddings, source=cfg.name)


def program(cfg: ModelCfg, batch_fn=None):
    """(the program's ``LMBackend``, an optimizer and a ``StageSpec`` per
    stage) for the configuration: AdamW at its lr, its precision preset,
    the plan ``make_plan`` cuts, which has to be the benchmark's."""
    from repro.core.partition import make_plan
    from repro.train.backends import LMBackend, make_optimizer_for
    from repro.train.spec import StageSpec, TrainSpec

    mc = program_config(cfg)
    plan = make_plan(mc, cfg.stages)
    for k in range(cfg.stages):
        if tuple(plan.bounds[k]) != tuple(cfg.stage_bounds()[k]):
            raise RuntimeError(f"the program cuts stage {k} as "
                               f"{plan.bounds[k]}, the benchmark as "
                               f"{cfg.stage_bounds()[k]}")
    hp = StageSpec(steps=UNBOUNDED, lr=cfg.lr, optimizer=cfg.optimizer)
    spec = TrainSpec(n_stages=cfg.stages, kappa=cfg.kappa,
                     precision=cfg.precision, stages=(hp,) * cfg.stages)
    be = LMBackend(mc, plan, batch_fn, spec)
    opts = [make_optimizer_for(hp, spec) for _ in range(cfg.stages)]
    return be, opts, [hp] * cfg.stages


def step_inputs(k: int, batch: dict):
    """What stage k's step is fed of a host batch: stage 0 the batch, a
    later stage its labels only."""
    return batch if k == 0 else batch["labels"]


def step_args(k: int, params, opt_state, x, tables) -> tuple:
    """The arguments of stage k's jitted program, given its inputs ``x``:
    (sp, st, batch, labels, mask, SIL_0) for stage 0, (sp, st, labels,
    SIL_{k-1}, SIL_k or None) for a later stage."""
    if k == 0:
        return (params, opt_state, x, x["labels"], None, tables[0])
    return (params, opt_state, x, tables[k - 1], tables.get(k))


class StageRun:
    """Set-up, the timed step and its feed, for one stage on one chip and
    one seed.

    ``half_batch`` plants a fault for the checks of the comparison: the
    step is fed the first half of each batch's rows, so its mean is taken
    over them alone."""

    def __init__(self, cfg: ModelCfg, traffic: Traffic, seed: int, *,
                 half_batch: bool = False):
        self.cfg, self.traffic, self.seed = cfg, traffic, seed
        (self.k,) = traffic.stages(cfg.stages)
        k = self.k
        self.stages = [k]
        self.devices = jax.devices()[:1]
        self.batches = Batches(traffic, cfg.vocab_size, seed)
        self.rows = traffic.batch // 2 if half_batch else traffic.batch

        be, opts, _ = program(cfg)
        opt = opts[k]
        self.key_params, key_tables = common.seed_keys(seed)
        self.params = stage_params(cfg, k, self.key_params)
        self.opt_state = jax.jit(opt.init)(be.trainable(self.params))
        self.tables = jax.jit(functools.partial(common.make_tables, cfg, k))(
            key_tables)
        self.step_fn = (be.build_stage_step(0, opt, self.tables[0]) if k == 0
                        else be.build_parallel_stage_step(
                            k, opt, self.tables[k - 1], self.tables.get(k)))
        self.losses: List = []
        self.input_s: List[float] = []

    # -- the timed path ----------------------------------------------------

    def _inputs(self, i: int):
        b = self.batches.host(i)
        if self.rows != self.traffic.batch:
            b = {n: a[:self.rows] for n, a in b.items()}
        return step_inputs(self.k, b)

    def feed(self, i: int):
        """Step i's inputs, built on the host and put on the device."""
        t0 = time.perf_counter()
        out = jax.device_put(self._inputs(i))
        self.input_s.append(time.perf_counter() - t0)
        return out

    def dispatch(self, x):
        if self.k == 0:
            self.params, self.opt_state, loss = self.step_fn(
                self.params, self.opt_state, x, x["labels"])
        else:
            self.params, self.opt_state, loss = self.step_fn(
                self.params, self.opt_state, x)
        self.losses.append(loss)
        return loss

    def wait(self):
        """Block until the step before the last one dispatched is done, so
        two steps at most are in flight."""
        self.losses[-2].block_until_ready()

    def drain(self):
        jax.block_until_ready((self.params, self.opt_state, self.losses[-1]))

    def window_losses(self, n_check: int) -> List[float]:
        return [float(v) for v in jax.device_get(self.losses[n_check:])]

    def step(self, i: int):
        return self.dispatch(self.feed(i))

    # -- set-up readings ---------------------------------------------------

    def temp_bytes(self) -> List[int]:
        """``temp_size_in_bytes`` of the compiled timed step, lowered again
        at the timed arguments (``common.temp_bytes``), for the one chip."""
        x = jax.device_put(self._inputs(0))
        return [common.temp_bytes(self.step_fn, step_args(
            self.k, self.params, self.opt_state, x, self.tables))]

    def first_steps(self) -> Dict[int, dict]:
        """{stage: readings} of the checked first steps through the timed
        call and feed: each step's loss, the per-leaf norms of the first
        gradient as AdamW got it, and of the parameters' change over all
        of them."""
        n = self.traffic.check_steps
        grads = None
        for i in range(n):
            self.step(i)
            if i == 0:
                grads = common.first_grad_norms(self.opt_state, self.cfg.b1)
        change = change_norms(self.cfg, self.k, self.params, self.key_params)
        return {self.k: common.host_readings(self.losses[:n], grads, change)}

    def free(self):
        """Delete every device array of the run, so the reference that
        follows has the chip to itself."""
        for tree in (self.params, self.opt_state, self.tables, self.losses):
            for leaf in jax.tree_util.tree_leaves(tree):
                if isinstance(leaf, jax.Array) and not leaf.is_deleted():
                    leaf.delete()
        self.params = self.opt_state = self.tables = self.step_fn = None
        self.losses = []


# --------------------------------------------------------------------------
# the plain reference
# --------------------------------------------------------------------------

def rope(x, cfg: ModelCfg, seq: int):
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


def layer(x, p, cfg: ModelCfg, dot):
    b, s, d = x.shape
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim

    def proj(y, w):
        out = dot("bsd,de->bse", y, w["w"])
        return out + w["b"] if "b" in w else out
    a = p["attn"]
    y = common.rms_norm(x, p["norm1"]["scale"], cfg.norm_eps)
    q = rope(proj(y, a["wq"]).reshape(b, s, h, hd), cfg, s)
    k = rope(proj(y, a["wk"]).reshape(b, s, kv, hd), cfg, s)
    v = proj(y, a["wv"]).reshape(b, s, kv, hd)
    x = x + proj(attention(q, k, v, dot).reshape(b, s, h * hd), a["wo"])
    m = p["mlp"]
    y = common.rms_norm(x, p["norm2"]["scale"], cfg.norm_eps)
    u = jax.nn.silu(proj(y, m["wg"])) * proj(y, m["wu"])
    return x + proj(u, m["wd"])


def stage_loss(cfg: ModelCfg, k: int, train, frozen, tables, tokens,
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
    x = common.rms_norm(x, train["final_norm"]["scale"], cfg.norm_eps)
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


def make_step(cfg: ModelCfg, k: int, precision: str):
    dot = common.make_dot(precision)

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def step(train, m, v, count, frozen, tables, tokens, labels):
        loss, g = jax.value_and_grad(stage_loss, argnums=2)(
            cfg, k, train, frozen, tables, tokens, labels, dot)
        train, m, v = common.adamw(cfg, train, g, m, v, count)
        return train, m, v, loss, common.leaf_norms(g)
    return step


def readings(cfg: ModelCfg, k: int, batches, seed: int, n_steps: int,
             precision: str = "fp32") -> dict:
    """Three numbers' raw readings of the reference over the first
    ``n_steps`` steps: each step's loss, the per-leaf norms of the first
    gradient, and the per-leaf norms of the parameters' change."""
    kp, kt = common.seed_keys(seed)
    init = jax.jit(functools.partial(init_stage, cfg, k))
    train, frozen = split_frozen(init(jax.random.fold_in(kp, k)))
    tables = jax.jit(functools.partial(common.make_tables, cfg, k))(kt)
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
    out = common.host_readings(losses, grads, change)
    del train, frozen
    return out


def change_norms(cfg: ModelCfg, k: int, params, key_params):
    """Per-leaf norms of (params - their initial values), the initial values
    made again from the key inside the same program."""
    @jax.jit
    def f(p, key):
        p0, _ = split_frozen(init_stage(cfg, k, jax.random.fold_in(key, k)))
        p, _ = split_frozen(p)
        return common.leaf_norms(jax.tree_util.tree_map(
            lambda a, b: a.astype(jnp.float32) - b, p, p0))
    return f(params, key_params)


# --------------------------------------------------------------------------
# the counts: operations and bytes from shapes
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


def kernel_costs(cfg: ModelCfg, k: int, batch: int, seq: int
                 ) -> Dict[str, Tuple[int, int]]:
    """(FLOPs, bytes) a call of each Pallas kernel stage k's step runs
    needs, keyed by the kernel's name in the trace: the flash-attention
    forward in every stage, the SIL-MSE head in all but the last."""
    out = {"flash_attention_tpu": flash_forward_cost(cfg, batch, seq)}
    if not cfg.is_last(k):
        out["sil_mse_fwd_tpu"] = sil_mse_cost(cfg, batch, seq)
    return out
