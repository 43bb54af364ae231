"""The system under test: one stage of a stage-per-chip SIL deployment,
driven through the program's public step builders as ``dist.StageExecutor``
drives it in ``ParallelSilPhase``.

Stage 0 runs ``LMBackend.build_stage_step(0, opt, SIL_0)`` fed real token
batches; a stage k > 0 runs ``build_parallel_stage_step(k, opt, SIL_{k-1},
SIL_k or None)`` fed labels only.  The plan is ``make_plan(cfg, K)``, the
optimizer ``make_optimizer_for`` of an AdamW ``StageSpec``, and the
precision preset the configuration's.  The benchmark makes the stage's
parameters, AdamW state and the tables it reads on the device from the
seed; nothing else of the model is built.

This is the only module of the benchmark that imports the program.
"""
from __future__ import annotations

import functools
import time
from typing import List

import jax

from bench import model as bm
from bench import reference
from bench.traffic import Batches, Traffic


def program_config(cfg: bm.ModelCfg):
    from repro.configs.base import ModelConfig
    return ModelConfig(
        name=cfg.name, family="dense", n_layers=cfg.n_layers,
        d_model=cfg.d_model, n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
        d_ff=cfg.d_ff, vocab_size=cfg.vocab_size, head_dim=cfg.head_dim,
        qkv_bias=cfg.qkv_bias, rope_theta=cfg.rope_theta,
        rope_fraction=cfg.rope_fraction, norm="rmsnorm", mlp_type="swiglu",
        tie_embeddings=cfg.tie_embeddings, source=cfg.name)


def _jitted_of(call):
    """The jitted program behind a step builder's closure, for its
    ``memory_analysis``; None where the closure holds none."""
    for cell in getattr(call, "__closure__", None) or ():
        fn = cell.cell_contents
        if hasattr(fn, "lower") and hasattr(fn, "trace"):
            return fn
    return None


class StageRun:
    """Set-up, the timed step and its feed, for one cell and one seed.

    ``half_batch`` plants a fault for the checks of the comparison: the
    step is fed the first half of each batch's rows, so its mean is taken
    over them alone."""

    def __init__(self, cfg: bm.ModelCfg, traffic: Traffic, seed: int, *,
                 half_batch: bool = False):
        from repro.core.partition import make_plan
        from repro.train.backends import LMBackend, make_optimizer_for
        from repro.train.spec import StageSpec, TrainSpec

        self.cfg, self.traffic, self.seed = cfg, traffic, seed
        self.k = k = traffic.stage_index(cfg.stages)
        self.batches = Batches(traffic, cfg.vocab_size, seed)
        self.rows = traffic.batch // 2 if half_batch else traffic.batch

        mc = program_config(cfg)
        plan = make_plan(mc, cfg.stages)
        if tuple(plan.bounds[k]) != tuple(cfg.stage_bounds()[k]):
            raise RuntimeError(f"the program cuts stage {k} as {plan.bounds[k]}"
                               f", the benchmark as {cfg.stage_bounds()[k]}")
        hp = StageSpec(steps=1, lr=cfg.lr, optimizer=cfg.optimizer)
        spec = TrainSpec(n_stages=cfg.stages, kappa=cfg.kappa,
                         precision=cfg.precision, stages=(hp,) * cfg.stages)
        be = LMBackend(mc, plan, None, spec)
        opt = make_optimizer_for(hp, spec)

        self.key_params, key_tables = bm.seed_keys(seed)
        self.params = jax.jit(functools.partial(bm.init_stage, cfg, k))(
            jax.random.fold_in(self.key_params, k))
        self.opt_state = jax.jit(opt.init)(be.trainable(self.params))
        self.tables = jax.jit(functools.partial(bm.make_tables, cfg, k))(
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
        return b if self.k == 0 else b["labels"]

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

    def step(self, i: int):
        return self.dispatch(self.feed(i))

    # -- set-up readings ---------------------------------------------------

    def temp_bytes(self) -> int:
        """``temp_size_in_bytes`` of the compiled timed step: the builder's
        own jitted program, already compiled, lowered again at the timed
        arguments.  A builder that hides its program, or takes other
        arguments, is an error: the metric has no other source."""
        jitted = _jitted_of(self.step_fn)
        if jitted is None:
            raise RuntimeError("the step builder's closure holds no jitted "
                               "program to read memory_analysis from")
        x = jax.device_put(self._inputs(0))
        if self.k == 0:           # jitted(sp, st, batch, labels, mask, SIL_0)
            args = (x, x["labels"], None, self.tables[0])
        else:                     # jitted(sp, st, labels, SIL_{k-1}, SIL_k)
            args = (x, self.tables[self.k - 1], self.tables.get(self.k))
        mem = jitted.lower(self.params, self.opt_state, *args).compile() \
            .memory_analysis()
        if mem is None:
            raise RuntimeError("the backend gives no memory_analysis")
        return int(mem.temp_size_in_bytes)

    def first_steps(self) -> dict:
        """The checked first steps through the timed call and feed: each
        step's loss, the per-leaf norms of the first gradient as AdamW got
        it (its first moment after one step over 1 - b1), and the per-leaf
        norms of the parameters' change over all of them."""
        n = self.traffic.check_steps
        grads = None
        for i in range(n):
            self.step(i)
            if i == 0:
                grads = _first_grad_norms(self.opt_state, self.cfg.b1)
        change = reference.change_norms(self.cfg, self.k, self.params,
                                        self.key_params)
        losses = jax.device_get(self.losses[:n])
        return {"losses": [float(x) for x in losses],
                "grad": {n_: float(v) for n_, v in
                         jax.device_get(grads).items()},
                "change": {n_: float(v) for n_, v in
                           jax.device_get(change).items()}}

    def free(self):
        """Delete every device array of the run, so the reference that
        follows has the chip to itself."""
        for tree in (self.params, self.opt_state, self.tables, self.losses):
            for leaf in jax.tree_util.tree_leaves(tree):
                if isinstance(leaf, jax.Array) and not leaf.is_deleted():
                    leaf.delete()
        self.params = self.opt_state = self.tables = self.step_fn = None
        self.losses = []


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


def _first_grad_norms(opt_state, b1: float):
    m = _find_first_moment(opt_state)
    if m is None:
        raise RuntimeError("no AdamW first moment in the optimizer state")
    return jax.jit(lambda m_: {n: v / (1 - b1) for n, v in
                               reference.leaf_norms(m_).items()})(m)

