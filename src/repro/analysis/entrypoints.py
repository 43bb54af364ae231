"""The registered hot-path entry points the trace lint inspects.

Each builder constructs ONE production entry point — the same step builders
``phases`` / ``dist`` / ``serve`` use, on the same tiny scenario worlds the
conformance oracles use (``verify.scenarios``) — plus concrete example args,
and returns a ``TraceTarget``.  Nothing is compiled or executed; the args
exist only to drive ``jax.make_jaxpr``.

Everything is built under ``runtime.assume_donation()``: the CPU hosts that
run the analyzer can't *execute* donation, but the jitted steps read
``donate_argnums`` at wrap time, and tracing only needs the requested masks
to land in the jaxpr's jit params.  That env contract (REPRO_ASSUME_DONATION)
is exactly what makes the donation-coverage rule meaningful off-TPU.

Arch routing: MLP configs (paper_mlp) get the MLP epoch steps; LM configs
get the PartitionPlan stage steps and the serving engine steps.  The SIL
lookup+loss kernel entry exists for both.

These targets double as the repro.obs instrumentation proof: the builders
go through the instrumented classes (``Engine``, the backends the
``Trainer``/``StageExecutor`` drive), so the trace lint failing clean on
``train/mlp_guarded_epoch`` / ``train/lm_parallel_stage_step`` /
``serve/decode_chunk`` certifies that metrics/span collection lives
entirely OUTSIDE the jitted steps — zero host callbacks added
(tests/test_obs.py also pins the jaxprs byte-identical).
"""
from __future__ import annotations

from typing import Callable, Dict, List

import jax
import jax.numpy as jnp

from repro import runtime
from repro.analysis.core import AnalysisContext
from repro.analysis.trace import TraceArtifact, TraceTarget, trace
from repro.configs import get
from repro.core import losses
from repro.core import sil as sil_lib
from repro.models.mlp import MLPConfig
from repro.train.backends import (LMBackend, MLPBackend, make_optimizer_for,
                                  scanned_epoch_fn)
from repro.verify import scenarios


def _mlp_world(ctx: AnalysisContext):
    cfg = get(ctx.arch, smoke=True)
    _, data, spec = scenarios.tiny_mlp(
        n_stages=3, n_train=256, n_test=64, batch_size=64,
        sizes=cfg.sizes, precision=ctx.precision)
    from repro.models import mlp as MLP
    be = MLPBackend(cfg, data, spec)
    params = MLP.init_params(cfg, jax.random.PRNGKey(0))
    sps = be.split(params)
    sils = be.make_sils(jax.random.PRNGKey(1), spec.kappa)
    return be, spec, sps, sils


def _mlp_targets(ctx: AnalysisContext) -> List[TraceTarget]:
    be, spec, sps, sils = _mlp_world(ctx)
    batches = be.epoch_arrays(0, shuffle=False)
    opt = make_optimizer_for(spec.stage(0), spec)
    # the NaN/inf-guarded variant (repro.resilience): skip-and-count must
    # stay on-device — the trace lint proves the guard adds no host callback
    from repro.optim import step_guard
    gopt = step_guard(make_optimizer_for(spec.stage(0), spec))
    entries = (
        ("train/mlp_sil_epoch", be.build_sil_step(0, opt, sils[0]), sps[0],
         opt),
        ("train/mlp_parallel_epoch", be.build_parallel_step(1, opt, sils),
         sps[1], opt),
        ("train/mlp_guarded_epoch", be.build_sil_step(0, gopt, sils[0]),
         sps[0], gopt),
    )
    return [TraceTarget(name=name, fn=scanned_epoch_fn(step),
                        args=(p, o.init(p), batches), donate=(0, 1),
                        policy=ctx.precision, state_map=((0, 0), (1, 1)),
                        tags=("train", "mlp"))
            for name, step, p, o in entries]


def _lm_train_targets(ctx: AnalysisContext) -> List[TraceTarget]:
    cfg, plan, batch_fn, spec, params = scenarios.tiny_lm(
        ctx.arch, n_stages=2, precision=ctx.precision)
    be = LMBackend(cfg, plan, batch_fn, spec)
    sps = be.split(params)
    sils = be.make_sils(jax.random.PRNGKey(1), spec.kappa)
    batch = batch_fn(0)
    opt = make_optimizer_for(spec.stage(0), spec)
    st0 = opt.init(be.trainable(sps[0]))
    step0 = be.build_stage_step(0, opt, sils[0])
    st1 = opt.init(be.trainable(sps[1]))
    # n_stages=2 -> stage 1 is the last stage: CE head, sil_target=None
    step1 = be.build_parallel_stage_step(1, opt, sils[0], None)
    # the searched-cut variant: the same step builder over a repro.plan
    # auto partition — the lint rules must hold for searched bounds too
    # (the cut changes which groups each stage's step closes over)
    from repro.core import partition
    aplan = partition.make_plan(cfg, 2, strategy="auto")
    abe = LMBackend(cfg, aplan, batch_fn, spec)
    asps = abe.split(params)
    asils = abe.make_sils(jax.random.PRNGKey(1), spec.kappa)
    ast = opt.init(abe.trainable(asps[1]))
    astep = abe.build_parallel_stage_step(1, opt, asils[0], None)
    return [
        TraceTarget(name="train/lm_stage_step", fn=step0,
                    args=(sps[0], st0, batch, batch["labels"]),
                    donate=(0, 1), policy=ctx.precision,
                    state_map=((0, 0), (1, 1)), tags=("train", "lm")),
        TraceTarget(name="train/lm_parallel_stage_step", fn=step1,
                    args=(sps[1], st1, batch["labels"]),
                    donate=(0, 1), policy=ctx.precision,
                    state_map=((0, 0), (1, 1)), tags=("train", "lm")),
        TraceTarget(name="train/lm_auto_parallel_stage_step", fn=astep,
                    args=(asps[1], ast, batch["labels"]),
                    donate=(0, 1), policy=ctx.precision,
                    state_map=((0, 0), (1, 1)), tags=("train", "lm", "plan")),
    ]


def _serve_targets(ctx: AnalysisContext) -> List[TraceTarget]:
    from repro.serve.engine import Engine
    cfg = get(ctx.arch, smoke=True)
    eng = Engine(cfg, key=jax.random.PRNGKey(0), max_slots=4,
                 precision=ctx.precision)
    cfg = eng.cfg
    b, plen, new = 2, 8, 8
    extra = cfg.vision_tokens if cfg.frontend == "vision" else 0
    pool = eng._pool_for(plen + new + extra)
    cache_len = pool.cache_len
    n_slots = eng.max_slots
    batch = {"tokens": jnp.zeros((b, plen), jnp.int32)}
    if cfg.enc_dec:
        batch["frames"] = jnp.zeros((b, cfg.enc_seq, cfg.d_model),
                                    jnp.float32)
    if cfg.frontend == "vision":
        batch["image_embeds"] = jnp.zeros((b, cfg.vision_tokens, cfg.d_model),
                                          jnp.float32)
    tok = jnp.zeros((n_slots,), jnp.int32)
    pos = jnp.zeros((n_slots,), jnp.int32)
    keys = jnp.zeros((n_slots, 2), jnp.uint32)
    temps = jnp.zeros((n_slots,), jnp.float32)
    tks = jnp.zeros((n_slots,), jnp.int32)
    tps = jnp.ones((n_slots,), jnp.float32)
    admit = eng._admit_step(batch["tokens"].shape, cache_len, "greedy")
    admit_args = (eng.params, batch, pool.cache, tok, pos, keys, temps, tks,
                  tps, jnp.asarray([0, 1], jnp.int32),
                  jnp.zeros((b,), jnp.uint32), jnp.zeros((b,), jnp.float32),
                  jnp.zeros((b,), jnp.int32), jnp.ones((b,), jnp.float32))
    chunk = eng._decode_chunk(4, "greedy")
    chunk_args = (eng.params, pool.cache, tok, pos, keys, temps, tks, tps)
    return [
        TraceTarget(name="serve/prefill_admit", fn=admit, args=admit_args,
                    donate=tuple(range(2, 9)), policy=ctx.precision,
                    state_map=tuple((i + 2, i) for i in range(7)),
                    tags=("serve",)),
        TraceTarget(name="serve/decode_chunk", fn=chunk, args=chunk_args,
                    donate=(1, 2, 3, 4), policy=ctx.precision,
                    state_map=((1, 0), (2, 1), (3, 2), (4, 3)),
                    tags=("serve",)),
    ]


def _sil_target(ctx: AnalysisContext) -> List[TraceTarget]:
    cfg = get(ctx.arch, smoke=True)
    if isinstance(cfg, MLPConfig):
        d, m = cfg.sizes[cfg.cut], cfg.n_classes
        h = jnp.zeros((64, d), _compute_dtype(ctx))
        labels = jnp.zeros((64,), jnp.int32)
    else:
        d, m = cfg.d_model, cfg.vocab_size
        h = jnp.zeros((2, 16, d), _compute_dtype(ctx))
        labels = jnp.zeros((2, 16), jnp.int32)
    sil = sil_lib.make_sil(jax.random.PRNGKey(0), d, m, kappa=1.0)

    @jax.jit
    def lookup_loss(sil, h, labels):
        return losses.sil_stage_loss(h, sil, labels), \
            sil_lib.sil_lookup(sil, labels)

    return [TraceTarget(name="sil/lookup_loss", fn=lookup_loss,
                        args=(sil, h, labels), donate=(),
                        policy=ctx.precision, tags=("sil",))]


def _compute_dtype(ctx: AnalysisContext):
    from repro.precision import get_policy
    return get_policy(ctx.precision).compute_jnp


_BUILDERS: Dict[str, Callable[[AnalysisContext], List[TraceTarget]]] = {
    "mlp": _mlp_targets,
    "lm_train": _lm_train_targets,
    "serve": _serve_targets,
    "sil": _sil_target,
}


def build_targets(ctx: AnalysisContext) -> List[TraceTarget]:
    """All entry points applicable to ctx.arch (built under donation)."""
    cfg = get(ctx.arch, smoke=True)
    groups = ["mlp", "sil"] if isinstance(cfg, MLPConfig) \
        else ["lm_train", "serve", "sil"]
    out = []
    with runtime.assume_donation():
        for g in groups:
            out.extend(_BUILDERS[g](ctx))
    return out


def cache_key(ctx: AnalysisContext) -> str:
    """ctx.cache key for the traced artifacts (fixture tests seed this)."""
    return f"artifacts:{ctx.arch}:{ctx.precision}"


def artifacts(ctx: AnalysisContext) -> Dict[str, TraceArtifact]:
    """Traced artifacts for ctx.arch, built+traced once per context."""
    key = cache_key(ctx)
    if key not in ctx.cache:
        with runtime.assume_donation():
            arts = {t.name: trace(t) for t in build_targets(ctx)}
        ctx.cache[key] = arts
    return ctx.cache[key]
