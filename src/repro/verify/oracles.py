"""The registered equivalence oracles.

Every contract the codebase has historically asserted ad hoc — kernel ==
reference, concurrent == sequential, batched == sequential decode, fused ==
per-token, bf16 ~= fp32, resume+replay == uninterrupted, staged == joined —
lives here as one declarative registration.  Adding a feature with an
equivalence claim means adding one ``@register`` block; the pytest
collector and the ``launch/verify`` CLI pick it up automatically.

Naming: ``group/contract``.  Groups mirror the subsystems: ``kernel``,
``train``, ``serve``, ``precision``, ``checkpoint``, ``paper``.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp

from repro.verify import scenarios
from repro.verify.compare import AccuracyGap, Allclose, Bitwise, TokensEqual
from repro.verify.oracle import Context, register

KEY = jax.random.PRNGKey(0)


# ==========================================================================
# kernels: each Pallas kernel (interpret mode off-TPU) vs its pure-jnp ref
# ==========================================================================

def _fa_shapes(preset: str):
    tiny = [(1, 64, 4, 2, 32, jnp.float32, True, 0),
            (1, 48, 4, 4, 32, jnp.bfloat16, True, 16),
            (1, 40, 2, 2, 32, jnp.float32, False, 0)]
    full = tiny + [(2, 256, 4, 2, 64, jnp.float32, True, 0),
                   (2, 200, 8, 2, 128, jnp.bfloat16, True, 64)]
    return full if preset == "full" else tiny


@register("kernel/flash_attention",
          "Pallas flash attention == naive attention reference "
          "(fp32 + bf16, causal/window variants)",
          Allclose(), tags=("kernel",))
def _flash_attention(ctx: Context):
    from repro.kernels.flash_attention import ref
    from repro.kernels.flash_attention.kernel import flash_attention_tpu
    ref_out, opt_out = {}, {}
    for b, s, h, kv, d, dtype, causal, window in _fa_shapes(ctx.preset):
        ks = jax.random.split(KEY, 3)
        q = jax.random.normal(ks[0], (b, s, h, d), dtype)
        k = jax.random.normal(ks[1], (b, s, kv, d), dtype)
        v = jax.random.normal(ks[2], (b, s, kv, d), dtype)
        name = f"s{s}_{jnp.dtype(dtype).name}_c{int(causal)}_w{window}"
        opt_out[name] = flash_attention_tpu(q, k, v, causal=causal,
                                            window=window)
        ref_out[name] = ref.naive_attention(q, k, v, causal=causal,
                                            window=window)
    return ref_out, opt_out


@register("kernel/flash_attention_grad",
          "Gradients through the Pallas flash attention (custom VJP whose "
          "backward is the chunked reference's VJP) == gradients of the "
          "naive reference",
          Allclose(), tags=("kernel", "train"))
def _flash_attention_grad(ctx: Context):
    from repro.kernels.flash_attention import ref
    from repro.kernels.flash_attention.ops import _flash_pallas
    shapes = [(1, 128, 4, 2, 64, True, 0), (1, 96, 4, 4, 64, True, 32)]
    if ctx.preset == "full":
        shapes.append((2, 256, 8, 2, 128, False, 0))
    ref_out, opt_out = {}, {}
    for b, s, h, kv, d, causal, window in shapes:
        ks = jax.random.split(KEY, 4)
        q = jax.random.normal(ks[0], (b, s, h, d), jnp.float32)
        k = jax.random.normal(ks[1], (b, s, kv, d), jnp.float32)
        v = jax.random.normal(ks[2], (b, s, kv, d), jnp.float32)
        w = jax.random.normal(ks[3], (b, s, h, d), jnp.float32)

        def loss(fn):
            return lambda q, k, v: (fn(q, k, v) * w).sum()
        name = f"s{s}_c{int(causal)}_w{window}"
        opt_out[name] = jax.grad(loss(lambda q, k, v: _flash_pallas(
            q, k, v, causal, window, 64)), (0, 1, 2))(q, k, v)
        ref_out[name] = jax.grad(loss(lambda q, k, v: ref.naive_attention(
            q, k, v, causal=causal, window=window)), (0, 1, 2))(q, k, v)
    return ref_out, opt_out


@register("kernel/decode_attention",
          "Pallas decode attention over a KV cache == reference "
          "(scalar / ragged / ring-full position variants)",
          Allclose(), tags=("kernel", "serve"))
def _decode_attention(ctx: Context):
    from repro.kernels.flash_attention import ref
    from repro.kernels.flash_attention.kernel import decode_attention_tpu
    b, lc, h, kv, d = (2, 64, 8, 2, 64) if ctx.preset == "full" \
        else (2, 32, 4, 2, 32)
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (b, 1, h, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, lc, kv, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, lc, kv, d), jnp.float32)
    ref_out, opt_out = {}, {}
    for name, pos in [("partial", lc // 2),
                      ("ragged", jnp.arange(b, dtype=jnp.int32) + 3),
                      ("ring_full", 2 * lc)]:
        opt_out[name] = decode_attention_tpu(q, k, v, pos, bk=16)
        ref_out[name] = ref.decode_attention(q, k, v, pos)
    return ref_out, opt_out


@register("kernel/selective_scan",
          "Pallas chunked selective scan == reference scan (outputs and "
          "final recurrent state)",
          Allclose(rtol=1e-4, atol=1e-4), tags=("kernel",))
def _selective_scan(ctx: Context):
    from repro.kernels.selective_scan import ref
    from repro.kernels.selective_scan.kernel import selective_scan_tpu
    ba, s, di, n = (2, 128, 64, 16) if ctx.preset == "full" \
        else (2, 64, 32, 8)
    ks = jax.random.split(KEY, 6)
    u = jax.random.normal(ks[0], (ba, s, di), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (ba, s, di)))
    A = -jnp.exp(jax.random.normal(ks[2], (di, n)) * 0.5)
    B = jax.random.normal(ks[3], (ba, s, n))
    C = jax.random.normal(ks[4], (ba, s, n))
    D = jax.random.normal(ks[5], (di,))
    y, h = selective_scan_tpu(u, dt, A, B, C, D, chunk=32, bd=32)
    ey, eh = ref.selective_scan(u, dt, A, B, C, D, chunk=32)
    return {"y": ey, "h": eh}, {"y": y, "h": h}


@register("kernel/sil_mse",
          "Pallas fused SIL-MSE (loss + activation grad) == reference "
          "(fp32 + bf16 activations, fp32 accumulation)",
          Allclose(rtol=5e-2, atol=1e-4), tags=("kernel", "train"))
def _sil_mse(ctx: Context):
    from repro.kernels.sil_mse import ref
    from repro.kernels.sil_mse.kernel import sil_mse_fwd_tpu
    t, d, m = (256, 512, 1000) if ctx.preset == "full" else (64, 60, 47)
    ref_out, opt_out = {}, {}
    for dtype in (jnp.float32, jnp.bfloat16):
        ks = jax.random.split(KEY, 3)
        act = jax.random.normal(ks[0], (t, d), dtype)
        sil = jax.random.uniform(ks[1], (d, m), jnp.float32) * 10
        lab = jax.random.randint(ks[2], (t,), 0, m)
        loss, grad = sil_mse_fwd_tpu(act, sil, lab, bt=32, bd=32)
        name = jnp.dtype(dtype).name
        opt_out[name] = {"loss": loss,
                         "grad": grad.astype(jnp.float32)}
        ref_out[name] = {"loss": ref.sil_mse(act, sil, lab),
                         "grad": ref.sil_mse_grad_act(act, sil, lab)
                         .astype(jnp.float32)}
    return ref_out, opt_out


# ==========================================================================
# train: device-placed concurrent execution vs the sequential phase
# ==========================================================================

@register("train/mlp_dist_vs_sequential",
          "ParallelSilPhase through the dist.StageExecutor (device-placed, "
          "async ticks) == the sequential phase loop, MLP backend",
          Allclose(), tags=("train", "dist"))
def _mlp_dist_vs_sequential(ctx: Context):
    from repro.train import recipes
    n = 3 if ctx.preset == "tiny" else 4
    cfg, data, spec = scenarios.tiny_mlp(
        n_stages=n, epochs=(2,) * n,
        n_train=1024 if ctx.preset == "tiny" else 8192)
    key = jax.random.PRNGKey(0)
    p_seq, _ = recipes.run_mlp_fig5(cfg, data, spec, key, n_stages=n)
    p_con, _ = recipes.run_mlp_fig5(cfg, data, spec, key, n_stages=n,
                                    dist="round_robin")
    return p_seq, p_con


@register("train/lm_dist_vs_sequential",
          "ParallelSilPhase through the dist.StageExecutor == sequential, "
          "LM backend (params and drained loss curves)",
          Allclose(), tags=("train", "dist"), arch_aware=True)
def _lm_dist_vs_sequential(ctx: Context):
    from repro.train import recipes
    steps = 2 if ctx.preset == "tiny" else 4
    cfg, plan, batch_fn, spec, params = scenarios.tiny_lm(
        ctx.arch, steps=steps, n_stages=2)
    key = jax.random.PRNGKey(1)
    p_seq, h_seq = recipes.run_lm_parallel(cfg, plan, params, batch_fn,
                                           spec, key)
    p_con, h_con = recipes.run_lm_parallel(cfg, plan, params, batch_fn,
                                           spec, key, dist="round_robin")
    return ({"params": p_seq, "loss": h_seq.column("loss")},
            {"params": p_con, "loss": h_con.column("loss")})


# ==========================================================================
# serve: every engine optimization is a pure latency change, never tokens
# ==========================================================================

def _serve_world(ctx: Context):
    cfg = scenarios.serve_cfg(ctx.arch)
    params = scenarios.serve_params(cfg)
    lens, news = ((8, 12, 5, 10), (6, 9, 4, 7)) if ctx.preset == "full" \
        else ((8, 5, 10), (5, 4, 6))
    return cfg, params, scenarios.serve_requests(cfg, lens, news)


@register("serve/batched_vs_sequential",
          "Engine continuous batching (slot pool, batched admission) == "
          "one-request-at-a-time prefill+decode, token-identical",
          TokensEqual(), tags=("serve",), arch_aware=True)
def _batched_vs_sequential(ctx: Context):
    from repro.serve import Engine
    cfg, params, reqs = _serve_world(ctx)
    outs = Engine(cfg, params, max_slots=2, decode_block=4).generate(reqs)
    ref = [scenarios.greedy_reference(cfg, params, r) for r in reqs]
    return ref, [c.tokens for c in outs]


@register("serve/fused_chunk_vs_per_token",
          "Fused multi-token decode (lax.scan chunks, sampling folded in) "
          "== per-token decode (decode_block=1), token-identical",
          TokensEqual(), tags=("serve",), arch_aware=True)
def _fused_vs_per_token(ctx: Context):
    from repro.serve import Engine
    cfg, params, reqs = _serve_world(ctx)
    fused = Engine(cfg, params, max_slots=2, decode_block=8).generate(reqs)
    per_tok = Engine(cfg, params, max_slots=2, decode_block=1).generate(reqs)
    return [c.tokens for c in per_tok], [c.tokens for c in fused]


@register("serve/staged_vs_joined",
          "PartitionPlan-staged serving (partitions deployed unjoined) == "
          "serving the joined params, token-identical",
          TokensEqual(), tags=("serve", "dist"), arch_aware=True)
def _staged_vs_joined(ctx: Context):
    from repro.core import partition
    from repro.serve import Engine
    cfg, params, reqs = _serve_world(ctx)
    joined = Engine(cfg, params, max_slots=2, decode_block=4).generate(reqs)
    plan = partition.make_plan(cfg, 2)
    sp = [partition.slice_stage_params(cfg, plan, params, k)
          for k in range(plan.n_stages)]
    staged = Engine(cfg, plan=plan, stage_params=sp, max_slots=2,
                    decode_block=4).generate(reqs)
    return [c.tokens for c in joined], [c.tokens for c in staged]


@register("serve/paged_vs_contiguous",
          "Block-paged cache pool (block tables, shared-prefix reuse, "
          "garbage block) == the contiguous slot pool, token-identical — "
          "flat and sliding-window attention, joined and staged",
          TokensEqual(), tags=("serve",), arch_aware=True)
def _paged_vs_contiguous(ctx: Context):
    import numpy as np

    from repro.core import partition
    from repro.serve import Engine, Request
    cfg, params, reqs = _serve_world(ctx)
    # a shared-prefix pair: same leading 8 tokens (two full 4-token
    # blocks), so the second admission increfs the first one's blocks
    t0 = np.asarray(reqs[0].tokens, np.int32).reshape(-1)
    t1 = np.concatenate([t0[:8],
                         np.asarray(reqs[1].tokens, np.int32).reshape(-1)])
    reqs = list(reqs) + [Request(tokens=t1.tolist(), gen=reqs[1].gen)]
    want, got = [], []

    def run(paged_engine, contiguous_engine):
        want.extend(c.tokens for c in contiguous_engine.generate(reqs))
        got.extend(c.tokens for c in paged_engine.generate(reqs))

    run(Engine(cfg, params, max_slots=2, decode_block=4, paged=True,
               block_size=4),
        Engine(cfg, params, max_slots=2, decode_block=4))
    cfgw = scenarios.serve_cfg(ctx.arch, window=8)
    run(Engine(cfgw, params, max_slots=2, decode_block=4, paged=True,
               block_size=4),
        Engine(cfgw, params, max_slots=2, decode_block=4))
    plan = partition.make_plan(cfg, 2)
    sp = [partition.slice_stage_params(cfg, plan, params, k)
          for k in range(plan.n_stages)]
    run(Engine(cfg, plan=plan, stage_params=sp, max_slots=2, decode_block=4,
               paged=True, block_size=4),
        Engine(cfg, plan=plan, stage_params=sp, max_slots=2,
               decode_block=4))
    return want, got


# ==========================================================================
# precision: bf16 compute under the PrecisionPolicy reaches fp32 accuracy
# ==========================================================================

@register("precision/bf16_vs_fp32_train",
          "Baseline MLP training under the bf16 PrecisionPolicy (bf16 "
          "compute, fp32 accumulate) reaches fp32 test accuracy",
          AccuracyGap(budget=0.01, floor=0.85), tags=("precision", "train"))
def _bf16_vs_fp32(ctx: Context):
    from repro.models import mlp as MLP
    from repro.train import BaselinePhase, MLPBackend, Trainer
    n_train, epochs = (18800, 20) if ctx.preset == "full" else (9400, 15)
    accs = {}
    for prec in (None, "bf16"):
        cfg, data, spec = scenarios.tiny_mlp(
            n_stages=2, epochs=(), sizes=(784, 32, 16, 16, 47),
            n_train=n_train, n_test=940, batch_size=470, lr=0.02,
            precision=prec, baseline_epochs=epochs)
        be = MLPBackend(cfg, data, spec)
        _, hist = Trainer(be, spec).run([BaselinePhase()],
                                        params=MLP.init_params(cfg, KEY))
        accs[prec] = hist.column("acc")[-1]
    return accs[None], accs["bf16"]


# ==========================================================================
# checkpoint: per-stage resume + replay == uninterrupted training
# ==========================================================================

@register("checkpoint/resume_vs_uninterrupted",
          "Stage failure -> restore from its own checkpoint -> replay "
          "lost ticks == the uninterrupted run, bitwise",
          Bitwise(), tags=("checkpoint", "dist", "train"))
def _resume_vs_uninterrupted(ctx: Context):
    from repro.dist import StageExecutor, placement
    from repro.models import mlp as MLP
    from repro.train import MLPBackend
    from repro.train.backends import balanced_bounds, make_optimizer_for
    n_ticks = 3 if ctx.preset == "tiny" else 6
    cfg, data, spec = scenarios.tiny_mlp(n_stages=3,
                                         epochs=(n_ticks,) * 3)
    be = MLPBackend(cfg, data, spec, bounds=balanced_bounds(cfg, 3))
    params = MLP.init_params(cfg, jax.random.PRNGKey(0))
    sils = be.make_sils(jax.random.PRNGKey(3), spec.kappa)
    sp0 = be.split(params)
    hps = [spec.stage(k) for k in range(3)]
    pl = placement.round_robin(3)

    def make_ex(root, ckpt_every):
        opts = [make_optimizer_for(hp, spec) for hp in hps]
        return StageExecutor(be, pl, sp0, sils, opts, hps, shuffle=True,
                             ckpt_dir=root, ckpt_every=ckpt_every)

    # uninterrupted reference
    ref_ex = make_ex(os.path.join(ctx.workdir, "ref"), ckpt_every=0)
    ref_ex.run(n_ticks)
    ref = ref_ex.gather()

    # interrupted run: stage 1 dies after tick 1, resumes from ITS OWN
    # checkpoint, replays — stages 0/2 keep their live state
    root = os.path.join(ctx.workdir, "stages")
    ex = make_ex(root, ckpt_every=1)
    ex.run(1)
    ex.params[1] = jax.tree_util.tree_map(jnp.zeros_like, ex.params[1])
    assert ex.resume_stage(1, step=1) == 1
    ex.run(n_ticks, stages=[1])
    ex.run(n_ticks, stages=[0, 2])
    return ref, ex.gather()


# ==========================================================================
# resilience: faults injected, recovered, and provably invisible
# ==========================================================================

@register("resilience/crash_equivalence",
          "Training under an injected fault schedule (crash, transient, "
          "checkpoint corruption, straggler) self-heals and finishes "
          "bitwise-equal to the fault-free run",
          Bitwise(), tags=("resilience", "dist", "checkpoint", "train"))
def _crash_equivalence(ctx: Context):
    from repro.dist import StageExecutor, placement
    from repro.models import mlp as MLP
    from repro.resilience import (CheckpointCorruption, FakeClock,
                                  FaultSchedule, RetryPolicy, StageCrash,
                                  StragglerDelay, SupervisedExecutor,
                                  TransientError)
    from repro.train import MLPBackend
    from repro.train.backends import balanced_bounds, make_optimizer_for
    n_ticks = 4 if ctx.preset == "tiny" else 6
    cfg, data, spec = scenarios.tiny_mlp(n_stages=2,
                                         epochs=(n_ticks,) * 2,
                                         n_train=512, batch_size=128)
    be = MLPBackend(cfg, data, spec, bounds=balanced_bounds(cfg, 2))
    params = MLP.init_params(cfg, jax.random.PRNGKey(0))
    sils = be.make_sils(jax.random.PRNGKey(3), spec.kappa)
    sp0 = be.split(params)
    hps = [spec.stage(k) for k in range(2)]
    pl = placement.round_robin(2)

    def make_ex(root):
        opts = [make_optimizer_for(hp, spec) for hp in hps]
        return StageExecutor(be, pl, sp0, sils, opts, hps, shuffle=True,
                             ckpt_dir=root)

    ref_ex = make_ex(os.path.join(ctx.workdir, "ref"))
    ref_ex.run(n_ticks)
    ref = ref_ex.gather()

    # one of each recoverable fault kind, at fixed coordinates so the run
    # is replayable without even a seed
    schedule = FaultSchedule(faults=[
        TransientError(stage=0, tick=1, failures=2),
        StageCrash(stage=1, tick=2),
        StragglerDelay(stage=1, tick=3, delay=0.7),
        CheckpointCorruption(stage=0, tick=3, mode="truncate_manifest"),
    ])
    clk = FakeClock()
    ex = make_ex(os.path.join(ctx.workdir, "chaos"))
    sup = SupervisedExecutor(ex, schedule=schedule, clock=clk.monotonic,
                             sleep=clk.sleep, ckpt_every=1,
                             policy=RetryPolicy(max_retries=4), strict=True)
    sup.run(n_ticks)
    assert not sup.unrecovered, sup.report()
    assert len(sup.faults_seen) >= 4, sup.report()
    return ref, ex.gather()


@register("resilience/nan_skip",
          "A NaN/inf-poisoned batch under the step guard == the same run "
          "with the poisoned batch excised, bitwise (skip leaves params "
          "and optimizer state untouched)",
          Bitwise(), tags=("resilience", "train"))
def _nan_skip(ctx: Context):
    from dataclasses import replace

    import numpy as np

    from repro.models import mlp as MLP
    from repro.optim import read_skipped
    from repro.train import MLPBackend
    from repro.train.backends import (balanced_bounds, make_optimizer_for,
                                      scanned_epoch_fn)
    cfg, data, spec = scenarios.tiny_mlp(n_stages=2, epochs=(1, 1),
                                         n_train=512, batch_size=128)
    spec = replace(spec, nan_guard=True)
    be = MLPBackend(cfg, data, spec, bounds=balanced_bounds(cfg, 2))
    params = MLP.init_params(cfg, jax.random.PRNGKey(0))
    sils = be.make_sils(jax.random.PRNGKey(3), spec.kappa)
    p0 = be.split(params)[0]
    opt = make_optimizer_for(spec.stage(0), spec)
    assert opt.name.startswith("guard("), opt.name
    epoch_fn = scanned_epoch_fn(be.build_parallel_step(0, opt, sils,
                                                       accum=1))
    batches = be.epoch_arrays(0, shuffle=False)
    poison_idx = batches[0].shape[0] // 2
    x = np.asarray(batches[0]).copy()
    x[poison_idx, 0, 0] = np.inf          # one bad batch mid-epoch
    poisoned = (jnp.asarray(x),) + tuple(batches[1:])
    excised = tuple(jnp.concatenate([b[:poison_idx], b[poison_idx + 1:]])
                    for b in batches)

    p_ref, o_ref, _ = epoch_fn(p0, opt.init(be.trainable(p0)), excised)
    p_got, o_got, _ = epoch_fn(p0, opt.init(be.trainable(p0)), poisoned)
    assert int(read_skipped(o_got)) == 1, "guard did not skip the bad batch"
    assert int(read_skipped(o_ref)) == 0
    return p_ref, p_got


# ==========================================================================
# plan: the auto-partitioner's searched cut is as trainable as the hand cut
# ==========================================================================

def _plan_policy(ctx: Context):
    # budgets mirror the paper gate's presets: both runs sit on the same
    # (reduced or full) schedule, so the cut is the only variable
    return AccuracyGap(budget=0.05 if ctx.preset == "tiny" else 0.02,
                       floor=0.6)


@register("plan/auto_vs_hand",
          "Fig.-3 SIL training at the repro.plan searched cut matches the "
          "paper's hand-picked cut within the accuracy budget; on an "
          "equal-width MLP every balanced cut ties and the searcher "
          "reproduces the divmod hand bounds exactly",
          _plan_policy, tags=("plan", "train"))
def _plan_auto_vs_hand(ctx: Context):
    from repro import plan as plan_lib
    from repro.configs import get as get_cfg
    from repro.data.images import emnist_like
    from repro.models.mlp import MLPConfig
    from repro.train import recipes
    from repro.train.backends import mlp_default_bounds, mlp_test_accuracy

    # exact-tie determinism: an equal-width stack makes every balanced cut
    # tie at the optimal bottleneck, and the tie-break must reproduce the
    # hand (divmod) bounds bit-for-bit — auto is a drop-in there
    ucfg = MLPConfig(sizes=(32,) * 7, cut=3)
    for k in (1, 2, 3):
        auto_b = plan_lib.auto_mlp_bounds(ucfg, k)
        hand_b = mlp_default_bounds(ucfg, k)
        assert auto_b == hand_b, \
            f"tie-break drifted at K={k}: {auto_b} != {hand_b}"

    # accuracy parity on the paper's (non-uniform) MLP, where the searcher
    # picks its own cut: same data, spec, and key schedule for both runs.
    # The right stage ramps late (lr_right=0.003): ~80 epochs is where the
    # boundary-trained head separates from chance, so the tiny preset uses
    # the paper gate's own tiny schedule rather than a shorter one
    cfg = get_cfg("paper_mlp")
    n_right, n_recovery = (80, 20) if ctx.preset == "tiny" else (160, 10)
    data = emnist_like(n_train=28200, n_test=2820, seed=0, noise=0.5)
    spec = recipes.paper_spec(n_right=n_right, n_baseline=0,
                              n_recovery=n_recovery)
    key = jax.random.PRNGKey(1)
    p_hand, _ = recipes.run_mlp_fig3(cfg, data, spec, key)
    p_auto, _ = recipes.run_mlp_fig3(
        cfg, data, spec, key, bounds=plan_lib.auto_mlp_bounds(cfg, 2))
    return (mlp_test_accuracy(cfg, p_hand, data[2], data[3]),
            mlp_test_accuracy(cfg, p_auto, data[2], data[3]))


# ==========================================================================
# paper: the reproduction gate (EMNIST 6-layer / 2-stage SIL experiment)
# ==========================================================================

def _paper_policy(ctx: Context):
    from repro.verify import paper
    return paper.gap_policy(ctx.preset)


@register("paper/emnist_parity",
          "PNN (paper Fig. 3 schedule, 2 stages, SIL targets) matches "
          "conventional training accuracy on the EMNIST-like task within "
          "the paper's reported budget",
          _paper_policy,
          tags=("paper", "train"))
def _emnist_parity(ctx: Context):
    from repro.verify import paper
    res = paper.run_paper_parity(ctx.preset)
    return res["baseline_acc"], res["pnn_acc"]
