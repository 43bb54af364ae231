"""Production training launcher.

On a slice of 256 or more chips the train step runs sharded over the
production mesh; with fewer devices (one chip, or the CPU with
``JAX_PLATFORMS=cpu``) the same code path runs unsharded on one device.
``--smoke`` picks the reduced configs sized for CPU runs; without it the
published widths run (``chip_smoke.py`` drives qwen2-1.5b that way on one
v5e chip).  ``main(argv)`` returns the run's ``History``.

Usage:
  PYTHONPATH=src python -m repro.launch.train --arch qwen2-1.5b --smoke \
      --steps 20 --batch 8 --seq 128 [--mode pnn --stages 2] [--seq-shard]
      [--dist round_robin --devices 8] [--resume ckpts/run1]

``--stages`` accepts a count (uniform split), ``auto`` (cost-model searched
boundaries via ``repro.plan``, default K=2), or ``auto:K``.  ``--arch
paper_mlp`` runs the paper's EMNIST MLP experiment through the same flags.
"""
from __future__ import annotations

import argparse
import os
import time

import jax
import jax.numpy as jnp

from repro.checkpoint import latest_step, restore_checkpoint, save_checkpoint
from repro.configs import ARCH_NAMES, get
from repro.core import partition
from repro.data.lm import lm_batch_at, lm_batches, synthetic_token_stream
from repro.launch.mesh import make_production_mesh
from repro.launch.sharding import Policy
from repro.launch.steps import (build_train_step, pick_accum,
                                pick_optimizer_name, _shard_x_fn)
from repro.configs.base import InputShape
from repro.models import model as M
from repro.optim import cosine_warmup, make_optimizer
from repro.runtime import enable_compile_cache
from repro.train import StageSpec, TrainSpec, recipes
from repro.train.history import History


def main(argv=None) -> History:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-1.5b",
                    choices=ARCH_NAMES + ["paper_mlp"])
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-sized)")
    ap.add_argument("--steps", type=int, default=20,
                    help="LM: optimizer steps; paper_mlp: epochs per stage")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--mode", default="baseline", choices=["baseline", "pnn"])
    ap.add_argument("--stages", default="2",
                    help="PNN partition count: N (uniform split), 'auto' "
                         "(repro.plan searched boundaries, K=2), or "
                         "'auto:K'")
    ap.add_argument("--seq-shard", action="store_true")
    ap.add_argument("--precision", default=None,
                    choices=["fp32", "bf16", "fp16"],
                    help="precision policy: compute dtype for activations/"
                         "caches, fp32 accumulation, loss scaling + master "
                         "weights under fp16 (default: the arch config's "
                         "dtype)")
    ap.add_argument("--accum", type=int, default=1,
                    help="gradient-accumulation microbatches per step "
                         "(fp32 accumulators inside the jitted step)")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=0,
                    help="per-stage checkpoint cadence in ticks "
                         "(--dist modes; 0 = final only)")
    ap.add_argument("--resume", default=None,
                    help="checkpoint dir to restore params from before "
                         "training (latest step; log lines carry the "
                         "step offset)")
    ap.add_argument("--dist", default="none",
                    choices=["none", "round_robin", "memory"],
                    help="PNN stage placement: run ParallelSilPhase through "
                         "the repro.dist StageExecutor with stages placed "
                         "across devices (requires --mode pnn)")
    ap.add_argument("--devices", type=int, default=0,
                    help="force N host devices (CPU: sets XLA_FLAGS "
                         "--xla_force_host_platform_device_count pre-init) "
                         "and place stages across them")
    args = ap.parse_args(argv)

    if args.devices:
        # must precede every jax backend touch in this process
        from repro.launch.mesh import force_host_device_count
        force_host_device_count(args.devices)
    if args.dist != "none" and args.mode != "pnn":
        raise SystemExit("--dist requires --mode pnn (stage placement only "
                         "exists for partitioned training)")

    enable_compile_cache()
    from repro.plan import parse_stages
    stage_strategy, n_stages = parse_stages(args.stages)

    if args.arch == "paper_mlp":
        return _run_paper_mlp(args, stage_strategy, n_stages)

    cfg = get(args.arch, smoke=args.smoke)
    prec = None
    if args.precision:
        from repro.precision import get_policy
        prec = get_policy(args.precision)
        cfg = prec.apply_to_model(cfg)
        print(f"precision={prec.name}: compute={cfg.dtype} "
              f"params={cfg.param_dtype} accum=float32 "
              f"loss_scale={'dynamic' if prec.dynamic_scale else prec.loss_scale}")
    n_dev = len(jax.devices())
    use_mesh = n_dev >= 256
    print(f"arch={cfg.name} devices={n_dev} "
          f"mesh={'production 16x16' if use_mesh else 'single-device'}")

    stream = synthetic_token_stream(1_000_000, cfg.vocab_size, seed=0)
    it = lm_batches(stream, args.batch, args.seq, seed=0)

    def next_batch(_):
        return {k: jnp.asarray(v) for k, v in next(it).items()}

    # built on the device, kept on the host: the trainer puts on the device
    # only what each phase trains
    params = jax.device_get(  # repro: allow-host-sync
        M.init_params(cfg, jax.random.PRNGKey(0)))
    step0 = 0
    if args.resume:
        step0 = latest_step(args.resume) or 0
        params = restore_checkpoint(args.resume, {"params": params})["params"]
        params = jax.tree_util.tree_map(jnp.asarray, params)
        print(f"resumed params from {args.resume} @ step {step0} "
              f"(training continues to step {step0 + args.steps})")

    if args.mode == "pnn" and args.dist != "none":
        # repro.dist: every stage trains simultaneously, each pinned to its
        # own device (Fig. 5 actually executed; see src/repro/dist/)
        from repro.launch.mesh import stage_devices
        devs = stage_devices(args.devices or min(n_stages, n_dev))
        plan = partition.make_plan(cfg, n_stages, strategy=stage_strategy)
        _print_plan(stage_strategy, plan)
        spec = TrainSpec(
            n_stages=n_stages, kappa=1.0, precision=args.precision,
            stages=tuple(StageSpec(steps=args.steps, lr=args.lr,
                                   optimizer="adamw", accum=args.accum)
                         for _ in range(n_stages)))
        ckpt_dir = os.path.join(args.ckpt_dir, "stages") \
            if args.ckpt_dir else None

        def batch_at(i):
            # PURE function of the tick index (not the shared stateful
            # iterator): a resumed stage replaying ticks t..n must see
            # exactly the batches the other stages consumed at those ticks
            return {k: jnp.asarray(v) for k, v in
                    lm_batch_at(stream, args.batch, args.seq, i).items()}
        params, hist = recipes.run_lm_parallel(
            cfg, plan, params, batch_at, spec, jax.random.PRNGKey(1),
            dist=args.dist, dist_devices=devs, ckpt_dir=ckpt_dir,
            ckpt_every=args.ckpt_every)
        losses_tail = hist.column("loss")[-5:]
        print(f"dist={args.dist} over {len(devs)} devices; "
              "PNN parallel losses (tail):",
              [round(l, 3) for l in losses_tail])
    elif args.mode == "pnn":
        # PNN stage steps go through the SAME Policy/sharding plumbing as
        # baseline training; on sub-mesh hosts --seq-shard fails loudly
        # instead of being silently ignored (it used to be).
        shard_fn, pspecs_fn = None, None
        if use_mesh:
            mesh = make_production_mesh()
            policy = Policy(cfg, mesh)
            if args.seq_shard:
                shard_fn = _shard_x_fn(cfg, policy, args.batch, args.seq)
            # NamedShardings (not bare PartitionSpecs): the stage steps are
            # traced outside any `with mesh:` context
            pspecs_fn = policy.params_shardings
        elif args.seq_shard:
            raise SystemExit(
                "--seq-shard with --mode pnn requires the production mesh "
                f"(>=256 devices; have {n_dev}). Run without --seq-shard "
                "or on a full slice.")
        plan = partition.make_plan(cfg, n_stages, strategy=stage_strategy)
        _print_plan(stage_strategy, plan)
        spec = TrainSpec(
            n_stages=n_stages, kappa=1.0, precision=args.precision,
            stages=tuple(StageSpec(steps=args.steps // n_stages,
                                   lr=args.lr, optimizer="adamw",
                                   accum=args.accum)
                         for _ in range(n_stages)),
            recovery=StageSpec(steps=args.steps // 4, lr=args.lr / 10,
                               optimizer="adamw", accum=args.accum))
        params, hist = recipes.run_lm_sequential(
            cfg, plan, params, next_batch, spec, jax.random.PRNGKey(1),
            shard_x=shard_fn, grad_pspecs_fn=pspecs_fn)
        losses_tail = hist.column("loss")[-5:]
        print("PNN losses (tail):", [round(l, 3) for l in losses_tail])
    else:
        opt_name = pick_optimizer_name(cfg) if not args.smoke else "adamw"
        opt = make_optimizer(opt_name, cosine_warmup(args.lr, 10, args.steps))
        wrapped = prec is not None and prec.wraps_optimizer
        if wrapped:
            from repro.optim import mixed_precision
            opt = mixed_precision(opt, loss_scale=prec.loss_scale,
                                  dynamic=prec.dynamic_scale,
                                  growth_interval=prec.scale_growth_interval)
        hist = History()
        state = opt.init(params)
        shape = InputShape("cli", args.seq, args.batch, "train")
        if use_mesh:
            mesh = make_production_mesh()
            policy = Policy(cfg, mesh)
            # an explicit --accum wins; otherwise the memory-aware default
            accum = args.accum if args.accum > 1 \
                else pick_accum(cfg, shape, policy)
            shard_fn = _shard_x_fn(cfg, policy, args.batch, args.seq) \
                if args.seq_shard else None
            step = build_train_step(cfg, opt, accum=accum,
                                    seq_shard_fn=shard_fn)
            p_sh = policy.params_shardings(params)
            o_sh = policy.opt_state_shardings(opt_name, params)
            if wrapped:
                # the mixed_precision wrapper nests the inner state and adds
                # replicated scalars (+ fp32 masters mirroring the params)
                from jax.sharding import NamedSharding, PartitionSpec as P
                rep = NamedSharding(mesh, P())
                o_sh = {"inner": o_sh, "loss_scale": rep, "good_steps": rep}
                if "master" in state:
                    o_sh["master"] = p_sh
            step_fn = jax.jit(step, in_shardings=(p_sh, o_sh, None),
                              out_shardings=(p_sh, o_sh, None),
                              donate_argnums=(0, 1))
            params = jax.device_put(params, p_sh)
            state = jax.device_put(state, o_sh)
        else:
            step_fn = jax.jit(build_train_step(cfg, opt, accum=args.accum))
        t0 = time.time()
        for i in range(args.steps):
            params, state, metrics = step_fn(params, state, next_batch(i))
            if (i + 1) % max(args.steps // 5, 1) == 0 or i == 0:
                hist.log(phase="baseline", stage=-1, step=step0 + i,
                         loss=float(metrics["ce"]))
                print(f"step {step0+i+1:4d} ce={float(metrics['ce']):.3f} "
                      f"grad_norm={float(metrics['grad_norm']):.2f} "
                      f"({(i+1)*args.batch*args.seq/(time.time()-t0):.0f} tok/s)")

    if args.ckpt_dir:
        path = save_checkpoint(args.ckpt_dir, step0 + args.steps,
                               {"params": params})
        print("saved:", path)
    return hist


def _print_plan(strategy: str, plan) -> None:
    if strategy == "auto":
        print(f"plan[auto]: {plan.n_stages} stages, searched bounds "
              f"{plan.bounds} (repro.plan cost-model cut)")
    else:
        print(f"plan[uniform]: {plan.n_stages} stages, bounds {plan.bounds}")


def _run_paper_mlp(args, strategy: str, n_stages: int):
    """The paper's EMNIST MLP through the same CLI: baseline, or PNN with
    uniform/paper/searched stage bounds (``--steps`` = epochs per stage)."""
    from repro import plan as plan_lib
    from repro.data.images import emnist_like
    from repro.train import recipes
    from repro.train.backends import mlp_default_bounds, mlp_test_accuracy

    cfg = get("paper_mlp", smoke=args.smoke)
    n_train, n_test = (9400, 940) if args.smoke else (28200, 2820)
    data = emnist_like(n_train=n_train, n_test=n_test, seed=0, noise=0.5)
    epochs = args.steps
    spec = TrainSpec(
        batch_size=1410, kappa=10.0, shuffle=True, n_stages=n_stages,
        precision=args.precision,
        stages=tuple(StageSpec(epochs=epochs, lr=0.01, optimizer="sgdm",
                               momentum=0.9) for _ in range(n_stages)),
        baseline=StageSpec(epochs=epochs, lr=0.01, optimizer="sgdm",
                           momentum=0.9))
    key = jax.random.PRNGKey(0)  # repro: allow-const-key
    if args.mode == "baseline":
        params, hist = recipes.run_mlp_baseline(cfg, data, spec, key)
    else:
        if strategy == "auto":
            bounds = plan_lib.auto_mlp_bounds(cfg, n_stages,
                                              batch_size=spec.batch_size)
        else:
            bounds = mlp_default_bounds(cfg, n_stages)
        table = plan_lib.mlp_costs(cfg, batch_size=spec.batch_size)
        rows = table.stage_costs(bounds)
        print(f"plan[{strategy}]: {n_stages} stages, bounds {bounds}")
        for c in rows:
            print(f"  stage{c.stage}: layers[{c.lo},{c.hi}) "
                  f"bytes={c.bytes_total:,} flops={c.flops:.3g}")
        if args.dist != "none":
            params, hist = recipes.run_mlp_fig5(
                cfg, data, spec, key, n_stages=n_stages, bounds=bounds,
                dist=args.dist)
        else:
            params, hist = recipes.run_mlp_fig5(
                cfg, data, spec, key, n_stages=n_stages, bounds=bounds)
    acc = mlp_test_accuracy(cfg, params, data[2], data[3])
    print(f"paper_mlp {args.mode}: test acc {acc:.4f}")
    if args.ckpt_dir:
        path = save_checkpoint(args.ckpt_dir, epochs, {"params": params})
        print("saved:", path)
    return hist


if __name__ == "__main__":
    main()
