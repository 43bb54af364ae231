"""Composable training phases (the paper's schedule, decomposed).

Each phase is a small dataclass with ``run(trainer, state)``; a training
procedure is a *list* of phases executed in order over shared ``TrainState``:

* ``BaselinePhase``            — conventional end-to-end training.
* ``SilStagePhase``            — train one stage against its SIL targets
                                 (paper Fig. 3 "left" phase; interior LM
                                 stages consume the live frozen prefix).
* ``BoundaryMaterializePhase`` — run the frozen prefix over the data once and
                                 store the boundary (the paper's only
                                 communication), into a ``BoundaryCache``.
* ``FrozenPrefixPhase``        — train a stage on frozen-prefix inputs
                                 (stored or live) with its natural loss (CE
                                 for the last stage; Fig. 3 "right" phase).
* ``RecoveryPhase``            — §5: fine-tune one stage end-to-end with the
                                 others frozen.
* ``ParallelSilPhase``         — Fig. 5: every stage trains simultaneously on
                                 synthetic inputs/targets, zero dependencies.

Per-phase ``lr`` / ``optimizer`` / duration default to the ``TrainSpec``'s
per-stage entries; seeds (``seed_base``) reproduce the legacy trainers'
epoch seeding so schedules are bit-for-bit comparable with the pre-redesign
functions.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.train.backends import make_optimizer_for, scanned_epoch_fn
from repro.train.boundary import BoundaryCache
from repro.train.spec import StageSpec


def _resolve_placement(plan, devices, trainer, state):
    """Plan-or-strategy-name -> validated ``repro.dist.PlacementPlan``.

    The ``"memory"`` strategy's byte estimates come from the LIVE per-stage
    param trees plus each stage's configured optimizer (deferred — only
    computed when that strategy is chosen)."""
    from repro.dist import placement as P
    be = trainer.backend

    def stage_bytes():
        return [P.estimate_stage_bytes(state.stage_params[k],
                                       trainer.spec.stage(k).optimizer)
                for k in range(be.n_stages)]
    return P.resolve(plan, be.n_stages, devices=devices,
                     stage_bytes=stage_bytes)


def _to_host(tree):
    """Stage params live on the host between phases: a phase puts on the
    device (its placement's, or the default) only the stages and the SIL
    table it reads, so a stage-sequential run needs one stage's training
    state at a time, not the whole model's (qwen2-1.5b does not fit a
    16 GB chip otherwise), and no stage stays committed to the device a
    placed phase trained it on."""
    return jax.device_get(tree)  # repro: allow-host-sync


@dataclass
class PhaseBase:
    # overrides; default to the TrainSpec's per-stage entries
    epochs: Optional[int] = None
    steps: Optional[int] = None
    lr: Optional[float] = None
    optimizer: Optional[str] = None
    momentum: Optional[float] = None
    accum: Optional[int] = None
    precision: Optional[object] = None
    nan_guard: Optional[bool] = None
    seed_base: int = 0
    needs_sil = False

    def resolve(self, base: StageSpec) -> StageSpec:
        return StageSpec(
            epochs=self.epochs if self.epochs is not None else base.epochs,
            steps=self.steps if self.steps is not None else base.steps,
            lr=self.lr if self.lr is not None else base.lr,
            optimizer=self.optimizer or base.optimizer,
            momentum=self.momentum if self.momentum is not None
            else base.momentum,
            accum=self.accum if self.accum is not None else base.accum,
            precision=self.precision if self.precision is not None
            else base.precision,
            nan_guard=self.nan_guard if self.nan_guard is not None
            else base.nan_guard)


# ==========================================================================

@dataclass
class BaselinePhase(PhaseBase):
    """Conventional training of the unpartitioned network."""
    name: str = "baseline"

    def run(self, trainer, state) -> None:
        be = trainer.backend
        hp = self.resolve(trainer.spec.baseline or trainer.spec.stage(0))
        opt = make_optimizer_for(hp, trainer.spec)
        if be.kind == "mlp":
            params = be.join(state.stage_params)
            opt_state = opt.init(params)
            params, _ = trainer.drive_epochs(
                state, step=be.build_baseline_step(opt, accum=hp.accum),
                train_params=params,
                opt_state=opt_state, epochs=hp.epochs, phase_name=self.name,
                stage=-1, macs_per_sample=be.full_macs(),
                seed_base=self.seed_base, log_mode="cadence+last",
                eval_fn=be.eval_full)
            state.stage_params = be.split(params)
        else:
            # true unpartitioned training: the full joined tree through
            # M.forward (tied embeddings receive unembedding gradients)
            params = be.join(state.stage_params)
            step = be.build_baseline_step(opt, accum=hp.accum)
            opt_state = opt.init(params)

            def inputs(i):
                return (be.batch_fn(i),)
            params, _ = trainer.drive_steps(
                state, step=step, inputs_fn=inputs, n_steps=hp.steps,
                phase_name=self.name, stage=-1,
                train_params=params, opt_state=opt_state)
            state.stage_params = be.split(params)


# ==========================================================================

@dataclass
class SilStagePhase(PhaseBase):
    """Train stage `stage` against its SIL table (paper's left phase).

    MLP backend: stage 0 only (real inputs).  LM backend: any interior
    stage; stages > 0 consume the live frozen prefix per step."""
    stage: int = 0
    name: str = "left"
    needs_sil = True

    def run(self, trainer, state) -> None:
        be = trainer.backend
        k = self.stage
        if k >= be.n_stages - 1:
            raise ValueError("SilStagePhase is for interior stages; the last "
                             "stage trains with CE (FrozenPrefixPhase)")
        hp = self.resolve(trainer.spec.stage(k))
        opt = make_optimizer_for(hp, trainer.spec)
        sil = state.sils[k]
        if be.kind == "mlp":
            if k != 0:
                raise ValueError("MLP SilStagePhase supports stage 0 only "
                                 "(materialize the boundary for later stages)")
            opt_state = opt.init(state.stage_params[k])
            state.stage_params[k], _ = trainer.drive_epochs(
                state, step=be.build_sil_step(k, opt, sil, accum=hp.accum),
                train_params=state.stage_params[k], opt_state=opt_state,
                epochs=hp.epochs, phase_name=self.name, stage=k,
                macs_per_sample=be.stage_macs(k), seed_base=self.seed_base,
                log_mode="cadence")
        else:
            sp = jax.device_put(state.stage_params[k])
            step = be.build_stage_step(k, opt, jax.device_put(sil), sp,
                                       accum=hp.accum)
            opt_state = opt.init(be.trainable(sp))
            prefix = be.prefix_forward(k) if k else None
            frozen = jax.device_put(tuple(state.stage_params[:k]))

            def inputs(i):
                batch = be.batch_fn(i)
                xin = batch if k == 0 else prefix(frozen, batch)
                return (xin, batch["labels"], batch.get("mask"))
            sp, _ = trainer.drive_steps(
                state, step=step, inputs_fn=inputs, n_steps=hp.steps,
                phase_name=self.name, stage=k,
                train_params=sp, opt_state=opt_state)
            state.stage_params[k] = _to_host(sp)


# ==========================================================================

@dataclass
class BoundaryMaterializePhase(PhaseBase):
    """Store the frozen prefix's boundary activations (stages < `upto`).

    This is the paper's single inter-partition communication.  Activations
    are pulled from the device in chunks straight into a reserved
    ``BoundaryCache`` buffer (optionally memmap-spilled to `spill_dir`).
    LM backend: captures `n_batches` batches from the stream (decoder-only
    models).

    With a ``plan`` (a ``repro.dist`` PlacementPlan or strategy name) the
    frozen-prefix forward runs as the PRODUCER on the device that owns the
    last prefix stage — paired with ``FrozenPrefixPhase(plan=...)`` the
    paper's single communication becomes an actual inter-device hop."""
    upto: int = 1
    spill_dir: Optional[str] = None
    spill_threshold_bytes: Optional[int] = None
    n_batches: Optional[int] = None    # LM backend only
    plan: Optional[object] = None
    devices: Optional[Sequence] = None
    name: str = "materialize"

    def _cache(self) -> BoundaryCache:
        kw = {}
        if self.spill_threshold_bytes is not None:
            kw["spill_threshold_bytes"] = self.spill_threshold_bytes
        return BoundaryCache(spill_dir=self.spill_dir, **kw)

    def run(self, trainer, state) -> None:
        be = trainer.backend
        fwd = be.prefix_forward(self.upto)
        producer = None
        if self.plan is not None:
            # producer placement: the prefix forward runs on the device
            # owning the last frozen stage (batches follow the committed
            # params; the cache append pulls to host as before)
            placement = _resolve_placement(self.plan, self.devices,
                                           trainer, state)
            producer = placement.device_for(self.upto - 1)
        frozen = jax.device_put(tuple(state.stage_params[: self.upto]),
                                producer)
        old = state.boundary.get("h")
        if old is not None and hasattr(old, "close"):
            old.close()   # re-materialization must not leak a spill file
        cache = self._cache()
        if be.kind == "mlp":
            bx, by = be.epoch_arrays(seed=0, shuffle=False)
            nb, bs = bx.shape[0], bx.shape[1]
            cache.reserve(nb * bs, (be.boundary_width(self.upto - 1),),
                          be.boundary_dtype())
            for i in range(nb):
                cache.append(fwd(frozen, bx[i]))
            labels = np.asarray(
                jax.device_get(by)).reshape(-1)  # repro: allow-host-sync
            state.boundary = {"h": cache, "labels": labels}
        else:
            if be.cfg.enc_dec:
                raise NotImplementedError(
                    "boundary materialization for enc-dec payloads is not "
                    "supported; use FrozenPrefixPhase(source='live')")
            if not self.n_batches:
                raise ValueError("LM materialization needs n_batches")
            hs, labels, masks = None, [], []
            for j in range(self.n_batches):
                batch = be.batch_fn(state.step_idx + j)
                h = fwd(frozen, batch)
                if hs is None:
                    b, s, d = h.shape
                    cache.reserve(self.n_batches * b, (s, d),
                                  be.boundary_dtype())
                    hs = True
                cache.append(h)
                labels.append(np.asarray(batch["labels"]))
                if batch.get("mask") is not None:
                    masks.append(np.asarray(batch["mask"]))
            state.boundary = {"h": cache,
                              "labels": np.concatenate(labels),
                              "mask": np.concatenate(masks) if masks
                              else None,
                              "batch_size": int(labels[0].shape[0])}


# ==========================================================================

@dataclass
class FrozenPrefixPhase(PhaseBase):
    """Train stage `stage` on frozen-prefix inputs with its natural loss
    (CE if it is the last stage, SIL-MSE otherwise).

    source='cache': inputs come from the materialized BoundaryCache (the
    paper's Fig.-3 right phase — zero prefix compute during training).
    source='live': the frozen prefix runs forward every step (the
    transformer-sequential default, where data is a stream).

    With a ``plan`` (``repro.dist`` PlacementPlan or strategy name) the
    trained stage lives on its assigned device as the CONSUMER; under
    source='live' the frozen prefix runs as the PRODUCER on the device
    owning stage k-1 and each boundary activation hops producer->consumer
    (the paper's sole communication, as a real transfer)."""
    stage: int = 1
    source: str = "cache"
    plan: Optional[object] = None
    devices: Optional[Sequence] = None
    name: str = "right"
    seed_base: int = 100
    # interior stages regress to their SIL table; the last stage does not,
    # but SIL derivation is cheap, so be conservative (pass sils=[] to a
    # Trainer.run that genuinely needs none)
    needs_sil = True

    def run(self, trainer, state) -> None:
        be = trainer.backend
        k = self.stage
        last = k == be.n_stages - 1
        if not last and not state.sils:
            raise ValueError("interior FrozenPrefixPhase needs SIL tables: "
                             "pass sils= or key= to Trainer.run")
        hp = self.resolve(trainer.spec.stage(k))
        opt = make_optimizer_for(hp, trainer.spec)
        if hasattr(be, "before_stage_train"):
            be.before_stage_train(state.stage_params, k)
        consumer = producer = None
        if self.plan is not None:
            placement = _resolve_placement(self.plan, self.devices,
                                           trainer, state)
            consumer = placement.device_for(k)
            producer = placement.device_for(k - 1) if k > 0 else consumer
        train_params = jax.device_put(state.stage_params[k], consumer)
        if be.kind == "mlp":
            if self.source != "cache" or "h" not in state.boundary:
                raise ValueError("MLP FrozenPrefixPhase needs a preceding "
                                 "BoundaryMaterializePhase")
            step = be.build_ce_step(k, opt, accum=hp.accum) if last \
                else be.build_sil_step(k, opt, state.sils[k],
                                       accum=hp.accum)
            h = jnp.asarray(state.boundary["h"].array())
            y = jnp.asarray(state.boundary["labels"])

            def batch_arrays(ep):
                return be.array_epoch_arrays(h, y, self.seed_base + ep,
                                             be.spec.shuffle)
            opt_state = opt.init(train_params)
            train_params, _ = trainer.drive_epochs(
                state, step=step, train_params=train_params,
                opt_state=opt_state, epochs=hp.epochs, phase_name=self.name,
                stage=k, macs_per_sample=be.stage_macs(k),
                seed_base=self.seed_base, log_mode="cadence+last",
                batch_arrays=batch_arrays)
        else:
            sil = None if last else jax.device_put(state.sils[k], consumer)
            step = be.build_stage_step(k, opt, sil, train_params,
                                       accum=hp.accum)
            opt_state = opt.init(be.trainable(train_params))
            if self.source == "cache":
                if "h" not in state.boundary:
                    raise ValueError("no materialized boundary; add a "
                                     "BoundaryMaterializePhase first")
                h = state.boundary["h"].array()
                labels = state.boundary["labels"]
                mask = state.boundary.get("mask")
                b = state.boundary["batch_size"]
                n_batches = len(h) // b

                def inputs(i):
                    j = (i % n_batches) * b
                    m = None if mask is None else jnp.asarray(mask[j:j + b])
                    return (jnp.asarray(h[j:j + b]),
                            jnp.asarray(labels[j:j + b]), m)
            else:
                prefix = be.prefix_forward(k)
                frozen = jax.device_put(tuple(state.stage_params[:k]),
                                        producer)

                def inputs(i):
                    batch = be.batch_fn(i)
                    hb = prefix(frozen, batch)
                    if consumer is not None:
                        # the paper's single inter-partition communication,
                        # as an actual producer->consumer device transfer
                        hb = jax.device_put(hb, consumer)
                    return (hb, batch["labels"], batch.get("mask"))
            train_params, _ = trainer.drive_steps(
                state, step=step, inputs_fn=inputs, n_steps=hp.steps,
                phase_name=self.name, stage=k,
                train_params=train_params, opt_state=opt_state)
        state.stage_params[k] = _to_host(train_params)


# ==========================================================================

@dataclass
class RecoveryPhase(PhaseBase):
    """§5 recovery: fine-tune stage `stage` end-to-end, the rest frozen."""
    stage: int = 0
    name: str = "recovery"
    seed_base: int = 200

    def run(self, trainer, state) -> None:
        be = trainer.backend
        j = self.stage
        base = trainer.spec.recovery
        if base is None and self.epochs is None and self.steps is None:
            return   # recovery disabled in the spec and not forced here
        hp = self.resolve(base or trainer.spec.stage(j))
        n = hp.epochs if be.kind == "mlp" else hp.steps
        if not n:
            return
        opt = make_optimizer_for(hp, trainer.spec)
        frozen = list(state.stage_params)
        if be.kind == "mlp":
            step = be.build_recovery_step(j, frozen, opt, accum=hp.accum)
            opt_state = opt.init(state.stage_params[j])
            state.stage_params[j], _ = trainer.drive_epochs(
                state, step=step, train_params=state.stage_params[j],
                opt_state=opt_state, epochs=n, phase_name=self.name,
                stage=j, macs_per_sample=be.full_macs(),
                seed_base=self.seed_base, log_mode="every")
        else:
            frozen = jax.device_put(frozen)
            step = be.build_recovery_step(j, frozen, opt, accum=hp.accum)
            opt_state = opt.init(be.trainable(frozen[j]))

            def inputs(i):
                return (be.batch_fn(i),)
            pj, _ = trainer.drive_steps(
                state, step=step, inputs_fn=inputs, n_steps=n,
                phase_name=self.name, stage=-1,   # legacy: recovery logs -1
                train_params=frozen.pop(j), opt_state=opt_state)
            del frozen, step
            state.stage_params[j] = _to_host(pj)


# ==========================================================================

@dataclass
class ParallelSilPhase(PhaseBase):
    """Fig. 5: ALL stages train simultaneously with zero dependencies.

    Interior stage k consumes SIL_{k-1}[:, y] and regresses to SIL_k[:, y];
    stage 0 consumes real inputs; the last stage trains with CE.  The paper
    deems the mode impractical for accuracy; it is the zero-communication
    extreme of the schedule space.

    ``plan`` (a ``repro.dist`` PlacementPlan, a strategy name
    'round_robin'/'memory', or an explicit assignment list) routes the phase
    through ``repro.dist.StageExecutor``: every stage's params/optimizer
    state pin to its assigned device and all stage steps dispatch per tick
    with no host sync — the paper's Fig.-5 simultaneity actually executed.
    ``ckpt_dir``/``ckpt_every`` enable per-stage checkpointing (one manifest
    and tick counter per stage; see ``repro.dist.lifecycle``)."""
    name: str = "parallel"
    needs_sil = True
    shuffle: bool = True           # legacy MLP fig-5 shuffles
    plan: Optional[object] = None
    devices: Optional[Sequence] = None
    ckpt_dir: Optional[str] = None
    ckpt_every: int = 0
    ckpt_keep_last: Optional[int] = None

    def run(self, trainer, state) -> None:
        be = trainer.backend
        if self.plan is not None:
            self._run_dist(trainer, state)
        elif be.kind == "mlp":
            self._run_mlp(trainer, state)
        else:
            self._run_lm(trainer, state)

    def _run_dist(self, trainer, state) -> None:
        from repro.dist.executor import StageExecutor
        be = trainer.backend
        if be.kind != "mlp" and (be.shard_x is not None
                                 or be.grad_pspecs_fn is not None):
            # the executor builds steps without the mesh-sharding hooks;
            # dropping a caller's with_sharding_constraint pass silently
            # would be a correctness trap on real meshes
            raise ValueError(
                "dist placement is incompatible with the Policy sharding "
                "hooks (shard_x/grad_pspecs_fn): stages pin whole trees to "
                "single devices. Drop the hooks or run without plan=.")
        placement = _resolve_placement(self.plan, self.devices,
                                       trainer, state)
        hps = [self.resolve(trainer.spec.stage(k))
               for k in range(be.n_stages)]
        opts = [make_optimizer_for(hp, trainer.spec) for hp in hps]
        ex = StageExecutor(be, placement, state.stage_params, state.sils,
                           opts, hps, seed_base=self.seed_base,
                           shuffle=self.shuffle, ckpt_dir=self.ckpt_dir,
                           ckpt_every=self.ckpt_every,
                           ckpt_keep_last=self.ckpt_keep_last,
                           metrics=trainer.metrics, tracer=trainer.tracer)
        if be.kind == "mlp":
            n_ticks = max(hp.epochs for hp in hps)
        else:
            n_ticks = max(hp.steps for hp in hps)
        ex.run(n_ticks)
        if self.ckpt_dir:
            ex.checkpoint()    # final per-stage manifests at their ticks
        ex.finalize(trainer, state, phase_name=self.name)

    def _run_mlp(self, trainer, state) -> None:
        be = trainer.backend
        hps = [self.resolve(trainer.spec.stage(k))
               for k in range(be.n_stages)]
        opts = [make_optimizer_for(hp, trainer.spec) for hp in hps]
        opt_states = [opts[k].init(state.stage_params[k])
                      for k in range(be.n_stages)]
        epoch_fns = [scanned_epoch_fn(
            be.build_parallel_step(k, opts[k], state.sils, accum=hps[k].accum))
            for k in range(be.n_stages)]
        # epoch loop outside the stage loop: the (shuffled) epoch gather is
        # done once per epoch, shared by every independent stage
        for ep in range(max(hp.epochs for hp in hps)):
            batches = be.epoch_arrays(self.seed_base + ep, self.shuffle)
            n_samples = batches[0].shape[0] * batches[0].shape[1]
            for k in range(be.n_stages):
                if ep >= hps[k].epochs:
                    continue
                state.stage_params[k], opt_states[k], _ = epoch_fns[k](
                    state.stage_params[k], opt_states[k], batches)
                state.cum_macs += be.stage_macs(k) * n_samples
        state.history.log(phase=self.name, stage=-1, step=state.step_idx,
                          macs=state.cum_macs,
                          acc=be.eval_joined(state.stage_params))

    def _run_lm(self, trainer, state) -> None:
        be = trainer.backend
        hps = [self.resolve(trainer.spec.stage(k))
               for k in range(be.n_stages)]
        opts = [make_optimizer_for(hp, trainer.spec) for hp in hps]
        params = jax.device_put(list(state.stage_params))
        sils = jax.device_put(list(state.sils))
        opt_states = [opts[k].init(be.trainable(params[k]))
                      for k in range(be.n_stages)]
        steps = [be.build_stage_step(
            k, opts[k], None if k == be.n_stages - 1 else sils[k],
            params[k], accum=hps[k].accum)
            for k in range(be.n_stages)]
        pending, logged_steps, logged_stages = [], [], []
        n_steps = max(hp.steps for hp in hps)
        for i in range(n_steps):
            batch = be.batch_fn(i)
            labels = batch["labels"]
            for k in range(be.n_stages):
                if i >= hps[k].steps:
                    continue
                xin = batch if k == 0 else be.synthetic_input(k, sils,
                                                              labels)
                params[k], opt_states[k], loss = steps[k](
                    params[k], opt_states[k], xin, labels)
                pending.append(loss)
                logged_steps.append(i)
                logged_stages.append(k)
            state.step_idx += 1
        trainer.flush_losses(state, pending, logged_steps, self.name,
                             logged_stages)
        state.stage_params = _to_host(params)
