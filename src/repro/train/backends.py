"""Training backends for the phase API.

A backend binds the phase engine to a model family and its data access
pattern:

* ``MLPBackend`` — the paper's fully-connected EMNIST experiment.  Dataset is
  array-resident, so the inner loop is a single jitted ``jax.lax.scan`` over
  the epoch's stacked batches: metrics stay device-resident and the host sees
  one transfer per epoch instead of one blocking ``float(loss)`` per step.
* ``LMBackend`` — the transformer generalization over a
  ``partition.PartitionPlan``.  Batches come from a host ``batch_fn`` stream,
  so steps run in a python loop, but losses are kept as device scalars and
  fetched in one transfer at phase end, which keeps dispatch asynchronous.

Both backends donate params + optimizer state into their jitted steps on
accelerators (donation is a no-op on CPU, where JAX would only warn), and
defensively copy shared leaves when slicing stages so donation can never
invalidate a caller-held param tree.
"""
from __future__ import annotations

import functools
from typing import Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import precision as precision_lib
from repro import runtime
from repro.core import losses, partition, sil as sil_lib
from repro.models import mlp as MLP
from repro.models import model as M
from repro.obs import scopes
from repro.optim import make_optimizer, mixed_precision, step_guard

from repro.train.spec import StageSpec, TrainSpec


def donate_argnums(*nums) -> Tuple[int, ...]:
    """Buffer donation is unimplemented on CPU (JAX emits a warning and
    ignores it); only request it where it exists.  ``repro.runtime`` owns
    the decision (REPRO_ASSUME_DONATION=1 forces the request on for
    trace-only introspection such as ``repro.analysis``)."""
    return runtime.donate_argnums(*nums)


def _copy_tree(tree):
    return jax.tree_util.tree_map(jnp.copy, tree)


def resolve_policy(hp=None, spec=None):
    """The explicitly-requested PrecisionPolicy for a stage (StageSpec
    override first, then the TrainSpec-wide default), or None — None keeps
    the legacy numerics exactly (MLP backend fp32; LM backend whatever the
    ModelConfig's dtype says)."""
    p = getattr(hp, "precision", None) if hp is not None else None
    if p is None and spec is not None:
        p = getattr(spec, "precision", None)
    return None if p is None else precision_lib.get_policy(p)


def make_optimizer_for(hp: StageSpec, spec: Optional[TrainSpec] = None):
    kw = {"momentum": hp.momentum} if hp.optimizer == "sgdm" else {}
    opt = make_optimizer(hp.optimizer, hp.lr, **kw)
    pol = resolve_policy(hp, spec)
    if pol is not None and pol.wraps_optimizer:
        opt = mixed_precision(opt, loss_scale=pol.loss_scale,
                              dynamic=pol.dynamic_scale,
                              growth_interval=pol.scale_growth_interval)
    else:
        # NaN/inf step guard (repro.resilience) for the unscaled precisions
        # only: mixed_precision already skips-and-counts non-finite steps,
        # and a guard stacked OUTSIDE it would veto scaled gradients before
        # the dynamic loss scale could cure them by halving
        guard = hp.nan_guard
        if guard is None:
            guard = bool(getattr(spec, "nan_guard", False))
        if guard:
            opt = step_guard(opt)
    return opt


def value_and_accum_grads(loss_fn, params, args, accum: int,
                          accum_dtype=jnp.float32):
    """(mean loss, grads) of ``loss_fn(params, *args)`` with the batch split
    into ``accum`` microbatches inside the (caller-jitted) step; gradients
    accumulate in ``accum_dtype`` (fp32) regardless of the compute dtype.
    ``accum=1`` is the exact legacy single-shot path."""
    grad_fn = jax.value_and_grad(loss_fn)
    if accum <= 1:
        return grad_fn(params, *args)

    def fold(a):
        if a.shape[0] % accum:
            raise ValueError(f"batch dim {a.shape[0]} not divisible by "
                             f"accum={accum}")
        return a.reshape((accum, a.shape[0] // accum) + a.shape[1:])

    mbs = jax.tree_util.tree_map(fold, args)

    def body(acc, mb):
        loss, g = grad_fn(params, *mb)
        acc = jax.tree_util.tree_map(
            lambda a, gi: a + gi.astype(a.dtype), acc, g)
        return acc, loss

    zeros = jax.tree_util.tree_map(
        lambda p: jnp.zeros(p.shape, accum_dtype), params)
    gsum, mb_losses = jax.lax.scan(body, zeros, mbs)
    grads = jax.tree_util.tree_map(lambda g: g / accum, gsum)
    return mb_losses.mean(), grads


def scanned_epoch_fn(step):
    """One jitted epoch: scan `step` over stacked batches, returning the
    per-step losses as a device array (no per-step host sync)."""

    def epoch(params, opt_state, batches):
        def body(carry, batch):
            p, s = carry
            p, s, loss = step(p, s, *batch)
            return (p, s), loss
        (p, s), ls = jax.lax.scan(body, (params, opt_state), batches)
        return p, s, ls

    return jax.jit(epoch, donate_argnums=donate_argnums(0, 1))


# ==========================================================================
# MLP backend (paper §3-§5)
# ==========================================================================

def balanced_bounds(cfg: MLP.MLPConfig, n_stages: int, *,
                    costs=None) -> Tuple[Tuple[int, int], ...]:
    """Balanced contiguous layer split (the legacy fig-5 scheme).

    ``costs`` routes through the ``repro.plan`` bottleneck searcher instead:
    pass a ``plan.ModelCosts`` table (head/tail-overhead-aware), a per-layer
    scalar cost sequence, or ``"auto"`` to build the MLP cost table from the
    config (paper batch size, sgdm slots)."""
    if costs is not None:
        from repro import plan as plan_lib
        if isinstance(costs, str):
            if costs != "auto":
                raise ValueError(f"bad costs={costs!r}; expected 'auto', a "
                                 "ModelCosts table, or a scalar sequence")
            return plan_lib.auto_mlp_bounds(cfg, n_stages)
        if isinstance(costs, plan_lib.ModelCosts):
            return plan_lib.solve(costs, n_stages)
        from repro.plan.search import searched_bounds_for_sequence
        return searched_bounds_for_sequence(costs, n_stages)
    base, rem = divmod(cfg.n_layers, n_stages)
    bounds, s = [], 0
    for k in range(n_stages):
        e = s + base + (1 if k < rem else 0)
        bounds.append((s, e))
        s = e
    return tuple(bounds)


def mlp_default_bounds(cfg: MLP.MLPConfig, n_stages: int
                       ) -> Tuple[Tuple[int, int], ...]:
    """2 stages -> the paper's cut; otherwise balanced contiguous split."""
    if n_stages == 2:
        return ((0, cfg.cut), (cfg.cut, cfg.n_layers))
    return balanced_bounds(cfg, n_stages)


class MLPBackend:
    kind = "mlp"

    def __init__(self, cfg: MLP.MLPConfig, data, spec: TrainSpec,
                 bounds: Optional[Sequence[Tuple[int, int]]] = None):
        self.cfg = cfg
        self.spec = spec
        # spec-wide policy; None = legacy fp32-everything (bit-exact).
        # Per-stage StageSpec.precision overrides only affect the optimizer
        # wrapper (built in phases via make_optimizer_for) — the forward
        # compute dtype is a backend-wide choice
        self.policy = resolve_policy(None, spec)
        tx, ty, vx, vy = data
        self._tx = jnp.asarray(tx)
        self._ty = jnp.asarray(ty)
        self._vx, self._vy = vx, vy
        self.bounds = tuple(bounds) if bounds is not None \
            else mlp_default_bounds(cfg, spec.n_stages)
        self.n_stages = len(self.bounds)
        bs = spec.batch_size
        self.n_train = len(tx)
        self.batches_per_epoch = self.n_train // bs
        self.samples_per_epoch = self.batches_per_epoch * bs
        self.dropped_per_epoch = self.n_train - self.samples_per_epoch
        self._plain_epoch = None   # cached unshuffled epoch arrays

    # -- params ------------------------------------------------------------

    def split(self, params) -> List[list]:
        return [_copy_tree(params[b0:b1]) for b0, b1 in self.bounds]

    def join(self, stage_params) -> list:
        return sum(stage_params, [])

    @staticmethod
    def trainable(stage_params):
        return stage_params       # no frozen leaves in the MLP stages

    def boundary_width(self, k: int) -> int:
        return self.cfg.sizes[self.bounds[k][1]]

    def make_sils(self, key, kappa: float) -> list:
        """Legacy-compatible fig-5 scheme: split(key, n_stages + 2), sils
        keyed from keys[1 + k].  (The fig-3 recipe derives its single SIL
        differently for seed compatibility — see recipes.run_mlp_fig3.)"""
        keys = jax.random.split(key, self.n_stages + 2)
        return [sil_lib.make_sil(keys[1 + k], self.boundary_width(k),
                                 self.cfg.n_classes, kappa)
                for k in range(self.n_stages - 1)]

    # -- macs --------------------------------------------------------------

    def stage_macs(self, k: int) -> int:
        b0, b1 = self.bounds[k]
        return MLP.macs(self.cfg, b0, b1)

    def full_macs(self) -> int:
        return MLP.macs(self.cfg)

    # -- data --------------------------------------------------------------

    def epoch_arrays(self, seed: int, shuffle: bool):
        """Stacked (nb, bs, ...) device arrays for one epoch, in the exact
        order the legacy `_batches` generator produced."""
        bs = self.spec.batch_size
        nb = self.batches_per_epoch
        n = self.samples_per_epoch
        if not shuffle:
            if self._plain_epoch is None:
                self._plain_epoch = (
                    self._tx[:n].reshape(nb, bs, -1),
                    self._ty[:n].reshape(nb, bs))
            return self._plain_epoch
        order = np.arange(self.n_train)
        np.random.RandomState(seed).shuffle(order)
        idx = jnp.asarray(order[:n])
        return (jnp.take(self._tx, idx, axis=0).reshape(nb, bs, -1),
                jnp.take(self._ty, idx, axis=0).reshape(nb, bs))

    def array_epoch_arrays(self, x, y, seed: int, shuffle: bool):
        """Same batching over caller-supplied arrays (e.g. the materialized
        boundary from a BoundaryCache)."""
        bs = self.spec.batch_size
        n = (len(x) // bs) * bs
        nb = n // bs
        x = jnp.asarray(x) if not isinstance(x, jax.Array) else x
        y = jnp.asarray(y) if not isinstance(y, jax.Array) else y
        if shuffle:
            order = np.arange(len(x))
            np.random.RandomState(seed).shuffle(order)
            idx = jnp.asarray(order[:n])
            return (jnp.take(x, idx, axis=0).reshape(nb, bs, -1),
                    jnp.take(y, idx, axis=0).reshape(nb, bs))
        return x[:n].reshape(nb, bs, -1), y[:n].reshape(nb, bs)

    # -- step builders -----------------------------------------------------

    def _compute_dtype(self):
        return None if self.policy is None else self.policy.compute_jnp

    def _range_forward(self, p, x, b0, b1):
        return MLP.forward_range(self.cfg, p, x, b0, b1,
                                 compute_dtype=self._compute_dtype())

    def _cast_in(self, x):
        """Inputs enter the network in the compute dtype (no-op legacy)."""
        return x if self.policy is None else self.policy.cast_compute(x)

    def _finish_step(self, opt, loss_fn, p, st, args, accum: int):
        """Shared tail of every MLP step: (scaled) grads — accumulated over
        `accum` microbatches in fp32 — into the optimizer; the returned loss
        is unscaled.  accum=1 / no policy is the exact legacy path."""
        scale = precision_lib.read_loss_scale(st)

        def scaled(p_, *a):
            return loss_fn(p_, *a) * scale
        loss, grads = value_and_accum_grads(scaled, p, args, accum)
        p2, st2 = opt.update(grads, st, p)
        return p2, st2, loss / scale

    def build_sil_step(self, k: int, opt, sil, accum: int = 1):
        b0, b1 = self.bounds[k]

        def step(p, st, x, y):
            def loss_fn(p_, xb, yb):
                h = self._range_forward(p_, xb, b0, b1)
                return losses.sil_stage_loss(h, sil, yb)
            return self._finish_step(opt, loss_fn, p, st,
                                     (self._cast_in(x), y), accum)
        return step

    def build_ce_step(self, k: int, opt, accum: int = 1):
        """CE through stage k alone (its input is the stage boundary)."""
        b0, b1 = self.bounds[k]

        def step(p, st, h, y):
            def loss_fn(p_, hb, yb):
                logits = self._range_forward(p_, hb, b0, b1)
                return losses.cross_entropy(logits, yb)
            return self._finish_step(opt, loss_fn, p, st,
                                     (self._cast_in(h), y), accum)
        return step

    def build_baseline_step(self, opt, accum: int = 1):
        cfg = self.cfg

        def step(p, st, x, y):
            def loss_fn(p_, xb, yb):
                logits = self._range_forward(p_, xb, 0, cfg.n_layers)
                return losses.cross_entropy(logits, yb)
            return self._finish_step(opt, loss_fn, p, st,
                                     (self._cast_in(x), y), accum)
        return step

    def build_recovery_step(self, j: int, frozen: list, opt, accum: int = 1):
        """End-to-end CE training of stage j with every other stage frozen
        (paper §5 for j=0)."""
        bounds = self.bounds

        def step(pj, st, x, y):
            def loss_fn(pj_, xb, yb):
                h = xb
                for k, (b0, b1) in enumerate(bounds):
                    p = pj_ if k == j else jax.lax.stop_gradient(frozen[k])
                    h = self._range_forward(p, h, b0, b1)
                return losses.cross_entropy(h, yb)
            return self._finish_step(opt, loss_fn, pj, st,
                                     (self._cast_in(x), y), accum)
        return step

    def build_parallel_step(self, k: int, opt, sils, accum: int = 1):
        """Fig.-5 stage step: interior stages consume SIL_{k-1}[:, y] and
        regress to SIL_k[:, y]; the last trains with CE; stage 0 consumes
        the real batch.  The synthetic input is looked up inside the jitted
        step from the labels (identical math to the legacy host lookup)."""
        b0, b1 = self.bounds[k]
        last = k == self.n_stages - 1

        def step(p, st, x, y):
            def loss_fn(p_, xb, yb):
                xin = xb if k == 0 else sil_lib.sil_lookup(sils[k - 1], yb)
                h = self._range_forward(p_, self._cast_in(xin), b0, b1)
                if last:
                    return losses.cross_entropy(h, yb)
                return losses.sil_stage_loss(h, sils[k], yb)
            return self._finish_step(opt, loss_fn, p, st, (x, y), accum)
        return step

    # -- prefix / eval -----------------------------------------------------

    def boundary_dtype(self):
        """Storage dtype for materialized boundary activations — the policy's
        compute dtype (halving the memmap spill under bf16)."""
        return np.dtype(jnp.float32) if self.policy is None \
            else np.dtype(self.policy.compute_jnp)

    def prefix_forward(self, k: int):
        bounds = self.bounds

        @jax.jit
        def fwd(prefix: tuple, x):
            x = self._cast_in(x)
            for j in range(k):
                b0, b1 = bounds[j]
                x = self._range_forward(prefix[j], x, b0, b1)
            return x
        return fwd

    def eval_joined(self, stage_params) -> float:
        return self.eval_full(self.join(stage_params))

    def eval_full(self, params) -> float:
        return mlp_test_accuracy(self.cfg, params, self._vx, self._vy)


@functools.partial(jax.jit, static_argnums=(0,))
def _mlp_eval(cfg: MLP.MLPConfig, params, x, y):
    logits = MLP.forward_range(cfg, params, x, 0, cfg.n_layers)
    return losses.accuracy(logits, y)


def mlp_test_accuracy(cfg, params, tx, ty, bs=4096) -> float:
    accs = []
    for i in range(0, len(tx), bs):
        accs.append(float(_mlp_eval(cfg, params, tx[i:i + bs], ty[i:i + bs]))
                    * len(tx[i:i + bs]))
    return sum(accs) / len(tx)


# ==========================================================================
# Transformer (PartitionPlan) backend
# ==========================================================================

class LMBackend:
    kind = "lm"

    def __init__(self, cfg, plan: partition.PartitionPlan,
                 batch_fn: Callable[[int], dict], spec: TrainSpec, *,
                 shard_x=None, grad_pspecs_fn=None):
        """shard_x / grad_pspecs_fn: the production sharding hooks —
        `launch/train.py` passes the Policy's sequence-shard constraint and
        `policy.params_shardings` (NamedShardings, usable outside a mesh
        context) so PNN stage steps run through the same plumbing as
        baseline training."""
        # an explicit spec.precision re-dtypes the whole stage forward
        # (activations, caches, boundary spills run in compute dtype);
        # params keep cfg.param_dtype — see repro.precision
        self.policy = resolve_policy(None, spec)
        if self.policy is not None:
            cfg = self.policy.apply_to_model(cfg)
        self.cfg = cfg
        self.plan = plan
        self.batch_fn = batch_fn
        self.spec = spec
        self.n_stages = plan.n_stages
        self.shard_x = shard_x
        self.grad_pspecs_fn = grad_pspecs_fn

    # -- params ------------------------------------------------------------

    def split(self, params) -> List[dict]:
        # host copies: donated stage buffers can never alias the caller's
        # tree, and the phases put on the device only what they train
        return [jax.device_get(partition.slice_stage_params(  # repro: allow-host-sync
            self.cfg, self.plan, params, k)) for k in range(self.n_stages)]

    def join(self, stage_params) -> dict:
        return partition.join_stage_params(self.cfg, self.plan, stage_params)

    def make_sils(self, key, kappa: float) -> list:
        # exact legacy key schedule: split(key, n_stages), sils from keys[:n-1]
        # host-resident like the stage params: a phase puts its own table
        # on the device (each is d_model x vocab fp32)
        keys = jax.random.split(key, self.n_stages)
        return [jax.device_get(  # repro: allow-host-sync
                    sil_lib.make_sil(keys[k], self.cfg.d_model,
                                     self.cfg.vocab_size, kappa))
                for k in range(self.n_stages - 1)]

    def before_stage_train(self, stage_params: list, k: int) -> None:
        """Refresh the last stage's frozen tied-unembedding copy from stage
        0's (possibly already trained) embedding before training it."""
        if k == self.n_stages - 1:
            partition.refresh_tied_unembed(self.cfg, self.plan, stage_params)

    @staticmethod
    def trainable(stage_params: dict) -> dict:
        """The stage's differentiated/optimized subtree.  The frozen
        ``tied_unembed`` snapshot is excluded so no gradient or optimizer
        state is ever allocated for it (the paper's per-stage memory claim)."""
        return {k: v for k, v in stage_params.items() if k != "tied_unembed"}

    # -- step builders -----------------------------------------------------

    def _trim_vision(self, x):
        if self.cfg.frontend == "vision":
            return x[:, self.cfg.vision_tokens:]
        return x

    def _objective(self, last_params, h, labels, aux, mask):
        """CE (+ MoE aux) of the last stage's final hidden states ``h``,
        chunked over tokens so the whole logits never exist at once."""
        with jax.named_scope("ce"):
            return losses.chunked_train_objective(
                self.cfg, functools.partial(partition.stage_unembed,
                                            self.cfg, last_params),
                self._trim_vision(h), labels, aux, mask)

    def _jit_step(self, step):
        return jax.jit(step, donate_argnums=donate_argnums(0, 1))

    def _grad_pspecs(self, stage_params):
        if self.grad_pspecs_fn is None:
            return None
        return self.grad_pspecs_fn(stage_params)

    @staticmethod
    def _split_frozen(sp: dict):
        frozen = {k: v for k, v in sp.items() if k == "tied_unembed"}
        train = {k: v for k, v in sp.items() if k != "tied_unembed"}
        return train, frozen

    def _cast_in(self, xin):
        """Boundary inputs enter the stage in the compute dtype (handles
        stale dtypes from caches materialized under another policy)."""
        if self.policy is None:
            return xin
        return self.policy.cast_compute(xin)

    def build_stage_step(self, k: int, opt, sil, stage_params_struct=None,
                         accum: int = 1):
        """Train step for stage k: SIL-MSE on the boundary for interior
        stages, CE (+ MoE aux) through the real unembedding for the last.
        The frozen tied_unembed snapshot (if any) is carried outside the
        differentiated tree — zero grad/optimizer-state cost.  Gradients of
        ``accum`` microbatches accumulate in fp32 inside the jitted step;
        the loss is scaled by the live loss scale (1.0 unless the optimizer
        is a mixed_precision fp16 wrapper).

        Returns ``step(sp, st, xin, labels, mask=None)``; the SIL table is
        passed to the jitted program as a trailing argument (a closed-over
        array would be embedded in the program as a constant)."""
        jitted = self._jit_step(self._stage_step(k, opt, stage_params_struct,
                                                 accum))

        def call(sp, st, xin, labels, mask=None):
            args = (sp, st, xin, labels, mask, sil)
            if not call.registered:
                call.registered = scopes.register(f"lm_stage_step.{k}",
                                                  jitted, args)
            return jitted(*args)
        call.registered = False
        return call

    def _stage_step(self, k: int, opt, stage_params_struct, accum: int):
        """The un-jitted stage-k step ``(sp, st, xin, labels, mask, sil)``."""
        cfg, plan = self.cfg, self.plan
        last = k == self.n_stages - 1
        pspecs = self._grad_pspecs(self.trainable(stage_params_struct)) \
            if stage_params_struct is not None else None

        def step(sp, st, xin, labels, mask, sil):
            train, frozen = self._split_frozen(sp)
            scale = precision_lib.read_loss_scale(st)

            def loss_fn(p, xin, labels, mask):
                full = {**p, **frozen}
                out, aux = partition.stage_forward(cfg, plan, k, full, xin,
                                                   shard_x=self.shard_x,
                                                   logits=False)
                if last:
                    loss, _ = self._objective(full, out, labels, aux, mask)
                    return loss * scale
                bound = out[0] if cfg.enc_dec else out
                bound = self._trim_vision(bound)
                loss = losses.sil_stage_loss(bound, sil, labels)
                if cfg.moe is not None:
                    loss = loss + cfg.moe.load_balance_loss * aux["lb_loss"] \
                        + cfg.moe.router_z_loss * aux["z_loss"]
                return loss * scale
            loss, grads = value_and_accum_grads(
                loss_fn, train, (self._cast_in(xin), labels, mask), accum)
            if pspecs is not None:
                grads = jax.tree_util.tree_map(
                    lambda g, s: jax.lax.with_sharding_constraint(g, s),
                    grads, pspecs)
            with jax.named_scope("optimizer"):
                new_train, st2 = opt.update(grads, st, train)
            return {**new_train, **frozen}, st2, loss / scale

        return step

    def build_parallel_stage_step(self, k: int, opt, sil_in, sil_target,
                                  stage_params_struct=None, accum: int = 1):
        """Fig.-5 step for stage k>0 with the synthetic-input lookup FUSED
        into the jitted program: callers pass only (sp, st, labels) and
        SIL_{k-1}[:, y] is derived on-device from ``sil_in``.  The
        ``repro.dist`` executor uses this so one tick dispatches one call
        per stage with zero host-side array construction between stages —
        ``sil_in`` is expected to be pre-pinned to the stage's device.

        ``sil_target`` is SIL_k (None for the last stage, which trains CE);
        math is identical to ``synthetic_input`` + ``build_stage_step``.
        Both tables are trailing arguments of the jitted program."""
        if k == 0:
            raise ValueError("stage 0 consumes the real batch; use "
                             "build_stage_step")
        inner = self._stage_step(k, opt, stage_params_struct, accum)
        act = self.cfg.activation_dtype()
        enc_dec = self.cfg.enc_dec

        def step(sp, st, labels, sil_in, sil_target):
            with jax.named_scope("sil_input"):
                syn = sil_lib.sil_lookup(sil_in, labels).astype(act)
            xin = (syn, None) if enc_dec else syn
            return inner(sp, st, xin, labels, None, sil_target)

        jitted = self._jit_step(step)

        def call(sp, st, labels):
            args = (sp, st, labels, sil_in, sil_target)
            if not call.registered:
                call.registered = scopes.register(
                    f"lm_parallel_stage_step.{k}", jitted, args)
            return jitted(*args)
        call.registered = False
        return call

    def build_recovery_step(self, j: int, frozen_stages: list, opt,
                            accum: int = 1):
        """End-to-end CE training of stage j, all other stages frozen.

        The frozen stages are trailing arguments of the jitted program
        (closed over, they would be embedded in it as constants)."""
        cfg, plan = self.cfg, self.plan
        others = tuple(None if k == j else p
                       for k, p in enumerate(frozen_stages))

        def step(pj, st, batch, frozen_stages):
            train, snap = self._split_frozen(pj)
            scale = precision_lib.read_loss_scale(st)

            def loss_fn(pj_, batch):
                x = batch
                aux = {}
                for k in range(self.n_stages):
                    p = {**pj_, **snap} if k == j \
                        else jax.lax.stop_gradient(frozen_stages[k])
                    x, aux = partition.stage_forward(cfg, plan, k, p, x,
                                                     shard_x=self.shard_x,
                                                     logits=False)
                loss, _ = self._objective(p, x, batch["labels"], aux,
                                          batch.get("mask"))
                return loss * scale
            loss, grads = value_and_accum_grads(loss_fn, train, (batch,),
                                                accum)
            with jax.named_scope("optimizer"):
                new_train, st2 = opt.update(grads, st, train)
            return {**new_train, **snap}, st2, loss / scale

        jitted = self._jit_step(step)

        def call(pj, st, batch):
            return jitted(pj, st, batch, others)
        return call

    def build_baseline_step(self, opt, accum: int = 1):
        """Conventional end-to-end training of the UNPARTITIONED network
        (full joined param tree through M.forward — tied embeddings train
        with gradient flowing through the unembedding, exactly as outside
        the phase API)."""
        cfg = self.cfg

        def step(params, st, batch):
            scale = precision_lib.read_loss_scale(st)

            def loss_fn(p, batch):
                logits, aux = M.forward(cfg, p, batch, shard_x=self.shard_x)
                loss, _ = losses.train_objective(
                    cfg, self._trim_vision(logits), batch["labels"], aux,
                    batch.get("mask"))
                return loss * scale
            loss, grads = value_and_accum_grads(loss_fn, params, (batch,),
                                                accum)
            p2, st2 = opt.update(grads, st, params)
            return p2, st2, loss / scale

        return self._jit_step(step)

    def boundary_dtype(self):
        """Storage dtype for materialized boundaries (= activation dtype)."""
        return np.dtype(self.cfg.activation_dtype())

    def prefix_forward(self, k: int):
        """Jitted frozen forward of stages < k — the paper's sole
        inter-partition communication."""
        cfg, plan = self.cfg, self.plan

        @jax.jit
        def fwd(prefix_params: tuple, batch):
            x = batch
            for j in range(k):
                x, _ = partition.stage_forward(cfg, plan, j, prefix_params[j],
                                               x, remat=False,
                                               shard_x=self.shard_x)
            return x
        return fwd

    def synthetic_input(self, k: int, sils, labels):
        """Fig.-5 synthetic input for stage k>0: SIL_{k-1}[:, y]."""
        syn = sil_lib.sil_lookup(sils[k - 1], labels).astype(
            self.cfg.activation_dtype())
        return (syn, None) if self.cfg.enc_dec else syn
