"""The concurrent multi-device stage executor.

``StageExecutor`` turns a backend's stage list plus a ``PlacementPlan`` into
a genuinely device-placed program:

* **Pin once, up front** — each stage's params are ``jax.device_put`` onto
  its assigned device; the optimizer state is initialized FROM those
  committed buffers (so it materializes on the same device); the SIL tables
  a stage reads are replicated onto its device.  JAX's committed-data rule
  then compiles each stage's jitted step for that device — the modern
  spelling of ``jax.jit(..., device=)`` (deprecated in favor of placement
  via the data).
* **No host sync inside a tick** — ``tick(i)`` dispatches every due stage's
  step and returns; XLA's async dispatch lets the per-device programs
  overlap.  LM losses accumulate as device-resident scalars and drain in
  ONE transfer at ``finalize`` (the PR-1 contract); MLP ticks are whole
  scanned epochs per stage.
* **Independent per-stage progress** — ``ticks[k]`` counts how far stage k
  has advanced.  ``run(n, stages=[k])`` replays only stage k (deterministic
  data access by tick index), which is how a failed stage catches up after
  ``resume_stage(k)`` without perturbing the others.

Equivalence contract: with every stage placed on one device this executes
the exact ``ParallelSilPhase`` schedule; spread across devices the per-stage
programs are unchanged (same HLO per step), so results stay allclose to the
sequential path — pinned by tests/test_dist.py under 8 forced host devices.
"""
from __future__ import annotations

from typing import List, Optional, Sequence

import jax

from repro.dist import lifecycle
from repro.dist.placement import PlacementPlan
from repro.obs.metrics import LOSS_BUCKETS
from repro.obs.registry import MetricsRegistry
from repro.obs.trace import TID_STAGE0, Tracer
from repro.train.backends import scanned_epoch_fn


class StageExecutor:
    """Runs all stages of one backend concurrently per the placement plan."""

    def __init__(self, backend, placement: PlacementPlan,
                 stage_params: Sequence, sils: Sequence, opts: Sequence,
                 hps: Sequence, *, seed_base: int = 0, shuffle: bool = True,
                 ckpt_dir: Optional[str] = None, ckpt_every: int = 0,
                 ckpt_keep_last: Optional[int] = None,
                 metrics: Optional[MetricsRegistry] = None,
                 tracer: Optional[Tracer] = None):
        placement.validate(backend.n_stages)
        self.be = backend
        self.placement = placement
        self.opts = list(opts)
        self.hps = list(hps)
        self.seed_base = seed_base
        self.shuffle = shuffle
        self.ckpt_dir = ckpt_dir
        self.ckpt_every = int(ckpt_every or 0)
        self.ckpt_keep_last = ckpt_keep_last
        # fault-injection seam (repro.resilience): when set, every stage's
        # input batch passes through ``batch_hook(stage, tick, batch)``
        # before dispatch.  Deterministic data access by (stage, tick) is
        # what makes an injected fault — and its replay — reproducible
        self.batch_hook = None
        n = self.n = backend.n_stages
        self.devices = [placement.device_for(k) for k in range(n)]
        # pin per-stage state to its device ONCE; everything downstream
        # (optimizer init, step dispatch) follows the committed buffers
        self.params = [jax.device_put(stage_params[k], self.devices[k])
                       for k in range(n)]
        self.opt_states = [self.opts[k].init(backend.trainable(self.params[k]))
                           for k in range(n)]
        self.ticks: List[int] = [0] * n
        self.cum_macs = 0
        self._global_ticks = 0
        # metrics high-water mark per stage: a replayed tick (after
        # resume_stage) re-runs the math but must not re-log its loss or
        # re-count its MACs — finalize would double-report otherwise.
        # obs observes sit INSIDE this guard for the same reason (drain
        # after replay must not double-count; pinned in tests/test_obs.py)
        self._metrics_upto: List[int] = [0] * n
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        self.tracer = tracer if tracer is not None else Tracer()
        self._loss_hist = self.metrics.device_histogram(
            "train_loss", LOSS_BUCKETS,
            help="per-step training loss (device-accumulated)")
        self._ticks_counter = self.metrics.counter(
            "executor_ticks_total", help="dispatched stage ticks, by stage")
        self._pending: list = []
        self._logged_steps: list = []
        self._logged_stages: list = []
        if backend.kind == "mlp":
            # each stage's scanned-epoch program reads SILs replicated on
            # its own device (cross-device constants would refuse to mix
            # with the committed params)
            sils_dev = [jax.device_put(list(sils), d) for d in self.devices]
            self._fns = [scanned_epoch_fn(backend.build_parallel_step(
                k, self.opts[k], sils_dev[k], accum=self.hps[k].accum))
                for k in range(n)]
        else:
            self._fns = []
            for k in range(n):
                dev = self.devices[k]
                sil_t = None if k == n - 1 else jax.device_put(sils[k], dev)
                if k == 0:
                    self._fns.append(backend.build_stage_step(
                        0, self.opts[0], sil_t, accum=self.hps[0].accum))
                else:
                    sil_in = jax.device_put(sils[k - 1], dev)
                    self._fns.append(backend.build_parallel_stage_step(
                        k, self.opts[k], sil_in, sil_t,
                        accum=self.hps[k].accum))

    # -- tick dispatch -----------------------------------------------------

    def _duration(self, k: int) -> int:
        hp = self.hps[k]
        return hp.epochs if self.be.kind == "mlp" else hp.steps

    def tick(self, i: int, stages: Optional[Sequence[int]] = None) -> None:
        """Dispatch tick `i` (epoch for MLP, step for LM) to every listed
        stage that is exactly at tick `i` and still within its duration.
        Returns without any host synchronization."""
        ks = range(self.n) if stages is None else stages
        ks = [k for k in ks if self.ticks[k] == i and i < self._duration(k)]
        if not ks:
            return
        if self.be.kind == "mlp":
            self._tick_mlp(i, ks)
        else:
            self._tick_lm(i, ks)
        self._global_ticks = max(self._global_ticks, i + 1)

    def _tick_mlp(self, ep: int, ks: Sequence[int]) -> None:
        be = self.be
        batches = be.epoch_arrays(self.seed_base + ep, self.shuffle)
        n_samples = batches[0].shape[0] * batches[0].shape[1]
        for k in ks:
            bk = batches if self.batch_hook is None \
                else self.batch_hook(k, ep, batches)
            bk = jax.device_put(bk, self.devices[k])
            with self.tracer.span(f"tick {ep}", cat="stage",
                                  tid=TID_STAGE0 + k, stage=k, tick=ep):
                self.params[k], self.opt_states[k], losses = self._fns[k](
                    self.params[k], self.opt_states[k], bk)
            if ep >= self._metrics_upto[k]:
                self.cum_macs += be.stage_macs(k) * n_samples
                self._loss_hist.observe_device(losses)
                self._ticks_counter.inc(1, stage=k)
                self._metrics_upto[k] = ep + 1
            self.ticks[k] = ep + 1

    def _tick_lm(self, i: int, ks: Sequence[int]) -> None:
        be = self.be
        batch = be.batch_fn(i)
        for k in ks:
            dev = self.devices[k]
            bk = batch if self.batch_hook is None \
                else self.batch_hook(k, i, batch)
            with self.tracer.span(f"tick {i}", cat="stage",
                                  tid=TID_STAGE0 + k, stage=k, tick=i):
                if k == 0:
                    b0 = jax.device_put(bk, dev)
                    self.params[0], self.opt_states[0], loss = self._fns[0](
                        self.params[0], self.opt_states[0], b0, b0["labels"])
                else:
                    labels = jax.device_put(bk["labels"], dev)
                    self.params[k], self.opt_states[k], loss = self._fns[k](
                        self.params[k], self.opt_states[k], labels)
            if i >= self._metrics_upto[k]:
                self._pending.append(loss)
                self._loss_hist.observe_device(loss)
                self._ticks_counter.inc(1, stage=k)
                self._logged_steps.append(i)
                self._logged_stages.append(k)
                self._metrics_upto[k] = i + 1
            self.ticks[k] = i + 1

    def run(self, n_ticks: int, stages: Optional[Sequence[int]] = None
            ) -> "StageExecutor":
        """Advance the listed stages (default: all) up to ``n_ticks``,
        checkpointing every ``ckpt_every`` ticks when a ``ckpt_dir`` is
        configured.  Resumed stages start from their own tick counter."""
        ks = list(range(self.n)) if stages is None else list(stages)
        start = min(self.ticks[k] for k in ks)
        for i in range(start, n_ticks):
            self.tick(i, stages=ks)
            if self.ckpt_dir and self.ckpt_every \
                    and (i + 1) % self.ckpt_every == 0:
                self.checkpoint(stages=ks)
        return self

    # -- lifecycle ---------------------------------------------------------

    def checkpoint(self, stages: Optional[Sequence[int]] = None) -> None:
        """One manifest per stage, at each stage's OWN tick counter."""
        if not self.ckpt_dir:
            raise ValueError("executor built without ckpt_dir")
        for k in (range(self.n) if stages is None else stages):
            lifecycle.save_stage(
                self.ckpt_dir, k, self.ticks[k], self.params[k],
                self.opt_states[k],
                metadata={"device": str(self.devices[k]),
                          "placement": self.placement.strategy,
                          "kind": self.be.kind},
                keep_last=self.ckpt_keep_last)

    def resume_stage(self, k: int, step: Optional[int] = None) -> int:
        """Reload stage k (params + optimizer state + tick counter) from its
        own checkpoints, committed back onto its assigned device.  The other
        stages' live state is untouched; follow with ``run(n, stages=[k])``
        to replay the lost ticks."""
        params, opt_state, tick = lifecycle.restore_stage(
            self.ckpt_dir, k, like_params=self.params[k],
            like_opt=self.opt_states[k], step=step, device=self.devices[k])
        self.params[k], self.opt_states[k] = params, opt_state
        self.ticks[k] = tick
        return tick

    # -- drain / handoff ---------------------------------------------------

    def gather(self) -> list:
        """Per-stage params pulled to host (ONE blocking point, at the end —
        committed buffers on different devices must not feed a joint op)."""
        return [jax.device_get(p) for p in self.params]  # repro: allow-host-sync

    def finalize(self, trainer, state, phase_name: str = "parallel") -> None:
        """Hand results back to the TrainState: params pulled to the host
        (so joins, eval, and later phases never mix committed devices), the
        pending device-resident losses flushed in one transfer, counters
        folded in."""
        state.stage_params = self.gather()
        state.cum_macs += self.cum_macs
        self.cum_macs = 0
        if self.be.kind == "mlp":
            state.history.log(phase=phase_name, stage=-1,
                              step=state.step_idx, macs=state.cum_macs,
                              acc=self.be.eval_joined(state.stage_params))
        else:
            state.step_idx += self._global_ticks
            trainer.flush_losses(state, self._pending, self._logged_steps,
                                 phase_name, self._logged_stages)
            self._pending, self._logged_steps, self._logged_stages = \
                [], [], []
        # NaN/inf-guard telemetry: one host read per stage, at the single
        # blocking point the executor already has
        for k in range(self.n):
            trainer.note_skipped(state, self.opt_states[k], phase_name, k)
        # executor-join flush boundary: fold the device-resident metric
        # accumulators into their host series (idempotent)
        self.metrics.drain()
