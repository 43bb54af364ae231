"""The phase-sequence Trainer.

``Trainer(backend, spec).run(phases, params=...)`` executes a list of
``repro.train.phases`` objects over shared mutable ``TrainState`` and returns
the joined parameters plus a unified ``History``.  Every legacy trainer is a
short phase list (see ``repro.train.recipes``):

    Fig. 3     [SilStagePhase(0), BoundaryMaterializePhase(1),
                FrozenPrefixPhase(1), RecoveryPhase(0)]
    baseline   [BaselinePhase()]
    Fig. 5     [ParallelSilPhase()]

The loop drivers here implement the perf contract: the MLP backend's epochs
run as one jitted ``lax.scan`` per epoch (device-resident losses, donated
carry), and the LM backend's step loop never blocks on a loss — device
scalars are collected and fetched in a single transfer when the phase ends.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp

from repro.obs.metrics import LOSS_BUCKETS
from repro.obs.registry import MetricsRegistry
from repro.obs.trace import TID_LOOP, Tracer
from repro.optim import read_skipped
from repro.train.backends import scanned_epoch_fn
from repro.train.history import History


class SkippedStepBudgetExceeded(RuntimeError):
    """More optimizer steps were NaN/inf-skipped than
    ``TrainSpec.max_skipped_steps`` allows — the run is diverging, not
    hiccuping, so it aborts loudly instead of burning compute on a
    params-frozen loop."""


@dataclass
class TrainState:
    stage_params: List[Any]
    sils: List[Any] = field(default_factory=list)
    history: History = field(default_factory=History)
    boundary: Dict[str, Any] = field(default_factory=dict)
    cum_macs: int = 0
    step_idx: int = 0          # global LM optimizer-step counter (batch_fn arg)
    skipped_steps: int = 0     # NaN/inf-guarded steps skipped (all stages)


class Trainer:
    """Runs any phase sequence over an MLP or transformer backend."""

    def __init__(self, backend, spec, *,
                 metrics: Optional[MetricsRegistry] = None,
                 tracer: Optional[Tracer] = None):
        """metrics/tracer (repro.obs, optional): a ``MetricsRegistry`` for
        the trainer's series (defaults to a private one) and a ``Tracer``
        for phase spans.  The loss histogram is **device-resident** — loop
        drivers observe the device scalars the step already returns (a few
        lazily-dispatched ops, no sync) and it drains only at the flush
        boundaries the loop already has (``flush_losses`` / end of
        ``run``)."""
        self.backend = backend
        self.spec = spec
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        self.tracer = tracer if tracer is not None else Tracer()
        self._loss_hist = self.metrics.device_histogram(
            "train_loss", LOSS_BUCKETS,
            help="per-step training loss (device-accumulated)")
        self._skipped = self.metrics.counter(
            "train_skipped_steps_total",
            help="NaN/inf-guarded optimizer steps skipped, by phase[stage]")

    def run(self, phases: Sequence, *, params, sils: Optional[list] = None,
            key=None):
        """Execute `phases` starting from full `params`.

        `sils`: per-cut SIL tables; derived from `key` via the backend's
        legacy-compatible schedule when omitted and any phase needs them.
        Returns (joined_params, History).
        """
        needs_sil = any(getattr(p, "needs_sil", False) for p in phases)
        if sils is None and needs_sil:
            if key is None:
                raise ValueError("phases need SIL tables: pass sils= or key=")
            sils = self.backend.make_sils(key, self.spec.kappa)
        state = TrainState(stage_params=self.backend.split(params),
                           sils=sils or [])
        if getattr(self.backend, "dropped_per_epoch", 0):
            # tail-drop is silent no more: surface it in every history
            # AND in the metrics schema (satellite of history.meta)
            state.history.meta["dropped_per_epoch"] = \
                self.backend.dropped_per_epoch
            self.metrics.gauge(
                "train_dropped_per_epoch",
                help="samples tail-dropped per epoch by batching").set(
                    self.backend.dropped_per_epoch)
        for phase in phases:
            peak0 = _device_stat("peak_bytes_in_use")
            t0 = time.perf_counter()
            with self.tracer.span(type(phase).__name__, cat="phase",
                                  tid=TID_LOOP):
                phase.run(self, state)
            state.history.phases.append({
                "phase": type(phase).__name__,
                "stage": getattr(phase, "stage", None),
                "start": t0, "end": time.perf_counter(),
                "peak_bytes_before": peak0,
                "peak_bytes": _device_stat("peak_bytes_in_use"),
                "bytes_in_use": _device_stat("bytes_in_use")})
        for cache in state.boundary.values():
            if hasattr(cache, "close"):
                cache.close()
        self.metrics.drain()     # end-of-run flush boundary (idempotent)
        return self.backend.join(state.stage_params), state.history

    # ------------------------------------------------------------------
    # loop drivers (used by the phases)
    # ------------------------------------------------------------------

    def drive_epochs(self, state: TrainState, *, step, train_params,
                     opt_state, epochs: int, phase_name: str, stage: int,
                     macs_per_sample: int, seed_base: int, log_mode: str,
                     eval_fn=None, batch_arrays=None,
                     shuffle: Optional[bool] = None):
        """MLP driver: one jitted scan per epoch over stacked batches.

        batch_arrays(ep) -> tuple of (nb, bs, ...) arrays; defaults to the
        backend dataset.  eval_fn(train_params) -> joined-network accuracy
        (the paper's y-axis); defaults to substituting the in-flight stage
        into the current stage list.  log_mode: 'cadence' | 'cadence+last'
        | 'every' (the three cadences the legacy trainers used)."""
        be = self.backend
        shuffle = be.spec.shuffle if shuffle is None else shuffle
        if batch_arrays is None:
            def batch_arrays(ep):
                return be.epoch_arrays(seed_base + ep, shuffle)
        if eval_fn is None:
            def eval_fn(tp):
                sp = list(state.stage_params)
                sp[stage] = tp
                return be.eval_joined(sp)
        epoch_fn = scanned_epoch_fn(step)
        eval_every = be.spec.eval_every
        for ep in range(epochs):
            batches = batch_arrays(ep)
            train_params, opt_state, losses = epoch_fn(train_params,
                                                       opt_state, batches)
            # device-side: bucket the epoch's per-batch losses without a sync
            self._loss_hist.observe_device(losses)
            n_samples = batches[0].shape[0] * batches[0].shape[1]
            state.cum_macs += macs_per_sample * n_samples
            log = (log_mode == "every"
                   or (ep + 1) % eval_every == 0
                   or (log_mode == "cadence+last" and ep == epochs - 1))
            if log:
                state.history.log(phase=phase_name, stage=stage,
                                  step=state.step_idx, macs=state.cum_macs,
                                  acc=eval_fn(train_params))
        self.note_skipped(state, opt_state, phase_name, stage)
        return train_params, opt_state

    def drive_steps(self, state: TrainState, *, step, inputs_fn,
                    n_steps: int, phase_name: str, stage: int,
                    train_params, opt_state, advance_global: bool = True):
        """LM driver: python step loop, losses collected as device scalars
        and fetched in ONE transfer at the end (async dispatch preserved)."""
        pending, steps_logged = [], []
        for _ in range(n_steps):
            args = inputs_fn(state.step_idx)
            train_params, opt_state, loss = step(train_params, opt_state,
                                                 *args)
            self._loss_hist.observe_device(loss)
            pending.append(loss)
            steps_logged.append(state.step_idx)
            if advance_global:
                state.step_idx += 1
        self.flush_losses(state, pending, steps_logged, phase_name, stage)
        self.note_skipped(state, opt_state, phase_name, stage)
        return train_params, opt_state

    def note_skipped(self, state: TrainState, opt_state, phase_name,
                     stage) -> None:
        """End-of-phase skipped-step telemetry (repro.resilience).

        The NaN/inf guard counts skips in a device-resident int32 inside the
        jitted step; this is the single sanctioned host read of it, at phase
        granularity — the hot loop never syncs.  Raises
        ``SkippedStepBudgetExceeded`` past ``spec.max_skipped_steps``."""
        counter = read_skipped(opt_state)
        if counter is None:
            return
        skipped = int(jax.device_get(counter))  # repro: allow-host-sync
        if not skipped:
            return
        per_phase = state.history.meta.setdefault("skipped_steps", {})
        key = f"{phase_name}[{stage}]"
        # counters are cumulative per opt_state; record the high-water mark
        # so replayed/repeated reads of the same state don't double-count —
        # the metrics counter advances by the same high-water delta
        prev = per_phase.get(key, 0)
        if skipped > prev:
            self._skipped.inc(skipped - prev, phase=key)
        per_phase[key] = max(prev, skipped)
        state.skipped_steps = sum(per_phase.values())
        budget = getattr(self.spec, "max_skipped_steps", None)
        if budget is not None and state.skipped_steps > budget:
            raise SkippedStepBudgetExceeded(
                f"{state.skipped_steps} non-finite optimizer steps skipped "
                f"(> budget {budget}) by phase {phase_name!r} stage {stage}: "
                "the run is diverging — lower the lr, raise the loss scale, "
                "or raise TrainSpec.max_skipped_steps")

    def flush_losses(self, state: TrainState, pending: list,
                     steps_logged: list, phase_name, stage) -> None:
        """One device->host transfer (per device) for a phase's loss curve.

        The pending scalars may live on DIFFERENT devices (repro.dist
        places each stage's step on its own device) and committed buffers
        refuse to stack across devices — so scalars are stacked per
        device-group and fetched in one transfer each.  The common
        single-device case keeps the exact legacy one-stack-one-transfer
        path."""
        if not pending:
            return
        groups: dict = {}
        for idx, leaf in enumerate(pending):
            dev = tuple(sorted(map(str, leaf.devices()))) \
                if isinstance(leaf, jax.Array) else None
            groups.setdefault(dev, []).append(idx)
        if len(groups) == 1:
            values = jax.device_get(jnp.stack(pending))  # repro: allow-host-sync
        else:
            values = [None] * len(pending)
            for idxs in groups.values():
                got = jax.device_get(  # repro: allow-host-sync
                    jnp.stack([pending[i] for i in idxs]))
                for j, i in enumerate(idxs):
                    values[i] = got[j]
        stages = stage if isinstance(stage, list) else [stage] * len(pending)
        names = phase_name if isinstance(phase_name, list) \
            else [phase_name] * len(pending)
        for name, st, i, v in zip(names, stages, steps_logged, values):
            state.history.log(phase=name, stage=st, step=i, loss=float(v))
        # this is already a sanctioned sync point — drain the device-resident
        # metrics accumulated since the last flush (idempotent)
        self.metrics.drain()


def _device_stat(key: str) -> list:
    """Each local device's ``memory_stats()[key]`` (None without stats)."""
    out = []
    for d in jax.local_devices():
        stats = d.memory_stats()
        out.append(stats.get(key) if stats else None)
    return out
