"""Every stage of a configuration at once, stage k on chip k: the paper's
Fig. 5 deployment, driven through the program's ``dist.StageExecutor`` as
``ParallelSilPhase`` drives it (its public constructor and ``tick``), with
a ``round_robin`` placement over the cell's chips.

The run knows the model only through its family module: each stage's
parameters (``stage_params``), the program's backend and optimizers
(``program``), what a step is fed and takes (``step_inputs``,
``step_args``) and the change of its parameters (``change_norms``).  The
SIL tables and seed keys are ``bench/common.py``'s; SIL_j is made on chip
j, and the executor copies it to chip j + 1, whose input it is.

One tick is one window step: every stage's step dispatched, each on its
own chip; the tick's batch is built on the host in the ``input`` span and
handed to the executor as its backend's ``batch_fn``.  The executor keeps
each dispatched loss in ``_pending`` and each stage's step in ``_fns``
until it finalises; the run reads both (the losses to wait on and check,
the steps for their ``memory_analysis``), as nothing public exposes them.
"""
from __future__ import annotations

import functools
import gc
import math
import time
from typing import Dict, List

import jax
from jax.sharding import SingleDeviceSharding

from bench import common
from bench.traffic import Batches, Traffic


class DeploymentRun:
    """Set-up, the timed tick and its feed, for all stages and one seed.

    ``half_batch`` plants a fault for the checks of the comparison: every
    stage is fed the first half of each batch's rows."""

    def __init__(self, family, cfg, traffic: Traffic, seed: int, devices, *,
                 half_batch: bool = False):
        from repro.dist import StageExecutor, round_robin

        self.family, self.cfg, self.traffic, self.seed = \
            family, cfg, traffic, seed
        self.stages = traffic.stages(cfg.stages)
        if len(devices) < len(self.stages):
            raise RuntimeError(f"{len(self.stages)} stages need as many "
                               f"chips; {len(devices)} given")
        self.devices = list(devices[:len(self.stages)])
        self.batches = Batches(traffic, cfg.vocab_size, seed)
        self.rows = traffic.batch // 2 if half_batch else traffic.batch
        self._host = None          # the host batch of the tick to dispatch
        self.losses: List[list] = []
        self.input_s: List[float] = []

        be, opts, hps = family.program(cfg, self._batch_fn)
        self.key_params, key_tables = common.seed_keys(seed)
        params = [family.stage_params(cfg, k, self.key_params, dev)
                  for k, dev in zip(self.stages, self.devices)]
        make = jax.jit(functools.partial(common.make_table, cfg),
                       static_argnums=0)
        sils = [make(j, jax.device_put(key_tables, self.devices[j]))
                for j in self.stages[:-1]]
        self.table_shape = (sils[0].shape, sils[0].dtype) if sils else None
        self.ex = StageExecutor(be, round_robin(len(self.stages),
                                                self.devices),
                                params, sils, opts, hps)

    # -- the timed path ----------------------------------------------------

    def _batch_fn(self, i: int):
        if self._host is None or self._host[0] != i:
            raise RuntimeError(f"tick {i} dispatched before it was fed")
        return self._host[1]

    def _inputs(self, i: int) -> dict:
        b = self.batches.host(i)
        if self.rows != self.traffic.batch:
            b = {n: a[:self.rows] for n, a in b.items()}
        return b

    def feed(self, i: int) -> int:
        """Tick i's batch, built on the host; the executor puts each
        stage's share of it on its chip when it dispatches the tick."""
        t0 = time.perf_counter()
        self._host = (i, self._inputs(i))
        self.input_s.append(time.perf_counter() - t0)
        return i

    def dispatch(self, i: int):
        self.ex.tick(i)
        losses = self.ex._pending[-len(self.stages):]
        self.losses.append(losses)
        return losses

    def wait(self):
        """Block until every stage has completed the tick before the last
        one dispatched, so two ticks at most are in flight."""
        jax.block_until_ready(self.losses[-2])

    def drain(self):
        jax.block_until_ready((self.ex.params, self.ex.opt_states,
                               self.losses[-1]))

    def window_losses(self, n_check: int) -> List[float]:
        """Each window tick's loss: the largest stage loss, or NaN where a
        stage's is not finite."""
        out = []
        for tick in jax.device_get(self.losses[n_check:]):
            vals = [float(v) for v in tick]
            out.append(max(vals) if all(map(math.isfinite, vals))
                       else math.nan)
        return out

    def step(self, i: int):
        return self.dispatch(self.feed(i))

    # -- set-up readings ---------------------------------------------------

    def temp_bytes(self) -> List[int]:
        """Per chip, ``temp_size_in_bytes`` of its stage's compiled step,
        lowered again at the timed arguments (``common.temp_bytes``)."""
        out = []
        b = self._inputs(0)
        for k, dev in zip(self.stages, self.devices):
            x = jax.device_put(self.family.step_inputs(k, b), dev)
            shape, dtype = self.table_shape
            tables = {j: jax.ShapeDtypeStruct(
                shape, dtype, sharding=SingleDeviceSharding(dev))
                for j in common.table_ids(self.cfg, k)}
            out.append(common.temp_bytes(self.ex._fns[k], self.family.step_args(
                k, self.ex.params[k], self.ex.opt_states[k], x, tables)))
        return out

    def first_steps(self) -> Dict[int, dict]:
        """{stage: readings} of the checked first ticks through the timed
        call and feed, stage by stage (see ``StageRun.first_steps``)."""
        n = self.traffic.check_steps
        grads = None
        for i in range(n):
            self.step(i)
            if i == 0:
                grads = [common.first_grad_norms(st, self.cfg.b1)
                         for st in self.ex.opt_states]
        out = {}
        for j, k in enumerate(self.stages):
            change = self.family.change_norms(self.cfg, k, self.ex.params[j],
                                              self.key_params)
            out[k] = common.host_readings(
                [tick[j] for tick in self.losses[:n]], grads[j], change)
        return out

    def free(self):
        """Delete every device array of the run, so the references that
        follow have the chips to themselves."""
        ex = self.ex
        for tree in (ex.params, ex.opt_states, self.losses):
            for leaf in jax.tree_util.tree_leaves(tree):
                if isinstance(leaf, jax.Array) and not leaf.is_deleted():
                    leaf.delete()
        self.ex = None
        self.losses = []
        gc.collect()        # the tables live in the executor's step closures
