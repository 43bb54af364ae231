"""Chaos sweep CLI: drive the fault matrix through the supervised executor
and report every cell into ``results/RESILIENCE_8.json``.

Each cell injects one fault family (or a seeded mixed schedule) into a
2-stage EMNIST-like run under ``resilience.SupervisedExecutor`` and checks
the recovery guarantee that applies:

* crash / transient / ckpt_corruption / straggler / mixed — the recovered
  run must be **bitwise equal** to the fault-free reference (the paper's
  zero-communication property makes per-stage replay exact).
* nan — the step guard must skip exactly the poisoned steps and leave the
  final params finite (a skipped step is *absent*, not approximated, so
  there is no fault-free twin to compare against).

Time is a ``FakeClock`` everywhere: backoff and straggler delays advance a
counter, so the whole matrix is deterministic and fast enough for CI.

Usage:
  PYTHONPATH=src python -m repro.launch.chaos --preset tiny \
      [--seed 0] [--json results/RESILIENCE_8.json]

Exit status is non-zero when any cell has an unrecovered fault or a failed
equivalence — CI gates on it.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile

import jax
import jax.numpy as jnp
import numpy as np

from repro.runtime import enable_compile_cache

SCHEMA = "repro.resilience/1"

TINY = {"n_ticks": 3, "n_train": 256, "batch_size": 64, "mixed_seeds": (0,)}
FULL = {"n_ticks": 6, "n_train": 1024, "batch_size": 128,
        "mixed_seeds": (0, 1, 2)}
PRESETS = {"tiny": TINY, "full": FULL}


def _world(preset: dict, *, nan_guard: bool = False):
    """(backend, stage_params, sils, hps, spec) for the 2-stage cell setup —
    identical across cells so the fault is the only variable."""
    from dataclasses import replace

    from repro.models import mlp as MLP
    from repro.train.backends import MLPBackend, balanced_bounds
    from repro.verify import scenarios
    cfg, data, spec = scenarios.tiny_mlp(
        n_stages=2, epochs=(preset["n_ticks"],) * 2,
        n_train=preset["n_train"], batch_size=preset["batch_size"])
    if nan_guard:
        spec = replace(spec, nan_guard=True)
    be = MLPBackend(cfg, data, spec, bounds=balanced_bounds(cfg, 2))
    params = MLP.init_params(cfg, jax.random.PRNGKey(0))
    sils = be.make_sils(jax.random.PRNGKey(3), spec.kappa)
    hps = [spec.stage(k) for k in range(2)]
    return be, be.split(params), sils, hps, spec


def _executor(world, root):
    from repro.dist import placement
    from repro.dist.executor import StageExecutor
    from repro.train.backends import make_optimizer_for
    be, sp0, sils, hps, spec = world
    opts = [make_optimizer_for(hp, spec) for hp in hps]
    return StageExecutor(be, placement.round_robin(2), sp0, sils, opts, hps,
                         shuffle=True, ckpt_dir=root)


def _bitwise_equal(a, b) -> bool:
    for x, y in zip(jax.tree_util.tree_leaves(a),
                    jax.tree_util.tree_leaves(b)):
        if not np.array_equal(np.asarray(x), np.asarray(y)):
            return False
    return True


def _cell_schedules(preset: dict, seed: int):
    """The fault matrix: (cell name, schedule, needs nan_guard)."""
    from repro.resilience import (CheckpointCorruption, FaultSchedule,
                                  NaNInjection, StageCrash, StragglerDelay,
                                  TransientError)
    n_ticks = preset["n_ticks"]
    mid = max(1, n_ticks // 2)
    cells = [
        ("crash", FaultSchedule([StageCrash(stage=1, tick=mid)]), False),
        ("transient", FaultSchedule(
            [TransientError(stage=0, tick=1, failures=2)]), False),
        ("ckpt_corruption/truncate_manifest", FaultSchedule(
            [CheckpointCorruption(stage=0, tick=mid,
                                  mode="truncate_manifest")]), False),
        ("ckpt_corruption/truncate_npz", FaultSchedule(
            [CheckpointCorruption(stage=1, tick=mid,
                                  mode="truncate_npz")]), False),
        ("ckpt_corruption/flip_bytes", FaultSchedule(
            [CheckpointCorruption(stage=0, tick=mid,
                                  mode="flip_bytes")]), False),
        ("straggler", FaultSchedule(
            [StragglerDelay(stage=1, tick=1, delay=1.5)]), False),
        # both on stage 0: MLP stages k>0 take sil_lookup(sils[k-1], y) as
        # input (int labels), so a poisoned float x never reaches them
        ("nan", FaultSchedule(
            [NaNInjection(stage=0, tick=1),
             NaNInjection(stage=0, tick=2, value=float("nan"))]), True),
    ]
    for s in preset["mixed_seeds"]:
        # mixed schedules stay bitwise-comparable: nan is excluded because
        # a guarded skip has no fault-free twin (it gets its own cell)
        cells.append((f"mixed/seed{seed + s}", FaultSchedule.sample(
            seed + s, n_stages=2, n_ticks=n_ticks, n_faults=3,
            kinds=("crash", "transient", "ckpt_corruption", "straggler")),
            False))
    return cells


def run_matrix(preset_name: str, seed: int, workdir: str) -> dict:
    from repro.optim import read_skipped
    from repro.resilience import FakeClock, RetryPolicy, SupervisedExecutor
    preset = PRESETS[preset_name]
    n_ticks = preset["n_ticks"]

    world = _world(preset)
    ref_ex = _executor(world, os.path.join(workdir, "ref"))
    ref_ex.run(n_ticks)
    ref = ref_ex.gather()

    cells = []
    for name, schedule, needs_guard in _cell_schedules(preset, seed):
        w = _world(preset, nan_guard=True) if needs_guard else world
        root = os.path.join(workdir, name.replace("/", "_"))
        ex = _executor(w, root)
        clk = FakeClock()
        sup = SupervisedExecutor(ex, schedule=schedule, clock=clk.monotonic,
                                 sleep=clk.sleep, ckpt_every=1,
                                 policy=RetryPolicy(max_retries=5, seed=seed),
                                 strict=False)
        sup.run(n_ticks)
        got = ex.gather()
        report = sup.report()
        if needs_guard:
            skipped = sum(int(jax.device_get(read_skipped(o)))
                          for o in ex.opt_states)
            n_inject = len(schedule.faults)
            finite = all(bool(jnp.all(jnp.isfinite(leaf)))
                         for p in got
                         for leaf in jax.tree_util.tree_leaves(p))
            ok = (skipped == n_inject and finite and not sup.unrecovered)
            equivalence = "skip-count"
            detail = {"skipped": skipped, "expected": n_inject,
                      "finite": finite}
        else:
            equal = _bitwise_equal(ref, got)
            ok = equal and not sup.unrecovered and not report["never_fired"]
            equivalence = "bitwise-vs-fault-free"
            detail = {"bitwise_equal": equal}
        cells.append({
            "cell": name,
            "ok": bool(ok),
            "equivalence": equivalence,
            "faults": schedule.describe(),
            "faults_seen": report["faults_seen"],
            "unrecovered": report["unrecovered"],
            "never_fired": report["never_fired"],
            "final_ticks": report["ticks"],
            **detail,
        })
        status = "PASS" if ok else "FAIL"
        print(f"[{status}] {name:36s} faults={len(schedule.faults)} "
              f"seen={len(report['faults_seen'])} "
              f"unrecovered={len(report['unrecovered'])}")

    n_failed = sum(not c["ok"] for c in cells)
    n_unrecovered = sum(len(c["unrecovered"]) for c in cells)
    return {
        "schema": SCHEMA,
        "preset": preset_name,
        "seed": seed,
        "env": {
            "jax": jax.__version__,
            "backend": jax.default_backend(),
            "n_devices": len(jax.devices()),
        },
        "n_ticks": n_ticks,
        "n_cells": len(cells),
        "n_passed": len(cells) - n_failed,
        "n_failed": n_failed,
        "n_unrecovered_faults": n_unrecovered,
        "cells": cells,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="sweep the resilience fault matrix through the "
                    "supervised executor")
    ap.add_argument("--preset", default="tiny", choices=sorted(PRESETS))
    ap.add_argument("--seed", type=int, default=0,
                    help="base seed for the sampled mixed schedules")
    ap.add_argument("--json", default="results/RESILIENCE_8.json",
                    help="report path ('' disables)")
    args = ap.parse_args(argv)
    enable_compile_cache()

    print(f"# repro.resilience chaos sweep: preset={args.preset} "
          f"seed={args.seed}")
    with tempfile.TemporaryDirectory(prefix="chaos_") as workdir:
        report = run_matrix(args.preset, args.seed, workdir)
    print(f"# {report['n_passed']}/{report['n_cells']} cells passed, "
          f"{report['n_unrecovered_faults']} unrecovered faults")
    if args.json:
        os.makedirs(os.path.dirname(args.json) or ".", exist_ok=True)
        with open(args.json, "w") as f:
            json.dump(report, f, indent=1)
        print(f"# wrote {args.json}")
    return 1 if (report["n_failed"] or report["n_unrecovered_faults"]) else 0


if __name__ == "__main__":
    sys.exit(main())
