"""The paper-parity experiment runner — the repo's end-to-end reproduction
gate.

The paper's central experimental claim (§3-§5): a 6-layer fully-connected
network on EMNIST-balanced, cut into two partitions after the second hidden
layer and trained with synthetic intermediate labels (left vs SIL, one
boundary materialization, right on stored activations, §5 recovery),
reaches testing accuracy similar to conventional end-to-end training at a
fraction of the memory and compute.  This module executes exactly that
comparison through the ``repro.train`` phase API and asserts the accuracy
gap stays within a budget:

* ``tiny`` — CPU-container sized (reduced data and epochs, ~1 min); the
  gate run by CI and the ``paper/emnist_parity`` oracle.  Budget is looser
  because both runs are further from convergence.
* ``full`` — the paper's own sizes (EMNIST-balanced-scale data, N_L=5,
  N_R=160, N_B=40, 10 recovery epochs).  Budget 0.02: the paper reports the
  partitioned accuracy within ~1-2 points of conventional training.

CLI:  PYTHONPATH=src python -m repro.verify.paper --preset tiny
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from dataclasses import asdict, dataclass

import jax

from repro.data.images import emnist_like
from repro.models.mlp import MLPConfig
from repro.train import recipes
from repro.verify.compare import AccuracyGap


@dataclass(frozen=True)
class PaperPreset:
    """One fidelity level of the paper's EMNIST experiment."""
    n_train: int
    n_test: int
    noise: float
    n_left: int          # N_L: left-partition epochs vs SIL
    n_right: int         # N_R: right-partition epochs on the boundary
    n_baseline: int      # N_B: conventional end-to-end epochs
    n_recovery: int      # §5 recovery epochs (stage 0, rest frozen)
    lr_recovery: float   # §5 recovery learning rate
    budget: float        # |acc_baseline - acc_pnn| ceiling
    floor: float         # baseline must at least reach this (learned at all)


PRESETS = {
    # reduced but honest: both schedules train long enough to separate a
    # learned model from chance (floor) before the gap is judged.  Budgets
    # are calibrated against the synthetic EMNIST stand-in, where the
    # conventional baseline saturates (~0.99) — harsher on PNN than the
    # paper's real-EMNIST setting (~0.85 both sides).  A quarter of the
    # data, so N_L is 4x the paper's 5 epochs: the left partition keeps the
    # paper's 400 optimizer steps against its SIL.  With 100 steps it ends
    # far from the SIL targets (boundary MSE above the targets' variance,
    # a fifth of the boundary ReLUs dead), and the right partition's
    # accuracy then swings with the seed from 0.55 to 0.98
    "tiny": PaperPreset(n_train=28200, n_test=2820, noise=0.5,
                        n_left=20, n_right=80, n_baseline=40, n_recovery=20,
                        lr_recovery=3e-4, budget=0.05, floor=0.60),
    # the paper's own schedule (§3: EMNIST-balanced sizes, §4-§5 epochs);
    # measured gap at this fidelity is ~0.001 (PNN slightly ahead), so the
    # 0.02 budget mirrors the paper's "similar testing accuracies" claim
    # with real margin
    "full": PaperPreset(n_train=112800, n_test=18800, noise=0.5,
                        n_left=5, n_right=160, n_baseline=40, n_recovery=10,
                        lr_recovery=3e-4, budget=0.02, floor=0.70),
}


def gap_policy(preset: str) -> AccuracyGap:
    p = PRESETS[preset]
    return AccuracyGap(budget=p.budget, floor=p.floor)


def run_paper_parity(preset: str = "tiny", *, seed: int = 0,
                     eval_every: int = 1000) -> dict:
    """Run baseline vs PNN (Fig. 3 + §5) and measure the accuracy gap.

    Returns a report dict; ``ok`` is the paper's claim verdict.  Both runs
    use the legacy-exact seed schedules (``recipes.run_mlp_*``), the
    paper's batch size/learning rates, and the same data."""
    p = PRESETS[preset]
    cfg = MLPConfig()                      # the paper's exact 6-layer net
    # the SYNTHETIC stand-in, always (not load_emnist): the budgets above
    # are calibrated against this exact distribution, and a stray real
    # data/emnist.npz would silently override the preset's n_train/n_test
    # and invalidate them — the gate must be deterministic everywhere
    data = emnist_like(n_train=p.n_train, n_test=p.n_test, seed=seed,
                       noise=p.noise)
    spec = recipes.paper_spec(n_left=p.n_left, n_right=p.n_right,
                              n_baseline=p.n_baseline,
                              n_recovery=p.n_recovery,
                              lr_recovery=p.lr_recovery)

    t0 = time.perf_counter()
    _, hist_b = recipes.run_mlp_baseline(cfg, data, spec,
                                         jax.random.PRNGKey(seed),
                                         eval_every=eval_every)
    t_base = time.perf_counter() - t0
    t0 = time.perf_counter()
    _, hist_p = recipes.run_mlp_fig3(cfg, data, spec,
                                     jax.random.PRNGKey(seed + 1),
                                     eval_every=eval_every)
    t_pnn = time.perf_counter() - t0

    acc_b = hist_b.column("acc")[-1]
    acc_p = hist_p.column("acc")[-1]
    macs_b = hist_b.column("macs")[-1]
    macs_p = hist_p.column("macs")[-1]
    verdict = gap_policy(preset).compare(acc_b, acc_p)
    return {
        "preset": preset,
        "config": asdict(p),
        "baseline_acc": float(acc_b),
        "pnn_acc": float(acc_p),
        "gap": abs(float(acc_b) - float(acc_p)),
        "budget": p.budget,
        "ok": verdict.ok,
        "detail": verdict.detail,
        # the paper's efficiency axis: cumulative per-sample MACs
        "baseline_macs": int(macs_b),
        "pnn_macs": int(macs_p),
        "macs_ratio": float(macs_p) / float(macs_b),
        "seconds": {"baseline": round(t_base, 1), "pnn": round(t_pnn, 1)},
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="paper-parity gate: PNN vs conventional training on "
                    "the EMNIST 6-layer / 2-partition experiment")
    ap.add_argument("--preset", default="tiny", choices=sorted(PRESETS))
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--json", default=None,
                    help="also write the report to this path")
    args = ap.parse_args(argv)

    res = run_paper_parity(args.preset, seed=args.seed)
    status = "PASS" if res["ok"] else "FAIL"
    print(f"[{status}] paper parity ({args.preset}): "
          f"baseline={res['baseline_acc']:.4f} pnn={res['pnn_acc']:.4f} "
          f"gap={res['gap']:.4f} (budget {res['budget']}) "
          f"macs_ratio={res['macs_ratio']:.2f}")
    if not res["ok"]:
        print("  " + res["detail"])
    if args.json:
        os.makedirs(os.path.dirname(args.json) or ".", exist_ok=True)
        with open(args.json, "w") as f:
            json.dump(res, f, indent=1)
        print(f"wrote {args.json}")
    return 0 if res["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
