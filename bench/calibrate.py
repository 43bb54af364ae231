#!/usr/bin/env python3
"""Readings that a cell's limits are set from, all in one process.

    python3 bench/calibrate.py --workload <cell> --seeds 12 [--control 3]
        [--faults 3] [--first-seed N] [--out FILE]

For each seed: the program's first steps through the timed call and feed
(the run's ``first_steps``; every stage at once where the cell runs them
all) against the float32 reference, giving the sound runs' readings of
``loss_gap``, ``grad_gap`` and ``change_gap`` of each stage.  For the
first ``--control`` seeds also the control, the reference computed with
float8 matmul operands in the program's place, and for the first
``--faults`` seeds the program fed half of each batch, the mean taken over
the rest.  (A step that returns its state unchanged reads ``change_gap``
1 by construction and needs no run.)  One JSON line per seed and reading
goes to standard output and to ``--out``; the benchmark's own runs never
run this.
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--faults", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=3_000_000_000)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    from bench import check, harness

    cell = harness.load_cell(ROOT, args.workload)
    import jax
    harness.configure_jax_cache(ROOT)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"error: no TPU ({devices[0].platform})", file=sys.stderr)
        return 3
    out = open(args.out, "a") if args.out else None

    def emit(rec):
        line = json.dumps(rec)
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()

    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i
        t0 = time.perf_counter()
        run = harness.make_run(cell, seed, devices)
        prog = run.first_steps()
        stages, chips, batches = run.stages, run.devices, run.batches
        run.free()
        t1 = time.perf_counter()
        ref = harness.reference_readings(cell, stages, chips, batches, seed)
        t2 = time.perf_counter()
        readings = {"program": prog}
        if i < args.faults:
            half = harness.make_run(cell, seed, devices, half_batch=True)
            readings["half_batch"] = half.first_steps()
            half.free()
        if i < args.control:
            readings["control"] = harness.reference_readings(
                cell, stages, chips, batches, seed, precision="fp8")
        for kind, r in readings.items():
            nums = harness.compare(cell, r, ref)
            emit({"cell": args.workload, "seed": seed, "kind": kind,
                  **{k: v for k, (v, _) in nums.items()},
                  "at": {k: w for k, (_, w) in nums.items()},
                  "stages": {cell.prefix(k) or str(k): {
                      "losses": r[k]["losses"],
                      "ref_losses": ref[k]["losses"],
                      "leaves": len(ref[k]["grad"]),
                      "left_out": sorted(set(ref[k]["grad"])
                                         - set(check.moved_leaves(ref[k])))}
                      for k in stages},
                  "program_s": round(t1 - t0, 3),
                  "reference_s": round(t2 - t1, 3)})
    return 0


if __name__ == "__main__":
    sys.exit(main())
