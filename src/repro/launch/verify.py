"""Conformance sweep CLI: run the `repro.verify` oracle registry and emit a
machine-readable report into ``results/``.

Every registered equivalence contract (kernel == reference, concurrent ==
sequential, batched == sequential decode, bf16 ~= fp32, resume ==
uninterrupted, staged == joined, paper parity) runs under one (preset,
arch) context; arch-aware oracles sweep any ``repro.configs`` entry.

Usage:
  PYTHONPATH=src python -m repro.launch.verify --preset tiny \
      [--arch qwen2-1.5b] [--only serve] [--tags kernel,serve] [--list] \
      [--json results/CONFORMANCE_5.json]

Exit status is non-zero when any oracle fails — CI gates on it.
"""
from __future__ import annotations

import argparse
import sys

from repro.configs import ARCH_NAMES
from repro.runtime import enable_compile_cache
from repro.verify import Context, all_oracles, run_oracle, write_report
from repro.verify.oracle import PRESETS


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="sweep the repro.verify conformance oracles")
    ap.add_argument("--preset", default="tiny", choices=sorted(PRESETS))
    ap.add_argument("--arch", default="qwen2-1.5b", choices=ARCH_NAMES,
                    help="repro.configs entry for arch-aware oracles "
                         "(serve / LM-train contracts)")
    ap.add_argument("--only", default=None,
                    help="substring filter on oracle names")
    ap.add_argument("--tags", default=None,
                    help="comma-separated tag filter (kernel, train, "
                         "serve, dist, precision, checkpoint, paper)")
    ap.add_argument("--list", action="store_true",
                    help="list matching oracles and exit")
    ap.add_argument("--json", default="results/CONFORMANCE_5.json",
                    help="conformance report path ('' disables)")
    args = ap.parse_args(argv)
    enable_compile_cache()

    oracles = all_oracles(tags=args.tags.split(",") if args.tags else None)
    if args.only:
        oracles = [o for o in oracles if args.only in o.name]
    if not oracles:
        print("no oracles match the filter", file=sys.stderr)
        return 2
    if args.list:
        for o in oracles:
            arch = " [arch-aware]" if o.arch_aware else ""
            print(f"{o.name:38s} tags={','.join(o.tags)}{arch}")
            print(f"  {o.contract}")
        return 0

    ctx = Context(preset=args.preset, arch=args.arch)
    print(f"# repro.verify sweep: preset={args.preset} arch={args.arch} "
          f"({len(oracles)} oracles)")
    results = []
    for o in oracles:
        res = run_oracle(o, Context(preset=ctx.preset, arch=ctx.arch))
        results.append(res)
        status = "PASS" if res.ok else "FAIL"
        line = f"[{status}] {o.name:38s} {res.seconds:7.1f}s"
        if res.verdict is not None and res.verdict.metrics:
            interesting = {k: v for k, v in res.verdict.metrics.items()
                           if k in ("max_abs_err", "gap", "n_tokens",
                                    "n_leaves", "n_sequences")}
            if interesting:
                line += "  " + " ".join(
                    f"{k}={v:.3g}" if isinstance(v, float) else f"{k}={v}"
                    for k, v in interesting.items())
        print(line)
        if not res.ok:
            print("  " + (res.error or res.verdict.detail).strip()
                  .replace("\n", "\n  "))

    n_failed = sum(not r.ok for r in results)
    print(f"# {len(results) - n_failed}/{len(results)} oracles passed")
    if args.json:
        write_report(args.json, results, preset=args.preset, arch=args.arch)
        print(f"# wrote {args.json}")
    return 1 if n_failed else 0


if __name__ == "__main__":
    sys.exit(main())
