"""Benchmark harness entry point: one function per paper figure plus the
wall-clock microbenches of the core training paths.

Prints ``name,us_per_call,derived`` CSV (one line per benchmark) and writes
the same rows — plus the fp32-vs-reduced-precision pairs — as machine-
readable JSON (``results/BENCH_4.json``, uploaded as a CI artifact so the
perf trajectory persists across PRs).  The paper figures run in reduced mode
here (minutes on CPU); ``python -m benchmarks.paper_figures --full``
reproduces the paper-fidelity versions.  Roofline tables come from ``python
-m benchmarks.roofline`` (reads the dry-run JSON).

The ``dist`` group (sequential-vs-concurrent stage ticks + per-device
bytes) needs 8 forced host devices, so it runs ``repro.dist.bench`` in a
subprocess with ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` —
this process keeps its single real CPU device.

Usage:
  python benchmarks/run.py [--only core,precision,dist] [--precision bf16]
      [--json results/BENCH_4.json]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# repo root (for `import benchmarks.*` when run as a script) + src
sys.path.insert(0, _ROOT)
sys.path.insert(0, os.path.join(_ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402


def _timeit(fn, *args, reps=5, warmup=2):
    for _ in range(warmup):
        r = fn(*args)
    jax.block_until_ready(r)
    t0 = time.perf_counter()
    for _ in range(reps):
        r = fn(*args)
    jax.block_until_ready(r)
    return (time.perf_counter() - t0) / reps * 1e6  # us


def bench_figures():
    from benchmarks import paper_figures as F
    rows = []
    spec = [
        ("fig1_weight_randomness",
         lambda r: f"max_w_std={r['max_weight_std']:.4f};"
                   f"persists={r['randomness_persists']}"),
        ("fig6_pnn_vs_baseline",
         lambda r: f"pnn={r['pnn_acc_mean']:.3f}+-{r['pnn_acc_std']:.3f};"
                   f"base={r['baseline_acc_mean']:.3f}"),
        ("fig7_nl_sweep",
         lambda r: ";".join(f"k{k.split('=')[1]}:"
                            + "/".join(f"{a:.2f}" for _, a in v)
                            for k, v in r.items())),
        ("fig8_kappa_sweep",
         lambda r: "optimum=" + str(r["optimum_exists"]) + ";" + ";".join(
             f"k{k}={a:.2f}" for k, a in r["sweep"])),
        ("fig9_kappa_lr_equivalence",
         lambda r: f"r2={r['r2']:.3f}"),
        ("fig10_recovery",
         lambda r: f"right={r['acc_after_right']:.3f};"
                   f"rec={r['acc_after_recovery']:.3f};"
                   f"improves={r['recovery_improves']}"),
    ]
    for name, derive in spec:
        t0 = time.time()
        res = F.ALL_FIGURES[name](full=False)
        us = (time.time() - t0) * 1e6
        rows.append((name, us, derive(res)))
    return rows


def bench_core_paths():
    """Wall-clock per-call microbenches of the production step builders."""
    from repro.configs import get
    from repro.core import partition
    from repro.launch.steps import (build_decode_step, build_pnn_stage_step,
                                    build_prefill_step, build_train_step)
    from repro.models import model as M
    from repro.optim import make_optimizer

    rows = []
    cfg = get("qwen2-1.5b", smoke=True)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    opt = make_optimizer("adamw", 1e-3)
    state = opt.init(params)
    batch = {"tokens": jnp.ones((4, 128), jnp.int32),
             "labels": jnp.ones((4, 128), jnp.int32)}
    step = jax.jit(build_train_step(cfg, opt))
    us = _timeit(step, params, state, batch)
    toks = 4 * 128
    rows.append(("train_step_smoke", us, f"tokens_per_s={toks/us*1e6:.0f}"))

    prefill = jax.jit(build_prefill_step(cfg, cache_len=160))
    us = _timeit(prefill, params, {"tokens": batch["tokens"]})
    rows.append(("prefill_smoke", us, f"tokens_per_s={toks/us*1e6:.0f}"))

    _, cache, pos = prefill(params, {"tokens": batch["tokens"]})
    decode = jax.jit(build_decode_step(cfg))
    tok = jnp.ones((4,), jnp.int32)
    us = _timeit(decode, params, cache, tok, pos)
    rows.append(("decode_step_smoke", us, f"tokens_per_s={4/us*1e6:.0f}"))

    plan = partition.make_plan(cfg, 2)
    sp = partition.slice_stage_params(cfg, plan, params, 0)
    sopt = make_optimizer("adamw", 1e-3)
    sstate = sopt.init(sp)
    sil = jnp.ones((cfg.d_model, cfg.vocab_padded), jnp.float32)
    sstep = jax.jit(build_pnn_stage_step(cfg, plan, 0, sopt))
    us = _timeit(sstep, sp, sstate, {"tokens": batch["tokens"]},
                 batch["labels"], sil)
    rows.append(("pnn_stage0_step_smoke", us,
                 f"tokens_per_s={toks/us*1e6:.0f}"))
    return rows


def bench_train_api():
    """Scan-based epochs (repro.train) vs the legacy per-step python loop
    with a blocking float(loss) host sync — the quickstart MLP baseline
    workload.  Derived column reports steps/s for both and the speedup."""
    from repro.data.images import emnist_like
    from repro.models import mlp as MLP
    from repro.models.mlp import MLPConfig
    from repro.core import losses
    from repro.optim import make_optimizer
    from repro.train import MLPBackend, StageSpec, TrainSpec
    from repro.train.backends import scanned_epoch_fn

    cfg = MLPConfig()
    data = emnist_like(n_train=28200, n_test=470, seed=0, noise=0.5)
    tx, ty = data[0], data[1]
    epochs = 3
    spec = TrainSpec(batch_size=1410,
                     baseline=StageSpec(epochs=epochs, lr=0.01,
                                        optimizer="sgdm"))
    be = MLPBackend(cfg, data, spec)
    n_steps = be.batches_per_epoch * epochs
    params0 = MLP.init_params(cfg, jax.random.PRNGKey(0))
    opt = make_optimizer("sgdm", 0.01, momentum=0.9)

    @jax.jit
    def step(p, s, x, y):
        def loss_fn(p_):
            return losses.cross_entropy(
                MLP.forward_range(cfg, p_, x, 0, cfg.n_layers), y)
        l, g = jax.value_and_grad(loss_fn)(p)
        p2, s2 = opt.update(g, s, p)
        return p2, s2, l

    def fresh_params():
        # per-call copy: scanned_epoch_fn donates its inputs on accelerators
        return jax.tree_util.tree_map(jnp.copy, params0)

    def legacy_loop():
        """The pre-redesign inner loop: python batches + per-step host sync."""
        params = fresh_params()
        st = opt.init(params)
        bs = spec.batch_size
        n = be.samples_per_epoch
        for ep in range(epochs):
            for i in range(0, n, bs):
                params, st, loss = step(params, st, tx[i:i + bs],
                                        ty[i:i + bs])
                float(loss)              # the old per-step host sync
        return params

    epoch_fn = scanned_epoch_fn(be.build_baseline_step(opt))
    batches = be.epoch_arrays(0, shuffle=False)

    def scan_loop():
        params = fresh_params()
        st = opt.init(params)
        for ep in range(epochs):
            params, st, _ = epoch_fn(params, st, batches)
        return params

    def timed(fn):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        return time.perf_counter() - t0

    # interleaved min-of-reps: both loops see the same scheduler noise
    legacy_loop(), scan_loop()   # warmup/compile
    us_legacy = us_scan = float("inf")
    for _ in range(5):
        us_legacy = min(us_legacy, timed(legacy_loop) * 1e6)
        us_scan = min(us_scan, timed(scan_loop) * 1e6)
    sps_legacy = n_steps / us_legacy * 1e6
    sps_scan = n_steps / us_scan * 1e6
    return [("mlp_epoch_legacy_hostsync", us_legacy,
             f"steps_per_s={sps_legacy:.0f}"),
            ("mlp_epoch_scan_device_metrics", us_scan,
             f"steps_per_s={sps_scan:.0f};speedup={us_legacy/us_scan:.2f}x")]


def bench_serve():
    """Engine (fused scan decode, continuous batching) vs the legacy script
    loop (python per-token decode with host-side sampling) on the smoke
    config.  Derived column reports decode_toks_per_s for both."""
    import jax.numpy as jnp
    from repro.configs import get
    from repro.launch.steps import build_decode_step, build_prefill_step
    from repro.models import model as M
    from repro.serve import Engine, GenerationConfig, Request

    cfg = get("qwen2-1.5b", smoke=True)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    batch, prompt_len, new_tokens = 4, 64, 32
    toks = np.random.RandomState(0).randint(
        0, cfg.vocab_size, size=(batch, prompt_len)).astype(np.int32)

    # -- legacy: the pre-engine serve.py inner loop, verbatim shape ---------
    lc = prompt_len + new_tokens
    prefill = jax.jit(build_prefill_step(cfg, cache_len=lc))
    decode = jax.jit(build_decode_step(cfg))

    def legacy():
        logits, cache, pos = prefill(params, {"tokens": jnp.asarray(toks)})
        key = jax.random.PRNGKey(0)
        tok = jnp.argmax(logits[:, : cfg.vocab_size], -1).astype(jnp.int32)
        for i in range(new_tokens - 1):
            key, _ = jax.random.split(key)
            logits, cache = decode(params, cache, tok, pos + i)
            tok = jnp.argmax(logits[:, : cfg.vocab_size], -1).astype(jnp.int32)
        return tok

    # -- engine: same requests through repro.serve --------------------------
    engine = Engine(cfg, params, max_slots=batch)
    gen = GenerationConfig(max_new_tokens=new_tokens)
    requests = [Request(tokens=toks[i], gen=gen) for i in range(batch)]

    def timed(fn):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        return time.perf_counter() - t0

    # interleaved min-of-reps: both paths see the same scheduler noise
    legacy(), engine.generate(requests)          # warmup/compile
    us_legacy = us_engine = float("inf")
    for _ in range(5):
        us_legacy = min(us_legacy, timed(legacy) * 1e6)
        us_engine = min(us_engine, timed(lambda: engine.generate(requests))
                        * 1e6)
    n = batch * new_tokens
    rows = [("serve_decode_legacy_loop", us_legacy,
             f"decode_toks_per_s={n/us_legacy*1e6:.0f}"),
            ("serve_decode_engine", us_engine,
             f"decode_toks_per_s={n/us_engine*1e6:.0f};"
             f"speedup={us_legacy/us_engine:.2f}x")]

    # continuous batching over mixed lengths/durations (legacy loops cannot
    # express this shape at all)
    mixed = [Request(tokens=toks[i, : 16 + 16 * i],
                     gen=GenerationConfig(max_new_tokens=8 + 8 * i))
             for i in range(batch)]
    eng2 = Engine(cfg, params, max_slots=2)
    eng2.generate(mixed)                         # warmup/compile
    us_mixed = min(timed(lambda: eng2.generate(mixed)) * 1e6
                   for _ in range(3))
    nm = sum(8 + 8 * i for i in range(batch))
    rows.append(("serve_batch_mixed_2slots", us_mixed,
                 f"decode_toks_per_s={nm/us_mixed*1e6:.0f}"))
    return rows


def bench_kernels():
    from repro.kernels.flash_attention.kernel import flash_attention_tpu
    from repro.kernels.flash_attention import ref as fa_ref
    from repro.kernels.sil_mse.kernel import sil_mse_fwd_tpu
    rows = []
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (1, 512, 4, 64), jnp.float32)
    k = jax.random.normal(ks[1], (1, 512, 2, 64), jnp.float32)
    v = jax.random.normal(ks[2], (1, 512, 2, 64), jnp.float32)
    us_ref = _timeit(lambda: fa_ref.chunked_attention(q, k, v), reps=3)
    rows.append(("flash_attention_jnp_ref", us_ref, "512tok_interpret_basis"))
    us_pal = _timeit(lambda: flash_attention_tpu(q, k, v), reps=1, warmup=1)
    rows.append(("flash_attention_pallas_interpret", us_pal,
                 "correctness_mode_not_perf"))
    act = jax.random.normal(ks[0], (2048, 256), jnp.float32)
    sil = jax.random.uniform(ks[1], (256, 1024)) * 10
    lab = jax.random.randint(ks[2], (2048,), 0, 1024)
    us = _timeit(lambda: sil_mse_fwd_tpu(act, sil, lab), reps=1, warmup=1)
    rows.append(("sil_mse_pallas_interpret", us, "fused_loss+grad"))
    return rows


def bench_precision(precision="bf16"):
    """fp32 vs reduced-precision pairs for the three serving/training hot
    paths (train step, prefill, decode) on the smoke config.

    The paired rows land in the BENCH json so the precision win (a ~2x
    activation/cache-bandwidth cut, structural on real accelerators) is
    tracked across PRs.  On this 2-core CPU container XLA emulates bf16
    matmuls, so wall-clock parity — not speedup — is the expected outcome
    here; the memory halving is asserted directly (cache bytes).
    """
    from repro.configs import get
    from repro.launch.steps import (build_decode_step, build_prefill_step,
                                    build_train_step)
    from repro.models import model as M
    from repro.optim import make_optimizer
    from repro.precision import get_policy, tree_bytes

    base = get("qwen2-1.5b", smoke=True)
    params = M.init_params(base, jax.random.PRNGKey(0))
    batch = {"tokens": jnp.ones((4, 128), jnp.int32),
             "labels": jnp.ones((4, 128), jnp.int32)}
    toks = 4 * 128
    rows, pairs = [], {}

    def run_policy(name):
        cfg = get_policy(name).apply_to_model(base)
        opt = make_optimizer("adamw", 1e-3)
        state = opt.init(params)
        step = jax.jit(build_train_step(cfg, opt))
        t_us = _timeit(step, params, state, batch)
        prefill = jax.jit(build_prefill_step(cfg, cache_len=160))
        p_us = _timeit(prefill, params, {"tokens": batch["tokens"]})
        _, cache, pos = prefill(params, {"tokens": batch["tokens"]})
        decode = jax.jit(build_decode_step(cfg))
        tok = jnp.ones((4,), jnp.int32)
        d_us = _timeit(decode, params, cache, tok, pos)
        return {"train_step": t_us, "prefill": p_us, "decode": d_us,
                "cache_bytes": int(tree_bytes(cache))}

    r32 = run_policy("fp32")
    rlo = run_policy(precision)
    for path, n_tok in (("train_step", toks), ("prefill", toks),
                        ("decode", 4)):
        tps32 = n_tok / r32[path] * 1e6
        tpslo = n_tok / rlo[path] * 1e6
        ratio = tpslo / tps32
        rows.append((f"{path}_fp32", r32[path],
                     f"tokens_per_s={tps32:.0f}"))
        rows.append((f"{path}_{precision}", rlo[path],
                     f"tokens_per_s={tpslo:.0f};vs_fp32={ratio:.2f}x"))
        pairs[path] = {"fp32_us": r32[path], f"{precision}_us": rlo[path],
                       "tokens_per_s_fp32": tps32,
                       f"tokens_per_s_{precision}": tpslo,
                       "ratio_vs_fp32": ratio}
    cache_ratio = r32["cache_bytes"] / max(rlo["cache_bytes"], 1)
    rows.append((f"kv_cache_bytes_{precision}", float(rlo["cache_bytes"]),
                 f"fp32_bytes={r32['cache_bytes']};"
                 f"reduction={cache_ratio:.2f}x"))
    pairs["kv_cache_bytes"] = {"fp32": r32["cache_bytes"],
                               precision: rlo["cache_bytes"],
                               "reduction": cache_ratio}
    return rows, pairs


def bench_dist():
    """Sequential-vs-concurrent stage ticks (repro.dist) under 8 forced
    host devices — in a subprocess, because the device count is fixed at
    first backend touch and this process must stay single-device.  The
    child always runs with ``JAX_PLATFORMS=cpu``: it measures forced host
    devices, and on a TPU host this process already holds the chip."""
    import subprocess
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(_ROOT, "src") + os.pathsep \
        + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-m", "repro.dist.bench"],
                         capture_output=True, text=True, env=env)
    if out.returncode != 0:
        raise RuntimeError(f"repro.dist.bench failed:\n{out.stderr[-2000:]}")
    payload = json.loads(out.stdout)
    return [(r["name"], r["us"], r["derived"]) for r in payload["rows"]]


GROUPS = {
    "core": lambda a: bench_core_paths(),
    "train_api": lambda a: bench_train_api(),
    "serve": lambda a: bench_serve(),
    "kernels": lambda a: bench_kernels(),
    "figures": lambda a: bench_figures(),
    "dist": lambda a: bench_dist(),
    "precision": None,  # handled specially (also returns pairs)
}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--only", default=None,
                    help="comma-separated bench groups "
                         f"({','.join(GROUPS)}); default: all")
    ap.add_argument("--precision", default="bf16",
                    choices=["bf16", "fp16"],
                    help="reduced-precision side of the precision pairs")
    ap.add_argument("--json", default="results/BENCH_4.json",
                    help="machine-readable output path ('' disables)")
    args = ap.parse_args(argv)
    selected = list(GROUPS) if not args.only else args.only.split(",")
    for g in selected:
        if g not in GROUPS:
            raise SystemExit(f"unknown group {g!r}; choose from "
                             f"{','.join(GROUPS)}")

    all_rows, pairs = [], {}
    print("name,us_per_call,derived")
    for g in selected:
        if g == "precision":
            rows, pairs = bench_precision(args.precision)
        else:
            rows = GROUPS[g](args)
        for name, us, derived in rows:
            print(f"{name},{us:.0f},{derived}", flush=True)
            all_rows.append({"name": name, "us": us, "derived": derived,
                             "group": g})

    if args.json:
        os.makedirs(os.path.dirname(args.json) or ".", exist_ok=True)
        payload = {
            "bench_schema": 1,
            "backend": jax.default_backend(),
            "precision": args.precision,
            "groups": selected,
            "rows": all_rows,
            "precision_pairs": pairs,
            # CPU context note: bf16 matmuls are emulated on this container,
            # so the wall-clock pairs document parity; the bandwidth/memory
            # win (cache bytes halved) is the structural signal
            "note": ("ratios measured on CPU are structural-parity checks; "
                     "bf16 throughput >= fp32 is expected on TPU/GPU where "
                     "reduced precision maps to hardware"),
        }
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=1)
        print(f"# wrote {args.json}", file=sys.stderr)


if __name__ == "__main__":
    main()
