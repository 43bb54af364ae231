#!/usr/bin/env python3
"""Chip smoke test: the system's main paths on a TPU, through its entry points.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the four-chip stage-placement phase only

One chip, in order:

1. device: a TPU must be present, ``REPRO_FORCE_REF`` unset, and the kernel
   dispatch must choose Pallas for every family the phases run;
2. the paper's experiment at full fidelity (``run_paper_parity("full")``):
   the 784-80-60-60-60-47 MLP on EMNIST-balanced-sized synthetic data,
   through the SIL-MSE kernel, held to its 0.02 accuracy-gap budget;
3. qwen2-1.5b at published width, stage-sequential PNN in bf16 through
   ``repro.launch.train.main``: 4 stages, sequence 4096, a few steps per
   stage plus recovery; every loss finite, per-stage rate and peak bytes;
4. serving at the same width through ``repro.launch.serve.main`` on the
   contiguous and the paged KV pool (greedy tokens identical), and the
   decode kernels against their references at those widths.

With ``--chips 4``: qwen2-1.5b, 4 stages trained at once by
``ParallelSilPhase`` through ``dist.StageExecutor`` (one stage per chip),
each stage's loss curve compared with the same stage trained alone on
chip 0.

The last line of standard output is one JSON object,
``{"ok": true, "device": {...}}``, printed only when every phase passed.
Without a TPU (or without the repo's sources next to this file) the script
exits non-zero and prints no such line.  All data is made from seeds.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(ROOT, "src")

ARCH = "qwen2-1.5b"
SEQ = 4096
# stage count and batch for one 16 GiB v5e, from compiling every stage
# program at seq 4096 for the chip: K=2 and K=3 leave no room for stage 0's
# AdamW state next to its step; at K=4 the largest program is the recovery
# step (stage 0 trained end to end, the other stages frozen on the device),
# which the compiler accepts at batch 2 and refuses at batch 3 (23 MiB over
# the chip's 15.75 GiB)
STAGES = 4
BATCH = 2
STEPS_PER_STAGE = 3
DIST_TICKS = 3
SERVE_ARGS = ["--arch", ARCH, "--precision", "bf16", "--batch", "4",
              "--prompt-len", "64", "--new-tokens", "16"]


class SmokeFailure(Exception):
    pass


def check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


def banner(name):
    print(f"\n== {name}", flush=True)


def gib(n):
    return "n/a" if n is None else f"{n / 2**30:.2f} GiB"


def memory(ph, i):
    """Device ``i``'s memory around one ``History.phases`` record: the
    process's peak bytes in use before and after the phase, and the bytes
    still live at its end."""
    return (f"peak_bytes_in_use={gib(ph['peak_bytes'][i])} "
            f"({ph['peak_bytes'][i]}) before the phase "
            f"{gib(ph['peak_bytes_before'][i])} live at its end "
            f"{gib(ph['bytes_in_use'][i])}")


class CompileClock:
    """Host seconds JAX spends lowering and compiling programs, with the
    host clock at which each event ended — so a phase's wall time can be
    split into compilation and steps.  (Tracing is left out: its events
    nest, and it is small next to compilation.)"""

    EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self, jax):
        self.events = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event in self.EVENTS:
            self.events.append((time.perf_counter(), duration))

    def within(self, t0, t1):
        return sum(d for t, d in self.events if t0 <= t <= t1)


# --------------------------------------------------------------------------
# phases
# --------------------------------------------------------------------------

def phase_device(jax, families):
    banner("phase 1: device")
    from repro.kernels import dispatch
    check(os.environ.get("REPRO_FORCE_REF", "") != "1",
          "REPRO_FORCE_REF=1 pins the reference kernels; unset it")
    devs = jax.devices()
    print(f"platform={devs[0].platform} kind={devs[0].device_kind} "
          f"count={len(devs)}")
    for fam in families:
        d = dispatch.decide(fam)
        print(f"dispatch {fam}: use_pallas={d.use_pallas} ({d.reason})")
        check(d.use_pallas, f"{fam} dispatch chose the reference path "
                            f"({d.reason})")


def _compare(oracle_name, ref, opt, label):
    from repro.verify.oracle import Context, get
    policy = get(oracle_name).resolve_policy(Context())
    v = policy.compare(ref, opt)
    print(f"{label}: {'ok' if v.ok else 'FAIL'} vs ref "
          f"({oracle_name} tolerance) {v.detail}")
    check(v.ok, f"{label} disagrees with its reference: {v.detail}")


def phase_paper(jax):
    banner("phase 2: paper experiment, full fidelity (SIL-MSE kernel)")
    import jax.numpy as jnp
    from repro.kernels.sil_mse import ref as sm_ref
    from repro.kernels.sil_mse.ops import sil_mse
    from repro.kernels.sil_mse.kernel import sil_mse_fwd_tpu
    from repro.verify.paper import run_paper_parity
    for t, d, m, dt in ((1410, 60, 47, jnp.float32),
                        (BATCH * SEQ, 1536, 151936, jnp.bfloat16)):
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        act = jax.random.normal(ks[0], (t, d), dt)
        sil = jax.random.uniform(ks[1], (d, m), jnp.float32) * 10
        lab = jax.random.randint(ks[2], (t,), 0, m)
        loss, grad = sil_mse_fwd_tpu(act, sil, lab)
        _compare("kernel/sil_mse",
                 {"loss": sm_ref.sil_mse(act, sil, lab),
                  "grad": sm_ref.sil_mse_grad_act(act, sil, lab)
                  .astype(jnp.float32)},
                 {"loss": loss, "grad": grad.astype(jnp.float32)},
                 f"sil_mse kernel act={t}x{d} sil={d}x{m}")
        # the production entry point (custom VJP) runs the same kernel
        g = jax.grad(lambda a: sil_mse(a, sil, lab))(act)
        check(bool(jnp.isfinite(g.astype(jnp.float32)).all()),
              "non-finite SIL-MSE gradient")
    t0 = time.perf_counter()
    res = run_paper_parity("full")
    print(f"baseline_acc={res['baseline_acc']:.4f} "
          f"pnn_acc={res['pnn_acc']:.4f} gap={res['gap']:.4f} "
          f"(budget {res['budget']}, floor {res['config']['floor']}) "
          f"macs_ratio={res['macs_ratio']:.3f} "
          f"seconds={time.perf_counter() - t0:.1f}")
    check(res["ok"], f"paper parity failed: {res['detail']}")


def phase_flash(jax):
    import jax.numpy as jnp
    from repro.kernels.flash_attention import ref as fa_ref
    from repro.kernels.flash_attention.ops import flash_attention
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (1, SEQ, 12, 128), jnp.bfloat16)
    k = jax.random.normal(ks[1], (1, SEQ, 2, 128), jnp.bfloat16)
    v = jax.random.normal(ks[2], (1, SEQ, 2, 128), jnp.bfloat16)

    def loss(f):
        return lambda *a: f(*a).astype(jnp.float32).sum()
    out, grads = jax.value_and_grad(loss(flash_attention), (0, 1, 2))(q, k, v)
    ref_grads = jax.grad(loss(fa_ref.chunked_attention), (0, 1, 2))(q, k, v)
    _compare("kernel/flash_attention",
             fa_ref.naive_attention(q, k, v), flash_attention(q, k, v),
             f"flash forward q={tuple(q.shape)}")
    _compare("kernel/flash_attention_grad", ref_grads, grads,
             f"flash grad q={tuple(q.shape)}")


def phase_train(jax, clock):
    banner(f"phase 3: {ARCH} PNN stage-sequential, bf16, K={STAGES}, "
           f"batch {BATCH} x seq {SEQ}")
    from repro.launch import train
    phase_flash(jax)
    argv = ["--arch", ARCH, "--mode", "pnn", "--stages", str(STAGES),
            "--precision", "bf16", "--batch", str(BATCH), "--seq", str(SEQ),
            "--steps", str(STEPS_PER_STAGE * STAGES)]
    print("repro.launch.train", " ".join(argv), flush=True)
    hist = train.main(argv)
    losses = hist.column("loss")
    check(losses and all(math.isfinite(x) for x in losses),
          f"non-finite training loss: {losses}")
    for ph in hist.phases:
        stage = -1 if ph["phase"] == "RecoveryPhase" else ph["stage"]
        name = "recovery" if stage == -1 else f"stage {stage}"
        vals = [r.loss for r in hist.records
                if r.loss is not None and r.stage == stage
                and (stage != -1 or r.phase == "recovery")]
        wall = ph["end"] - ph["start"]
        comp = clock.within(ph["start"], ph["end"])
        steady = wall - comp
        rate = f"{len(vals) / steady:.3f}" if steady > 0 else "n/a"
        print(f"{name}: steps={len(vals)} losses="
              f"{[round(x, 4) for x in vals]} wall={wall:.1f}s "
              f"compile={comp:.1f}s steps/s(excl. compile)={rate} "
              f"{memory(ph, 0)}", flush=True)


def phase_serve(jax):
    banner(f"phase 4: serving {ARCH} at bf16, contiguous vs paged pool")
    import jax.numpy as jnp
    from repro.kernels.flash_attention import ref as fa_ref
    from repro.kernels.flash_attention.kernel import (
        decode_attention_tpu, paged_decode_attention_tpu)
    from repro.launch import serve
    toks = {}
    for pool, extra in (("contiguous", []), ("paged", ["--paged"])):
        print(f"repro.launch.serve {' '.join(SERVE_ARGS + extra)}",
              flush=True)
        toks[pool] = [list(c.tokens) for c in serve.main(SERVE_ARGS + extra)]
    same = toks["contiguous"] == toks["paged"]
    print(f"greedy tokens identical across pools: {same}")
    check(same, f"paged tokens differ: {toks}")

    b, lc, h, kv, d, bs = 4, 1024, 12, 2, 128, 16
    ks = jax.random.split(jax.random.PRNGKey(2), 4)
    q = jax.random.normal(ks[0], (b, 1, h, d), jnp.bfloat16)
    kc = jax.random.normal(ks[1], (b, lc, kv, d), jnp.bfloat16)
    vc = jax.random.normal(ks[2], (b, lc, kv, d), jnp.bfloat16)
    pos = jnp.array([5, 300, 777, lc - 1], jnp.int32)
    _compare("kernel/decode_attention", fa_ref.decode_attention(q, kc, vc, pos),
             decode_attention_tpu(q, kc, vc, pos),
             f"decode kernel cache={b}x{lc}x{kv}x{d}")
    nb = lc // bs
    n_blocks = b * nb + 1
    perm = jax.random.permutation(ks[3], jnp.arange(1, n_blocks))
    tables = perm.reshape(b, nb).astype(jnp.int32)
    kp = jnp.zeros((n_blocks, bs, kv, d), jnp.bfloat16).at[tables].set(
        kc.reshape(b, nb, bs, kv, d))
    vp = jnp.zeros((n_blocks, bs, kv, d), jnp.bfloat16).at[tables].set(
        vc.reshape(b, nb, bs, kv, d))
    _compare("kernel/decode_attention",
             fa_ref.paged_decode_attention(q, kp, vp, tables, pos,
                                           logical_len=lc),
             paged_decode_attention_tpu(q, kp, vp, tables, pos,
                                        logical_len=lc),
             f"paged decode kernel blocks={n_blocks}x{bs}")


def phase_four_chips(jax):
    banner(f"four chips: {ARCH} ParallelSilPhase through StageExecutor, "
           f"{STAGES} stages round-robin, {DIST_TICKS} ticks")
    check(len(jax.devices()) >= STAGES,
          f"need {STAGES} chips, have {len(jax.devices())}")
    from repro.configs import get
    from repro.core import partition
    from repro.data.lm import lm_batch_at, synthetic_token_stream
    from repro.launch import train
    from repro.models import model as M
    from repro.train import StageSpec, TrainSpec
    from repro.train.backends import LMBackend, make_optimizer_for
    import jax.numpy as jnp

    argv = ["--arch", ARCH, "--mode", "pnn", "--stages", str(STAGES),
            "--dist", "round_robin", "--precision", "bf16",
            "--batch", str(BATCH), "--seq", str(SEQ),
            "--steps", str(DIST_TICKS)]
    print("repro.launch.train", " ".join(argv), flush=True)
    hist = train.main(argv)
    ph = hist.phases[-1]
    for i in range(len(ph["peak_bytes"])):
        print(f"chip {i}: {memory(ph, i)}")
    dist = {k: [r.loss for r in hist.records
                if r.loss is not None and r.stage == k]
            for k in range(STAGES)}

    # the comparison: each stage alone on chip 0, same seeds, same ticks,
    # the same step programs the executor dispatches (see launch/train.py)
    cfg = get(ARCH)
    plan = partition.make_plan(cfg, STAGES)
    stream = synthetic_token_stream(1_000_000, cfg.vocab_size, seed=0)

    def batch_at(i):
        return {k: jnp.asarray(v) for k, v in
                lm_batch_at(stream, BATCH, SEQ, i).items()}
    spec = TrainSpec(n_stages=STAGES, kappa=1.0, precision="bf16",
                     stages=tuple(StageSpec(steps=DIST_TICKS, lr=3e-4,
                                            optimizer="adamw")
                                  for _ in range(STAGES)))
    be = LMBackend(cfg, plan, batch_at, spec)
    params = jax.device_get(M.init_params(be.cfg, jax.random.PRNGKey(0)))
    stage_params = be.split(params)
    del params
    sils = be.make_sils(jax.random.PRNGKey(1), spec.kappa)
    dev0 = jax.devices()[0]
    from repro.verify.oracle import Context, get as get_oracle
    policy = get_oracle("train/lm_dist_vs_sequential").resolve_policy(
        Context())
    for k in range(STAGES):
        opt = make_optimizer_for(spec.stage(k), spec)
        sp = jax.device_put(stage_params[k], dev0)
        st = opt.init(be.trainable(sp))
        sil_t = None if k == STAGES - 1 else jax.device_put(sils[k], dev0)
        if k == 0:
            fn = be.build_stage_step(0, opt, sil_t)
        else:
            fn = be.build_parallel_stage_step(
                k, opt, jax.device_put(sils[k - 1], dev0), sil_t)
        alone = []
        for i in range(DIST_TICKS):
            batch = jax.device_put(batch_at(i), dev0)
            if k == 0:
                sp, st, loss = fn(sp, st, batch, batch["labels"])
            else:
                sp, st, loss = fn(sp, st, batch["labels"])
            alone.append(float(loss))
        del sp, st, fn
        v = policy.compare(alone, dist[k])
        print(f"stage {k}: 4-chip losses={[round(x, 5) for x in dist[k]]} "
              f"alone on chip 0={[round(x, 5) for x in alone]} "
              f"{'ok' if v.ok else 'FAIL'} {v.detail}", flush=True)
        check(all(math.isfinite(x) for x in dist[k]),
              f"stage {k}: non-finite loss")
        check(v.ok, f"stage {k}: 4-chip curve differs from alone: "
                    f"{v.detail}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the four-chip stage-placement phase")
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(SRC, "repro")):
        print(f"FAIL: the repro sources are not next to this script "
              f"({SRC})", flush=True)
        return 2
    sys.path.insert(0, SRC)
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"FAIL: no TPU found (JAX sees platform "
              f"{devs[0].platform!r}); this smoke test needs a TPU chip",
              flush=True)
        return 1
    from repro.runtime import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}")
    clock = CompileClock(jax)
    t0 = time.perf_counter()
    try:
        if args.chips == 4:
            phase_device(jax, ("sil_mse", "flash_attention"))
            phase_four_chips(jax)
        else:
            phase_device(jax, ("sil_mse", "flash_attention"))
            phase_paper(jax)
            phase_train(jax, clock)
            phase_serve(jax)
    except SmokeFailure as e:
        print(f"FAIL: {e}", flush=True)
        return 1
    print(f"\nall phases passed in {time.perf_counter() - t0:.1f}s "
          f"(compile {clock.within(t0, time.perf_counter()):.1f}s)")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
