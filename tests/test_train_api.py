"""repro.train phase API: equivalence with the legacy trainers, the
BoundaryCache, tail-drop surfacing, and the tied-embedding fix."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get
from repro.core import losses, partition, pnn, sil as sil_lib
from repro.data.images import emnist_like
from repro.models import mlp as MLP
from repro.models import model as M
from repro.optim import make_optimizer
from repro.train import (BoundaryCache, StageSpec, TrainSpec, recipes,
                         spec_from_paper_hp)
from repro.train.backends import mlp_test_accuracy


# ==========================================================================
# Fig. 3 phase list == the hand-rolled sequential PNN loop (same seeds)
# ==========================================================================

def _reference_mlp_pnn(cfg, data, hp, key, eval_every):
    """The pre-redesign train_mlp_pnn loop, verbatim math: per-step python
    loop, float(loss) syncs, numpy concat for the boundary."""
    tx, ty, vx, vy = data
    kp, ks = jax.random.split(key)
    params = MLP.init_params(cfg, kp)
    left, right = params[:cfg.cut], params[cfg.cut:]
    sil = sil_lib.make_sil(ks, cfg.boundary_width, cfg.n_classes, hp.kappa)
    opt_l = make_optimizer("sgdm", hp.lr, momentum=hp.momentum)
    opt_r = make_optimizer("sgdm", hp.lr_right or hp.lr, momentum=hp.momentum)
    st_l, st_r = opt_l.init(left), opt_r.init(right)
    lstep, rstep = pnn._make_left_step(cfg, opt_l), \
        pnn._make_right_step(cfg, opt_r)
    macs_l = MLP.macs(cfg, 0, cfg.cut)
    macs_r = MLP.macs(cfg, cfg.cut, cfg.n_layers)
    hist = {"macs": [], "acc": [], "phase": []}
    cum = 0

    def log(phase):
        hist["macs"].append(cum)
        hist["acc"].append(mlp_test_accuracy(cfg, left + right, vx, vy))
        hist["phase"].append(phase)

    for ep in range(hp.n_left):
        for x, y in pnn._batches(tx, ty, hp.batch_size, shuffle=hp.shuffle,
                                 seed=ep):
            left, st_l, _ = lstep(left, st_l, x, y, sil)
            cum += macs_l * len(x)
        if (ep + 1) % eval_every == 0:
            log("left")

    fwd = jax.jit(lambda p, x: MLP.forward_range(cfg, p, x, 0, cfg.cut))
    stored = [np.asarray(fwd(left, x))
              for x, _ in pnn._batches(tx, ty, hp.batch_size, shuffle=False,
                                       seed=0)]
    boundary = np.concatenate(stored)
    ty_trunc = ty[: len(boundary)]

    for ep in range(hp.n_right):
        for h, y in pnn._batches(boundary, ty_trunc, hp.batch_size,
                                 shuffle=hp.shuffle, seed=100 + ep):
            right, st_r, _ = rstep(right, st_r, h, y)
            cum += macs_r * len(h)
        if (ep + 1) % eval_every == 0 or ep == hp.n_right - 1:
            log("right")

    if hp.n_recovery:
        rec_lr = hp.lr_recovery or (hp.lr_right or hp.lr) / 10.0
        opt_rec = make_optimizer("sgdm", rec_lr, momentum=hp.momentum)
        st_rec = opt_rec.init(left)

        @jax.jit
        def rec(pl, st, pr, x, y):
            def loss_fn(pl_):
                h = MLP.forward_range(cfg, pl_, x, 0, cfg.cut)
                logits = MLP.forward_range(
                    cfg, jax.lax.stop_gradient(pr), h, cfg.cut, cfg.n_layers)
                return losses.cross_entropy(logits, y)
            l, g = jax.value_and_grad(loss_fn)(pl)
            pl2, st2 = opt_rec.update(g, st, pl)
            return pl2, st2, l

        macs_full = MLP.macs(cfg)
        for ep in range(hp.n_recovery):
            for x, y in pnn._batches(tx, ty, hp.batch_size,
                                     shuffle=hp.shuffle, seed=200 + ep):
                left, st_rec, _ = rec(left, st_rec, right, x, y)
                cum += macs_full * len(x)
            log("recovery")
    return left + right, hist


@pytest.fixture(scope="module")
def small_data():
    return emnist_like(n_train=4700, n_test=940, seed=1, noise=0.5)


def test_fig3_phase_list_reproduces_sequential_pnn(small_data):
    """Trainer + fig3 phases == the bespoke loop, same seeds, same history."""
    cfg = MLP.MLPConfig(sizes=(784, 32, 16, 16, 47), cut=2)
    hp = pnn.PaperHP(n_left=2, n_right=4, n_recovery=2, batch_size=470,
                     lr=0.01, lr_right=0.003)
    key = jax.random.PRNGKey(42)
    p_ref, h_ref = _reference_mlp_pnn(cfg, small_data, hp, key, eval_every=2)
    _, hist = recipes.run_mlp_fig3(cfg, small_data, spec_from_paper_hp(hp),
                                   key, eval_every=2)
    h_new = hist.to_mlp_legacy()
    assert h_new["phase"] == h_ref["phase"]
    assert h_new["macs"] == h_ref["macs"]
    np.testing.assert_allclose(h_new["acc"], h_ref["acc"], atol=5e-3)
    # joined accuracy agrees at convergence tolerance
    assert abs(h_new["acc"][-1] - h_ref["acc"][-1]) < 5e-3


# ==========================================================================
# Fig. 5 phase list == the hand-rolled all-parallel LM loop
# ==========================================================================

def _reference_lm_parallel(cfg, plan, params, batch_fn, steps, kappa, lr,
                           key):
    """The pre-redesign pnn_parallel_train_lm loop, verbatim math."""
    keys = jax.random.split(key, plan.n_stages)
    sils = [sil_lib.make_sil(keys[k], cfg.d_model, cfg.vocab_size, kappa)
            for k in range(plan.n_stages - 1)]
    stage_params = [partition.slice_stage_params(cfg, plan, params, k)
                    for k in range(plan.n_stages)]
    opts = [make_optimizer("adamw", lr) for _ in range(plan.n_stages)]
    states = [opts[k].init(stage_params[k]) for k in range(plan.n_stages)]
    steps_fns = [pnn.build_stage_step(
        cfg, plan, k, sils[k] if k < plan.n_stages - 1 else None, opts[k])
        for k in range(plan.n_stages)]
    hist = {"stage": [], "step": [], "loss": []}
    for i in range(steps):
        batch = batch_fn(i)
        labels = batch["labels"]
        for k in range(plan.n_stages):
            if k == 0:
                xin = batch
            else:
                syn = sil_lib.sil_lookup(sils[k - 1], labels).astype(
                    cfg.activation_dtype())
                xin = (syn, None) if cfg.enc_dec else syn
            stage_params[k], states[k], loss = steps_fns[k](
                stage_params[k], states[k], xin, labels)
            hist["stage"].append(k)
            hist["step"].append(i)
            hist["loss"].append(float(loss))
    return partition.join_stage_params(cfg, plan, stage_params), hist


def test_fig5_phase_list_reproduces_parallel_lm():
    cfg = get("stablelm-3b", smoke=True)  # untied embeddings: exact parity
    plan = partition.make_plan(cfg, 2)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    from repro.data.lm import synthetic_token_stream, lm_batches
    stream = synthetic_token_stream(8000, cfg.vocab_size, seed=0)
    it = lm_batches(stream, 4, 32, seed=0)
    bs = [{k: jnp.asarray(v) for k, v in next(it).items()} for _ in range(4)]
    bf = lambda i: bs[i % 4]  # noqa: E731
    key = jax.random.PRNGKey(1)
    _, h_ref = _reference_lm_parallel(cfg, plan, params, bf, steps=4,
                                      kappa=1.0, lr=1e-3, key=key)
    spec = TrainSpec(n_stages=2, kappa=1.0,
                     stages=tuple(StageSpec(steps=4, lr=1e-3,
                                            optimizer="adamw")
                                  for _ in range(2)))
    _, hist = recipes.run_lm_parallel(cfg, plan, params, bf, spec, key)
    h_new = hist.to_lm_legacy()
    assert h_new["stage"] == h_ref["stage"]
    assert h_new["step"] == h_ref["step"]
    np.testing.assert_allclose(h_new["loss"], h_ref["loss"], rtol=1e-4,
                               atol=1e-5)


# ==========================================================================
# tail-drop surfacing
# ==========================================================================

def test_batches_tail_drop_is_surfaced(small_data):
    tx, ty = small_data[0], small_data[1]
    bs = 450                      # 4700 = 10*450 + 200 dropped
    batches = list(pnn._batches(tx, ty, bs, shuffle=False, seed=0))
    assert len(batches) == len(tx) // bs
    assert sum(len(x) for x, _ in batches) == (len(tx) // bs) * bs
    assert pnn.dropped_sample_count(len(tx), bs) == len(tx) % bs == 200

    cfg = MLP.MLPConfig(sizes=(784, 16, 16, 47), cut=1)
    hp = pnn.PaperHP(n_left=1, n_right=1, n_baseline=1, batch_size=bs,
                     lr_right=0.003)
    _, hist = pnn.train_mlp_pnn(cfg, small_data, hp, jax.random.PRNGKey(0))
    assert hist["dropped_per_epoch"] == 200   # no longer silent


# ==========================================================================
# BoundaryCache
# ==========================================================================

def test_boundary_cache_chunked_fill():
    cache = BoundaryCache()
    cache.reserve(10, (4,), np.float32)
    for i in range(5):
        cache.append(np.full((2, 4), i, np.float32))
    assert cache.n_rows == 10 and not cache.spilled
    np.testing.assert_array_equal(cache.array()[2:4], np.full((2, 4), 1))
    with pytest.raises(ValueError):
        cache.append(np.zeros((1, 4), np.float32))   # overflow guarded
    cache.close()


def test_boundary_cache_disk_spill(tmp_path):
    cache = BoundaryCache(spill_dir=str(tmp_path), spill_threshold_bytes=0)
    cache.reserve(6, (3,), np.float32)
    cache.append(np.ones((6, 3), np.float32))
    assert cache.spilled
    assert len(os.listdir(tmp_path)) == 1
    np.testing.assert_array_equal(cache.array(), np.ones((6, 3)))
    cache.close()
    assert len(os.listdir(tmp_path)) == 0   # spill file removed


# ==========================================================================
# tied-embedding join hazard (regression)
# ==========================================================================

def test_tied_unembed_is_frozen_and_join_keeps_stage0():
    cfg = get("qwen2-1.5b", smoke=True)
    assert cfg.tie_embeddings
    plan = partition.make_plan(cfg, 2)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    sp = [partition.slice_stage_params(cfg, plan, params, k) for k in (0, 1)]
    # the last stage holds a frozen snapshot, not a trainable tok_embed
    assert "tied_unembed" in sp[1] and "tok_embed" not in sp[1]
    assert "tok_embed" in sp[0]

    # gradients do not flow into the snapshot
    from conftest import make_batch
    batch = make_batch(cfg)
    h = jax.random.normal(jax.random.PRNGKey(1),
                          (2, 16, cfg.d_model), jnp.float32)

    def loss_fn(p1):
        out, _ = partition.stage_forward(cfg, plan, 1, p1, h, remat=False)
        return losses.cross_entropy(out[..., :cfg.vocab_size],
                                    batch["labels"])
    grads = jax.grad(loss_fn)(sp[1])
    assert float(jnp.abs(grads["tied_unembed"]).max()) == 0.0
    assert float(jnp.abs(grads["final_norm"]["scale"]).max()) > 0.0

    # join keeps stage 0's (trained) embedding even if the stale snapshot
    # differs — the legacy bug kept the last stage's copy
    sp[0]["tok_embed"] = sp[0]["tok_embed"] + 1.0
    joined = partition.join_stage_params(cfg, plan, sp)
    np.testing.assert_array_equal(np.asarray(joined["tok_embed"]),
                                  np.asarray(sp[0]["tok_embed"]))
    assert "tied_unembed" not in joined

    # refresh syncs the snapshot to stage 0's current embedding
    partition.refresh_tied_unembed(cfg, plan, sp)
    np.testing.assert_array_equal(np.asarray(sp[1]["tied_unembed"]),
                                  np.asarray(sp[0]["tok_embed"]))


def test_lm_baseline_phase_trains_tied_unpartitioned():
    """BaselinePhase on a tied LM is true unpartitioned training: loss
    drops and the tied embedding receives unembedding gradients."""
    from repro.train import BaselinePhase, LMBackend, Trainer
    cfg = get("qwen2-1.5b", smoke=True)
    plan = partition.make_plan(cfg, 2)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    from repro.data.lm import synthetic_token_stream, lm_batches
    stream = synthetic_token_stream(8000, cfg.vocab_size, seed=0)
    it = lm_batches(stream, 4, 32, seed=0)
    bs = [{k: jnp.asarray(v) for k, v in next(it).items()} for _ in range(4)]
    spec = TrainSpec(n_stages=2, baseline=StageSpec(steps=6, lr=1e-3,
                                                    optimizer="adamw"))
    be = LMBackend(cfg, plan, lambda i: bs[i % 4], spec)
    joined, hist = Trainer(be, spec).run([BaselinePhase()], params=params)
    ls = hist.column("loss")
    assert ls[-1] < ls[0]
    assert float(jnp.abs(joined["tok_embed"] -
                         params["tok_embed"]).max()) > 0.0


def test_tied_sequential_training_still_learns():
    """End-to-end: sequential PNN on a tied arch still trains every stage
    and produces a finite joined model with stage 0's embedding."""
    cfg = get("qwen2-1.5b", smoke=True)
    plan = partition.make_plan(cfg, 2)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    from repro.data.lm import synthetic_token_stream, lm_batches
    stream = synthetic_token_stream(8000, cfg.vocab_size, seed=0)
    it = lm_batches(stream, 4, 32, seed=0)
    bs = [{k: jnp.asarray(v) for k, v in next(it).items()} for _ in range(4)]
    pc = pnn.PNNLMConfig(n_stages=2, kappa=1.0,
                         stages=[pnn.PNNStageHP(steps=4, lr=2e-3)] * 2)
    joined, hist = pnn.pnn_train_lm(cfg, plan, params, lambda i: bs[i % 4],
                                    pc, jax.random.PRNGKey(1))
    s0 = [l for s, l in zip(hist["stage"], hist["loss"]) if s == 0]
    s1 = [l for s, l in zip(hist["stage"], hist["loss"]) if s == 1]
    assert s0[-1] < s0[0]
    assert s1[-1] < s1[0]
    assert bool(jnp.isfinite(
        M.forward(cfg, joined, bs[0])[0].astype(jnp.float32)).all())
    # stage 0 trained the embedding; the joined model keeps that copy
    assert float(jnp.abs(joined["tok_embed"] -
                         params["tok_embed"]).max()) > 0.0


# ==========================================================================
# the chunked last-stage objective and per-phase records
# ==========================================================================

@pytest.mark.parametrize("masked", [False, True])
def test_chunked_objective_matches_whole_logits(masked):
    """Chunking the unembedding + CE over tokens (padding the last chunk)
    gives the objective of the whole logits."""
    cfg = get("qwen2-1.5b", smoke=True)
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    x = jax.random.normal(ks[0], (3, 50, cfg.d_model), jnp.float32)
    w = jax.random.normal(ks[1], (cfg.d_model, cfg.vocab_size)) * 0.05
    labels = jax.random.randint(ks[2], (3, 50), 0, cfg.vocab_size)
    mask = (jax.random.uniform(ks[3], (3, 50)) > 0.3) if masked else None
    aux = {}
    whole, _ = losses.train_objective(cfg, x @ w, labels, aux, mask)
    chunked, _ = losses.chunked_train_objective(cfg, lambda h: h @ w, x,
                                                labels, aux, mask, chunk=32)
    np.testing.assert_allclose(float(chunked), float(whole), rtol=1e-5)
    g_whole = jax.grad(lambda x: losses.train_objective(
        cfg, x @ w, labels, aux, mask)[0])(x)
    g_chunk = jax.grad(lambda x: losses.chunked_train_objective(
        cfg, lambda h: h @ w, x, labels, aux, mask, chunk=32)[0])(x)
    np.testing.assert_allclose(np.asarray(g_chunk), np.asarray(g_whole),
                               rtol=1e-4, atol=1e-7)


def test_lm_stages_train_through_the_chunked_objective(monkeypatch):
    """A 4k-token step on the chip scans the CE in chunks; a 24-token chunk
    makes this CPU-sized stage-sequential run (64 tokens a step: two whole
    chunks and a padded one) take the same scan in the last stage and in
    recovery, and it gives the losses of the whole-logits path."""
    from repro.train.phases import (FrozenPrefixPhase, RecoveryPhase,
                                    SilStagePhase)
    from repro.train.trainer import Trainer
    from repro.train.backends import LMBackend
    from repro.verify import scenarios
    cfg, plan, batch_fn, spec, params = scenarios.tiny_lm(
        steps=3, n_stages=2, optimizer="sgdm", lr=0.01)

    def run():
        be = LMBackend(cfg, plan, batch_fn, spec)
        _, hist = Trainer(be, spec).run(
            [SilStagePhase(stage=0),
             FrozenPrefixPhase(stage=1, source="live"),
             RecoveryPhase(stage=0, steps=2)],
            params=params, key=jax.random.PRNGKey(1))
        return hist.column("loss")
    whole = run()
    monkeypatch.setattr(losses, "LOSS_CHUNK", 24)
    chunked = run()
    assert len(chunked) == len(whole) == 8
    assert all(np.isfinite(chunked))
    np.testing.assert_allclose(chunked, whole, rtol=1e-4)


def test_lm_sequential_records_phases_and_keeps_stages_on_host():
    """Each phase leaves a timing record, and between phases the LM stage
    params live on the host (numpy), not on the device."""
    from repro.train.phases import RecoveryPhase, SilStagePhase
    from repro.train.trainer import Trainer
    from repro.train.backends import LMBackend
    from repro.verify import scenarios
    cfg, plan, batch_fn, spec, params = scenarios.tiny_lm(steps=2,
                                                          n_stages=2)
    be = LMBackend(cfg, plan, batch_fn, spec)
    seen = []

    class Probe(SilStagePhase):
        def run(self, trainer, state):
            seen.append([type(leaf) for sp in state.stage_params
                         for leaf in jax.tree_util.tree_leaves(sp)])
            super().run(trainer, state)

    _, hist = Trainer(be, spec).run(
        [Probe(stage=0), RecoveryPhase(stage=0, steps=1), Probe(stage=0)],
        params=params, key=jax.random.PRNGKey(1))
    assert len(seen) == 2
    assert all(t is np.ndarray for types in seen for t in types)
    assert [(p["phase"], p["stage"]) for p in hist.phases] == [
        ("Probe", 0), ("RecoveryPhase", 0), ("Probe", 0)]
    assert all(p["end"] >= p["start"] for p in hist.phases)
    assert all(np.isfinite(hist.column("loss")))
