"""Mixed-precision policy invariants (repro.precision).

* params keep param_dtype through training under any policy
* norms / attention-softmax / residual adds accumulate in fp32
* loss_scale=1 wrapped steps bit-match unscaled steps
* dynamic loss scaling halves on overflow (step skipped) and regrows
* Pallas kernels + refs take compute-dtype inputs with fp32 accumulators,
  cross-checked under REPRO_FORCE_REF
* paper-MLP smoke accuracy under bf16 within 1% of fp32
* serve engine: batched == sequential token identity under a bf16 cache
* StageSpec.accum: accumulated fp32 grads match the single-shot step
* dtype-aware memory accounting: bf16 halves activation/cache byte estimates
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import precision as P
from repro.configs import get
from repro.models import mlp as MLP
from repro.models import model as M
from repro.optim import make_optimizer, mixed_precision
from repro.train import (BaselinePhase, BoundaryMaterializePhase, MLPBackend,
                         StageSpec, Trainer, TrainSpec)
from repro.train.trainer import TrainState

KEY = jax.random.PRNGKey(0)


# ==========================================================================
# policy object
# ==========================================================================

def test_policy_presets():
    bf16 = P.get_policy("bf16")
    assert bf16.compute_jnp == jnp.bfloat16
    assert bf16.param_jnp == jnp.float32
    assert bf16.accum_jnp == jnp.float32
    assert not bf16.wraps_optimizer          # full exponent range, no scale
    fp16 = P.get_policy("fp16")
    assert fp16.wraps_optimizer and fp16.dynamic_scale
    assert P.get_policy(None).name == "fp32"
    assert P.get_policy(bf16) is bf16
    with pytest.raises(ValueError):
        P.get_policy("int4")


def test_apply_to_model_keeps_param_dtype():
    cfg = get("qwen2-1.5b", smoke=True)
    out = P.get_policy("fp16").apply_to_model(cfg)
    assert out.dtype == "float16" and out.param_dtype == cfg.param_dtype
    assert P.dtype_itemsize(out.dtype) == 2
    assert P.dtype_itemsize("float32") == 4


def test_cast_floating_skips_ints():
    tree = {"w": jnp.ones((2,), jnp.float32), "i": jnp.ones((2,), jnp.int32)}
    out = P.get_policy("bf16").cast_compute(tree)
    assert out["w"].dtype == jnp.bfloat16 and out["i"].dtype == jnp.int32


# ==========================================================================
# fp32 accumulation invariants in the model blocks
# ==========================================================================

def test_norm_stats_accumulate_fp32():
    """With d=8192 a bf16-accumulated mean-square would be off by far more
    than one bf16 ulp; the fp32-stats norm stays within rounding."""
    from repro.models import layers as L
    d = 8192
    x = jax.random.normal(KEY, (2, 4, d), jnp.float32)
    p = {"scale": jnp.ones((d,), jnp.float32)}
    ref = L.norm_apply(p, x)
    out = L.norm_apply(p, x.astype(jnp.bfloat16))
    # atol covers bf16 input/output rounding only (~4e-2 at |y|~4); bf16
    # accumulation of the d=8192 mean-square would miss by ~0.5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref), atol=4e-2)
    assert out.dtype == jnp.bfloat16


def test_residual_add_promotes():
    from repro.models.layers import residual_add
    x = jax.random.normal(KEY, (64,), jnp.float32)
    y = jax.random.normal(jax.random.PRNGKey(1), (64,), jnp.float32)
    out = residual_add(x.astype(jnp.bfloat16), y.astype(jnp.bfloat16))
    assert out.dtype == jnp.bfloat16
    exp = (x.astype(jnp.bfloat16).astype(jnp.float32)
           + y.astype(jnp.bfloat16).astype(jnp.float32)).astype(jnp.bfloat16)
    np.testing.assert_array_equal(np.asarray(out, np.float32),
                                  np.asarray(exp, np.float32))
    # fp32 inputs take the untouched legacy path
    assert residual_add(x, y).dtype == jnp.float32


def test_params_stay_param_dtype_under_bf16_train():
    from repro.launch.steps import build_train_step
    cfg = P.get_policy("bf16").apply_to_model(get("qwen2-1.5b", smoke=True))
    params = M.init_params(cfg, KEY)
    opt = make_optimizer("adamw", 1e-3)
    state = opt.init(params)
    batch = {"tokens": jnp.ones((2, 16), jnp.int32),
             "labels": jnp.ones((2, 16), jnp.int32)}
    step = jax.jit(build_train_step(cfg, opt))
    params, state, metrics = step(params, state, batch)
    for leaf in jax.tree_util.tree_leaves(params):
        if jnp.issubdtype(leaf.dtype, jnp.floating):
            assert leaf.dtype == jnp.dtype(cfg.param_dtype)
    assert np.isfinite(float(metrics["ce"]))


# ==========================================================================
# loss scaling / master weights (optim.mixed_precision)
# ==========================================================================

def _mlp_setup(precision, optimizer="sgdm", loss_scale=None):
    from repro.data.images import emnist_like
    cfg = MLP.MLPConfig(sizes=(784, 32, 16, 16, 47), cut=2)
    data = emnist_like(n_train=1880, n_test=470, seed=0, noise=0.5)
    spec = TrainSpec(batch_size=470, precision=precision,
                     baseline=StageSpec(epochs=1, lr=0.01,
                                        optimizer=optimizer))
    return cfg, data, spec


def test_loss_scale_one_bitmatches_unscaled():
    """mixed_precision(loss_scale=1) must be bit-exact with the raw
    optimizer: dividing by 1.0 and an always-true select are exact."""
    cfg, data, spec = _mlp_setup(None)
    be = MLPBackend(cfg, data, spec)
    params0 = MLP.init_params(cfg, KEY)
    batches = be.epoch_arrays(0, shuffle=False)

    def run(opt):
        params = jax.tree_util.tree_map(jnp.copy, params0)
        st = opt.init(params)
        step = be.build_baseline_step(opt)
        for i in range(batches[0].shape[0]):
            params, st, loss = step(params, st, batches[0][i], batches[1][i])
        return params, loss

    p_plain, l_plain = run(make_optimizer("sgdm", 0.01, momentum=0.9))
    p_mp, l_mp = run(mixed_precision(
        make_optimizer("sgdm", 0.01, momentum=0.9), loss_scale=1.0))
    assert float(l_plain) == float(l_mp)
    for a, b in zip(jax.tree_util.tree_leaves(p_plain),
                    jax.tree_util.tree_leaves(p_mp)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_dynamic_scale_overflow_skips_and_halves():
    opt = mixed_precision(make_optimizer("sgdm", 0.1, momentum=0.0),
                          loss_scale=8.0, dynamic=True, growth_interval=2)
    params = {"w": jnp.ones((4,), jnp.float32)}
    st = opt.init(params)
    bad = {"w": jnp.full((4,), jnp.inf, jnp.float32)}
    p1, st1 = opt.update(bad, st, params)
    np.testing.assert_array_equal(np.asarray(p1["w"]), np.asarray(params["w"]))
    assert float(st1["loss_scale"]) == 4.0
    assert int(st1["good_steps"]) == 0
    # scaled finite grads: update applies the UNSCALED gradient
    good = {"w": jnp.full((4,), 4.0 * 0.5, jnp.float32)}  # 0.5 at scale 4
    p2, st2 = opt.update(good, st1, params)
    np.testing.assert_allclose(np.asarray(p2["w"]),
                               np.asarray(params["w"]) - 0.1 * 0.5, rtol=1e-6)
    assert int(st2["good_steps"]) == 1
    _, st3 = opt.update(good, st2, p2)
    assert float(st3["loss_scale"]) == 8.0       # regrown after 2 clean steps
    assert int(st3["good_steps"]) == 0


def test_master_weights_for_half_params():
    params = {"w": jnp.ones((8,), jnp.float16)}
    opt = mixed_precision(make_optimizer("adamw", 1e-2), loss_scale=2.0)
    st = opt.init(params)
    assert st["master"]["w"].dtype == jnp.float32
    g = {"w": jnp.full((8,), 2.0 * 1e-3, jnp.float16)}
    p1, st1 = opt.update(g, st, params)
    assert p1["w"].dtype == jnp.float16          # storage dtype preserved
    # master moved even though the fp16 rounding of the step may be tiny
    assert float(jnp.abs(st1["master"]["w"] - 1.0).max()) > 0


def test_sgdm_momentum_is_fp32():
    opt = make_optimizer("sgdm", 0.01, momentum=0.9)
    st = opt.init({"w": jnp.ones((4,), jnp.bfloat16)})
    assert st["mu"]["w"].dtype == jnp.float32


# ==========================================================================
# kernels: compute-dtype inputs, fp32 accumulators (REPRO_FORCE_REF x-check)
# ==========================================================================

def test_flash_attention_bf16_vs_fp32_ref(monkeypatch):
    monkeypatch.setenv("REPRO_FORCE_REF", "1")
    from repro.kernels import dispatch
    from repro.kernels.flash_attention import flash_attention, ref
    assert dispatch.force_ref() and not dispatch.use_pallas()
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (1, 128, 4, 64), jnp.float32)
    k = jax.random.normal(ks[1], (1, 128, 2, 64), jnp.float32)
    v = jax.random.normal(ks[2], (1, 128, 2, 64), jnp.float32)
    exp = ref.naive_attention(q, k, v)
    out = flash_attention(q.astype(jnp.bfloat16), k.astype(jnp.bfloat16),
                          v.astype(jnp.bfloat16))
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(exp),
                               atol=3e-2)
    # pallas kernel (interpret) under the same bf16-in/fp32-accum contract
    from repro.kernels.flash_attention.kernel import flash_attention_tpu
    out_k = flash_attention_tpu(q.astype(jnp.bfloat16),
                                k.astype(jnp.bfloat16),
                                v.astype(jnp.bfloat16))
    np.testing.assert_allclose(np.asarray(out_k, np.float32),
                               np.asarray(exp), atol=3e-2)


def test_selective_scan_bf16_vs_fp32_ref(monkeypatch):
    monkeypatch.setenv("REPRO_FORCE_REF", "1")
    from repro.kernels.selective_scan import selective_scan
    from repro.kernels.selective_scan import ref as ss_ref
    from repro.kernels.selective_scan.kernel import selective_scan_tpu
    ks = jax.random.split(KEY, 5)
    ba, s, di, n = 2, 64, 32, 8
    u = jax.random.normal(ks[0], (ba, s, di), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (ba, s, di))) * 0.1
    A = -jnp.exp(jax.random.normal(ks[2], (di, n)) * 0.2)
    B = jax.random.normal(ks[3], (ba, s, n), jnp.float32)
    C = jax.random.normal(ks[4], (ba, s, n), jnp.float32)
    D = jnp.ones((di,), jnp.float32)
    y_ref, h_ref = ss_ref.selective_scan(u, dt, A, B, C, D)
    y, h = selective_scan(u.astype(jnp.bfloat16), dt, A, B, C, D)
    assert h.dtype == jnp.float32                # state accumulates fp32
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(y_ref), atol=5e-2)
    y_k, h_k = selective_scan_tpu(u.astype(jnp.bfloat16), dt, A, B, C, D)
    assert h_k.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(y_k, np.float32),
                               np.asarray(y_ref), atol=5e-2)


def test_sil_mse_bf16_vs_fp32_ref(monkeypatch):
    monkeypatch.setenv("REPRO_FORCE_REF", "1")
    from repro.kernels.sil_mse import sil_mse
    from repro.kernels.sil_mse import ref as sm_ref
    from repro.kernels.sil_mse.kernel import sil_mse_fwd_tpu
    ks = jax.random.split(KEY, 3)
    act = jax.random.normal(ks[0], (256, 128), jnp.float32)
    sil = jax.random.uniform(ks[1], (128, 64)) * 5
    lab = jax.random.randint(ks[2], (256,), 0, 64)
    exp = float(sm_ref.sil_mse(act, sil, lab))
    got = float(sil_mse(act.astype(jnp.bfloat16), sil, lab))
    assert got == pytest.approx(exp, rel=2e-2)
    loss_k, grad_k = sil_mse_fwd_tpu(act.astype(jnp.bfloat16), sil, lab)
    assert float(loss_k) == pytest.approx(exp, rel=2e-2)
    assert grad_k.dtype == jnp.float32           # unscaled grad kept fp32
    # the loss gradient wrt bf16 activations flows (custom VJP path)
    g = jax.grad(lambda a: sil_mse(a, sil, lab))(act.astype(jnp.bfloat16))
    assert g.dtype == jnp.bfloat16 and bool(jnp.isfinite(
        g.astype(jnp.float32)).all())


@pytest.mark.parametrize("path", ["ref", "pallas"])
def test_sil_mse_fp16_loss_scaled_grad_vs_fp32(monkeypatch, path):
    """fp16 activations under the fp16 preset's loss scale: the SIL-MSE
    gradient reaches fp16 only after the scale lifts it out of fp16's
    subnormal range, so it matches the fp32 gradient to fp16 rounding.
    The activations sit near their targets, as in a converging stage,
    where the unscaled 2/(T*d)*diff is a few fp16 subnormal steps."""
    from repro.kernels import dispatch
    from repro.kernels.sil_mse import sil_mse
    from repro.kernels.sil_mse import ref as sm_ref
    if path == "pallas":        # kernel in interpret mode on CPU
        monkeypatch.setattr(dispatch, "on_tpu", lambda: True)
    dispatch.cache_clear()
    scale = P.get_policy("fp16").loss_scale
    t, d, m = 1024, 512, 47
    ks = jax.random.split(KEY, 3)
    sil = jax.random.uniform(ks[0], (d, m)) * 5
    lab = jax.random.randint(ks[1], (t,), 0, m)
    act = (sil[:, lab].T + 0.05 * jax.random.normal(ks[2], (t, d))
           ).astype(jnp.float16)
    g = jax.grad(lambda a: sil_mse(a, sil, lab) * scale)(act)
    dispatch.cache_clear()
    assert g.dtype == jnp.float16
    exp = np.asarray(sm_ref.sil_mse_grad_act(act, sil, lab) * scale)
    got = np.asarray(g, np.float32)
    rel = np.linalg.norm(got - exp) / np.linalg.norm(exp)
    assert rel < 2e-3, rel


# ==========================================================================
# end-to-end: paper MLP under bf16, engine under bf16, accum
# ==========================================================================

def test_mlp_smoke_accuracy_bf16_within_1pct():
    from repro.data.images import emnist_like
    cfg = MLP.MLPConfig(sizes=(784, 32, 16, 16, 47), cut=2)
    data = emnist_like(n_train=9400, n_test=940, seed=0, noise=0.5)
    accs = {}
    for prec in (None, "bf16"):
        spec = TrainSpec(batch_size=470, precision=prec, eval_every=100,
                         baseline=StageSpec(epochs=15, lr=0.02,
                                            optimizer="sgdm"))
        be = MLPBackend(cfg, data, spec)
        _, hist = Trainer(be, spec).run(
            [BaselinePhase()], params=MLP.init_params(cfg, KEY))
        accs[prec] = hist.column("acc")[-1]
    assert accs[None] > 0.9                      # actually learned
    assert abs(accs[None] - accs["bf16"]) < 0.01


def test_boundary_spill_in_compute_dtype():
    """The materialized boundary (the paper's one communication) stores in
    the policy's compute dtype — half the memmap bytes under bf16."""
    from repro.data.images import emnist_like
    cfg = MLP.MLPConfig(sizes=(784, 32, 16, 16, 47), cut=2)
    data = emnist_like(n_train=940, n_test=470, seed=0, noise=0.5)
    spec = TrainSpec(batch_size=470, precision="bf16",
                     stages=(StageSpec(epochs=1, lr=0.01),
                             StageSpec(epochs=1, lr=0.01)))
    be = MLPBackend(cfg, data, spec)
    assert be.boundary_dtype() == np.dtype(jnp.bfloat16)
    tr = Trainer(be, spec)
    state = TrainState(stage_params=be.split(MLP.init_params(cfg, KEY)))
    BoundaryMaterializePhase(upto=1).run(tr, state)
    h = state.boundary["h"]
    assert h.array().dtype == np.dtype(jnp.bfloat16)
    assert h.nbytes == h.n_rows * cfg.boundary_width * 2
    h.close()


def test_engine_bf16_batched_equals_sequential():
    from repro.serve import Engine, GenerationConfig, Request
    cfg = get("qwen2-1.5b", smoke=True).replace(n_layers=2)
    params = M.init_params(cfg, KEY)
    rng = np.random.RandomState(0)
    reqs = [Request(tokens=rng.randint(0, cfg.vocab_size, size=(12,)),
                    gen=GenerationConfig(max_new_tokens=8), id=f"r{i}")
            for i in range(3)]
    eng = Engine(cfg, params, max_slots=3, precision="bf16")
    assert eng.cfg.dtype == "bfloat16"
    batched = eng.generate(reqs)
    seq = [Engine(cfg, params, max_slots=1, precision="bf16")
           .generate([r])[0] for r in reqs]
    for b, s in zip(batched, seq):
        assert b.tokens == s.tokens


def test_stage_accum_matches_single_shot():
    """StageSpec.accum: fp32-accumulated microbatch grads == one big batch
    (sgdm, fp32 — equality up to reduction order)."""
    cfg, data, spec = _mlp_setup(None)
    be = MLPBackend(cfg, data, spec)
    params0 = MLP.init_params(cfg, KEY)
    batches = be.epoch_arrays(0, shuffle=False)
    x, y = batches[0][0], batches[1][0]

    outs = {}
    for accum in (1, 2):
        opt = make_optimizer("sgdm", 0.01, momentum=0.9)
        params = jax.tree_util.tree_map(jnp.copy, params0)
        st = opt.init(params)
        step = be.build_baseline_step(opt, accum=accum)
        params, st, loss = step(params, st, x, y)
        outs[accum] = (params, float(loss))
    assert outs[1][1] == pytest.approx(outs[2][1], rel=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(outs[1][0]),
                    jax.tree_util.tree_leaves(outs[2][0])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=2e-6)


def test_lm_stage_step_accum_and_bf16():
    """LM stage step under an explicit bf16 TrainSpec with accum=2 runs and
    keeps params in param_dtype."""
    from repro.core import partition
    from repro.train import LMBackend
    cfg = get("stablelm-3b", smoke=True)
    plan = partition.make_plan(cfg, 2)
    params = M.init_params(cfg, KEY)
    spec = TrainSpec(n_stages=2, kappa=1.0, precision="bf16",
                     stages=(StageSpec(steps=1, lr=1e-3, optimizer="adamw",
                                       accum=2),) * 2)
    batch = {"tokens": jnp.ones((4, 16), jnp.int32),
             "labels": jnp.ones((4, 16), jnp.int32)}
    be = LMBackend(cfg, plan, lambda i: batch, spec)
    assert be.cfg.dtype == "bfloat16"
    sp = be.split(params)[0]
    from repro.train.backends import make_optimizer_for
    opt = make_optimizer_for(spec.stage(0), spec)
    st = opt.init(be.trainable(sp))
    sil = jnp.ones((cfg.d_model, cfg.vocab_padded), jnp.float32)
    step = be.build_stage_step(0, opt, sil, sp, accum=2)
    sp2, st2, loss = step(sp, st, batch, batch["labels"])
    assert np.isfinite(float(loss))
    for leaf in jax.tree_util.tree_leaves(sp2):
        if jnp.issubdtype(leaf.dtype, jnp.floating):
            assert leaf.dtype == jnp.dtype(cfg.param_dtype)


# ==========================================================================
# dtype-aware memory accounting
# ==========================================================================

def test_cache_pool_bytes_halve_under_bf16():
    from repro.serve.kv_cache import CachePool
    base = get("qwen2-1.5b", smoke=True)
    pool16 = CachePool(P.get_policy("bf16").apply_to_model(base), 4, 64)
    pool32 = CachePool(P.get_policy("fp32").apply_to_model(base), 4, 64)
    assert pool32.nbytes == 2 * pool16.nbytes


def test_analytic_hbm_bytes_follow_policy():
    from repro.configs import INPUT_SHAPES
    from repro.launch.hlo_analysis import analytic_hbm_bytes_per_chip
    base = get("qwen2-1.5b")
    shape = INPUT_SHAPES["train_4k"]
    kw = dict(params_bytes_per_chip=0, opt_bytes_per_chip=0)
    b16 = analytic_hbm_bytes_per_chip(
        P.get_policy("bf16").apply_to_model(base), shape, 256, **kw)
    b32 = analytic_hbm_bytes_per_chip(
        P.get_policy("fp32").apply_to_model(base), shape, 256, **kw)
    assert b32 > b16                              # activation stream shrank
    # the activation term itself halves: subtract the dtype-independent
    # logits term (fp32 both ways) and compare
    shape_dec = INPUT_SHAPES["decode_32k"]
    d16 = analytic_hbm_bytes_per_chip(
        P.get_policy("bf16").apply_to_model(base), shape_dec, 256,
        cache_bytes_per_chip=0, **kw)
    d32 = analytic_hbm_bytes_per_chip(
        P.get_policy("fp32").apply_to_model(base), shape_dec, 256,
        cache_bytes_per_chip=0, **kw)
    assert d32 == 2 * d16                         # pure activation stream
