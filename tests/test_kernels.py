"""Per-kernel shape/dtype sweeps: Pallas (interpret mode) vs pure-jnp oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import ref as fa_ref
from repro.kernels.flash_attention.kernel import flash_attention_tpu
from repro.kernels.selective_scan import ref as ss_ref
from repro.kernels.selective_scan.kernel import selective_scan_tpu
from repro.kernels.sil_mse import ref as sm_ref
from repro.kernels.sil_mse.kernel import sil_mse_fwd_tpu

KEY = jax.random.PRNGKey(0)


@pytest.mark.parametrize("b,s,h,kv,d", [
    (2, 256, 4, 2, 64), (1, 128, 4, 4, 64), (2, 200, 8, 2, 128),
    (1, 384, 6, 6, 64), (1, 96, 12, 2, 128),
    (1, 1100, 4, 2, 128), (1, 1536, 8, 2, 64),   # several 512 tiles
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal,window", [(True, 0), (True, 64), (False, 0)])
def test_flash_attention_sweep(b, s, h, kv, d, dtype, causal, window):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (b, s, h, d), dtype)
    k = jax.random.normal(ks[1], (b, s, kv, d), dtype)
    v = jax.random.normal(ks[2], (b, s, kv, d), dtype)
    out = flash_attention_tpu(q, k, v, causal=causal, window=window)
    exp = fa_ref.naive_attention(q, k, v, causal=causal, window=window)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal,window", [(False, 0), (True, 0),
                                           (True, 200)])
def test_flash_attention_q_shorter_than_k(dtype, causal, window):
    """Cross attention (96 queries over 700 keys: a 96-row q tile over two
    512-key tiles, the second padded); causal calls align q to the end of
    k, as the reference does."""
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (2, 96, 4, 64), dtype)
    k = jax.random.normal(ks[1], (2, 700, 2, 64), dtype)
    v = jax.random.normal(ks[2], (2, 700, 2, 64), dtype)
    out = flash_attention_tpu(q, k, v, causal=causal, window=window)
    exp = fa_ref.naive_attention(q, k, v, causal=causal, window=window)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("s,window", [(4096, 0), (1100, 0), (4096, 1000)])
def test_flash_plan_fetches_only_visible_kv_blocks(s, window):
    """The K/V index maps of a causal plan, over every (iq, ik) step, visit
    the KV blocks that q block iq can see in order and then repeat the last
    one, so a skipped step fetches nothing: the distinct (iq, kv block)
    fetches are the causal lower triangle (cut by the window)."""
    from repro.kernels.flash_attention.kernel import plan
    kp = plan(2, s, s, 12, 2, 128, causal=True, window=window,
              dtype="bfloat16")
    q_bp, k_bp, v_bp = kp.inputs
    bq, bk = q_bp.block_shape[2], k_bp.block_shape[2]
    _, _, nq, nk = kp.grid
    fetched = set()
    for iq in range(nq):
        rows = range(iq * bq, min((iq + 1) * bq, s))
        first = max(rows[0] - window + 1, 0) // bk if window else 0
        last = rows[-1] // bk
        seen = []
        for ik in range(nk):
            idx = tuple(int(i) for i in k_bp.index_map(1, 5, iq, ik))
            assert idx == tuple(int(i) for i in v_bp.index_map(1, 5, iq, ik))
            assert idx[:2] == (1, 5 // 6) and idx[3] == 0
            assert first <= idx[2] <= last
            seen.append(idx[2])
        visible = list(range(first, last + 1))
        assert seen == [first] * first + visible + [last] * (nk - 1 - last)
        fetched |= {(iq, j) for j in seen}
    need = {(iq, j) for iq in range(nq) for j in range(nk)
            if j * bk <= min((iq + 1) * bq, s) - 1
            and (not window or (j + 1) * bk - 1 > iq * bq - window)}
    assert fetched == need
    if not window and bq == bk:
        assert len(fetched) == nq * (nq + 1) // 2


@pytest.mark.parametrize("b,lc,h,kv,d", [
    (2, 32, 4, 2, 64), (1, 100, 8, 2, 128), (3, 16, 6, 6, 64),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_kernel_sweep(b, lc, h, kv, d, dtype):
    from repro.kernels.flash_attention.kernel import decode_attention_tpu
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (b, 1, h, d), dtype)
    k = jax.random.normal(ks[1], (b, lc, kv, d), dtype)
    v = jax.random.normal(ks[2], (b, lc, kv, d), dtype)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    for pos in (lc // 2,                                   # partial cache
                jnp.arange(b, dtype=jnp.int32) + 3,        # ragged batch
                2 * lc):                                   # ring: all valid
        out = decode_attention_tpu(q, k, v, pos, bk=16)
        exp = fa_ref.decode_attention(q, k, v, pos)
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(exp, np.float32),
                                   rtol=tol, atol=tol)


def test_attention_dispatch_force_ref(monkeypatch):
    """REPRO_FORCE_REF=1 pins the jnp reference even when the backend
    reports TPU; without it the TPU path takes the Pallas kernels."""
    from repro.kernels import dispatch
    from repro.kernels.flash_attention import kernel as fa_kernel
    from repro.kernels.flash_attention import ops
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (1, 1, 4, 64), jnp.float32)
    k = jax.random.normal(ks[1], (1, 16, 2, 64), jnp.float32)
    v = jax.random.normal(ks[2], (1, 16, 2, 64), jnp.float32)
    monkeypatch.setattr(dispatch, "on_tpu", lambda: True)
    hits = []
    monkeypatch.setattr(fa_kernel, "decode_attention_tpu",
                        lambda *a, **kw: hits.append("decode") or
                        fa_ref.decode_attention(a[0], a[1], a[2], a[3]))
    monkeypatch.setattr(fa_kernel, "flash_attention_tpu",
                        lambda *a, **kw: hits.append("flash") or
                        fa_ref.naive_attention(a[0], a[1], a[2]))
    monkeypatch.setenv("REPRO_FORCE_REF", "1")
    ops.decode_attention(q, k, v, 7)
    ops.flash_attention(q, k, v)
    assert hits == []                      # forced to the reference path
    monkeypatch.delenv("REPRO_FORCE_REF")
    ops.decode_attention(q, k, v, 7)
    ops.flash_attention(q, k, v)
    assert hits == ["decode", "flash"]     # TPU path dispatches the kernels


def test_flash_vs_chunked_ref_agree():
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (2, 160, 4, 64), jnp.float32)
    k = jax.random.normal(ks[1], (2, 160, 2, 64), jnp.float32)
    v = jax.random.normal(ks[2], (2, 160, 2, 64), jnp.float32)
    a = fa_ref.chunked_attention(q, k, v, causal=True, chunk=64)
    b = fa_ref.naive_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-5,
                               atol=2e-5)


@pytest.mark.parametrize("ba,s,di,n", [
    (2, 64, 32, 8), (1, 100, 64, 16), (2, 256, 128, 16), (1, 33, 48, 4),
])
def test_selective_scan_sweep(ba, s, di, n):
    ks = jax.random.split(KEY, 6)
    u = jax.random.normal(ks[0], (ba, s, di), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (ba, s, di)))
    A = -jnp.exp(jax.random.normal(ks[2], (di, n)) * 0.5)
    B = jax.random.normal(ks[3], (ba, s, n))
    C = jax.random.normal(ks[4], (ba, s, n))
    D = jax.random.normal(ks[5], (di,))
    y, h = selective_scan_tpu(u, dt, A, B, C, D, chunk=32, bd=32)
    ey, eh = ss_ref.selective_scan(u, dt, A, B, C, D, chunk=32)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ey), rtol=1e-4,
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(h), np.asarray(eh), rtol=1e-4,
                               atol=1e-4)


def test_selective_scan_step_matches_full():
    """Sequential decode steps reproduce the full scan."""
    ks = jax.random.split(KEY, 6)
    ba, s, di, n = 2, 16, 8, 4
    u = jax.random.normal(ks[0], (ba, s, di), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (ba, s, di)))
    A = -jnp.exp(jax.random.normal(ks[2], (di, n)) * 0.5)
    B = jax.random.normal(ks[3], (ba, s, n))
    C = jax.random.normal(ks[4], (ba, s, n))
    D = jax.random.normal(ks[5], (di,))
    y_full, h_full = ss_ref.selective_scan(u, dt, A, B, C, D, chunk=8)
    h = jnp.zeros((ba, di, n))
    ys = []
    for t in range(s):
        y, h = ss_ref.selective_scan_step(u[:, t], dt[:, t], A, B[:, t],
                                          C[:, t], D, h)
        ys.append(y)
    np.testing.assert_allclose(np.asarray(jnp.stack(ys, 1)),
                               np.asarray(y_full), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(h), np.asarray(h_full), rtol=1e-4,
                               atol=1e-4)


@pytest.mark.parametrize("t,d,m", [(64, 128, 47), (100, 96, 512),
                                   (256, 512, 1000), (37, 60, 47)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_sil_mse_sweep(t, d, m, dtype):
    ks = jax.random.split(KEY, 3)
    act = jax.random.normal(ks[0], (t, d), dtype)
    sil = jax.random.uniform(ks[1], (d, m), jnp.float32) * 10
    lab = jax.random.randint(ks[2], (t,), 0, m)
    loss, grad = sil_mse_fwd_tpu(act, sil, lab, bt=32, bd=64)
    eloss = sm_ref.sil_mse(act, sil, lab)
    egrad = sm_ref.sil_mse_grad_act(act, sil, lab)
    tol = 1e-5 if dtype == jnp.float32 else 5e-2
    assert abs(float(loss) - float(eloss)) <= tol * max(1.0, float(eloss))
    np.testing.assert_allclose(np.asarray(grad, np.float32), np.asarray(
        egrad * 1.0, np.float32), rtol=5e-2 if dtype == jnp.bfloat16 else 1e-5,
        atol=1e-4)


def test_sil_mse_custom_vjp_grad():
    """ops.sil_mse custom VJP == autodiff through the reference."""
    from repro.kernels.sil_mse import sil_mse
    ks = jax.random.split(KEY, 3)
    act = jax.random.normal(ks[0], (40, 24), jnp.float32)
    sil = jax.random.uniform(ks[1], (24, 10)) * 5
    lab = jax.random.randint(ks[2], (40,), 0, 10)
    g1 = jax.grad(lambda a: sil_mse(a, sil, lab))(act)
    g2 = jax.grad(lambda a: sm_ref.sil_mse(a, sil, lab))(act)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), rtol=1e-5,
                               atol=1e-7)
