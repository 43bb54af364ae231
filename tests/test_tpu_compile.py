"""Compile the main-path Pallas kernels for a described TPU v5e chip.

No chip is attached: the TPU compiler compiles for a topology it is given,
and refuses what the chip would refuse (blocks not aligned to the (8, 128)
tiling, too much VMEM, programs that do not fit).  Interpret mode on the
CPU cannot see any of that.  Shapes are the published widths: the paper's
MLP boundary and qwen2-1.5b (d_model 1536, 12 heads, 2 KV heads, head_dim
128, vocab 151936) at sequence 4096, and chatglm3-6b's attention (32 heads
over 2 KV groups of 128).

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and every test worker imports this
file.  The persistent compile cache is switched off around these compiles
(an entry compiled for a described chip cannot be read back without one).
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention import kernel as fa
from repro.kernels.flash_attention import ref as fa_ref
from repro.kernels.sil_mse.kernel import sil_mse_fwd_tpu

BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def one_chip():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _compile(fn, sharding, *shapes):
    """Compile ``fn`` for the described chip; returns the HLO text."""
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding)
            for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("t,d,m,dtype", [
    (1410, 60, 47, jnp.float32),          # paper MLP: batch 1410, cut 60
    (4096, 1536, 151936, BF16),           # qwen2-1.5b boundary, 4k tokens
])
def test_sil_mse_compiles(one_chip, t, d, m, dtype):
    hlo = _compile(functools.partial(sil_mse_fwd_tpu, interpret=False),
                   one_chip, ((t, d), dtype), ((d, m), jnp.float32),
                   ((t,), jnp.int32))
    assert "tpu_custom_call" in hlo


QWEN_QKV = (((1, 4096, 12, 128), BF16), ((1, 4096, 2, 128), BF16),
            ((1, 4096, 2, 128), BF16))


def test_flash_forward_compiles(one_chip):
    hlo = _compile(functools.partial(fa.flash_attention_tpu,
                                     interpret=False), one_chip, *QWEN_QKV)
    assert "tpu_custom_call" in hlo


def test_flash_forward_compiles_at_chatglm3_width(one_chip):
    """chatglm3-6b's attention: 32 query heads over 2 KV groups of 128."""
    hlo = _compile(functools.partial(fa.flash_attention_tpu,
                                     interpret=False), one_chip,
                   ((2, 4096, 32, 128), BF16), ((2, 4096, 2, 128), BF16),
                   ((2, 4096, 2, 128), BF16))
    assert "tpu_custom_call" in hlo


def test_flash_grad_compiles(one_chip, monkeypatch):
    """value_and_grad through the dispatched op: the Pallas forward plus the
    custom VJP (the chunked reference's backward)."""
    from repro.kernels import dispatch
    from repro.kernels.flash_attention import ops
    monkeypatch.setattr(dispatch, "on_tpu", lambda: True)
    monkeypatch.setattr(fa, "flash_attention_tpu", functools.partial(
        fa.flash_attention_tpu, interpret=False))
    dispatch.cache_clear()

    def loss(q, k, v):
        return ops.flash_attention(q, k, v).astype(jnp.float32).sum()
    try:
        hlo = _compile(jax.value_and_grad(loss, (0, 1, 2)), one_chip,
                       *QWEN_QKV)
    finally:
        dispatch.cache_clear()
    assert "tpu_custom_call" in hlo


def test_decode_compiles(one_chip):
    hlo = _compile(functools.partial(fa.decode_attention_tpu,
                                     interpret=False), one_chip,
                   ((4, 1, 12, 128), BF16), ((4, 4096, 2, 128), BF16),
                   ((4, 4096, 2, 128), BF16), ((4,), jnp.int32))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("bk", [8, 16])
def test_decode_compiles_at_serving_block_tile(one_chip, bk):
    """A serving engine tiles contiguous decode at its paged block size."""
    hlo = _compile(functools.partial(fa.decode_attention_tpu, bk=bk,
                                     interpret=False), one_chip,
                   ((4, 1, 12, 128), BF16), ((4, 4096, 2, 128), BF16),
                   ((4, 4096, 2, 128), BF16), ((4,), jnp.int32))
    assert "tpu_custom_call" in hlo


def test_paged_decode_compiles(one_chip):
    nb, bs = 4096 // 16, 16
    hlo = _compile(functools.partial(fa.paged_decode_attention_tpu,
                                     logical_len=4096, interpret=False),
                   one_chip, ((4, 1, 12, 128), BF16),
                   ((4 * nb + 1, bs, 2, 128), BF16),
                   ((4 * nb + 1, bs, 2, 128), BF16),
                   ((4, nb), jnp.int32), ((4,), jnp.int32))
    assert "tpu_custom_call" in hlo


def test_reference_attention_grad_compiles(one_chip):
    """The backward the custom VJP runs, at the same widths (its scan body
    is rematerialised: one KV chunk of scores live at a time)."""
    def loss(q, k, v):
        return fa_ref.chunked_attention(q, k, v).astype(jnp.float32).sum()
    _compile(jax.grad(loss, (0, 1, 2)), one_chip, *QWEN_QKV)


def test_stage_step_scopes_name_the_flash_kernel_and_its_backward(
        one_chip, monkeypatch):
    """A qwen-shaped stage-0 SIL step (head_dim 128, GQA, QKV bias; one
    layer at width 256) compiled for the chip: the step's table puts the
    Pallas forward in ``flash`` on the forward pass and again as the
    layer's recomputation, and the custom VJP's jnp ops in ``flash`` on the
    backward pass."""
    from repro.configs.base import ModelConfig
    from repro.core import partition
    from repro.kernels import dispatch
    from repro.kernels.sil_mse import kernel as sk
    from repro.obs import scopes
    from repro.train.backends import LMBackend, make_optimizer_for
    from repro.train.spec import StageSpec, TrainSpec
    monkeypatch.setattr(dispatch, "on_tpu", lambda: True)
    monkeypatch.setattr(fa, "flash_attention_tpu", functools.partial(
        fa.flash_attention_tpu, interpret=False))
    monkeypatch.setattr(sk, "sil_mse_fwd_tpu", functools.partial(
        sk.sil_mse_fwd_tpu, interpret=False))
    dispatch.cache_clear()
    cfg = ModelConfig(name="qwen-shaped", family="dense", n_layers=4,
                      d_model=256, n_heads=2, n_kv_heads=1, d_ff=512,
                      vocab_size=1024, head_dim=128, qkv_bias=True,
                      norm="rmsnorm", mlp_type="swiglu",
                      tie_embeddings=True, source="test")
    hp = StageSpec(steps=1, lr=1e-3, optimizer="adamw")
    spec = TrainSpec(n_stages=2, kappa=1.0, precision="bf16",
                     stages=(hp, hp))
    be = LMBackend(cfg, partition.make_plan(cfg, 2), None, spec)
    opt = make_optimizer_for(hp, spec)
    step = jax.jit(be._stage_step(0, opt, None, 1))

    def struct(tree):
        return jax.tree_util.tree_map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)
    from repro.models import model as M
    params = jax.eval_shape(lambda: be.split(M.init_params(
        cfg, jax.random.PRNGKey(0)))[0])
    sp = struct(params)
    st = struct(jax.eval_shape(opt.init, be.trainable(params)))
    tokens = jax.ShapeDtypeStruct((1, 512), jnp.int32, sharding=one_chip)
    sil = jax.ShapeDtypeStruct((256, 1024), jnp.float32, sharding=one_chip)
    try:
        text = step.lower(sp, st, {"tokens": tokens, "labels": tokens},
                          tokens, None, sil).compile().as_text()
    finally:
        dispatch.cache_clear()
    table = scopes.hlo_op_names(text)
    kernels = [scopes.scope_path(op) for instr, op in table.items()
               if instr.startswith("flash_attention_tpu")]
    assert sorted(p for names, p in kernels if "flash" in names) \
        == ["forward", "remat"]
    backward = [instr for instr, op in table.items()
                if scopes.scope_path(op) == (("layers", "attention", "flash"),
                                             "backward")]
    assert backward
    assert not any(i.startswith("flash_attention_tpu") for i in backward)
