"""The benchmark's arithmetic from shapes: FLOP and byte counts against hand
counts, the configurations against BENCHMARK.json and the program's own
layout, and the peak table."""
import json
import os

import jax
import pytest

from bench import common, harness

ROOT = harness.ROOT
BENCH = harness.load_benchmark(ROOT)
bm = harness.load_family(ROOT, "dense")


def cfg_of(name):
    return harness.load_config(ROOT, harness.config_entry(BENCH, name))[1]


QWEN, GLM = cfg_of("qwen2-1.5b"), cfg_of("chatglm3-6b")


def test_published_shapes():
    assert (QWEN.d_model, QWEN.n_heads, QWEN.n_kv_heads, QWEN.head_dim,
            QWEN.d_ff, QWEN.vocab_size, QWEN.n_layers) == \
        (1536, 12, 2, 128, 8960, 151936, 28)
    assert QWEN.tie_embeddings and QWEN.qkv_bias and QWEN.rope_dim == 128
    assert (GLM.d_model, GLM.n_heads, GLM.n_kv_heads, GLM.head_dim,
            GLM.d_ff, GLM.vocab_size, GLM.n_layers) == \
        (4096, 32, 2, 128, 13696, 65024, 28)
    assert not GLM.tie_embeddings and GLM.qkv_bias and GLM.rope_dim == 64


def test_layer_weights_by_hand():
    # qwen2: 1536*128*(12+4) + 12*128*1536 + 3*1536*8960 = 46.8M
    assert QWEN.layer_matmul_params() == 46_792_704
    # chatglm3: 4096*128*36 + 32*128*4096 + 3*4096*13696 = 204M
    assert GLM.layer_matmul_params() == 203_948_032


@pytest.mark.parametrize("cfg,stage,layers,attention,unembed", [
    # 7 layers x 46.8M x 6 x 8192 = 1.6e13; 6 * 2 * 12 * 4096^2 * 128 * 7
    (QWEN, 0, 1.61e13, 2.16e12, 0),
    # 2 layers x 204M x 6 x 8192 = 2.0e13; 32 heads, 2 layers: 1.65e12
    (GLM, 1, 2.01e13, 1.65e12, 0),
    # the last stage adds 4 * 1536 * 151936 * 8192 = 7.6e12
    (QWEN, 3, 1.61e13, 2.16e12, 7.65e12),
])
def test_step_flops_by_hand(cfg, stage, layers, attention, unembed):
    f = bm.step_flops(cfg, stage, 2, 4096)
    assert f["layers"] == pytest.approx(layers, rel=0.01)
    assert f["attention"] == pytest.approx(attention, rel=0.01)
    assert f["unembed"] == pytest.approx(unembed, rel=0.01)
    assert f["total"] == f["layers"] + f["attention"] + f["unembed"]


def test_kernel_costs_by_hand():
    flops, nbytes = bm.flash_forward_cost(QWEN, 2, 4096)
    assert flops == 2 * 2 * 12 * 4096 * 4096 * 128        # causal half
    assert nbytes == 2 * 4096 * 128 * (12 + 2 + 2 + 12) * 2
    flops, nbytes = bm.sil_mse_cost(QWEN, 2, 4096)
    t = 8192
    assert nbytes == t * 1536 * (2 + 4 + 4) + t * 4       # 126 MB
    assert flops == 4 * t * 1536


def test_stage_bounds_match_the_program():
    from repro.core.partition import make_plan
    program_config = bm.program_config
    for cfg in (QWEN, GLM):
        assert list(make_plan(program_config(cfg), cfg.stages).bounds) == \
            cfg.stage_bounds()
    assert QWEN.stage_bounds()[0] == (0, 7)
    assert GLM.stage_bounds()[1] == (2, 4)


@pytest.mark.parametrize("cfg,stage", [(QWEN, 0), (GLM, 1), (QWEN, 3)])
def test_layout_matches_the_program(cfg, stage):
    """The benchmark's own parameter layout is the tree the program's stage
    step takes: same paths, shapes and float32 dtype."""
    from repro.core import partition
    from repro.models import model as M
    mc = bm.program_config(cfg)
    plan = partition.make_plan(mc, cfg.stages)
    prog = jax.eval_shape(lambda: partition.slice_stage_params(
        mc, plan, M.init_params(mc, jax.random.PRNGKey(0)), stage))
    ours = jax.eval_shape(
        lambda: bm.init_stage(cfg, stage, jax.random.PRNGKey(0)))
    as_shapes = lambda t: jax.tree_util.tree_map(
        lambda a: (a.shape, str(a.dtype)), t)
    assert as_shapes(ours) == as_shapes(prog)


def test_config_files_agree_with_the_benchmark():
    for entry in BENCH["configs"]:
        with open(os.path.join(ROOT, entry["file"])) as f:
            raw = json.load(f)
        assert raw["name"] == entry["name"]
        assert raw["source"] == entry["source"]
        assert sorted(raw["reduced"]) == sorted(entry["reduced"])
        assert harness.load_config(ROOT, entry)[1].name == entry["name"]
        if raw["family"] == "dense":
            assert set(raw["keys"]) == set(bm.GENERIC)


def test_departure_runs_while_the_published_number_stays():
    """qwen2-1.5b keeps Qwen2's published epsilon; the program's fixed 1e-5
    is a stated departure that the run and the reference take."""
    path = os.path.join(ROOT, "bench/configs/qwen2-1.5b.json")
    with open(path) as f:
        raw = json.load(f)
    assert raw["rms_norm_eps"] == 1e-6 and not raw["reduced"]
    assert raw["departs"]["rms_norm_eps"]["run_at"] == 1e-5
    assert bm.parse(raw, path).norm_eps == 1e-5


def test_seed_keys_distinct_past_32_bits():
    a = jax.random.key_data(common.seed_keys(5)[0])
    b = jax.random.key_data(common.seed_keys(5 + 2 ** 32)[0])
    c = jax.random.key_data(common.seed_keys(5)[0])
    assert (a != b).any() and (a == c).all()


def test_peak_table_knows_v5e_and_refuses_others():
    row = harness.peaks_for(ROOT, "TPU v5 lite")
    assert row["bf16_flops_per_s"] == 197e12
    assert row["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="not in bench/peaks.json"):
        harness.peaks_for(ROOT, "TPU v9 imaginary")
