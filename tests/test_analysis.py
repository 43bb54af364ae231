"""repro.analysis conformance: every rule fires on a known-bad fixture and
stays silent on the real (clean) hot paths.

The bad fixtures are hand-built TraceTargets / KernelPlans seeded straight
into the AnalysisContext cache — the rules can't tell them from production
entry points, so "rule fires here" is a real regression assertion, not a
mock of one.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import AnalysisContext, all_rules, get_rule, run_rule
from repro.analysis import entrypoints, source
from repro.analysis.report import SCHEMA, build_report, write_report
from repro.analysis.rules_pallas import build_plans  # noqa: F401 (registers)
from repro.analysis.rules_trace import dtype_policy  # noqa: F401 (registers)
from repro.analysis.trace import TraceTarget, donated_invars, iter_eqns, trace
from repro.kernels.plan import BlockPlan, KernelPlan, ScratchPlan

FIXTURE_DIR = os.path.join(os.path.dirname(__file__), "fixtures")


def _ctx_with(targets):
    """Context whose traced-artifact cache holds exactly these targets."""
    ctx = AnalysisContext(arch="qwen2-1.5b", precision="bf16")
    ctx.cache[entrypoints.cache_key(ctx)] = {t.name: trace(t)
                                             for t in targets}
    return ctx


def _findings(rule_name, ctx):
    res = run_rule(get_rule(rule_name), ctx)
    assert res.error is None, res.error
    return res.findings


# ==========================================================================
# trace rules fire on bad fixtures
# ==========================================================================

def test_host_transfer_fires_on_debug_print_in_scan():
    @jax.jit
    def step(xs):
        def body(c, x):
            jax.debug.print("loss={l}", l=c)
            return c + x, c
        return jax.lax.scan(body, 0.0, xs)

    ctx = _ctx_with([TraceTarget(name="bad/scan_print", fn=step,
                                 args=(jnp.ones(4),))])
    fs = _findings("trace/host_transfer", ctx)
    assert [f.severity for f in fs] == ["fail"]
    assert "debug_print" in fs[0].message


def test_dtype_policy_fires_on_mixed_dot():
    @jax.jit
    def f(a, b):
        return jax.lax.dot(a, b, preferred_element_type=jnp.float32)

    ctx = _ctx_with([TraceTarget(
        name="bad/mixed_dot", fn=f,
        args=(jnp.ones((4, 8), jnp.bfloat16), jnp.ones((8, 4))))])
    fs = [f for f in _findings("trace/dtype_policy", ctx)
          if f.severity == "fail"]
    assert fs and "mixed-dtype dot_general" in fs[0].message


def test_dtype_policy_warns_on_bf16_scan_accumulator():
    @jax.jit
    def f(xs):
        def body(acc, x):
            return acc + x, x
        return jax.lax.scan(body, jnp.bfloat16(0), xs)

    ctx = _ctx_with([TraceTarget(name="bad/bf16_carry", fn=f,
                                 args=(jnp.ones(8, jnp.bfloat16),))])
    fs = _findings("trace/dtype_policy", ctx)
    assert any(f.severity == "warn" and "scan carry" in f.message
               for f in fs)


def test_dtype_policy_fires_on_state_dtype_drift():
    @jax.jit
    def f(p, x):
        return jax.tree_util.tree_map(lambda l: l.astype(jnp.bfloat16), p), x

    p = {"w": jnp.ones((4, 4))}
    ctx = _ctx_with([TraceTarget(name="bad/drift", fn=f,
                                 args=(p, jnp.ones(4)),
                                 state_map=((0, 0),))])
    fs = [f for f in _findings("trace/dtype_policy", ctx)
          if f.severity == "fail"]
    assert fs and "changes dtype" in fs[0].message


def test_donation_fires_on_missing_donation():
    def f(p, st, x):
        return jax.tree_util.tree_map(lambda l: l + 1, p), st, x.sum()

    p = {"w": jnp.ones((8, 8)), "b": jnp.ones(8)}
    st = (jnp.zeros((8, 8)),)
    args = (p, st, jnp.ones(8))
    # requested donate=(0, 1) but only argnum 0 actually jit-donated
    half = jax.jit(f, donate_argnums=(0,))
    ctx = _ctx_with([TraceTarget(name="bad/half_donated", fn=half,
                                 args=args, donate=(0, 1))])
    fs = [f for f in _findings("trace/donation", ctx)
          if f.severity == "fail"]
    assert len(fs) == 1
    ev = fs[0].evidence
    assert ev["actual"] == 2 and ev["expected"] == 3
    assert ev["undonated_bytes_by_dtype"]["float32"] == 8 * 8 * 4

    # ...and the fully-donated version reports clean (info only)
    full = jax.jit(f, donate_argnums=(0, 1))
    ctx2 = _ctx_with([TraceTarget(name="ok/donated", fn=full,
                                  args=args, donate=(0, 1))])
    fs2 = _findings("trace/donation", ctx2)
    assert [f.severity for f in fs2] == ["info"]


def test_donation_regression_runtime_gate(monkeypatch):
    """The in-tree fix this rule guards: donate_argnums() used to return ()
    off-TPU unconditionally, making donation invisible to tracing.  The
    REPRO_ASSUME_DONATION override must surface the real masks on CPU."""
    from repro import runtime
    from repro.train.backends import donate_argnums
    monkeypatch.delenv("REPRO_ASSUME_DONATION", raising=False)
    with runtime.assume_donation():
        assert donate_argnums(0, 1) == (0, 1)

        def f(p, x):
            return jax.tree_util.tree_map(lambda l: l + 1, p), x.sum()

        jf = jax.jit(f, donate_argnums=donate_argnums(0))
        art = trace(TraceTarget(name="t", fn=jf,
                                args=({"w": jnp.ones(4)}, jnp.ones(4)),
                                donate=(0,)))
        assert donated_invars(art) == (True, False)
    if jax.default_backend() not in ("gpu", "tpu"):
        assert donate_argnums(0, 1) == ()


def test_recompile_hazard_fires_on_untraceable_entry():
    @jax.jit
    def f(x):
        if x.sum() > 0:          # python branch on a traced value
            return x
        return -x

    ctx = _ctx_with([TraceTarget(name="bad/py_branch", fn=f,
                                 args=(jnp.ones(4),))])
    fs = _findings("trace/recompile_hazard", ctx)
    assert [f.severity for f in fs] == ["fail"]
    assert "failed to trace" in fs[0].message


def test_recompile_hazard_warns_on_unjitted_entry():
    def f(x):
        return x * 2 + 1         # two top-level eqns, no jit wrapper

    ctx = _ctx_with([TraceTarget(name="bad/unjitted", fn=f,
                                 args=(jnp.ones(4),))])
    fs = _findings("trace/recompile_hazard", ctx)
    assert [f.severity for f in fs] == ["warn"]


# ==========================================================================
# pallas rules fire on bad plans
# ==========================================================================

def _plan_ctx(*plans):
    ctx = AnalysisContext(arch="qwen2-1.5b")
    ctx.cache[f"plans:{ctx.arch}"] = list(plans)
    return ctx


def _bad_plan(**kw):
    base = dict(
        family="flash_attention", entry="flash_attention", grid=(2, 4),
        inputs=(BlockPlan("x", (1, 32), lambda i, j: (i, j), (2, 128)),),
        outputs=(BlockPlan("o", (1, 32), lambda i, j: (i, j), (2, 128)),),
        scratch=(ScratchPlan("acc", (8, 128), "float32", accumulator=True),))
    base.update(kw)
    return KernelPlan(**base)


def test_grid_divisibility_fires_on_indivisible_block():
    kp = _bad_plan(inputs=(BlockPlan("x", (1, 48), lambda i, j: (i, j),
                                     (2, 128)),))
    fs = _findings("pallas/grid_divisibility", _plan_ctx(kp))
    assert any(f.severity == "fail" and "not divisible" in f.message
               for f in fs)


def test_index_map_bounds_fires_on_oob_map():
    kp = _bad_plan(inputs=(BlockPlan("x", (1, 32), lambda i, j: (i, j + 1),
                                     (2, 128)),))
    fs = _findings("pallas/index_map_bounds", _plan_ctx(kp))
    assert any(f.severity == "fail" and "out of bounds" in f.message
               for f in fs)


def test_accum_dtype_fires_on_bf16_accumulator():
    kp = _bad_plan(scratch=(ScratchPlan("acc", (8, 128), "bfloat16",
                                        accumulator=True),))
    fs = _findings("pallas/accum_dtype", _plan_ctx(kp))
    assert [f.severity for f in fs] == ["fail"]


def test_real_kernel_plans_are_clean():
    for arch in ("paper_mlp", "qwen2-1.5b", "xlstm-125m"):
        ctx = AnalysisContext(arch=arch)
        for rule in ("pallas/grid_divisibility", "pallas/index_map_bounds",
                     "pallas/accum_dtype", "pallas/dispatch_symmetry"):
            fs = _findings(rule, ctx)
            assert not [f for f in fs if f.severity == "fail"], (arch, rule)


# ==========================================================================
# source lint
# ==========================================================================

def test_source_lint_fires_on_bad_fixture():
    fs = source.scan_file(os.path.join(FIXTURE_DIR, "bad_hotpath_source.py"))
    msgs = {(f.rule, f.target.rsplit(":", 1)[-1]) for f in fs}
    # one finding per banned idiom; the two pragma'd lines stay silent
    assert len(fs) == 4
    rules = sorted(f.rule for f in fs)
    assert rules == ["source/const_key"] + ["source/host_sync"] * 3, msgs


def test_source_lint_clean_on_hot_paths():
    fs = source.scan_paths(source.default_paths())
    assert fs == [], [f.target for f in fs]


# ==========================================================================
# the full pipeline is clean on the acceptance archs
# ==========================================================================

@pytest.fixture(scope="module")
def clean_results():
    out = {}
    for arch in ("paper_mlp", "qwen2-1.5b"):
        ctx = AnalysisContext(arch=arch, precision="bf16")
        out[arch] = (ctx, [run_rule(r, ctx) for r in all_rules()])
    return out


def test_no_false_positives_on_clean_archs(clean_results):
    for arch, (_, results) in clean_results.items():
        for res in results:
            assert res.error is None, (arch, res.name, res.error)
            fails = [f for f in res.findings if f.severity == "fail"]
            assert not fails, (arch, res.name,
                               [f.message for f in fails])


def test_entry_points_cover_all_surfaces(clean_results):
    mlp = entrypoints.artifacts(clean_results["paper_mlp"][0])
    lm = entrypoints.artifacts(clean_results["qwen2-1.5b"][0])
    assert set(mlp) == {"train/mlp_sil_epoch", "train/mlp_parallel_epoch",
                        "train/mlp_guarded_epoch", "sil/lookup_loss"}
    assert set(lm) == {"train/lm_stage_step", "train/lm_parallel_stage_step",
                       "train/lm_auto_parallel_stage_step",
                       "serve/prefill_admit", "serve/decode_chunk",
                       "sil/lookup_loss"}
    for art in list(mlp.values()) + list(lm.values()):
        assert art.error is None, (art.target.name, art.error)
        assert sum(1 for _ in iter_eqns(art.jaxpr)) > 0


def test_report_schema(clean_results, tmp_path):
    import json
    rep = build_report({a: rs for a, (_, rs) in clean_results.items()})
    assert rep["schema"] == SCHEMA == "repro.analysis/1"
    assert rep["ok"] and rep["n_fail_findings"] == 0
    assert sorted(rep["archs"]) == ["paper_mlp", "qwen2-1.5b"]
    p = write_report(rep, str(tmp_path / "ANALYSIS.json"))
    assert json.load(open(p))["schema"] == SCHEMA


# ==========================================================================
# byte accounting helper (shared with dryrun)
# ==========================================================================

def test_dtype_byte_breakdown():
    from repro.launch.hlo_analysis import (dtype_byte_breakdown,
                                           tree_bytes_per_chip)
    tree = {"a": jnp.zeros((4, 8), jnp.bfloat16),
            "b": jnp.zeros((2, 2), jnp.float32),
            "c": np.zeros((3,), np.int32)}
    bb = dtype_byte_breakdown(tree)
    assert bb == {"bfloat16": 64, "float32": 16, "int32": 12}
    assert tree_bytes_per_chip(tree) == 92
    # ShapeDtypeStructs work too (dryrun's path)
    structs = {"a": jax.ShapeDtypeStruct((4, 8), jnp.bfloat16)}
    assert dtype_byte_breakdown(structs) == {"bfloat16": 64}


def test_arg_bytes_per_chip_delegates():
    from repro.launch.dryrun import arg_bytes_per_chip
    from repro.launch.hlo_analysis import tree_bytes_per_chip
    tree = {"a": jax.ShapeDtypeStruct((8, 8), jnp.float32)}
    assert arg_bytes_per_chip(tree, None, None) \
        == tree_bytes_per_chip(tree) == 256
