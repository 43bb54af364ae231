"""The readers of the step program's named regions (bench/scopes.py) on a
synthetic reduced trace and instruction table: what each charges, that
container operations are left out, and that each stays silent where the
program registered no step, or has no table at all."""
import sys
from types import SimpleNamespace

import pytest

from bench import harness
from bench import scopes as bench_scopes
from bench.trace import Reduced

L = "jit(step)/transpose(jvp(layers))/while/body/closed_call/checkpoint"
F = "jit(step)/jvp(layers)/while/body/closed_call"
# instruction: (seconds over the window, kind, op_name or None)
OPS = {
    "flash_attention_tpu.18": (0.40, "custom-call",
                               F + "/attention/flash/pallas_call"),
    "flash_attention_tpu.19": (0.30, "custom-call", L + "/rematted_computation"
                               "/attention/flash/pallas_call"),
    "fusion.1": (0.20, "fusion", L + "/attention/flash/flash/"
                 "jvp(jit(chunked_attention))/exp"),
    "convolution.2": (0.10, "convolution", F + "/attention/dot_general"),
    "convolution.3": (0.15, "convolution", F + "/mlp/dot_general"),
    "fusion.4": (0.05, "fusion", L + "/mlp/transpose"),
    "fusion.5": (0.02, "fusion", "jit(step)/jvp(sil_mse)/pallas_call"),
    "fusion.6": (0.04, "fusion", "jit(step)/transpose(jvp(ce))/exp"),
    "fusion.7": (0.03, "fusion", "jit(step)/optimizer/mul"),
    "fusion.8": (0.01, "fusion", "jit(step)/jvp(layers)/while/body/"
                 "dynamic_update_slice"),
    "copy.9": (0.02, "copy", None),
    "fusion.10": (0.01, "fusion", "jit(step)/transpose(jvp())/mul"),
    # containers: their time is their children's
    "while.11": (1.00, "while", "jit(step)/jvp(layers)/while"),
    "conditional.12": (0.50, "conditional", "jit(step)/optimizer/cond"),
}
STEPS = 2
LEAF_S = sum(s for s, kind, _ in OPS.values()
             if kind not in ("while", "conditional"))

EXPECTED = {
    "attention_ms": 1e3 * (0.40 + 0.30 + 0.20 + 0.10) / STEPS,
    "flash_bwd_ms": 1e3 * 0.20 / STEPS,
    "mlp_ms": 1e3 * (0.15 + 0.05) / STEPS,
    "loss_head_ms": 1e3 * (0.02 + 0.04) / STEPS,
    "optimizer_ms": 1e3 * 0.03 / STEPS,
    "unscoped_share": 100.0 * (0.02 + 0.01) / LEAF_S,
}


def _measured():
    trace = Reduced(window_s=2.0, busy_s=1.5,
                    ops={i: (1, s) for i, (s, _, _) in OPS.items()},
                    kinds={i: k for i, (_, k, _) in OPS.items()})
    return SimpleNamespace(trace=trace, steps=STEPS)


@pytest.fixture
def table(monkeypatch):
    from repro.obs import scopes
    tab = {i: op for i, (_, _, op) in OPS.items() if op is not None}
    monkeypatch.setattr(scopes, "op_scopes", lambda name=None: tab)
    return tab


@pytest.mark.parametrize("metric", sorted(EXPECTED))
def test_reader_charges_its_regions(table, metric):
    read = harness.load_reader(harness.ROOT, metric)
    assert read(_measured()) == pytest.approx(EXPECTED[metric])


def test_regions_add_up_to_the_leaf_time(table):
    """Attention, MLP, loss head, optimizer, the layer scan's bookkeeping
    and the unscoped rest partition the leaf time: no container counted."""
    m = _measured()
    c = bench_scopes.charge(m)
    assert {r[0] for r in c.rows} == {i for i, (_, k, _) in OPS.items()
                                      if k not in ("while", "conditional")}
    rest = c.seconds(lambda names, _: names == ("layers",))
    parts = [EXPECTED[n] for n in ("attention_ms", "mlp_ms", "loss_head_ms",
                                   "optimizer_ms")]
    total_ms = sum(parts) + 1e3 * rest / STEPS \
        + EXPECTED["unscoped_share"] / 100 * 1e3 * LEAF_S / STEPS
    assert total_ms == pytest.approx(1e3 * LEAF_S / STEPS)


@pytest.mark.parametrize("metric", sorted(EXPECTED))
def test_reader_silent_without_a_trace_or_a_step(table, metric):
    read = harness.load_reader(harness.ROOT, metric)
    assert read(SimpleNamespace(trace=None, steps=STEPS)) is None
    assert read(SimpleNamespace(trace=_measured().trace, steps=0)) is None


@pytest.mark.parametrize("metric", sorted(EXPECTED))
def test_reader_silent_where_no_program_is_registered(monkeypatch, metric):
    from repro.obs import scopes
    monkeypatch.setattr(scopes, "_PROGRAMS", {})
    read = harness.load_reader(harness.ROOT, metric)
    assert read(_measured()) is None


def test_readers_silent_on_a_program_without_scopes(monkeypatch):
    """A program older than the named regions has no table to read."""
    import repro.obs
    monkeypatch.setitem(sys.modules, "repro.obs.scopes", None)
    monkeypatch.delattr(repro.obs, "scopes", raising=False)
    for metric in EXPECTED:
        assert harness.load_reader(harness.ROOT, metric)(_measured()) is None
