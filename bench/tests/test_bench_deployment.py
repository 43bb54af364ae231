"""A cell that runs every stage of its configuration at once, stage k on
chip k, through the program's ``dist.StageExecutor``, driven through the
harness at a tiny size on four forced host devices
(``XLA_FLAGS=--xla_force_host_platform_device_count=4``).  JAX fixes a
process's device count when it starts, so the runs go to a subprocess of
their own.  A sound run is correct and reports each stage's numbers; a run
with a middle stage's state left unchanged, one fed half of each batch and
the control are not."""
import json
import os
import subprocess
import sys

import pytest

from bench import harness
from bench.tests import tiny

SEED = 10 ** 10 + 93
# each run's fault ("none": a sound run, traced); the runs of one
# subprocess share its compile cache
SCRIPT = """
import json, os, sys, time
from bench import harness
from bench.tests import tiny
out = {}
for fault in sys.argv[3:]:
    r = harness.run_cell(tiny.DEPLOYMENT[0], int(sys.argv[2]), 0.2,
                         fault == "none", time.perf_counter(),
                         root=sys.argv[1], fault=None if fault == "none"
                         else fault, log=open(os.devnull, "w"),
                         **dict(tiny.CPU, cache=True))
    r.pop("_report")
    out[fault] = r
print(json.dumps(out))
"""


def _runs(root: str, *faults: str) -> dict:
    flags = os.environ.get("XLA_FLAGS", "")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"{flags} --xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([os.path.join(harness.ROOT, "src"),
                                           harness.ROOT]))
    p = subprocess.run([sys.executable, "-c", SCRIPT, root, str(SEED),
                        *faults], cwd=harness.ROOT, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(str(tmp_path_factory.mktemp("bench_root")))


@pytest.fixture(scope="module")
def sound(root):
    return _runs(root, "none", "unchanged")


@pytest.fixture(scope="module")
def broken(root):
    return _runs(root, "half_batch", "control")


def test_sound_deployment_run_is_correct_and_reports_its_metrics(sound):
    r = sound["none"]
    assert r["correct"], r["check"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert set(r["check"]) == {f"s{k}.{n}" for k in range(4)
                               for n in tiny.NUMBERS}
    bench = harness.load_benchmark(harness.ROOT)
    cell = tiny.DEPLOYMENT[4]
    # the traced run's per-layer metrics, the tiny cell standing for the
    # four-chip one
    assert set(r["metrics"]) == {
        m["name"] for m in harness.metrics_for(bench, cell, "per_layer")}
    assert 0 <= r["metrics"]["deployment_idle_share"]["value"] < 100
    assert r["metrics"]["dispatch_ms"]["value"] > 0
    # an untraced run's end-to-end metrics (allocator statistics, and so
    # hbm_peak_gib, are the chip's alone)
    assert set(sound["unchanged"]["metrics"]) == {
        m["name"] for m in harness.metrics_for(bench, cell, "end_to_end")
    } - {"hbm_peak_gib"}


def test_middle_stage_left_unchanged_is_not_correct(sound):
    r = sound["unchanged"]
    assert not r["correct"], r["check"]
    # stage 2 of 0..3 left its state as it was; the others are sound
    assert r["check"]["s2.change_gap"]["value"] == pytest.approx(1.0)
    assert all(c["value"] <= c["limit"] for n, c in r["check"].items()
               if not n.startswith("s2."))


@pytest.mark.parametrize("fault", ["half_batch", "control"])
def test_broken_deployment_is_not_correct(broken, fault):
    r = broken[fault]
    assert not r["correct"], r["check"]
