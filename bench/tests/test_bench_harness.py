"""The harness driven end to end without a chip, at a tiny size: it finds a
cell, a configuration and a metric added as files, a sound run comes out
correct.  The broken runs are in test_bench_faults.py."""
import json
import os
import subprocess
import sys
import time

import pytest

from bench import harness
from bench.tests import tiny

SEEDS = (10 ** 10, 10 ** 10 + 31, 10 ** 10 + 62)


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(str(tmp_path_factory.mktemp("bench_root")))


def run(root, cell, seed, trace=False, fault=None):
    return harness.run_cell(cell, seed, 0.2, trace, time.perf_counter(),
                            root=root, fault=fault, log=open(os.devnull, "w"),
                            **tiny.CPU)


@pytest.mark.parametrize("cell", [c[0] for c in tiny.CELLS])
def test_sound_run_is_correct_and_reports_its_metrics(root, cell):
    bench = harness.load_benchmark(root)
    r = run(root, cell, SEEDS[0])
    assert r["correct"], r["check"]
    assert r["attempted"] > 0 and r["failed"] == 0
    want = {m["name"] for m in harness.metrics_for(bench, cell, "end_to_end")}
    # hbm_peak_gib needs the device's allocator statistics: none on the CPU
    assert set(r["metrics"]) == want - {"hbm_peak_gib"}
    assert list(r)[-2:] == ["check", "_report"]
    for n, c in r["check"].items():
        assert c["value"] <= c["limit"], n


def test_traced_run_reports_per_layer_metrics_and_breakdown(root):
    cell = "tiny-qwen.stage0_sil"
    r = run(root, cell, SEEDS[1], trace=True)
    assert r["correct"]
    bench = harness.load_benchmark(root)
    want = {m["name"] for m in harness.metrics_for(bench, cell, "per_layer")}
    # the Pallas kernels do not run on the CPU: their rooflines stay silent
    assert set(r["metrics"]) == want - {"flash_fwd_roofline",
                                        "sil_mse_roofline"}
    assert 0 < r["device"]["busy_s"] <= r["device"]["window_s"]
    assert r["breakdown"]["device_ops"] and \
        len(r["breakdown"]["device_ops"]) <= 10


def test_a_cell_configuration_and_metric_added_as_files(root, tmp_path):
    """A new configuration, traffic, limits and per-layer metric are new
    files plus entries in BENCHMARK.json; no module of the harness is
    edited."""
    import shutil
    new = str(tmp_path / "root")
    shutil.copytree(root, new)
    bench = harness.load_benchmark(new)
    cfg = dict(json.load(open(os.path.join(new, "bench/configs/tiny-qwen.json"))),
               name="tiny-qwen-wide", hidden_size=96)
    json.dump(cfg, open(os.path.join(new, "bench/configs/tiny-qwen-wide.json"),
                        "w"))
    json.dump({"stage": 1, "batch": 2, "seq": 64, "pool": 8, "branch": 4,
               "repeat_p": 0.1, "span": 8, "check_steps": 3},
              open(os.path.join(new, "bench/traffic/tiny_new.json"), "w"))
    cell = "tiny-qwen-wide.tiny_new"
    json.dump({n: {"limit": 1.0} for n in tiny.NUMBERS},
              open(os.path.join(new, f"bench/limits/{cell}.json"), "w"))
    with open(os.path.join(new, "bench/metrics/steps_seen.py"), "w") as f:
        f.write("def read(m):\n    return float(m.steps)\n")
    bench["configs"].append({"name": "tiny-qwen-wide", "source": "x",
                             "file": "bench/configs/tiny-qwen-wide.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": cell, "config": "tiny-qwen-wide",
                               "traffic": "tiny_new", "chips": 1,
                               "why": "test"})
    bench["per_layer"].append({"name": "steps_seen", "unit": "steps",
                               "better": "higher", "source": "host_clock",
                               "layer": "test", "moves": "train_tokens_per_s",
                               "workloads": [cell]})
    json.dump(bench, open(os.path.join(new, "BENCHMARK.json"), "w"))
    r = run(new, cell, SEEDS[0], trace=True)
    assert r["metrics"]["steps_seen"]["value"] == r["attempted"]
    assert set(r["check"]) == set(tiny.NUMBERS)


def test_unknown_cell_and_missing_files_fail(root, tmp_path):
    with pytest.raises(KeyError, match="no workload"):
        run(root, "no-such.cell", SEEDS[0])
    import shutil
    new = str(tmp_path / "root")
    shutil.copytree(root, new)
    os.remove(os.path.join(new, "bench/limits/tiny-glm.stage_sil.json"))
    with pytest.raises(FileNotFoundError, match="limits"):
        run(new, "tiny-glm.stage_sil", SEEDS[0])


def test_command_without_chip_exits_nonzero_with_no_result():
    """``bench/run.py`` on the CPU: no result line, a non-zero exit."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, os.path.join(harness.BENCH, "run.py"),
                        "--workload", "qwen2-1.5b.stage0_sil", "--seed", "1",
                        "--seconds", "1", "--trace", "0"],
                       cwd=harness.ROOT, env=env, capture_output=True,
                       text=True, timeout=120)
    assert p.returncode != 0
    assert "TPU" in p.stderr
    assert not p.stdout.strip()


def _tiny_run(root):
    from bench import model as bm
    from bench.stage import StageRun
    from bench.traffic import load_traffic
    bench = harness.load_benchmark(root)
    cell = harness.workload(bench, "tiny-glm.stage_sil")
    cfg = bm.load_config(os.path.join(
        root, harness.config_entry(bench, cell["config"])["file"]))
    traffic = load_traffic(os.path.join(
        root, "bench/traffic", cell["traffic"] + ".json"), cell["traffic"])
    return StageRun(cfg, traffic, SEEDS[0])


def test_step_temporaries_from_the_builders_program(root):
    """``step_temp_gib`` and ``hbm_peak_gib`` read the timed step's
    temporaries from the builder's own jitted program, lowered at the
    timed arguments."""
    run = _tiny_run(root)
    run.first_steps()
    assert run.temp_bytes() > 0


def test_step_temporaries_without_the_builders_program_is_an_error(root):
    """A step whose closure holds no jitted program gives no temporaries
    from another program: the run fails."""
    run = _tiny_run(root)
    step = run.step_fn
    run.step_fn = lambda *a: step(*a)
    with pytest.raises(RuntimeError, match="no jitted program"):
        run.temp_bytes()
