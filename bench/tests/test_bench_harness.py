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
    """A new model family, configuration, traffic, limits and per-layer
    metric are new files plus entries in BENCHMARK.json; no module of the
    harness is edited.  The family (``probe``, which only the test tree
    holds) is named by the configuration's ``"family"``, and the metrics
    count with its numbers."""
    import shutil
    new = str(tmp_path / "root")
    shutil.copytree(root, new)
    bench = harness.load_benchmark(new)
    cfg = dict(json.load(open(os.path.join(new, "bench/configs/tiny-qwen.json"))),
               name="tiny-qwen-wide", hidden_size=96, family="probe")
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
    r = run(new, cell, SEEDS[0])
    assert r["correct"], r["check"]
    m = r["metrics"]
    assert set(m) == {"train_tokens_per_s", "mfu", "setup_s"}
    # mfu counts the family's FLOPs a step: 123456789 over 2 x 64 tokens
    from bench.tests import probe_family
    assert m["mfu"]["value"] == pytest.approx(
        100 * probe_family.FLOPS_PER_STEP * m["train_tokens_per_s"]["value"]
        / (2 * 64) / tiny.PEAK["bf16_flops_per_s"])


def test_configuration_naming_an_unknown_family_fails(root, tmp_path):
    import shutil
    new = str(tmp_path / "root")
    shutil.copytree(root, new)
    path = os.path.join(new, "bench/configs/tiny-glm.json")
    json.dump(dict(json.load(open(path)), family="no_such_family"),
              open(path, "w"))
    with pytest.raises(ValueError, match="no model family 'no_such_family'"):
        run(new, "tiny-glm.stage_sil", SEEDS[0])
    json.dump({k: v for k, v in json.load(open(path)).items()
               if k != "family"}, open(path, "w"))
    with pytest.raises(ValueError, match="names no \"family\""):
        run(new, "tiny-glm.stage_sil", SEEDS[0])


# the tiny qwen cells' numbers at SEEDS[0], read before the dense family
# was moved behind the family contract: the move changes no reading
BEFORE = {
    "tiny-qwen.stage0_sil": {"loss_gap": 4.4732224745095796e-05,
                             "grad_gap": 0.001616613901737397,
                             "change_gap": 0.003557526835196843},
    "tiny-qwen.stage_last_ce": {"loss_gap": 2.1598101835392982e-05,
                                "grad_gap": 0.004611403614382444,
                                "change_gap": 0.0014510109100782818},
}


@pytest.mark.parametrize("cell", sorted(BEFORE))
def test_tiny_cells_read_as_before_through_the_dense_family(root, cell):
    r = run(root, cell, SEEDS[0])
    got = {n: c["value"] for n, c in r["check"].items()}
    assert got == pytest.approx(BEFORE[cell], rel=1e-6)


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
    cell = harness.load_cell(root, "tiny-glm.stage_sil")
    return cell.family.StageRun(cell.cfg, cell.traffic, SEEDS[0])


def test_step_temporaries_from_the_builders_program(root):
    """``step_temp_gib`` and ``hbm_peak_gib`` read the timed step's
    temporaries from the builder's own jitted program, lowered at the
    timed arguments."""
    run = _tiny_run(root)
    run.first_steps()
    assert run.temp_bytes()[0] > 0


def test_step_temporaries_without_the_builders_program_is_an_error(root):
    """A step whose closure holds no jitted program gives no temporaries
    from another program: the run fails."""
    run = _tiny_run(root)
    step = run.step_fn
    run.step_fn = lambda *a: step(*a)
    with pytest.raises(RuntimeError, match="no jitted program"):
        run.temp_bytes()
