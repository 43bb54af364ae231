"""A model family that only the test tree holds (``tiny.make_root`` writes it
to ``bench/families/probe.py``): the dense family's model under another
name, whose counts are its own, so that a test can see the harness take a
family from files alone and its metrics from the family's counts."""
import os

from bench import harness

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_dense = harness.load_family(ROOT, "dense")

FLOPS_PER_STEP = 123_456_789

parse = _dense.parse
stage_params = _dense.stage_params
program = _dense.program
step_inputs = _dense.step_inputs
step_args = _dense.step_args
StageRun = _dense.StageRun
readings = _dense.readings
change_norms = _dense.change_norms


def step_flops(cfg, k, batch, seq):
    return {"total": FLOPS_PER_STEP}


def kernel_costs(cfg, k, batch, seq):
    return {}
