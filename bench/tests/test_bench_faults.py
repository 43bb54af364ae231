"""Runs whose timed path is broken underneath, driven through the harness
without a chip at a tiny size, come out not correct: once for each fault a
training cell can have (half of the batch left out with the mean taken over
the rest; a step that leaves its state unchanged) and for the control, the
reference computed with float8 matmul operands in the program's place.  On
one chip there is no exchange between chips to leave out, and no token or
answer is produced to alter."""
import os
import time

import pytest

from bench import harness
from bench.tests import tiny

SEED = 10 ** 10 + 62


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(str(tmp_path_factory.mktemp("bench_root")))


@pytest.mark.parametrize("fault", ["half_batch", "unchanged", "control"])
@pytest.mark.parametrize("cell", [c[0] for c in tiny.CELLS])
def test_broken_timed_path_is_not_correct(root, cell, fault):
    r = harness.run_cell(cell, SEED, 0.2, False, time.perf_counter(),
                         root=root, fault=fault, log=open(os.devnull, "w"),
                         **tiny.CPU)
    assert not r["correct"], r["check"]
