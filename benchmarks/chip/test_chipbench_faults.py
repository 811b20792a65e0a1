"""Each fault a cell can have, planted under the timed path at a tiny
size on the CPU, makes ``correct`` come out false under the cell's
committed limits. The faults are those the cell's kind declares in
``FAULTS`` of ``kinds/<kind>.py``; the training kinds name a step that
returns its state unchanged, and half of the training rows left out of
the loss with the mean taken over the rest (one chip: no exchange
between chips to leave out)."""
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)
sys.path.insert(1, os.path.join(ROOT, "src"))

import calibrate  # noqa: E402
import harness  # noqa: E402
from test_chipbench_cells import CELLS, tiny_bench, tiny_spec  # noqa: E402


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    return tiny_bench(tmp_path_factory.mktemp("bench"))


def _faults():
    spec = tiny_spec()
    return [(cell, fault) for cell in CELLS
            for fault in calibrate.faults(cell, spec)]


@pytest.mark.parametrize("cell,fault", _faults())
def test_fault_makes_the_run_incorrect(cell, fault, bench):
    res = harness.run_cell(cell, 77, 0.2, False, require_tpu=False,
                           hooks=calibrate.MODES[fault], spec=tiny_spec(),
                           bench_dir=bench)
    assert not res["correct"], res["checks"]
