"""The control of each cell, at a tiny size on the CPU, makes
``correct`` come out false under the cell's committed limits.

On the chip the control is the program one matmul precision below the
configuration's (``high`` for ``highest``). XLA:CPU computes float32
dots exactly at every precision, so here the next lower precision
stands in: the program's own bfloat16 training path."""
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


@pytest.mark.parametrize("cell", CELLS)
def test_control_makes_the_run_incorrect(cell, bench):
    res = harness.run_cell(cell, 91, 0.2, False, require_tpu=False,
                           hooks=calibrate.MODES["control_cpu"],
                           spec=tiny_spec(), bench_dir=bench)
    assert not res["correct"], res["checks"]
