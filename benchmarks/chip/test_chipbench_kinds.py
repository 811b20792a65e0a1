"""A kind of cell is added by files alone: in a copy of the benchmark
directory, a new ``kinds/<kind>.py`` with its ``FAULTS``, a mix with
its tiny fixture, limits and spec entries make a cell that rehearses on
the CPU, whose control and each declared fault come out incorrect, with
no committed file of the benchmark edited."""
import hashlib
import json
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)
sys.path.insert(1, os.path.join(ROOT, "src"))

import calibrate  # noqa: E402
import harness  # noqa: E402
from test_chipbench_cells import tiny_bench, tiny_spec  # noqa: E402


FIXTURE_KIND = '''"""A kind of cell added by files alone: full-graph training
under another name."""
import os

FAULTS = ("frozen", "half")


def run(run):
    from harness import load_module
    load_module(os.path.join(run.bench_dir, "kinds", "full_train.py"),
                "full_train").run(run)
'''


def _digest(root):
    h = hashlib.sha256()
    for d, dirs, files in sorted(os.walk(root)):
        dirs[:] = sorted(x for x in dirs if x != "__pycache__")
        for f in sorted(files):
            with open(os.path.join(d, f), "rb") as fh:
                h.update(f.encode() + fh.read())
    return h.hexdigest()


def test_a_kind_is_added_by_files_alone(tmp_path):
    before = _digest(HERE)
    src = tmp_path / "src"
    shutil.copytree(HERE, src, ignore=shutil.ignore_patterns(
        "__pycache__", "test_*.py"))
    (src / "kinds" / "full_fixture.py").write_text(FIXTURE_KIND)
    mix = harness.load_json(os.path.join(HERE, "mixes", "full.json"))
    mix["kind"] = "full_fixture"
    (src / "mixes" / "fixture.json").write_text(json.dumps(mix))
    tiny = dict(mix, ref_steps=2)
    (src / "fixtures" / "fixture-tiny.json").write_text(json.dumps(tiny))
    shutil.copyfile(src / "limits" / "sage-arxiv.full.json",
                    src / "limits" / "sage-arxiv.fixture.json")
    bench = tiny_bench(tmp_path / "run", str(src))
    # the new mix is cut to its fixture like any other
    assert harness.load_json(os.path.join(bench, "mixes",
                                          "fixture.json")) == tiny

    spec = tiny_spec()
    cell = "sage-arxiv.fixture"
    spec["workloads"].append({"name": cell, "config": "sage-arxiv",
                              "traffic": "fixture", "chips": 1,
                              "why": "fixture"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "sage-arxiv.full" in m.get("workloads", ()):
            m["workloads"].append(cell)
    faults = calibrate.faults(cell, spec, bench)
    assert faults == ("frozen", "half")
    for mode in ("sound", "control_cpu") + faults:
        res = harness.run_cell(cell, 2 ** 31 + 3, 0.1, False,
                               require_tpu=False,
                               hooks=calibrate.MODES[mode], spec=spec,
                               bench_dir=bench)
        assert res["correct"] == (mode == "sound"), (mode, res["checks"])
        if mode == "sound":
            assert set(res["metrics"]) == {"epoch_s", "setup_s"}
    assert _digest(HERE) == before
