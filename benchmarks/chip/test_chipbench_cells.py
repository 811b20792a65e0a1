"""A rehearsal of every cell on the CPU at a tiny size (Pallas
interpreted): each runs through the harness end to end with the chip
check skipped, its output is correct, it reports its end-to-end metrics
and no device metric; a change of ``--seed`` moves the weights and the
traffic but not the graph; and a cell, a mix and a per-layer metric can
be added by adding files and spec entries alone."""
import json
import os
import shutil
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)
sys.path.insert(1, os.path.join(ROOT, "src"))

import harness  # noqa: E402

SECONDS = 0.3


def tiny_spec():
    """The benchmark's spec with each configuration cut to a CPU size:
    ``fixtures/<prefix>-tiny.json``, the prefix being the
    configuration's name up to its first ``-`` (a configuration of a
    new model brings its own)."""
    spec = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    for c in spec["configs"]:
        c["file"] = os.path.join(HERE, "fixtures",
                                 c["name"].split("-")[0] + "-tiny.json")
    return spec


def tiny_bench(tmp_path, src=HERE):
    """A copy of the benchmark directory ``src`` in which each mix
    ``<traffic>`` that has a ``fixtures/<traffic>-tiny.json`` is
    replaced by that fixture, cut to a CPU size; everything else is as
    in ``src``."""
    dst = tmp_path / "chip"
    shutil.copytree(src, dst, ignore=shutil.ignore_patterns(
        "__pycache__", "test_*.py"))
    for name in os.listdir(dst / "mixes"):
        traffic, ext = os.path.splitext(name)
        tiny = dst / "fixtures" / f"{traffic}-tiny.json"
        if ext == ".json" and tiny.exists():
            shutil.copyfile(tiny, dst / "mixes" / name)
    return str(dst)


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    return tiny_bench(tmp_path_factory.mktemp("bench"))


CELLS = [w["name"] for w in harness.load_json(
    os.path.join(ROOT, "BENCHMARK.json"))["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
def test_cell_rehearses_on_the_cpu(cell, bench):
    spec = tiny_spec()
    res = harness.run_cell(cell, 2 ** 32 + 17, SECONDS, False,
                           require_tpu=False, spec=spec, bench_dir=bench)
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    want = {m["name"] for m in harness.resolve(spec, cell, bench).end_to_end}
    assert set(res["metrics"]) == want
    assert all(v["value"] > 0 for v in res["metrics"].values())
    assert res["device"]["platform"] == "cpu"
    assert list(res)[-1] == "checks"


def test_seed_moves_weights_and_traffic_but_not_the_graph(bench):
    import jax
    import graphs
    cfg = harness.load_json(os.path.join(HERE, "fixtures",
                                         "sage-tiny.json"))
    sage = harness.load_module(os.path.join(HERE, "models", "sage.py"),
                               "sage")
    make = sage.init(cfg)
    w1 = make(jax.random.split(harness.seed_key(1))[0])
    w2 = make(jax.random.split(harness.seed_key(2))[0])
    assert not np.array_equal(w1["layers"][0]["w"], w2["layers"][0]["w"])
    assert np.array_equal(graphs.build(cfg).src, graphs.build(cfg).src)

    from repro.core.graph import from_coo
    from repro.data.sampler import NeighborSampler
    gd = graphs.build(cfg)
    g = from_coo(gd.src, gd.dst, n_src=gd.n, n_dst=gd.n)
    ids = np.nonzero(gd.train_mask)[0]

    def first_seeds(seed):
        s = NeighborSampler(g, [2, 2, 2], 16, seed=seed)
        mb = next(s.batches(ids, gd.labels[ids], drop_last=True))
        return np.asarray(mb.seed_ids)
    assert np.array_equal(first_seeds(3), first_seeds(3))
    assert not np.array_equal(first_seeds(3), first_seeds(4))


def test_a_cell_mix_and_metric_are_added_by_files_alone(tmp_path):
    bench = tiny_bench(tmp_path)
    cfg = harness.load_json(os.path.join(HERE, "fixtures",
                                         "sage-tiny.json"))
    cfg["name"] = "sage-fixture"
    cfg["model"]["hidden"] = 8
    cfg_path = os.path.join(bench, "configs", "sage-fixture.json")
    with open(cfg_path, "w") as f:
        json.dump(cfg, f)
    mix = harness.load_json(os.path.join(HERE, "mixes", "full.json"))
    mix["ref_steps"] = 2
    with open(os.path.join(bench, "mixes", "full-short.json"), "w") as f:
        json.dump(mix, f)
    with open(os.path.join(bench, "limits",
                           "sage-fixture.full-short.json"), "w") as f:
        json.dump(harness.load_json(os.path.join(
            HERE, "limits", "sage-arxiv.full.json")), f)
    with open(os.path.join(bench, "metrics", "edge_slots.fixture.py"),
              "w") as f:
        f.write("def read(run):\n    return run.layer.get('n_edges')\n")

    spec = tiny_spec()
    spec["configs"].append({"name": "sage-fixture", "source": "fixture",
                            "file": cfg_path, "reduced": [],
                            "why": "fixture"})
    spec["workloads"].append({"name": "sage-fixture.full-short",
                              "config": "sage-fixture",
                              "traffic": "full-short", "chips": 1,
                              "why": "fixture"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if m["name"] in ("epoch_s", "mfu.full", "device_idle.full",
                         "pad_ratio.full"):
            m["workloads"].append("sage-fixture.full-short")
    spec["per_layer"].append({"name": "edge_slots.fixture", "unit": "edges",
                              "better": "lower", "source": "program_counter",
                              "layer": "planner", "moves": "epoch_s",
                              "workloads": ["sage-fixture.full-short"]})
    res = harness.run_cell("sage-fixture.full-short", 9, SECONDS, True,
                           require_tpu=False, spec=spec, bench_dir=bench)
    assert res["correct"], res["checks"]
    import graphs
    assert res["metrics"]["edge_slots.fixture"]["value"] == \
        graphs.build(cfg).n_edges
    # a traced run off the chip: no busy time, no breakdown, and of the
    # per-layer metrics only counts, never a time or a share of the device
    assert "busy_s" not in res["device"] and "breakdown" not in res
    sources = {m["name"]: m["source"] for m in spec["per_layer"]}
    assert set(res["metrics"]) <= {"edge_slots.fixture", "pad_ratio.full"}
    assert all(sources[k] == "program_counter" for k in res["metrics"])
