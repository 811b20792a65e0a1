"""Unit tests of the chip benchmark's yardstick, on the CPU: the trace
reduction on a synthetic trace and on a synthetic ``.xplane.pb``, the
operation and byte counts on shapes worked out by hand, the graph
generator, and the harness's refusal to run or report device numbers
off the chip."""
import os
import subprocess
import sys
import types

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)
sys.path.insert(1, os.path.join(ROOT, "src"))

import calibrate  # noqa: E402
import devtrace  # noqa: E402
import graphs  # noqa: E402
import harness  # noqa: E402


def _trace():
    # window 0..100 ns; two devices; host annotations name the gaps
    ops = {"/device:TPU:0": [("fusion.1", 10, 30), ("gather.2", 25, 40),
                             ("fusion.1", 60, 70), ("early", -20, 5)],
           "/device:TPU:1": [("fusion.1", 0, 50)]}
    host = [(devtrace.WINDOW, 0, 100), ("bench.step", 0, 100),
            ("bench.sample_wait", 40, 60), ("bench.step", 70, 95)]
    return devtrace.Trace(device_ops=ops, host=host, window=(0, 100))


def test_union_merges_overlaps_and_clips():
    assert devtrace.union_ns([(10, 30), (25, 40), (60, 70)], 0, 100) == 40
    assert devtrace.union_ns([(-20, 5), (95, 120)], 0, 100) == 10
    assert devtrace.union_ns([], 0, 100) == 0


def test_busy_is_the_device_average_inside_the_window():
    tr = _trace()
    # device 0: 0..5, 10..40, 60..70 -> 45 ns; device 1: 50 ns
    assert devtrace.busy_s(tr) == pytest.approx(47.5e-9)
    assert tr.window_s == pytest.approx(100e-9)


def test_op_seconds_and_top_ops():
    secs = devtrace.op_seconds(_trace())
    assert secs["fusion.1"] == pytest.approx((20 + 10 + 50) / 2 * 1e-9)
    assert secs["gather.2"] == pytest.approx(15 / 2 * 1e-9)
    assert secs["early"] == pytest.approx(5 / 2 * 1e-9)
    assert [n for n, _ in devtrace.top_ops(_trace(), 2)] == ["fusion.1",
                                                             "gather.2"]


def test_idle_gaps_are_named_by_the_innermost_annotation():
    gaps = devtrace.idle_gaps(_trace())
    # device 0 idles 70..100 (30), 40..60 (20), 5..10 (5)
    assert [round(s * 1e9) for _, s in gaps] == [30, 20, 5]
    assert [n for n, _ in gaps] == ["bench.step", "bench.sample_wait",
                                    "bench.step"]


B = 10 ** 15        # the profiler's clock: ns since an epoch
SCOPED = {"fusion.1": "jit(step)/jvp(sage.layer0)/weighted_copy_reduce/add",
          "gather.2": "jit(step)/transpose(jvp(sage.layer1))/"
                      "transpose(jvp(gspmm.u_copy_sum_v))/gather",
          "early": ""}


def _xspace(path):
    """Device 0 and the host of ``_trace()`` as a raw XSpace: ops name
    their scope by a ``tf_op`` stat in their event metadata, as a string
    or as a reference to a stat's name, beside another stat."""
    pb2 = devtrace._xplane_pb2()
    space = pb2.XSpace()
    dev = space.planes.add(name="/device:TPU:0")
    dev.stat_metadata[1].name = "tf_op"
    dev.stat_metadata[2].name = "flops"
    dev.stat_metadata[3].name = SCOPED["gather.2"]
    for k, name in enumerate(SCOPED, start=1):
        md = dev.event_metadata[k]
        md.id, md.name = k, name
        md.stats.add(metadata_id=2, uint64_value=64)
        if name == "gather.2":
            md.stats.add(metadata_id=1, ref_value=3)
        elif SCOPED[name]:
            md.stats.add(metadata_id=1, str_value=SCOPED[name])
    dev.lines.add(name="XLA Modules", timestamp_ns=B).events.add(
        metadata_id=1, offset_ps=0, duration_ps=100_000)
    ln = dev.lines.add(name="XLA Ops", timestamp_ns=B)
    ids = {name: k for k, name in enumerate(SCOPED, start=1)}
    for name, s, e in _trace().device_ops["/device:TPU:0"]:
        ln.events.add(metadata_id=ids[name], offset_ps=s * 1000,
                      duration_ps=(e - s) * 1000)
    host = space.planes.add(name="/host:CPU")
    hl = host.lines.add(name="python", timestamp_ns=B)
    for k, (name, s, e) in enumerate(_trace().host, start=1):
        host.event_metadata[k].name = name
        hl.events.add(metadata_id=k, offset_ps=s * 1000,
                      duration_ps=(e - s) * 1000)
    with open(path, "wb") as f:
        f.write(space.SerializeToString())


def test_scopes_are_read_beside_the_ops_from_a_raw_xspace(tmp_path):
    _xspace(str(tmp_path / "t.xplane.pb"))
    tr = devtrace.load(str(tmp_path))
    ops = _trace().device_ops["/device:TPU:0"]
    want = devtrace.Trace(
        device_ops={"/device:TPU:0": [(n, s + B, e + B) for n, s, e in ops]},
        host=[(n, s + B, e + B) for n, s, e in _trace().host],
        window=(B, B + 100))
    # the existing readings are those of the same ops without scopes
    assert tr.device_ops == want.device_ops and tr.window == want.window
    assert devtrace.busy_s(tr) == devtrace.busy_s(want) == \
        pytest.approx(45e-9)
    assert devtrace.top_ops(tr) == devtrace.top_ops(want)
    assert devtrace.idle_gaps(tr) == devtrace.idle_gaps(want)
    assert tr.scopes == {"/device:TPU:0": [SCOPED[n] for n, _, _ in ops]}


def test_scoped_time_unwraps_autodiff_and_clips_to_the_window(tmp_path):
    assert devtrace.scope_parts(SCOPED["gather.2"]) == [
        "step", "sage.layer1", "gspmm.u_copy_sum_v", "gather"]
    _xspace(str(tmp_path / "t.xplane.pb"))
    tr = devtrace.load(str(tmp_path))
    agg = harness.load_module(os.path.join(HERE, "metrics",
                                           "aggregate_ms.full.py"),
                              "aggregate_ms.full")
    # fusion.1 (10..30, 60..70) and gather.2 (25..40); "early" has no
    # scope; ``weighted_copy_reduce`` does not match ``.bwd`` or a prefix
    assert devtrace.scoped_ns(tr, agg.SCOPES) == 40
    assert devtrace.scoped_ns(tr, ["sage.layer1"]) == 15
    assert devtrace.scoped_ns(tr, ["weighted_copy_reduce.bwd", "gspmm",
                                   "step.*"]) == 0
    tr.window = (B + 12, B + 65)
    assert devtrace.scoped_ns(tr, agg.SCOPES) == (40 - 12) + (65 - 60)

    tr.window = (B, B + 100)
    roof = harness.load_module(os.path.join(HERE, "metrics",
                                            "aggregate_roofline.full.py"),
                               "aggregate_roofline.full")
    run = types.SimpleNamespace(
        bench_dir=HERE, trace_data=tr, devices=[0],
        peaks={"hbm_bytes_per_s": 1e12},
        layer={"steps": 2, "aggregate_bytes_per_step": 5e3})
    assert agg.read(run) == pytest.approx(20e-6)        # ms a step
    # 5 kB at 1 TB/s takes 5 ns of the 20 ns a step spent
    assert roof.read(run) == pytest.approx(25.0)
    tr.scopes = None
    assert agg.read(run) is None and roof.read(run) is None


def test_sage_aggregate_bytes_by_hand():
    sage = harness.load_module(os.path.join(HERE, "models", "sage.py"),
                               "sage")
    cfg = {"model": {"num_layers": 2, "hidden": 4, "in_feats": 3,
                     "n_classes": 2}}
    n, e = 5, 7
    # per mean 4 B x (e rows of width a + e indices + e weights + n rows)
    # layer 0 (a = 3): 21 + 14 + 15 = 50 values, forward only
    # layer 1 (a = 4): 28 + 14 + 20 = 62 values, forward and backward
    assert sage.aggregate_bytes_per_epoch(cfg, n, e) == 4 * (50 + 2 * 62)


def test_gat_aggregate_bytes_by_hand():
    gat = harness.load_module(os.path.join(HERE, "models", "gat.py"), "gat")
    cfg = {"model": {"num_layers": 2, "heads": 2, "hidden": 3,
                     "in_feats": 4, "n_classes": 2}}
    n, e = 5, 7
    # per direction e x (H (F + 1) + 1); per node forward H + HF,
    # backward H + HF read, HF + 2H written
    # layer 0 (H 2, F 3): edges 7 x 9 = 63 twice, nodes 5 x 8, 5 x 18
    # layer 1 (H 1, F 2): edges 7 x 4 = 28 twice, nodes 5 x 3, 5 x 7
    want = 4 * (2 * 63 + 40 + 90 + 2 * 28 + 15 + 35)
    assert gat.aggregate_bytes_per_epoch(cfg, n, e) == want


def test_sage_flops_by_hand():
    sage = harness.load_module(os.path.join(HERE, "models", "sage.py"),
                               "sage")
    cfg = {"model": {"num_layers": 2, "hidden": 4, "in_feats": 3,
                     "n_classes": 2}}
    n, e = 5, 7
    # layer 0 (3 -> 4): agg 2*7*3 = 42, dense 2*5*6*4 = 240, dW 240
    # layer 1 (4 -> 2): agg 56, dense 2*5*8*2 = 160, dW 160, dX 160 + 56
    assert sage.flops_per_epoch(cfg, n, e) == 42 + 240 + 240 + \
        56 + 160 + 160 + 160 + 56


def test_gat_flops_by_hand():
    gat = harness.load_module(os.path.join(HERE, "models", "gat.py"), "gat")
    cfg = {"model": {"num_layers": 2, "heads": 2, "hidden": 3,
                     "in_feats": 4, "n_classes": 2}}
    n, e = 5, 7
    # layer 0: proj 2*5*4*6 = 240, rest 4*5*6 + 6*7*2 + 2*7*6 = 288
    # layer 1 (one head of 2): proj 2*5*6*2 = 120,
    #   rest 4*5*2 + 6*7 + 2*7*2 = 110
    want = 3 * (240 + 288) - 240 + 3 * (120 + 110)
    assert gat.flops_per_epoch(cfg, n, e) == want


def test_graph_is_the_configs_alone():
    cfg = harness.load_json(os.path.join(HERE, "fixtures", "sage-tiny.json"))
    a, b = graphs.build(cfg), graphs.build(cfg)
    assert np.array_equal(a.src, b.src) and np.array_equal(a.dst, b.dst)
    assert np.array_equal(a.feats, b.feats)
    keys = a.dst.astype(np.int64) * a.n + a.src
    assert np.all(np.diff(keys) > 0)            # sorted, no duplicates
    pairs = set(zip(a.src.tolist(), a.dst.tolist()))
    assert all((d, s) in pairs for s, d in pairs)    # symmetric
    assert all((v, v) in pairs for v in range(a.n))  # self loops
    assert a.n_edges - a.n == 2 * len({(min(s, d), max(s, d))
                                       for s, d in pairs if s != d})


def test_peaks_refuse_an_unknown_device():
    assert harness.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(SystemExit):
        harness.peaks("TPU v4")


def test_seed_key_takes_seeds_wider_than_32_bits():
    import jax
    k1 = harness.seed_key(2 ** 31 + 5)
    k2 = harness.seed_key(2 ** 33 + 2 ** 31 + 5)
    assert not np.array_equal(jax.random.key_data(k1),
                              jax.random.key_data(k2))


def test_run_py_refuses_a_machine_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "benchmarks/chip/run.py",
                        "--workload", "sage-arxiv.full", "--seed", "1",
                        "--seconds", "1", "--trace", "0"], cwd=ROOT,
                       env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_spec_resolves_every_cell_to_its_files():
    spec = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    for wl in spec["workloads"]:
        cell = harness.resolve(spec, wl["name"])
        assert cell.end_to_end and cell.per_layer
        assert any(m["name"] == "setup_s" for m in cell.end_to_end)
        for m in cell.per_layer:
            assert os.path.exists(os.path.join(HERE, "metrics",
                                               m["name"] + ".py"))
        # the kind's file names the faults a cell of it can have
        faults = calibrate.faults(wl["name"], spec)
        assert faults and set(faults) <= set(calibrate.MODES)
