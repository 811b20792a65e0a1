"""The readers of the program's spans and counters, each on a synthetic
run: a hand-made span buffer (what ``repro.obs.trace_events()`` gives)
and a ``devtrace.Trace`` whose window the harness's host-clock window
matches."""
import os
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)
sys.path.insert(1, os.path.join(ROOT, "src"))

import devtrace  # noqa: E402
import harness  # noqa: E402

import repro.obs  # noqa: E402

T0 = 1_000_000_000          # the window opens 1 s into the host clock
MS = 1_000_000              # ns
OFF = 5_000 * MS            # the profiler's clock runs 5 s ahead


def _reader(name):
    return harness.load_module(os.path.join(HERE, "metrics", name + ".py"),
                               name)


def _run(events, monkeypatch, steps=2, trace_len_ns=100 * MS, ops=()):
    monkeypatch.setattr(repro.obs, "trace_events", lambda: list(events))
    td = devtrace.Trace(device_ops={"/device:TPU:0": list(ops)}, host=[],
                        window=(T0 + OFF, T0 + OFF + trace_len_ns))
    return types.SimpleNamespace(bench_dir=HERE, _t_window=T0 * 1e-9,
                                 window_s=0.1, trace_data=td,
                                 layer={"steps": steps})


_IDS = iter(range(1, 10_000))


def _ev(name, start_ms, end_ms, parent=0, cat="repro", tid=1, **args):
    return {"name": name, "cat": cat, "tid": tid,
            "start_ns": T0 + start_ms * MS, "end_ns": T0 + end_ms * MS,
            "args": dict(args, id=next(_IDS), parent=parent)}


def _sampled(events=None):
    """Three ``sampler.sample`` spans: one ends before the window, two
    in it (40 ms and 20 ms); children under each."""
    evs = list(events or [])
    for start, end, edges, slots in ((-50, -10, 1, 10), (0, 40, 30, 100),
                                     (50, 70, 10, 100)):
        top = _ev("sampler.sample", start, end, edges=edges,
                  edge_slots=slots)
        pid = top["args"]["id"]
        evs += [top,
                _ev("sampler.draw", start + 1, start + 3, pid),
                _ev("sampler.graph", start + 3, start + 9, pid),
                _ev("sampler.upload", start + 9, start + 10, pid),
                _ev("sampler.draw", start + 10, start + 12, pid),
                _ev("sampler.upload", start + 12, start + 13, pid)]
    return evs


def test_sampler_ms_is_the_mean_of_the_samples_that_ended_in_the_window(
        monkeypatch):
    run = _run(_sampled([_ev("sampler.sample", 90, 120)]), monkeypatch)
    assert _reader("sampler_ms.sampled").read(run) == pytest.approx(30.0)


@pytest.mark.parametrize("phase,want_ms", [("draw", 4.0), ("graph", 6.0),
                                           ("upload", 2.0)])
def test_a_phase_is_its_spans_summed_over_blocks_per_sample(
        monkeypatch, phase, want_ms):
    run = _run(_sampled(), monkeypatch)
    got = _reader(f"sampler_{phase}_ms.sampled").read(run)
    assert got == pytest.approx(want_ms)


def test_prefetch_wait_is_per_step(monkeypatch):
    waits = [_ev("prefetch.wait", -5, -1, batch=0),
             _ev("prefetch.wait", 0, 30, batch=1),
             _ev("prefetch.wait", 40, 50, batch=2)]
    run = _run(waits, monkeypatch, steps=2)
    assert _reader("prefetch_wait_ms.sampled").read(run) == \
        pytest.approx(20.0)


def test_edge_fill_is_real_edges_over_slots_in_the_window(monkeypatch):
    run = _run(_sampled(), monkeypatch)
    assert _reader("edge_fill.sampled").read(run) == pytest.approx(20.0)


def _ops():
    # device busy 0..20 and 60..70 ms of the window (profiler clock)
    return [("fusion.1", T0 + OFF, T0 + OFF + 20 * MS),
            ("fusion.2", T0 + OFF + 60 * MS, T0 + OFF + 70 * MS)]


def test_idle_input_share_intersects_waits_with_device_idle(monkeypatch):
    # idle: 20..60 and 70..100 ms (70 ms); the waits cover 10..50 (30 ms
    # of it idle) and 65..80 (10 ms idle): 40 of 70 ms
    waits = [_ev("prefetch.wait", 10, 50), _ev("prefetch.wait", 65, 80)]
    run = _run(waits, monkeypatch, ops=_ops())
    assert _reader("idle_input_share.sampled").read(run) == \
        pytest.approx(100.0 * 40 / 70)


def test_idle_input_share_refuses_windows_of_different_lengths(monkeypatch):
    waits = [_ev("prefetch.wait", 10, 50)]
    run = _run(waits, monkeypatch, trace_len_ns=102 * MS, ops=_ops())
    assert _reader("idle_input_share.sampled").read(run) is None


@pytest.mark.parametrize("metric", ["recompiles.full", "recompiles.sampled"])
def test_recompiles_count_builds_that_ended_in_the_window(monkeypatch,
                                                          metric):
    evs = [_ev("jax.compile", -30, -5, cat="jax", fun="jit(step)"),
           _ev("jax.compile", 10, 40, cat="jax", fun="jit(step)"),
           # a cache load inside a compile is that compile, counted once
           _ev("jax.cache_get", 20, 30, cat="jax"),
           _ev("jax.cache_get", 60, 61, cat="jax", tid=2),
           _ev("jax.trace", 80, 81, cat="jax")]
    run = _run(evs, monkeypatch)
    assert _reader(metric).read(run) == 2.0
    run = _run([], monkeypatch)
    assert _reader(metric).read(run) == 0.0


SPAN_READERS = ["sampler_ms.sampled", "sampler_draw_ms.sampled",
                "sampler_graph_ms.sampled", "sampler_upload_ms.sampled",
                "prefetch_wait_ms.sampled", "edge_fill.sampled",
                "idle_input_share.sampled"]


@pytest.mark.parametrize("metric", SPAN_READERS)
def test_a_program_without_the_spans_gives_no_reading(monkeypatch, metric):
    run = _run([_ev("bench.other", 0, 10)], monkeypatch, ops=_ops())
    assert _reader(metric).read(run) is None


def test_telemetry_off_gives_no_reading(monkeypatch):
    monkeypatch.setattr(repro.obs, "enabled", lambda: False)
    run = _run(_sampled(), monkeypatch)
    for metric in ["sampler_ms.sampled", "edge_fill.sampled",
                   "recompiles.full"]:
        assert _reader(metric).read(run) is None
