"""Spans on the profiler's clock, the span tree, and the spans the
program emits from inside: the sampler's phases, the prefetcher's
waits and JAX's compiles."""
import glob
import os
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core.graph import from_coo
from repro.data.pipeline import prefetch
from repro.data.sampler import NeighborSampler


@pytest.fixture(autouse=True)
def _clean_trace():
    obs.clear_trace()
    yield
    obs.clear_trace()


def _named(name):
    return [e for e in obs.trace_events() if e["name"] == name]


def _xplane_host_events(trace_dir):
    from jax.profiler import ProfileData
    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                        recursive=True)
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for ln in plane.lines:
                for e in ln.events:
                    out.setdefault(e.name, []).append((e.start_ns, e.end_ns))
    return out


def test_span_lands_in_the_profiler_trace_on_the_host_clock(tmp_path):
    jax.profiler.start_trace(str(tmp_path))
    try:
        # the anchor: one annotation and the host clock read right after
        with jax.profiler.TraceAnnotation("unit.anchor"):
            t_anchor = time.perf_counter_ns()
        with obs.span("unit.clock"):
            time.sleep(0.002)
    finally:
        jax.profiler.stop_trace()
    host = _xplane_host_events(str(tmp_path))
    (twin,) = host["unit.clock"]
    off = host["unit.anchor"][0][0] - t_anchor
    (ev,) = _named("unit.clock")
    assert abs(ev["start_ns"] + off - twin[0]) < 1e6
    assert abs(ev["end_ns"] + off - twin[1]) < 1e6
    assert ev["dur"] == pytest.approx((ev["end_ns"] - ev["start_ns"]) / 1e3)


def test_disabled_records_no_span_counter_or_annotation(tmp_path):
    edges0 = obs.counter("sampler.edges").value
    prev = obs.set_enabled(False)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with obs.span("unit.dead") as sp:
            sp.set(n=1)
        g = from_coo([0, 1], [1, 0], n_src=2, n_dst=2)
        NeighborSampler(g, [1], 2, seed=0).sample(np.arange(2), np.zeros(2))
    finally:
        jax.profiler.stop_trace()
        obs.set_enabled(prev)
    assert obs.trace_events() == []
    assert "unit.dead" not in _xplane_host_events(str(tmp_path))
    assert obs.counter("sampler.edges").value == edges0


def test_span_ids_and_parents_per_thread():
    inner_other = {}

    def other():
        with obs.span("t.other") as sp:
            inner_other["args"] = sp.args

    with obs.span("t.outer"):
        with obs.span("t.mid"):
            with obs.span("t.leaf"):
                th = threading.Thread(target=other)
                th.start()
                th.join()
        with obs.span("t.sibling"):
            pass
    ev = {e["name"]: e["args"] for e in obs.trace_events()}
    ids = [a["id"] for a in ev.values()]
    assert len(set(ids)) == len(ids) and all(i > 0 for i in ids)
    assert ev["t.outer"]["parent"] == 0 and ev["t.outer"]["depth"] == 0
    assert ev["t.mid"]["parent"] == ev["t.outer"]["id"]
    assert ev["t.leaf"]["parent"] == ev["t.mid"]["id"]
    assert ev["t.leaf"]["depth"] == 2
    assert ev["t.sibling"]["parent"] == ev["t.outer"]["id"]
    # another thread's span has its own tree
    assert ev["t.other"]["parent"] == 0 and ev["t.other"]["depth"] == 0


def test_batch_key_is_inherited_and_set_adds_counts():
    with obs.span("t.produce", args={"batch": 7}):
        with obs.span("t.work") as sp:
            sp.set(edges=3)
            with obs.span("t.inner", args={"batch": 8}):
                pass
    ev = {e["name"]: e["args"] for e in obs.trace_events()}
    assert ev["t.work"]["batch"] == 7 and ev["t.work"]["edges"] == 3
    assert ev["t.inner"]["batch"] == 8          # given keys win
    with obs.span("t.alone"):
        pass
    assert "batch" not in _named("t.alone")[0]["args"]


def _tiny(n=60, m=400, seed=0):
    rng = np.random.default_rng(seed)
    g = from_coo(rng.integers(0, n, m), rng.integers(0, n, m),
                 n_src=n, n_dst=n)
    return g, rng.integers(0, 3, n)


def test_sampler_spans_count_the_blocks_they_build():
    g, y = _tiny()
    edges0 = obs.counter("sampler.edges").value
    slots0 = obs.counter("sampler.edge_slots").value
    s = NeighborSampler(g, [2, 3], 8, seed=1)
    mb = s.sample(np.arange(5), y[:5])           # a short, padded batch
    (top,) = _named("sampler.sample")
    a = top["args"]
    masks = [np.asarray(b.bg.nbr_mask) for b in mb.blocks]
    ids = [np.asarray(b.src_ids) for b in mb.blocks]
    assert a["edges"] == sum(int(m.sum()) for m in masks)
    assert a["edge_slots"] == sum(m.size for m in masks)
    assert a["edge_slots"] == sum(b.bg.g.n_edges for b in mb.blocks)
    assert a["src"] == sum(int((i >= 0).sum()) for i in ids)
    assert a["src_slots"] == sum(len(i) for i in ids)
    assert obs.counter("sampler.edges").value - edges0 == a["edges"]
    assert obs.counter("sampler.edge_slots").value - slots0 == \
        a["edge_slots"]


def test_sampler_phases_lie_within_the_sample_span():
    g, y = _tiny()
    NeighborSampler(g, [2, 3], 8, seed=1).sample(np.arange(8), y[:8])
    (top,) = _named("sampler.sample")
    kids = [e for e in obs.trace_events()
            if e["args"].get("parent") == top["args"]["id"]]
    names = [e["name"] for e in kids]
    # one draw, graph and upload per block, then the seeds' upload
    assert names == ["sampler.draw", "sampler.graph", "sampler.upload"] * 2 \
        + ["sampler.upload"]
    for e in kids:
        assert top["start_ns"] <= e["start_ns"] <= e["end_ns"] \
            <= top["end_ns"]
    assert sum(e["end_ns"] - e["start_ns"] for e in kids) <= \
        top["end_ns"] - top["start_ns"]


def test_prefetch_wait_carries_the_producers_batch_number():
    g, y = _tiny()
    s = NeighborSampler(g, [2, 3], 8, seed=1)
    ids = np.arange(40)
    got = list(prefetch(s.batches(ids, y[ids]), depth=2))
    waits = _named("prefetch.wait")
    made = _named("sampler.sample")
    produced = _named("prefetch.produce")
    assert len(got) == 5 == len(made)
    # one wait per item plus the one that met the end of the stream
    numbered = [w["args"]["batch"] for w in waits if "batch" in w["args"]]
    assert numbered == [m["args"]["batch"] for m in made]
    assert numbered == sorted(numbered) and len(set(numbered)) == 5
    assert {p["args"]["batch"] for p in produced} >= set(numbered)
    # the sampler's spans ran on the producer thread, the waits not
    assert {m["tid"] for m in made} != {w["tid"] for w in waits}


def test_compile_listener_counts_a_fresh_jit_and_not_a_cached_call():
    @jax.jit
    def fresh_fn(x):
        return x * 3.0 + 1.0

    x = jnp.arange(4.0).block_until_ready()
    x1 = (x + 1.0).block_until_ready()
    obs.clear_trace()
    n0 = obs.counter("jax.compiles").value
    with obs.span("t.first"):
        fresh_fn(x).block_until_ready()
    first = _named("jax.compile")
    assert [e["args"]["fun"] for e in first] == ["jit(fresh_fn)"]
    assert obs.counter("jax.compiles").value == n0 + 1
    assert obs.counter("jax.compile_s").value > 0
    assert {e["name"] for e in obs.trace_events() if e["cat"] == "jax"} >= \
        {"jax.trace", "jax.lower", "jax.compile"}
    # the compile phases nest under the span that was open
    (outer,) = _named("t.first")
    assert first[0]["args"]["parent"] == outer["args"]["id"]
    assert outer["start_ns"] <= first[0]["start_ns"] <= first[0]["end_ns"] \
        <= outer["end_ns"]
    obs.clear_trace()
    fresh_fn(x1).block_until_ready()          # same shape: cached
    assert _named("jax.compile") == [] and _named("jax.trace") == []
    assert obs.counter("jax.compiles").value == n0 + 1
