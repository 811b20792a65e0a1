"""Span tracing: Chrome-trace export, nesting, fencing, coverage."""
import json
import time

import jax.numpy as jnp
import pytest

from repro import obs
from repro.obs import spans as S


@pytest.fixture(autouse=True)
def _clean_trace():
    obs.clear_trace()
    yield
    obs.clear_trace()


def test_span_records_chrome_complete_event():
    with obs.span("unit.work", args={"k": 3}):
        time.sleep(0.001)
    (ev,) = obs.trace_events()
    assert ev["name"] == "unit.work"
    assert ev["ph"] == "X"                      # complete event
    assert ev["dur"] >= 1_000                   # ≥ 1ms in µs
    assert ev["args"]["k"] == 3
    assert ev["args"]["depth"] == 0
    assert isinstance(ev["ts"], (int, float))


def test_nesting_depth():
    with obs.span("outer"):
        with obs.span("inner"):
            pass
        with obs.span("inner2"):
            pass
    by_name = {e["name"]: e for e in obs.trace_events()}
    assert by_name["outer"]["args"]["depth"] == 0
    assert by_name["inner"]["args"]["depth"] == 1
    assert by_name["inner2"]["args"]["depth"] == 1
    # children close before the parent and nest inside its window
    out, inn = by_name["outer"], by_name["inner"]
    assert out["ts"] <= inn["ts"]
    assert inn["ts"] + inn["dur"] <= out["ts"] + out["dur"] + 1


def test_fence_blocks_on_device_value():
    with obs.span("unit.fenced") as sp:
        y = sp.fence(jnp.arange(512.0) * 2.0)
    assert float(y[1]) == 2.0                   # fence returns the value
    # the op's first call also compiles: those are jax.* spans inside
    (ev,) = [e for e in obs.trace_events() if e["cat"] != "jax"]
    assert ev["name"] == "unit.fenced"


def test_export_chrome_trace_loads(tmp_path):
    with obs.span("a"):
        with obs.span("b"):
            pass
    path = tmp_path / "trace.json"
    obs.export_chrome_trace(str(path))
    doc = json.loads(path.read_text())
    assert isinstance(doc["traceEvents"], list)
    assert len(doc["traceEvents"]) == 2
    for ev in doc["traceEvents"]:
        assert {"name", "cat", "ph", "ts", "dur", "pid", "tid"} <= set(ev)


def test_disabled_records_nothing():
    prev = obs.set_enabled(False)
    try:
        with obs.span("dead") as sp:
            sp.fence(jnp.ones(4))
    finally:
        obs.set_enabled(prev)
    assert obs.trace_events() == []


def test_span_coverage_tiles():
    # two adjacent top-level spans covering the whole window
    with obs.span("s1"):
        time.sleep(0.002)
    with obs.span("s2"):
        time.sleep(0.002)
    cov = obs.span_coverage()
    assert cov > 0.5                            # tiny gap between spans
    # nested spans must not double-count: only depth-0 intervals union
    obs.clear_trace()
    with obs.span("outer"):
        with obs.span("inner"):
            time.sleep(0.002)
    assert obs.span_coverage() <= 1.0


def test_span_coverage_empty_is_zero():
    assert obs.span_coverage() == 0.0


def test_clear_trace():
    with obs.span("x"):
        pass
    assert len(obs.trace_events()) == 1
    obs.clear_trace()
    assert obs.trace_events() == []
