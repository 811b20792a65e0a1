"""Plan-event record format (golden schema) + e2e.

The schema tests PIN the record layout — BENCH_*.json consumers and
the CI artifacts read these dicts, so a field rename/removal is a
breaking change and must show up here, not downstream.
"""
import jax
import numpy as np
import pytest

from repro import obs
from repro.core import graph as G


@pytest.fixture(autouse=True)
def _clean_events():
    obs.clear_events()
    yield
    obs.clear_events()


# ------------------------------------------------------------------ #
# golden schema
# ------------------------------------------------------------------ #
def test_plan_event_fields_golden():
    assert obs.PLAN_EVENT_FIELDS == (
        "op", "family", "requested", "chosen", "count",
        "predicted_cost", "dtype")


def test_plan_event_rows_have_exact_keys():
    obs.plan_event("block:u_copy_add_v", "auto", "segment",
                   predicted_cost=10.0)
    rows = obs.plan_events()
    assert len(rows) == 1
    assert tuple(rows[0].keys()) == obs.PLAN_EVENT_FIELDS
    r = rows[0]
    assert r["family"] == "block"
    assert r["count"] == 1
    assert r["predicted_cost"] == pytest.approx(10.0)


def test_plan_event_keyed_by_dtype():
    # same (op, requested, chosen) at two dtypes → two rows, not one
    obs.plan_event("block:u_copy_add_v", "auto", "segment",
                   predicted_cost=10.0, dtype="float32")
    obs.plan_event("block:u_copy_add_v", "auto", "segment",
                   predicted_cost=10.0, dtype="bfloat16")
    rows = obs.plan_events()
    assert len(rows) == 2
    assert {r["dtype"] for r in rows} == {"float32", "bfloat16"}
    assert all(r["count"] == 1 for r in rows)


def test_family_of():
    assert obs.family_of("u_copy_add_v") == "gspmm"
    assert obs.family_of("block:u_copy_add_v") == "block"
    assert obs.family_of("block_bwd:u_copy_add_v") == "block_bwd"
    assert obs.family_of("hetero:u_w_mean_v") == "hetero"
    assert obs.family_of("sddmm:u_add_v_copy_e") == "sddmm"
    assert obs.family_of("attn:fused") == "attn"
    assert obs.family_of("serve:infer") == "serve"


def test_plan_event_disabled_noop():
    prev = obs.set_enabled(False)
    try:
        obs.plan_event("dead:x", "auto", "a", predicted_cost=1.0)
    finally:
        obs.set_enabled(prev)
    assert obs.plan_events() == []


# ------------------------------------------------------------------ #
# e2e: the real planner paths emit predicted rows
# ------------------------------------------------------------------ #
def test_gspmm_emits_predicted_and_measured():
    rng = np.random.default_rng(0)
    n, m = 64, 400
    g = G.from_coo(rng.integers(0, n, m), rng.integers(0, n, m),
                   n_src=n, n_dst=n)
    x = jax.numpy.asarray(rng.standard_normal((n, 8)).astype(np.float32))
    from repro.core import gspmm
    out = gspmm(g, "u_copy_add_v", u=x)
    jax.block_until_ready(out)
    rows = [r for r in obs.plan_events() if r["op"] == "u_copy_add_v"]
    assert rows, "gspmm plan row missing"
    assert all(tuple(r) == obs.PLAN_EVENT_FIELDS for r in rows)
    assert any(r["predicted_cost"] is not None for r in rows)
    assert all(r["requested"] == "auto" and r["count"] >= 1 for r in rows)
