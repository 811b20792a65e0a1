"""Planner layer: auto strategy selection, fallback, and pack caching.

These tests are hypothesis-free on purpose — they must run on the bare
tier-1 environment (seeded numpy loops instead of property search).
"""
import warnings

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import (from_coo, gspmm, copy_reduce, edge_softmax,
                        parse_op, planner)

from ..conftest import make_graph

# the exact configurations from the paper's Table 2
TABLE2 = [
    "u_copy_add_v",        # GCN/SAGE/GCMC/LGNN/RGCN
    "u_mul_e_add_v",       # MoNet, GAT
    "e_copy_add_v",        # GAT
    "e_copy_max_v",        # GAT
    "u_add_v_copy_e",      # GAT
    "e_sub_v_copy_e",      # GAT
    "e_div_v_copy_e",      # GAT
    "v_mul_e_copy_e",      # GAT
    "u_dot_v_add_e",       # GCMC
]

REDUCERS = ["add", "max", "min", "mul", "mean"]


def _graph(rng, n_u, n_v, nnz):
    src = rng.integers(0, n_u, nnz)
    dst = rng.integers(0, n_v, nnz)
    return from_coo(src, dst, n_src=n_u, n_dst=n_v)


def _operands(rng, n_u, n_v, nnz, d):
    """Values bounded away from 0 so div/prod stay well-conditioned."""
    def draw(shape):
        x = rng.uniform(0.5, 1.5, size=shape).astype(np.float32)
        sgn = np.where(rng.random(shape) < 0.5, -1.0, 1.0).astype(np.float32)
        return jnp.asarray(x * sgn)
    return draw((n_u, d)), draw((n_v, d)), draw((nnz, d))


def _assert_matches_segment(g, name, U, V, E, **kw):
    out = gspmm(g, name, u=U, v=V, e=E, **kw)
    ref = gspmm(g, name, u=U, v=V, e=E, strategy="segment")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-5, err_msg=name)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_auto_matches_segment_table2(seed):
    """strategy='auto' (the default) is numerically the segment answer
    for every Table-2 config, across random graph shapes."""
    rng = np.random.default_rng(seed)
    n_u, n_v, nnz = [(30, 20, 120), (80, 80, 1200), (200, 150, 3000)][seed]
    g = _graph(rng, n_u, n_v, nnz)
    U, V, E = _operands(rng, n_u, n_v, g.n_edges, 7)
    for name in TABLE2:
        _assert_matches_segment(g, name, U, V, E)   # no strategy argument


def test_auto_matches_segment_all_reducers():
    rng = np.random.default_rng(7)
    g = _graph(rng, 60, 40, 700)
    U, V, E = _operands(rng, 60, 40, g.n_edges, 5)
    for red in REDUCERS:
        _assert_matches_segment(g, f"u_copy_{red}_v", U, V, E)
        _assert_matches_segment(g, f"u_mul_e_{red}_v", U, V, E)
        _assert_matches_segment(g, f"e_copy_{red}_v", U, V, E)


def test_pinned_unsupported_falls_back_not_raises():
    """Pallas/onehot specs they can't run fall back down the chain."""
    rng = np.random.default_rng(3)
    g = _graph(rng, 40, 30, 200)
    U, V, E = _operands(rng, 40, 30, g.n_edges, 6)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        # max reducer: no pallas kernel
        _assert_matches_segment(g, "u_copy_max_v", U, V, E,
                                strategy="pallas")
        # dot ⊗: no pallas kernel, no onehot formulation
        _assert_matches_segment(g, "u_dot_v_add_v", U, V, E,
                                strategy="pallas")
        # onehot needs lhs on source nodes
        _assert_matches_segment(g, "e_copy_add_v", U, V, E,
                                strategy="onehot")
        # min reducer via onehot
        _assert_matches_segment(g, "u_copy_min_v", U, V, E,
                                strategy="onehot")
        # ell cannot reduce to source nodes -> generic path
        _assert_matches_segment(g, "v_copy_add_u", U, V, E,
                                strategy="ell")


def test_fallback_warns_once():
    rng = np.random.default_rng(4)
    g = _graph(rng, 25, 25, 100)
    U, _, _ = _operands(rng, 25, 25, g.n_edges, 4)
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        gspmm(g, "u_copy_prod_v", u=U, strategy="pallas")
        gspmm(g, "u_copy_prod_v", u=U, strategy="pallas")
    ours = [x for x in w if "falling back" in str(x.message)]
    assert len(ours) <= 1


def test_packs_built_at_most_once_per_graph():
    """Repeated auto calls + direct cache hits build each pack once."""
    rng = np.random.default_rng(5)
    # big enough (and wide enough) that the cost model picks ell
    g = _graph(rng, 1000, 1000, 6000)
    X = jnp.asarray(rng.normal(size=(1000, 64)).astype(np.float32))
    before = planner.pack_build_totals().get("ell", 0)
    for _ in range(3):
        out = copy_reduce(g, X)                       # default: auto
    cache = planner.get_plan_cache(g)
    assert cache.ell() is not None                    # direct hit, no build
    after = planner.pack_build_totals().get("ell", 0)
    assert after - before == 1
    assert planner.last_plan("u_copy_add_v") == "ell"
    np.testing.assert_allclose(
        np.asarray(out),
        np.asarray(copy_reduce(g, X, strategy="segment")),
        rtol=1e-4, atol=1e-4)


def test_auto_under_jit_with_cache():
    """A bundle-carried PlanCache lets the planner run inside a trace:
    static stats drive the cost model, traced packs feed the kernels."""
    rng = np.random.default_rng(6)
    g = _graph(rng, 800, 800, 5000)
    X = jnp.asarray(rng.normal(size=(800, 64)).astype(np.float32))
    cache = planner.get_plan_cache(g)
    cache.ell()
    f = jax.jit(lambda g, c, x: gspmm(g, "u_copy_add_v", u=x, cache=c))
    out = f(g, cache, X)
    ref = copy_reduce(g, X, strategy="segment")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)
    # traced graph with NO cache: planner degrades to segment, still right
    f2 = jax.jit(lambda g, x: gspmm(g, "u_copy_add_v", u=x))
    np.testing.assert_allclose(np.asarray(f2(g, X)), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


def test_edge_softmax_auto_matches_pinned():
    rng = np.random.default_rng(8)
    g = _graph(rng, 50, 50, 400)
    logits = jnp.asarray(rng.normal(size=(g.n_edges, 4)).astype(np.float32))
    a = edge_softmax(g, logits)                       # auto
    b = edge_softmax(g, logits, strategy="segment")
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-5, atol=1e-6)


def test_autotune_mode_matches_segment():
    rng = np.random.default_rng(9)
    g = _graph(rng, 300, 300, 2500)
    X = jnp.asarray(rng.normal(size=(300, 16)).astype(np.float32))
    ref = copy_reduce(g, X, strategy="segment")
    planner.set_mode("autotune")
    try:
        out1 = copy_reduce(g, X)
        out2 = copy_reduce(g, X)                      # cached decision
    finally:
        planner.set_mode("cost")
    np.testing.assert_allclose(np.asarray(out1), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(out2), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


def test_block_autotune_measures_once_and_matches_segment():
    """Autotune mode extends to block plans: an eager call measures the
    candidates once per shape signature; the cached winner then serves
    jitted calls of the same configuration."""
    from repro.core import block_gspmm
    from repro.data import NeighborSampler

    rng = np.random.default_rng(11)
    g = _graph(rng, 40, 40, 300)
    sampler = NeighborSampler(g, fanouts=[4], batch_size=8, seed=0)
    mb = sampler.sample(rng.permutation(40)[:8], np.zeros(8, np.int64))
    bg = mb.blocks[0].bg
    u = jnp.asarray(rng.normal(size=(bg.g.n_src, 6)).astype(np.float32))
    ref = block_gspmm(bg, "u_copy_mean_v", u=u, strategy="segment")

    planner.clear_block_plans()
    planner.set_mode("autotune")
    try:
        # a traced call first (the normal training path: planning
        # happens inside the jitted step) must NOT pin its cost-model
        # stand-in — the later eager call still gets to measure
        jitted0 = jax.jit(lambda bg, u: block_gspmm(bg, "u_copy_mean_v",
                                                    u=u))
        np.testing.assert_allclose(np.asarray(jitted0(bg, u)),
                                   np.asarray(ref), rtol=1e-4, atol=1e-5)
        assert not [k for k in planner._BLOCK_PLANS if k[3] == "auto"]
        out = block_gspmm(bg, "u_copy_mean_v", u=u)       # eager: measures
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-4, atol=1e-5)
        chosen = planner.last_plan("block:u_copy_mean_v")
        assert chosen in ("ell", "segment")
        # the measured decision is keyed on the existing shape
        # signature — a second (jitted, traced) call reuses it
        n_before = len(planner._BLOCK_PLANS)
        jitted = jax.jit(lambda bg, u: block_gspmm(bg, "u_copy_mean_v",
                                                   u=u))
        np.testing.assert_allclose(np.asarray(jitted(bg, u)),
                                   np.asarray(ref), rtol=1e-4, atol=1e-5)
        assert len(planner._BLOCK_PLANS) == n_before
        assert planner.last_plan("block:u_copy_mean_v") == chosen
    finally:
        planner.set_mode("cost")
        planner.clear_block_plans()


def test_ring_pinned_falls_back_without_mesh():
    """A pinned 'ring' with no active use_ring() context degrades down
    the single-device chain (blocked pull first) and stays correct."""
    rng = np.random.default_rng(12)
    g = _graph(rng, 30, 30, 150)
    U, V, E = _operands(rng, 30, 30, g.n_edges, 4)
    assert planner.active_ring() is None
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        _assert_matches_segment(g, "u_copy_add_v", U, V, E,
                                strategy="ring")
    assert planner.last_plan("u_copy_add_v", "ring") in ("ell", "segment")
    # and auto never picks ring without a mesh
    _assert_matches_segment(g, "u_copy_add_v", U, V, E)
    assert planner.last_plan("u_copy_add_v") != "ring"


def test_stats_and_cost_model_sanity():
    rng = np.random.default_rng(10)
    g = _graph(rng, 100, 100, 900)
    stats = planner.get_plan_cache(g).stats
    assert stats.n_edges == g.n_edges
    assert stats.ell_padded_slots >= stats.n_edges
    assert stats.pad_ratio >= 1.0
    # every strategy costs something, and costs grow with feature width
    # (across a 128-lane tile: the pallas kernels pad features to 128)
    for s in planner.STRATEGIES:
        assert planner.estimate_cost(s, stats, 8) > 0
        assert (planner.estimate_cost(s, stats, 256)
                > planner.estimate_cost(s, stats, 8))


def test_supports_predicates():
    spec2 = parse_op("u_mul_e_add_v")
    x = jnp.zeros((4, 3))
    e1 = jnp.zeros((5, 1))
    assert planner.supports("onehot", spec2, x, e1)
    assert planner.supports("pallas", spec2, x, e1)
    # 3-D operands are segment/ell territory
    x3 = jnp.zeros((4, 2, 3))
    e3 = jnp.zeros((5, 2, 1))
    assert planner.supports("ell", spec2, x3, e3)
    assert not planner.supports("onehot", spec2, x3, e3)
    assert not planner.supports("pallas", spec2, x3, e3)
    # max reducer never hits the MXU formulations
    specmax = parse_op("u_copy_max_v")
    assert not planner.supports("pallas", specmax, x, None)
    assert not planner.supports("onehot", specmax, x, None)
    assert planner.supports("ell", specmax, x, None)


def test_unknown_backend_has_no_cost_row():
    """A backend without a throughput row is an error, never priced
    with another device's numbers (bf16 included)."""
    with pytest.raises(ValueError, match="no cost row"):
        planner._throughput_row("gpu", None)
    with pytest.raises(ValueError, match="no cost row"):
        planner._throughput_row("gpu", jnp.bfloat16)
    assert planner._throughput_row("tpu", jnp.bfloat16) is \
        planner._THROUGHPUT["tpu:bf16"]


def test_tile_slots_match_built_pack():
    """The stats' tile-slot count is exactly what build_tiles allocates,
    so the pallas/onehot cost rows price the real padded buckets."""
    from repro.core import build_tiles
    rng = np.random.default_rng(11)
    for n_u, n_v, nnz in [(300, 200, 1500), (500, 10, 2000), (5, 7, 1)]:
        g, _, _ = make_graph(rng, n_u, n_v, nnz)
        pack = build_tiles(g)
        assert planner.compute_stats(g).tile_slots == \
            pack.dst_local.shape[0] * pack.eb


@pytest.mark.parametrize("strategy", ["onehot", "pallas"])
def test_tile_strategies_need_counted_slots(strategy):
    """Stats without a tile-slot count (block_stats) cannot price the
    tile kernels: an ideal-fill guess under-prices sparse graphs."""
    stats = planner.block_stats(1024, 512, 4096, 8)
    assert stats.tile_slots == 0
    with pytest.raises(ValueError, match="tile_slots"):
        planner.estimate_cost(strategy, stats, 64, backend="tpu")
