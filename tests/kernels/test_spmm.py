"""Pallas SpMM (Copy-Reduce) kernel vs pure-jnp oracle — shape/dtype sweep."""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import from_coo, build_tiles
from repro.kernels.spmm.ops import spmm
from repro.kernels.spmm.ref import spmm_ref

from ..conftest import make_graph

SHAPES = [
    (300, 200, 1500, 64),    # generic rectangular
    (64, 64, 200, 128),      # single tile pair
    (257, 130, 901, 33),     # ragged, non-tile-aligned everything
    (16, 16, 40, 300),       # wide features (multi N-tile)
    (500, 10, 2000, 8),      # high in-degree (bucket splitting)
    (10, 500, 400, 16),      # scatter-heavy
]


@pytest.mark.parametrize("n_u,n_v,nnz,d", SHAPES)
@pytest.mark.parametrize("reduce_op", ["sum", "mean"])
def test_spmm_matches_ref(n_u, n_v, nnz, d, reduce_op):
    rng = np.random.default_rng(42 + n_u)
    g, _, _ = make_graph(rng, n_u, n_v, nnz)
    B = jnp.asarray(rng.normal(size=(n_u, d)).astype(np.float32))
    out = spmm(g, B, reduce_op)
    ref = spmm_ref(g.src, g.dst, B, n_v, reduce_op)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_spmm_dtypes(dtype):
    rng = np.random.default_rng(7)
    g, _, _ = make_graph(rng, 130, 90, 600)
    B = jnp.asarray(rng.normal(size=(130, 64)), dtype=dtype)
    out = spmm(g, B, "sum")
    ref = spmm_ref(g.src, g.dst, B.astype(jnp.float32), 90, "sum")
    tol = 1e-5 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(out, dtype=np.float32),
                               np.asarray(ref), rtol=tol, atol=tol)
    assert out.dtype == dtype


def test_spmm_weighted():
    rng = np.random.default_rng(3)
    g, _, _ = make_graph(rng, 200, 150, 1200)
    B = jnp.asarray(rng.normal(size=(200, 48)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(1200,)).astype(np.float32))
    out = spmm(g, B, "sum", weight=w)
    ref = spmm_ref(g.src, g.dst, B, 150, "sum", weight=jnp.take(w, g.eid))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_spmm_custom_tile_geometry():
    """Block-shape sweep: kernel must be correct for any tile geometry."""
    rng = np.random.default_rng(9)
    g, _, _ = make_graph(rng, 300, 300, 2500)
    B = jnp.asarray(rng.normal(size=(300, 70)).astype(np.float32))
    ref = spmm_ref(g.src, g.dst, B, 300, "sum")
    for (bm, bk, eb) in [(64, 64, 64), (128, 256, 512), (256, 128, 128),
                         (8, 8, 16)]:
        tiles = build_tiles(g, bm=bm, bk=bk, eb=eb)
        out = spmm(g, B, "sum", tiles=tiles)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5,
                                   err_msg=f"bm={bm} bk={bk} eb={eb}")


@pytest.mark.parametrize("n_dst", [5, 400])
def test_spmm_empty_rows_zero(n_dst):
    """Nodes with no incoming edges must read 0 (DGL semantics) — also
    in 128-row output tiles that no edge bucket visits (n_dst=400)."""
    g = from_coo([0, 1], [2, 2], n_src=3, n_dst=n_dst)
    B = jnp.ones((3, 8), jnp.float32)
    out = np.asarray(spmm(g, B, "sum"))
    np.testing.assert_allclose(out[2], 2.0)
    rest = np.setdiff1d(np.arange(n_dst), [2])
    np.testing.assert_allclose(out[rest], 0.0)
