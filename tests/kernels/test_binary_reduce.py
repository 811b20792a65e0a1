"""Fused Binary-Reduce Pallas kernel vs oracle — binop/shape sweep."""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.kernels.binary_reduce.ops import binary_reduce
from repro.kernels.binary_reduce.ref import binary_reduce_ref

from ..conftest import make_graph


@pytest.mark.parametrize("binop", ["add", "sub", "mul", "div"])
def test_binop_sweep(binop):
    rng = np.random.default_rng(11)
    g, _, _ = make_graph(rng, 150, 90, 700)
    B = jnp.asarray(rng.normal(size=(150, 96)).astype(np.float32))
    E = jnp.asarray((rng.normal(size=(700, 96)) + 3).astype(np.float32))
    out = binary_reduce(g, B, E, binop=binop)
    ref = binary_reduce_ref(g.src, g.dst, B, jnp.take(E, g.eid, axis=0),
                            90, binop)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("n_u,n_v,nnz,d", [
    (60, 60, 300, 128), (301, 77, 999, 17), (33, 400, 1000, 256)])
def test_shape_sweep(n_u, n_v, nnz, d):
    rng = np.random.default_rng(n_u)
    g, _, _ = make_graph(rng, n_u, n_v, nnz)
    B = jnp.asarray(rng.normal(size=(n_u, d)).astype(np.float32))
    E = jnp.asarray(rng.normal(size=(nnz, d)).astype(np.float32))
    out = binary_reduce(g, B, E, binop="mul")
    ref = binary_reduce_ref(g.src, g.dst, B, jnp.take(E, g.eid, axis=0),
                            n_v, "mul")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


def test_scalar_edge_broadcast():
    rng = np.random.default_rng(5)
    g, _, _ = make_graph(rng, 100, 100, 500)
    B = jnp.asarray(rng.normal(size=(100, 32)).astype(np.float32))
    Es = jnp.asarray(rng.normal(size=(500, 1)).astype(np.float32))
    out = binary_reduce(g, B, Es, binop="mul")
    Efull = jnp.broadcast_to(Es, (500, 32))
    ref = binary_reduce_ref(g.src, g.dst, B,
                            jnp.take(Efull, g.eid, axis=0), 100, "mul")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


def test_mean_reduce():
    rng = np.random.default_rng(6)
    g, _, _ = make_graph(rng, 80, 70, 400)
    B = jnp.asarray(rng.normal(size=(80, 40)).astype(np.float32))
    E = jnp.asarray(rng.normal(size=(400, 40)).astype(np.float32))
    out = binary_reduce(g, B, E, binop="add", reduce_op="mean")
    ref = binary_reduce_ref(g.src, g.dst, B, jnp.take(E, g.eid, axis=0),
                            70, "add")
    deg = np.zeros(70); np.add.at(deg, np.asarray(g.dst), 1)
    ref = np.asarray(ref) / np.maximum(deg, 1)[:, None]
    np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-4, atol=1e-4)


def test_unvisited_tiles_zero():
    """Output tiles that no edge bucket visits read 0, not whatever the
    output buffer held."""
    from repro.core import from_coo
    g = from_coo([0, 1, 2], [3, 4, 5], n_src=3, n_dst=400)
    B = jnp.ones((3, 8), jnp.float32)
    E = jnp.full((3, 8), 2.0, jnp.float32)
    out = np.asarray(binary_reduce(g, B, E, binop="div"))
    np.testing.assert_allclose(out[3:6], 0.5)
    np.testing.assert_allclose(np.delete(out, [3, 4, 5], axis=0), 0.0)
