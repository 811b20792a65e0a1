"""Shared helpers for the aggregation Pallas kernels.

The kernels consume the (M,K)-tile-bucketed edge format of
``repro.core.tiling.TilePack`` — the TPU adaptation of the paper's
K-blocking + radix-sort (DESIGN.md §2). Sparse gather/scatter inside a
bucket is expressed as one-hot matmuls so the MXU does the indexing:

    G[e, k] = 1 iff bucket edge e has source-local index k   (gather)
    S[m, e] = w_e iff bucket edge e has dest-local index m   (scatter)

    C_tile += S @ (G @ B_tile)

Index streams reach the kernels as ``(1, eb)`` rows (edges on lanes).
Both one-hots are built from a row by broadcasting it down the
sublanes; the gather matrix is built as its transpose ``G^T`` (bk, eb)
and contracted on its leading axis, so no kernel ever moves an index
row onto the sublanes.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def should_interpret() -> bool:
    """Pallas interpret mode everywhere but real TPU."""
    return jax.default_backend() != "tpu"


def onehot_gather_matrix_t(src_local, mask, bk: int, dtype) -> jnp.ndarray:
    """(bk, eb) transposed one-hot gather matrix ``G^T`` from (1, eb)
    rows; masked-out edges are all-zero columns."""
    eb = src_local.shape[-1]
    iota = jax.lax.broadcasted_iota(jnp.int32, (bk, eb), 0)
    hot = (src_local == iota) & mask
    return hot.astype(dtype)


def gather_rows(GT, B) -> jnp.ndarray:
    """``G @ B`` from ``G^T``: (bk, eb) x (bk, nd) -> (eb, nd) in f32."""
    return jax.lax.dot_general(GT, B, (((0,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def onehot_scatter_matrix(dst_local, mask, bm: int, dtype,
                          weight=None) -> jnp.ndarray:
    """(bm, eb) one-hot scatter matrix from (1, eb) rows, optionally
    edge-weighted."""
    eb = dst_local.shape[-1]
    iota = jax.lax.broadcasted_iota(jnp.int32, (bm, eb), 0)
    hot = ((dst_local == iota) & mask).astype(dtype)
    if weight is not None:
        hot = hot * weight.astype(dtype)
    return hot


def visited_rows(out, pack) -> jnp.ndarray:
    """Zero the output rows of M-tiles that no bucket visits.

    The SpMM-family kernels initialise an output tile on its first
    bucket, so a tile with no edges is never written and holds whatever
    the device buffer held (zeros in the interpreter, garbage on a TPU).
    """
    hit = jnp.zeros((pack.n_tiles_m,), bool).at[pack.tile_m].set(True)
    return jnp.where(jnp.repeat(hit, pack.bm)[:, None], out,
                     jnp.zeros((), out.dtype))
