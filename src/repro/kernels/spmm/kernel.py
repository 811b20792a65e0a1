"""Copy-Reduce as a blocked SpMM Pallas TPU kernel (paper Alg. 3 → TPU).

Grid: ``(n_feature_tiles, n_buckets)`` — buckets (the paper's K-blocks,
pre-sorted by destination tile) iterate fastest, so every output tile
``C[tile_m, n]`` is visited by *consecutive* grid steps and accumulates in
VMEM; it is written back to HBM exactly once per feature tile (the paper's
"C panel stays in LLC until completely processed", with VMEM playing LLC).

Per grid step:
  * ``BlockSpec`` DMAs the K-block of source features ``B[tile_k]``
    (bk × nd) into VMEM — the paper's "B block stays in L2";
  * bucket edge indices arrive as ``(1, eb)`` int32 VMEM rows — the
    streams are stored ``(T, 1, eb)`` so a block's last two dimensions
    equal the array's, which the TPU lowering requires of blocks that
    are not (8, 128)-aligned;
  * gather/scatter run as one-hot matmuls on the MXU (DESIGN.md §2) —
    the TPU replacement for sorted scalar streams. Both one-hots are
    built from the index rows by a sublane broadcast: the gather matrix
    as its transpose ``G^T`` (bk, eb), contracted on its leading axis.

Reductions: sum (optionally edge-weighted). Mean is sum + a post-scale in
``ops.py``. Max/min intentionally stay on the segment path (see DESIGN.md).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..common import (gather_rows, onehot_gather_matrix_t,
                      onehot_scatter_matrix)


def _spmm_kernel(# scalar-prefetch refs
                 tile_m_ref, tile_k_ref, first_ref,
                 # tensor refs
                 dst_ref, src_ref, mask_ref, wgt_ref, b_ref,
                 # output
                 out_ref, *, bm: int, bk: int, weighted: bool):
    t = pl.program_id(1)

    @pl.when(first_ref[t] == 1)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    dst_local = dst_ref[...]        # (1, eb) int32
    src_local = src_ref[...]
    mask = mask_ref[...] != 0       # int32 block -> bool
    acc_t = jnp.float32

    GT = onehot_gather_matrix_t(src_local, mask, bk, b_ref.dtype)
    gathered = gather_rows(GT, b_ref[...])                   # (eb, nd)
    w = wgt_ref[...] if weighted else None
    S = onehot_scatter_matrix(dst_local, mask, bm, gathered.dtype, weight=w)
    out_ref[...] += jax.lax.dot(S, gathered,
                                preferred_element_type=acc_t
                                ).astype(out_ref.dtype)


def spmm_pallas_call(T: int, eb: int, bm: int, bk: int, nd: int,
                     n_tiles_m: int, n_tiles_k: int, d_pad: int,
                     dtype, *, weighted: bool, interpret: bool):
    """Build the pallas_call for given static geometry.

    Inputs (in order): tile_m (T,), tile_k (T,), first_of_m (T,)  [scalar
    prefetch]; dst_local (T,1,eb), src_local (T,1,eb), mask (T,1,eb)
    int32, weight (T,1,eb), B (n_tiles_k*bk, d_pad).
    Output: C (n_tiles_m*bm, d_pad).
    """
    n_nd = d_pad // nd

    grid = (n_nd, T)

    def edge_map(n, t, tm, tk, first):
        return (t, 0, 0)

    def b_map(n, t, tm, tk, first):
        return (tk[t], n)

    def out_map(n, t, tm, tk, first):
        return (tm[t], n)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, 1, eb), edge_map),   # dst_local
            pl.BlockSpec((None, 1, eb), edge_map),   # src_local
            pl.BlockSpec((None, 1, eb), edge_map),   # mask
            pl.BlockSpec((None, 1, eb), edge_map),   # weight
            pl.BlockSpec((bk, nd), b_map),     # B k-block
        ],
        out_specs=pl.BlockSpec((bm, nd), out_map),
    )

    kernel = functools.partial(_spmm_kernel, bm=bm, bk=bk, weighted=weighted)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_tiles_m * bm, d_pad), dtype),
        interpret=interpret,
    )
