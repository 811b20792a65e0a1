"""Fused Binary-Reduce Pallas kernel: ``u_⊗_e_add_v`` (paper Alg. 4/5 → TPU).

Same bucket geometry as the SpMM kernel, plus an edge-feature block
streamed per bucket. Because buckets are contiguous runs of the
tile-sorted edge array, edge features pre-permuted to tile order arrive
via plain ``BlockSpec`` DMA — no in-kernel gather for the edge operand.
The node operand is gathered on the MXU via the one-hot trick. The ⊗
intermediate lives only in VMEM — this is the fusion the paper gets by
interleaving ⊗ with the reduction loop (its Alg. 4 line 6).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..common import (gather_rows, onehot_gather_matrix_t,
                      onehot_scatter_matrix)

_BINOPS = {
    "add": jnp.add,
    "sub": jnp.subtract,
    "mul": jnp.multiply,
    "div": jnp.divide,
    "copy_lhs": lambda a, b: a,
    "copy_rhs": lambda a, b: b,
}


def _br_kernel(tile_m_ref, tile_k_ref, first_ref,
               dst_ref, src_ref, mask_ref, e_ref, b_ref, out_ref,
               *, bm: int, bk: int, binop: str):
    t = pl.program_id(1)

    @pl.when(first_ref[t] == 1)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    dst_local = dst_ref[...]                                # (1, eb)
    src_local = src_ref[...]
    mask = mask_ref[...] != 0
    acc_t = jnp.float32

    GT = onehot_gather_matrix_t(src_local, mask, bk, b_ref.dtype)
    u_vals = gather_rows(GT, b_ref[...])                    # (eb, nd)
    e_vals = e_ref[...].astype(acc_t)                       # (eb, nd)
    # padded slots gather u = 0 and carry e = 1 (ops.py), so every ⊗
    # stays finite there and the zero scatter column drops them
    msg = _BINOPS[binop](u_vals, e_vals)
    S = onehot_scatter_matrix(dst_local, mask, bm, msg.dtype)
    out_ref[...] += jax.lax.dot(S, msg, preferred_element_type=acc_t
                                ).astype(out_ref.dtype)


def binary_reduce_pallas_call(T: int, eb: int, bm: int, bk: int, nd: int,
                              n_tiles_m: int, n_tiles_k: int, d_pad: int,
                              dtype, *, binop: str, interpret: bool):
    """Inputs: tile_m, tile_k, first (scalar prefetch); dst_local,
    src_local, mask (T,1,eb) int32; E_tiles (T*eb, d_pad) tile-ordered
    edge features, 1 in padded slots; B (n_tiles_k*bk, d_pad).
    Output: C (n_tiles_m*bm, d_pad)."""
    n_nd = d_pad // nd
    grid = (n_nd, T)

    edge_map = lambda n, t, tm, tk, first: (t, 0, 0)
    e_map = lambda n, t, tm, tk, first: (t, n)
    b_map = lambda n, t, tm, tk, first: (tk[t], n)
    out_map = lambda n, t, tm, tk, first: (tm[t], n)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, 1, eb), edge_map),
            pl.BlockSpec((None, 1, eb), edge_map),
            pl.BlockSpec((None, 1, eb), edge_map),
            pl.BlockSpec((eb, nd), e_map),
            pl.BlockSpec((bk, nd), b_map),
        ],
        out_specs=pl.BlockSpec((bm, nd), out_map),
    )
    kernel = functools.partial(_br_kernel, bm=bm, bk=bk, binop=binop)
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_tiles_m * bm, d_pad), dtype),
        interpret=interpret)
