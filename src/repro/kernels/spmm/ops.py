"""Jitted public wrapper for the Copy-Reduce SpMM Pallas kernel."""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from ...core.graph import Graph
from ...core.planner import get_plan_cache
from ...core.tiling import TilePack
from ..common import should_interpret, visited_rows
from .kernel import spmm_pallas_call


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


@functools.partial(jax.jit, static_argnames=("reduce_op", "nd", "interpret"))
def _spmm_packed(pack: TilePack, B: jnp.ndarray,
                 weight_tiles: Optional[jnp.ndarray],
                 deg: Optional[jnp.ndarray],
                 reduce_op: str = "sum", nd: int = 128,
                 interpret: Optional[bool] = None) -> jnp.ndarray:
    T, eb = pack.dst_local.shape
    bm, bk = pack.bm, pack.bk
    d = B.shape[-1]
    nd = min(nd, _round_up(d, 128))
    d_pad = _round_up(d, nd)

    Bp = jnp.pad(B, ((0, pack.n_tiles_k * bk - B.shape[0]), (0, d_pad - d)))
    weighted = weight_tiles is not None
    w = weight_tiles if weighted else jnp.ones((T, eb), Bp.dtype)

    call = spmm_pallas_call(
        T=T, eb=eb, bm=bm, bk=bk, nd=nd,
        n_tiles_m=pack.n_tiles_m, n_tiles_k=pack.n_tiles_k, d_pad=d_pad,
        dtype=Bp.dtype, weighted=weighted,
        interpret=should_interpret() if interpret is None else interpret)

    rows = lambda a: a.reshape(T, 1, eb)
    out = call(pack.tile_m, pack.tile_k, pack.first_of_m,
               rows(pack.dst_local), rows(pack.src_local),
               rows(pack.mask.astype(jnp.int32)), rows(w.astype(Bp.dtype)),
               Bp)
    out = visited_rows(out, pack)[: pack.n_dst, :d]
    if reduce_op == "mean":
        out = out / jnp.maximum(deg, 1).astype(out.dtype)[:, None]
    return out


def spmm(g: Graph, B: jnp.ndarray, reduce_op: str = "sum",
         weight: Optional[jnp.ndarray] = None,
         tiles: Optional[TilePack] = None, nd: int = 128,
         interpret: Optional[bool] = None) -> jnp.ndarray:
    """Copy-Reduce ``C[v] = ⊕_(u→v) w·B[u]`` via the Pallas kernel.

    ``weight``: optional (n_edges,) per-edge scalar in the caller's edge
    order (covers ``u_mul_e_add_v`` with scalar gates).
    """
    if reduce_op not in ("sum", "mean"):
        raise ValueError("pallas spmm supports sum/mean (see DESIGN.md)")
    pack = tiles if tiles is not None else get_plan_cache(g).tiles()
    wt = None
    if weight is not None:
        wt = jnp.take(weight.reshape(-1), pack.eids, axis=0)  # (T, eb)
    deg = g.in_degrees if reduce_op == "mean" else None
    return _spmm_packed(pack, B, wt, deg, reduce_op=reduce_op, nd=nd,
                        interpret=interpret)
