"""Jitted public wrapper for the fused Binary-Reduce Pallas kernel."""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from ...core.graph import Graph
from ...core.planner import get_plan_cache
from ...core.tiling import TilePack
from ..common import should_interpret, visited_rows
from .kernel import binary_reduce_pallas_call


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


@functools.partial(jax.jit,
                   static_argnames=("binop", "reduce_op", "nd", "interpret"))
def _br_packed(pack: TilePack, B: jnp.ndarray, E_tiles: jnp.ndarray,
               deg: Optional[jnp.ndarray], binop: str = "mul",
               reduce_op: str = "sum", nd: int = 128,
               interpret: Optional[bool] = None) -> jnp.ndarray:
    T, eb = pack.dst_local.shape
    bm, bk = pack.bm, pack.bk
    d = B.shape[-1]
    nd = min(nd, _round_up(d, 128))
    d_pad = _round_up(d, nd)

    Bp = jnp.pad(B, ((0, pack.n_tiles_k * bk - B.shape[0]), (0, d_pad - d)))
    # padded slots carry 1 so every ⊗ (div included) stays finite there
    E_tiles = jnp.where(pack.mask.reshape(-1, 1), E_tiles,
                        jnp.ones((), E_tiles.dtype))
    Ep = jnp.pad(E_tiles, ((0, 0), (0, d_pad - d)))

    call = binary_reduce_pallas_call(
        T=T, eb=eb, bm=bm, bk=bk, nd=nd,
        n_tiles_m=pack.n_tiles_m, n_tiles_k=pack.n_tiles_k, d_pad=d_pad,
        dtype=Bp.dtype, binop=binop,
        interpret=should_interpret() if interpret is None else interpret)

    rows = lambda a: a.reshape(T, 1, eb)
    out = call(pack.tile_m, pack.tile_k, pack.first_of_m,
               rows(pack.dst_local), rows(pack.src_local),
               rows(pack.mask.astype(jnp.int32)), Ep, Bp)
    out = visited_rows(out, pack)[: pack.n_dst, :d]
    if reduce_op == "mean":
        out = out / jnp.maximum(deg, 1).astype(out.dtype)[:, None]
    return out


def binary_reduce(g: Graph, B: jnp.ndarray, E: jnp.ndarray,
                  binop: str = "mul", reduce_op: str = "sum",
                  tiles: Optional[TilePack] = None, nd: int = 128,
                  interpret: Optional[bool] = None) -> jnp.ndarray:
    """Fused ``u_⊗_e_add_v``: ``C[v] = Σ_(u→v)=e B[u] ⊗ E[e]``.

    ``E``: (n_edges, d) or (n_edges, 1) or (n_edges,) in the caller's edge
    order; scalar edge features broadcast across the feature dim.
    """
    if reduce_op not in ("sum", "mean"):
        raise ValueError("pallas binary_reduce supports sum/mean")
    pack = tiles if tiles is not None else get_plan_cache(g).tiles()
    d = B.shape[-1]
    E = E.reshape(E.shape[0], -1)
    if E.shape[1] == 1 and d != 1:
        E = jnp.broadcast_to(E, (E.shape[0], d))
    elif E.shape[1] != d:
        raise ValueError(f"edge feature dim {E.shape[1]} != node dim {d}")
    # permute edge features to tile order (contiguous per bucket)
    E_tiles = jnp.take(E, pack.eids.reshape(-1), axis=0)   # (T*eb, d)
    deg = g.in_degrees if reduce_op == "mean" else None
    return _br_packed(pack, B, E_tiles, deg, binop=binop,
                      reduce_op=reduce_op, nd=nd, interpret=interpret)
