"""Training-grade aggregation: blocked pull in BOTH directions.

Autodiff of a gather-based pull produces a scatter-add backward — the
push pathology the paper removed from the forward sneaks back into
training. But the adjoint of Copy-Reduce is Copy-Reduce on the REVERSE
graph (the paper makes exactly this observation for Embedding: backward
is scatter-reduce ≡ CR). ``weighted_copy_reduce`` wires it up with a
``custom_vjp``:

  forward:   out[v] = Σ_{e=(u→v)} w_e · x[u]       blocked pull on G
  ∂x:        dx[u]  = Σ_{e=(u→v)} w_e · ct[v]      blocked pull on Gᵀ
  ∂w:        dw[e]  = ⟨x[u_e], ct[v_e]⟩            per-edge dot (gathers)

Both directions use the degree-bucketed ELL packs carried by
:class:`TrainingGraph`.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from .graph import Graph, from_coo, reverse
from .planner import get_plan_cache
from .tiling import ELLPack
from . import strategies as S

__all__ = ["TrainingGraph", "make_training_graph", "weighted_copy_reduce"]


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True, eq=False)
class TrainingGraph:
    """Graph + reverse graph + blocked packs for both directions."""
    g: Graph
    g_rev: Graph
    ell: ELLPack
    ell_rev: ELLPack

    def tree_flatten(self):
        return ((self.g, self.g_rev, self.ell, self.ell_rev), ())

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


def make_training_graph(g: Graph, width_cap: int = 64) -> TrainingGraph:
    """Packs come from the per-graph :class:`PlanCache`, so the forward
    ELL is shared with direct ``gspmm(strategy="auto"/"ell")`` calls and
    built at most once per process."""
    g_rev = reverse(g)
    return TrainingGraph(g=g, g_rev=g_rev,
                         ell=get_plan_cache(g).ell(width_cap),
                         ell_rev=get_plan_cache(g_rev).ell(width_cap))


def _pull_weighted(g: Graph, pack: ELLPack, x, w):
    """Blocked-pull Σ w_e x[src_e] into destinations. w: (n_edges,1)."""
    def msg_fn(cls):
        vals = jnp.take(x, cls.chunk_cols, axis=0)        # (C, W, d)
        ws = jnp.take(w, cls.chunk_eids, axis=0)          # (C, W, 1)
        return vals * ws

    out = S.pull_ell_reduce(pack, msg_fn, "sum", deg=g.in_degrees)
    # bf16 x against fp32 norm weights promotes the message stream (and
    # thus the reduce) to fp32 — keep that accumulation, but hand back
    # the feature dtype so half-precision forwards stay half precision
    if (jnp.issubdtype(x.dtype, jnp.floating)
            and jnp.issubdtype(out.dtype, jnp.floating)
            and out.dtype != x.dtype):
        out = out.astype(x.dtype)
    return out


@partial(jax.custom_vjp, nondiff_argnums=())
def weighted_copy_reduce(tg: TrainingGraph, x: jnp.ndarray,
                         w: jnp.ndarray) -> jnp.ndarray:
    """out[v] = Σ_{(u→v)=e} w[e]·x[u] — blocked pull fwd AND bwd.

    ``x``: (n_src, d); ``w``: (n_edges, 1) caller edge order (pass ones
    for plain CR-sum). Its device ops are named under
    ``weighted_copy_reduce`` (forward) and ``weighted_copy_reduce.bwd``.
    """
    with jax.named_scope("weighted_copy_reduce"):
        return _pull_weighted(tg.g, tg.ell, x, w)


def _wcr_fwd(tg, x, w):
    with jax.named_scope("weighted_copy_reduce"):
        return _pull_weighted(tg.g, tg.ell, x, w), (tg, x, w)


def _wcr_bwd(res, ct):
    tg, x, w = res
    with jax.named_scope("weighted_copy_reduce.bwd"):
        # ∂x: pull over the reverse graph (edge ids kept by reverse())
        dx = _pull_weighted(tg.g_rev, tg.ell_rev, ct, w).astype(x.dtype)
        # ∂w: per-edge dot in caller edge order
        g = tg.g
        dot = jnp.sum(jnp.take(x, g.src, axis=0)
                      * jnp.take(ct, g.dst, axis=0), axis=-1,
                      keepdims=True)
        dw = jnp.take(dot, g.eid_inv, axis=0).astype(w.dtype)
    return None, dx, dw


weighted_copy_reduce.defvjp(_wcr_fwd, _wcr_bwd)
