"""Minibatch block-graph execution — sampled training (paper Fig. 3).

A *block* is the bipartite graph of one message-passing layer of a
sampled minibatch: sources are the layer-l frontier nodes, destinations
the layer-(l+1) seeds. Blocks produced by :class:`repro.data.NeighborSampler`
are padded to fully static shapes (node pads into a trailing dummy source
slot, edge pads into a trailing dummy destination row) so one jitted
train step serves every batch.

Because every real destination row holds at most ``fanout`` sampled
in-edges, a block admits a *uniform* blocked-pull format for free: the
sampler emits a dense ``(n_dst_real, fanout)`` neighbor table
(:class:`BlockGraph.nbr`) alongside the COO graph. That table is the
single-class analogue of the degree-bucketed :class:`~repro.core.tiling.ELLPack`
— no host-side pack build, no per-batch pytree-structure changes, and a
mask-corrected mean so pad slots contribute exactly zero.

:func:`block_gspmm` mirrors :func:`repro.core.binary_reduce.gspmm` for
blocks. ``strategy="auto"`` routes through the planner's *shape-keyed*
block plan cache (:func:`repro.core.planner.plan_block_gspmm`): the
decision depends only on the static padded shapes + op + feature width,
so it is stable across batches and valid inside a trace.

Training (DESIGN.md §7): autodiff of any forward block strategy turns
the ∂x computation into a scatter-add — the push pathology the paper
removed from the forward. The sampler therefore also emits a *reverse
table* (the block's edges sorted by source slot: ``rev_src``/
``rev_dst``/``rev_eid``) and :func:`block_gspmm` wraps sum/mean/max/min
reducers in a custom VJP that computes ∂x as a masked pull over that
table (gather cotangents at consuming destinations + one sorted
segment reduce) and ∂e as gathered per-edge products; for max/min the
forward records the winning slot per output element and the pull zeroes
every other slot's cotangent. The backward
strategy is planned independently of the forward one
(:func:`repro.core.planner.plan_block_vjp`, logged as
``block_bwd:<op>``) — ``gather`` is the reverse-table pull, ``scatter``
the autodiff baseline.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import planner
from .binary_reduce import (BINARY_OPS, BRSpec, _NEEDS_OTHER, _as2d,
                            _dmsg, _execute, _unbroadcast, gspmm,
                            parse_op)
from .graph import Graph
from .strategies import REDUCE_IDENTITY

__all__ = ["BlockGraph", "block_gspmm", "block_supports",
           "build_reverse_table", "attach_reverse",
           "serve_block_signature"]


def serve_block_signature(batch_size: int, fanouts, n_layers=None):
    """Predict ``MiniBatch.shape_signature()`` for a sampler config.

    Mirrors ``NeighborSampler``'s static layer-size math — every batch
    of ``batch_size`` seeds under ``fanouts`` (an int with ``n_layers``,
    or a per-layer sequence) produces blocks with EXACTLY these
    ``(n_src_pad, n_dst, n_edges_pad, fanout)`` signatures, outermost
    hop first. The serving tier plans and pre-registers compile-cache
    signatures from this without sampling anything.
    """
    if isinstance(fanouts, int):
        if n_layers is None:
            raise ValueError("int fanout needs n_layers")
        fanouts = [fanouts] * int(n_layers)
    fanouts = list(fanouts)
    sizes = [int(batch_size)]
    for f in reversed(fanouts):
        sizes.append(sizes[-1] * (int(f) + 1))
    sigs = [(sizes[li + 1], sizes[li], sizes[li] * int(f), int(f))
            for li, f in enumerate(reversed(fanouts))]
    return tuple(reversed(sigs))


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True, eq=False)
class BlockGraph:
    """One sampled bipartite layer with its uniform neighbor table.

    ``g`` is the padded COO/CSR block graph (``n_dst = n_dst_real + 1``:
    the extra row absorbs pad edges). The neighbor table views the same
    edges row-major: ``nbr[j, k]`` is the source *slot* of destination
    ``j``'s k-th sampled in-edge (pad slots point at the dummy source and
    are masked out), ``nbr_eid[j, k]`` the matching caller-order edge id
    (edge features are indexed with it), and ``real_deg[j]`` the number
    of real sampled in-edges — the mask-corrected mean denominator.

    The optional *reverse table* (``rev_src``/``rev_dst``/``rev_eid``,
    emitted for free by the sampler) views the same edges sorted by
    source slot: ``rev_src[t]`` is non-decreasing, ``rev_dst[t]`` the
    destination row that consumed reverse slot ``t``, ``rev_eid[t]`` the
    matching caller-order edge id. Pad edges sort last (their source is
    the dummy slot) and point at the dummy destination row, so a zero
    cotangent row masks them out of the gather backward exactly.
    """
    g: Graph
    nbr: jnp.ndarray        # (n_dst_real, fanout) int32 source slots
    nbr_eid: jnp.ndarray    # (n_dst_real, fanout) int32 caller edge ids
    nbr_mask: jnp.ndarray   # (n_dst_real, fanout) bool — True for real edges
    real_deg: jnp.ndarray   # (n_dst_real,) int32
    n_dst_real: int = dataclasses.field(metadata={"static": True})
    fanout: int = dataclasses.field(metadata={"static": True})
    rev_src: Optional[jnp.ndarray] = None   # (n_edges,) int32, sorted
    rev_dst: Optional[jnp.ndarray] = None   # (n_edges,) int32 dst rows
    rev_eid: Optional[jnp.ndarray] = None   # (n_edges,) int32 caller ids

    def tree_flatten(self):
        return ((self.g, self.nbr, self.nbr_eid, self.nbr_mask,
                 self.real_deg, self.rev_src, self.rev_dst,
                 self.rev_eid), (self.n_dst_real, self.fanout))

    @classmethod
    def tree_unflatten(cls, aux, children):
        (g, nbr, nbr_eid, nbr_mask, real_deg,
         rev_src, rev_dst, rev_eid) = children
        return cls(g=g, nbr=nbr, nbr_eid=nbr_eid, nbr_mask=nbr_mask,
                   real_deg=real_deg, n_dst_real=aux[0], fanout=aux[1],
                   rev_src=rev_src, rev_dst=rev_dst, rev_eid=rev_eid)

    @property
    def has_reverse(self) -> bool:
        """True when the reverse table is attached (gather backward
        available)."""
        return self.rev_src is not None

    @property
    def signature(self) -> Tuple[int, int, int, int]:
        """Static shape signature — the planner's block-plan cache key."""
        return (self.g.n_src, self.n_dst_real, self.g.n_edges, self.fanout)

    def __repr__(self):
        return (f"BlockGraph(n_src={self.g.n_src}, "
                f"n_dst_real={self.n_dst_real}, fanout={self.fanout})")


def build_reverse_table(g: Graph) -> Tuple[np.ndarray, np.ndarray,
                                           np.ndarray]:
    """Host-side reverse table of a (concrete) block graph.

    Returns ``(rev_src, rev_dst, rev_eid)``: the edge list sorted by
    source slot (stable, so a source's consumers stay in canonical
    order), with ``rev_eid`` in CALLER edge order — the order edge
    features are indexed in. The sampler builds the same arrays directly
    from its edge lists; this is the fallback for hand-built blocks.
    """
    src = np.asarray(g.src)
    dst = np.asarray(g.dst)
    eid = np.asarray(g.eid)
    order = np.argsort(src, kind="stable")
    return (src[order].astype(np.int32), dst[order].astype(np.int32),
            eid[order].astype(np.int32))


def attach_reverse(bg: BlockGraph) -> BlockGraph:
    """Return ``bg`` with the reverse table attached (no-op if present).

    Needs a concrete (non-traced) graph — sampler-produced blocks carry
    the table already; this serves blocks built by hand in tests or
    benchmarks.
    """
    if bg.has_reverse:
        return bg
    if planner.graph_is_traced(bg.g):
        raise ValueError("attach_reverse needs a concrete BlockGraph; "
                         "build the reverse table host-side (the sampler "
                         "emits it for free)")
    rev_src, rev_dst, rev_eid = build_reverse_table(bg.g)
    return dataclasses.replace(bg, rev_src=jnp.asarray(rev_src),
                               rev_dst=jnp.asarray(rev_dst),
                               rev_eid=jnp.asarray(rev_eid))


def block_supports(strategy: str, spec: BRSpec) -> bool:
    """Can ``strategy`` execute this spec on a block?

    The uniform pull ('ell') handles any ⊗ over u/v/e operands and every
    reducer, but only destination outputs. push/segment run the generic
    COO path on the padded graph. The MXU formulations (onehot/pallas)
    need host-built tile packs, which cannot be rebuilt per batch with a
    static pytree structure — they are never block candidates.
    """
    if spec.out != "v" or spec.reduce == "none":
        return False
    if strategy in ("push", "segment"):
        return True
    if strategy == "ell":
        return True
    return False  # onehot / pallas: no static per-batch tile pack


def _nbr_fetch(bg: BlockGraph, target: str, data: jnp.ndarray) -> jnp.ndarray:
    """Operand values laid out on the (n_dst_real, fanout) slot grid."""
    if target == "u":
        return jnp.take(data, bg.nbr, axis=0)            # (nd, F, d)
    if target == "e":
        return jnp.take(data, bg.nbr_eid, axis=0)        # (nd, F, d)
    if target == "v":
        # destination's own value, broadcast along the slot axis;
        # v operands are sized like g.n_dst (they include the pad row)
        return data[: bg.n_dst_real][:, None]            # (nd, 1, d)
    raise ValueError(target)


def _block_pull(bg: BlockGraph, spec: BRSpec, lhs_data, rhs_data
                ) -> jnp.ndarray:
    """Uniform blocked pull: dense masked reduce over the fanout axis."""
    lhs_val = _nbr_fetch(bg, spec.lhs, lhs_data)
    rhs_val = (_nbr_fetch(bg, spec.rhs, rhs_data)
               if spec.rhs is not None else None)
    msg = BINARY_OPS[spec.op](lhs_val, rhs_val)          # (nd, F, *feat)
    red = spec.reduce
    ident = jnp.asarray(REDUCE_IDENTITY[red], msg.dtype)
    mask = bg.nbr_mask.reshape(bg.nbr_mask.shape + (1,) * (msg.ndim - 2))
    msg = jnp.where(mask, msg, ident)
    base = "sum" if red in ("sum", "mean") else red
    if base == "sum":
        out = msg.sum(axis=1)
    elif base == "max":
        out = msg.max(axis=1)
    elif base == "min":
        out = msg.min(axis=1)
    elif base == "prod":
        out = msg.prod(axis=1)
    else:
        raise ValueError(f"unknown reduce op {red!r}")
    deg = bg.real_deg
    if red == "mean":
        d = jnp.maximum(deg, 1).astype(out.dtype)
        out = out / d.reshape((out.shape[0],) + (1,) * (out.ndim - 1))
    # DGL semantics: rows with no (real) incoming edge are 0 for every ⊕
    if red != "sum":
        has = (deg > 0).reshape((out.shape[0],) + (1,) * (out.ndim - 1))
        out = jnp.where(has, out, jnp.zeros((), out.dtype))
    return out


def block_gspmm(bg: BlockGraph, op_name: str, *,
                u: Optional[jnp.ndarray] = None,
                v: Optional[jnp.ndarray] = None,
                e: Optional[jnp.ndarray] = None,
                strategy: str = "auto",
                bwd_strategy: str = "auto") -> jnp.ndarray:
    """Generalized sparse aggregation over one sampled block.

    Same operand conventions as :func:`~repro.core.binary_reduce.gspmm`
    on ``bg.g`` — ``u``: (n_src_pad, d); ``v``: (n_dst_real + 1, d)
    (callers pad one dummy row); ``e``: (n_edges_pad, d) caller edge
    order. Node outputs are returned for REAL destination rows only:
    shape (n_dst_real, d) — the pad row is consumed internally.

    ``strategy="auto"`` consults the planner's shape-keyed block plan
    cache, so the choice is identical for every batch of the same
    sampler configuration and survives ``jit`` tracing. Pinned
    strategies unsupported on blocks fall back with a one-time warning.

    ``bwd_strategy`` picks the DIFFERENTIATION path, independently of
    the forward: ``"gather"`` wraps the call in the reverse-table
    custom VJP (∂x as a masked pull, no scatter — needs the sampler's
    reverse table and a linear reducer), ``"scatter"`` keeps plain
    autodiff, ``"auto"`` (default) lets the planner decide per shape
    signature (logged as ``block_bwd:<op>``).
    """
    spec = parse_op(op_name)
    data = {"u": u, "v": v, "e": e}
    if data[spec.lhs] is None:
        raise ValueError(f"{op_name}: operand {spec.lhs!r} missing")
    if spec.rhs is not None and data[spec.rhs] is None:
        raise ValueError(f"{op_name}: operand {spec.rhs!r} missing")
    if bwd_strategy != "auto" and \
            bwd_strategy not in planner.BLOCK_BWD_STRATEGIES:
        raise ValueError(
            f"unknown block backward strategy {bwd_strategy!r}; expected "
            f"one of {planner.BLOCK_BWD_STRATEGIES + ('auto',)}")

    # edge outputs are strategy-free gathers — delegate to the COO path
    # (their autodiff backward is already gather-shaped; bwd_strategy
    # does not apply)
    if spec.out == "e":
        return gspmm(bg.g, op_name, u=u, v=v, e=e)

    if spec.out != "v":
        raise ValueError(f"{op_name}: blocks only produce destination or "
                         f"edge outputs (got {spec.out!r})")
    if spec.reduce == "none":
        raise ValueError(f"{op_name}: copy-reduce to nodes needs a reducer")

    lhs_data = _as2d(data[spec.lhs])
    rhs_data = _as2d(data[spec.rhs]) if spec.rhs is not None else None
    d = int(np.prod(lhs_data.shape[1:]))

    concrete = (not planner.graph_is_traced(bg.g)
                and not planner._is_traced(lhs_data)
                and (rhs_data is None
                     or not planner._is_traced(rhs_data)))
    runner = None
    if (planner.get_mode() == "autotune" and strategy == "auto"
            and concrete):      # measuring candidates only works eagerly
        def runner(s):
            return _block_execute(bg, spec, lhs_data, rhs_data, s)

    chosen = planner.plan_block_gspmm(bg.signature, spec, d,
                                      requested=strategy, runner=runner,
                                      dtype=str(lhs_data.dtype))

    bwd_runner = None
    if (planner.get_mode() == "autotune" and bwd_strategy == "auto"
            and concrete and bg.has_reverse
            and jnp.issubdtype(lhs_data.dtype, jnp.floating)):
        def bwd_runner(s):      # measure the actual differentiated call
            def f(l):
                out = (_block_exec_rev(spec, chosen, bg, l, rhs_data)
                       if s == "gather"
                       else _block_execute(bg, spec, l, rhs_data, chosen))
                return jnp.sum(out)
            return jax.grad(f)(lhs_data)

    bwd = planner.plan_block_vjp(bg.signature, spec, d,
                                 requested=bwd_strategy,
                                 gather_available=bg.has_reverse,
                                 runner=bwd_runner,
                                 dtype=str(lhs_data.dtype))
    with jax.named_scope(f"block_gspmm.{spec.name}"):
        if bwd == "gather":
            return _block_exec_rev(spec, chosen, bg, lhs_data, rhs_data)
        return _block_execute(bg, spec, lhs_data, rhs_data, chosen)


def _block_execute(bg: BlockGraph, spec: BRSpec, lhs_data, rhs_data,
                   chosen: str) -> jnp.ndarray:
    """Run one block aggregation with an already-resolved strategy."""
    if chosen == "ell":
        return _block_pull(bg, spec, lhs_data, rhs_data)
    # planning is already done (shape-keyed) — execute the resolved
    # strategy directly rather than re-entering gspmm's planning front
    # door, which would build a PlanCache + stats for every throwaway
    # per-batch block graph in eager mode
    plan = planner.Plan(strategy=chosen, requested=chosen,
                        reason="block")
    out = _execute(bg.g, spec, lhs_data, rhs_data, plan)
    return out[: bg.n_dst_real]


# --------------------------------------------------------------------- #
# reverse-block VJP: gather-based backward (DESIGN.md §7)
# --------------------------------------------------------------------- #
# (the ⊗-adjoint helpers — _unbroadcast / _NEEDS_OTHER / _dmsg — live in
# binary_reduce.py, shared with the gsddmm custom VJP)


def _slot_of_edge(bg: BlockGraph) -> jnp.ndarray:
    """(n_edges,) int32: each caller edge's slot ``k`` on the neighbor
    grid (``nbr[dst, k]``), -1 for pad edges — index prep for masking
    extrema cotangents to the winning slot."""
    flat_eid = bg.nbr_eid.reshape(-1)
    flat_mask = bg.nbr_mask.reshape(-1)
    slots = jax.lax.broadcasted_iota(
        jnp.int32, bg.nbr.shape, 1).reshape(-1)
    safe = jnp.where(flat_mask, flat_eid, bg.g.n_edges)
    k_of = jnp.full((bg.g.n_edges,), -1, jnp.int32)
    return k_of.at[safe].set(jnp.where(flat_mask, slots, -1), mode="drop")


def _block_arg_extrema(bg: BlockGraph, spec: BRSpec, lhs_data, rhs_data
                       ) -> jnp.ndarray:
    """Winning slot per (destination row, feature element) of a max/min
    reduce on the neighbor grid; -1 for rows with no real in-edge."""
    lhs_val = _nbr_fetch(bg, spec.lhs, lhs_data)
    rhs_val = (_nbr_fetch(bg, spec.rhs, rhs_data)
               if spec.rhs is not None else None)
    msg = BINARY_OPS[spec.op](lhs_val, rhs_val)          # (nd, F, *feat)
    ident = jnp.asarray(REDUCE_IDENTITY[spec.reduce], msg.dtype)
    mask = bg.nbr_mask.reshape(bg.nbr_mask.shape + (1,) * (msg.ndim - 2))
    msg = jnp.where(mask, msg, ident)
    arg = (jnp.argmax if spec.reduce == "max" else jnp.argmin)(msg, axis=1)
    has = (bg.real_deg > 0).reshape((arg.shape[0],)
                                    + (1,) * (arg.ndim - 1))
    return jnp.where(has, arg, -1).astype(jnp.int32)


def _reverse_grads(bg: BlockGraph, spec: BRSpec, lhs_data, rhs_data, ct,
                   arg=None):
    """Gather-based adjoints of one block aggregation.

    ∂(u-operand): masked pull over the reverse table — gather the
    (mean-scaled, zero-padded) cotangents at each source's consuming
    destinations, one SORTED segment reduce, no scatter. ∂(e-operand):
    per-edge products of gathered endpoint values, directly in caller
    edge order. ∂(v-operand): same per-edge products reduced over the
    forward CSR (canonical order is dst-sorted already). For max/min,
    ``arg`` is the recorded arg-extrema table: cotangents are zeroed on
    every slot except the winner before the pull, which is exactly the
    extrema adjoint. prod stays on the autodiff backward.
    """
    g = bg.g
    if spec.reduce == "mean":
        d = jnp.maximum(bg.real_deg, 1).astype(ct.dtype)
        ct = ct / d.reshape((ct.shape[0],) + (1,) * (ct.ndim - 1))
    # dummy destination row pulls exactly zero: pad edges (and only pad
    # edges) point at it, so no mask arithmetic is needed in the pull
    ct_pad = jnp.concatenate(
        [ct, jnp.zeros((1,) + ct.shape[1:], ct.dtype)], axis=0)

    orders = {
        "rev": (bg.rev_src, bg.rev_dst, bg.rev_eid),
        "canon": (g.src, g.dst, g.eid),
        "caller": (jnp.take(g.src, g.eid_inv), jnp.take(g.dst, g.eid_inv),
                   None),     # eid in caller order is the identity
    }

    if arg is not None:
        # extrema backward: only the winning slot's edge receives the
        # cotangent. arg_pad's dummy row is -1 and pad edges carry slot
        # -1, so they select each other — harmless, their ct is zero.
        k_of = _slot_of_edge(bg)
        arg_pad = jnp.concatenate(
            [arg, jnp.full((1,) + arg.shape[1:], -1, arg.dtype)], axis=0)

    def fetch(target, data, order):
        s, dd, e = orders[order]
        if target == "u":
            return jnp.take(data, s, axis=0)
        if target == "v":
            return jnp.take(data, dd, axis=0)
        return data if e is None else jnp.take(data, e, axis=0)

    def grad_for(side):
        target = spec.lhs if side == "l" else spec.rhs
        data = lhs_data if side == "l" else rhs_data
        other = rhs_data if side == "l" else lhs_data
        other_t = spec.rhs if side == "l" else spec.lhs
        order = {"u": "rev", "v": "canon", "e": "caller"}[target]
        lhs_val = rhs_val = None
        if spec.op in _NEEDS_OTHER:
            val = fetch(other_t, other, order)
            lhs_val, rhs_val = ((None, val) if side == "l" else (val, None))
            if spec.op == "div" and side == "r":
                rhs_val = fetch(target, data, order)  # d/dr needs both
        ct_e = jnp.take(ct_pad, orders[order][1], axis=0)
        if arg is not None:
            e_ids = orders[order][2]
            k_e = k_of if e_ids is None else jnp.take(k_of, e_ids)
            sel = (jnp.take(arg_pad, orders[order][1], axis=0)
                   == k_e.reshape((k_e.shape[0],)
                                  + (1,) * (arg_pad.ndim - 1)))
            ct_e = jnp.where(sel, ct_e, jnp.zeros((), ct_e.dtype))
        gmsg = _dmsg(spec.op, side, lhs_val, rhs_val, ct_e)
        gmsg = _unbroadcast(gmsg, tuple(data.shape[1:]))
        if target == "u":
            out = jax.ops.segment_sum(gmsg, orders[order][0],
                                      num_segments=g.n_src,
                                      indices_are_sorted=True)
        elif target == "v":
            out = jax.ops.segment_sum(gmsg, orders[order][1],
                                      num_segments=g.n_dst,
                                      indices_are_sorted=True)
        else:
            out = gmsg
        return out.astype(data.dtype)

    dlhs = grad_for("l")
    drhs = grad_for("r") if spec.rhs is not None else None
    return dlhs, drhs


@partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _block_exec_rev(spec: BRSpec, fwd_strategy: str, bg: BlockGraph,
                    lhs_data, rhs_data):
    """``_block_execute`` with the gather (reverse-table) backward."""
    return _block_execute(bg, spec, lhs_data, rhs_data, fwd_strategy)


def _block_exec_rev_fwd(spec, fwd_strategy, bg, lhs_data, rhs_data):
    out = _block_execute(bg, spec, lhs_data, rhs_data, fwd_strategy)
    arg = (_block_arg_extrema(bg, spec, lhs_data, rhs_data)
           if spec.reduce in ("max", "min") else None)
    return out, (bg, lhs_data, rhs_data, arg)


def _block_exec_rev_bwd(spec, fwd_strategy, res, ct):
    bg, lhs_data, rhs_data, arg = res
    dlhs, drhs = _reverse_grads(bg, spec, lhs_data, rhs_data, ct,
                                arg=arg)
    return None, dlhs, drhs


_block_exec_rev.defvjp(_block_exec_rev_fwd, _block_exec_rev_bwd)
