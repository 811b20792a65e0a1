"""The Binary-Reduce / Copy-Reduce primitive lattice (paper §2).

``BR(x, y, ⊗, ⊕, z) : z ← ⊕(⊗(x, y), z)`` over a graph, where the operands
live on source nodes (``u``), destination nodes (``v``) or edges (``e``);

  ⊗ ∈ {add, sub, mul, div, dot, copy}          (element-wise; dot sums feat)
  ⊕ ∈ {add(sum), max, min, mul(prod), mean, copy}

Configs are named DGL-style, e.g. ``u_mul_e_add_v`` (BR) or ``u_copy_add_v``
(CR) — exactly the names in the paper's Table 2. ``copy`` as the reducer
means the per-edge result is written to edges without reduction.

The reduce stage dispatches across execution strategies (see
``strategies.py``): ``push`` (baseline Alg. 1), ``segment`` (Alg. 2),
``ell`` (Alg. 3 blocked pull), ``onehot`` (MXU adaptation), ``pallas``
(TPU kernel, see ``repro.kernels``). By default (``strategy="auto"``)
the planner (``planner.py``) selects the strategy from graph statistics
and memoizes any blocked packs per graph; pinning a strategy reproduces
the paper's baseline-vs-optimized experiments, and a pinned strategy
that cannot execute a spec falls back gracefully instead of raising.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from . import planner
from . import strategies as S
from .graph import Graph
from .tiling import ELLPack, TilePack

__all__ = ["BRSpec", "parse_op", "gspmm", "gsddmm", "copy_reduce",
           "binary_reduce", "BINARY_OPS", "REDUCE_OPS", "OP_TARGETS"]

OP_TARGETS = ("u", "v", "e")

BINARY_OPS: Dict[str, Callable] = {
    "add": jnp.add,
    "sub": jnp.subtract,
    "mul": jnp.multiply,
    "div": jnp.divide,
    "dot": lambda a, b: jnp.sum(a * b, axis=-1, keepdims=True),
    "copy": lambda a, b: a,  # unary: rhs ignored (CR, Eq. 3)
}

# DGL name -> internal reducer name
REDUCE_OPS: Dict[str, str] = {
    "add": "sum", "sum": "sum", "max": "max", "min": "min",
    "mul": "prod", "prod": "prod", "mean": "mean", "copy": "none",
}


@dataclasses.dataclass(frozen=True)
class BRSpec:
    """Parsed configuration of a Binary-Reduce."""
    lhs: str          # 'u' | 'v' | 'e'
    op: str           # key of BINARY_OPS
    rhs: Optional[str]  # 'u' | 'v' | 'e' | None (CR)
    reduce: str       # 'sum'|'max'|'min'|'prod'|'mean'|'none'
    out: str          # 'u' | 'v' | 'e'

    @property
    def name(self) -> str:
        r = "copy" if self.reduce == "none" else (
            "add" if self.reduce == "sum" else
            "mul" if self.reduce == "prod" else self.reduce)
        if self.op == "copy":
            return f"{self.lhs}_copy_{r}_{self.out}"
        return f"{self.lhs}_{self.op}_{self.rhs}_{r}_{self.out}"


def parse_op(name: str) -> BRSpec:
    """Parse a DGL-style op name into a :class:`BRSpec`.

    CR: ``<x>_copy_<red>_<z>``; BR: ``<x>_<op>_<y>_<red>_<z>``.
    """
    toks = name.split("_")
    if len(toks) == 4 and toks[1] == "copy":
        lhs, _, red, out = toks
        rhs = None
        op = "copy"
    elif len(toks) == 5:
        lhs, op, rhs, red, out = toks
        if rhs not in OP_TARGETS:
            raise ValueError(f"bad rhs target in {name!r}")
    else:
        raise ValueError(f"cannot parse BR op name {name!r}")
    if lhs not in OP_TARGETS or out not in OP_TARGETS:
        raise ValueError(f"bad operand targets in {name!r}")
    if op not in BINARY_OPS:
        raise ValueError(f"unknown binary op in {name!r}")
    if red not in REDUCE_OPS:
        raise ValueError(f"unknown reduce op in {name!r}")
    return BRSpec(lhs=lhs, op=op, rhs=rhs, reduce=REDUCE_OPS[red], out=out)


# --------------------------------------------------------------------- #
# operand gathering (canonical edge order = sorted by dst)
# --------------------------------------------------------------------- #
def _edge_val(g: Graph, target: str, data: jnp.ndarray) -> jnp.ndarray:
    """Per-edge operand values in canonical edge order."""
    if target == "u":
        return jnp.take(data, g.src, axis=0)
    if target == "v":
        return jnp.take(data, g.dst, axis=0)
    if target == "e":
        return jnp.take(data, g.eid, axis=0)
    raise ValueError(target)


def _as2d(x: jnp.ndarray) -> jnp.ndarray:
    return x[:, None] if x.ndim == 1 else x


# --------------------------------------------------------------------- #
# ⊗-adjoint machinery (shared by the gsddmm VJP and blocks.py's
# reverse-table backward)
# --------------------------------------------------------------------- #
def _unbroadcast(grad: jnp.ndarray, feat_shape: Tuple[int, ...]
                 ) -> jnp.ndarray:
    """Reduce a per-edge gradient ``(E, *G)`` to an operand's per-edge
    shape ``(E, *feat_shape)`` (right-aligned broadcasting adjoint)."""
    extra = (grad.ndim - 1) - len(feat_shape)
    if extra > 0:
        grad = grad.sum(axis=tuple(range(1, 1 + extra)))
    axes = tuple(i + 1 for i, w in enumerate(feat_shape)
                 if w == 1 and grad.shape[i + 1] != 1)
    if axes:
        grad = grad.sum(axis=axes, keepdims=True)
    return grad


# ⊗-adjoint factors: which operand values the partial derivative needs
_NEEDS_OTHER = ("mul", "div", "dot")


def _dmsg(op: str, side: str, lhs_val, rhs_val, ct_e):
    """Per-edge cotangent of ``msg = lhs ⊗ rhs`` w.r.t. one side."""
    if op in ("copy", "add"):
        return ct_e
    if op == "sub":
        return ct_e if side == "l" else -ct_e
    if op in ("mul", "dot"):    # dot: ct_e has a trailing 1 — broadcasts
        return ct_e * (rhs_val if side == "l" else lhs_val)
    if op == "div":
        if side == "l":
            return ct_e / rhs_val
        return -ct_e * lhs_val / (rhs_val * rhs_val)
    raise ValueError(f"no ⊗-adjoint for {op!r}")


# --------------------------------------------------------------------- #
# main entry
# --------------------------------------------------------------------- #
def gspmm(g: Graph, op_name: str, *,
          u: Optional[jnp.ndarray] = None,
          v: Optional[jnp.ndarray] = None,
          e: Optional[jnp.ndarray] = None,
          strategy: str = "auto",
          ell: Optional[ELLPack] = None,
          tiles: Optional[TilePack] = None,
          cache: Optional[planner.PlanCache] = None) -> jnp.ndarray:
    """Generalized sparse aggregation (paper Eq. 1/3).

    Operand tensors are indexed by node/edge id: ``u``: (n_src, d) or
    (n_src,), ``v``: (n_dst, d), ``e``: (n_edges, d) in the caller's
    original edge order. Returns features on ``spec.out`` — edge outputs
    are returned in the caller's original edge order.

    ``strategy="auto"`` (default) routes through the planner; explicit
    ``ell``/``tiles`` packs override the per-graph :class:`PlanCache`,
    and ``cache`` carries a pre-populated cache through ``jit`` (model
    bundles do this so planning works inside jitted train steps).
    """
    spec = parse_op(op_name)
    data = {"u": u, "v": v, "e": e}
    if data[spec.lhs] is None:
        raise ValueError(f"{op_name}: operand {spec.lhs!r} missing")
    if spec.rhs is not None and data[spec.rhs] is None:
        raise ValueError(f"{op_name}: operand {spec.rhs!r} missing")

    lhs_data = _as2d(data[spec.lhs])
    rhs_data = _as2d(data[spec.rhs]) if spec.rhs is not None else None

    # edge outputs are gSDDMMs — delegate to the planned path. Pinned
    # gspmm strategy names map onto the sddmm lattice: pallas stays
    # pallas, the baselines (push/segment) pin the caller-order gather,
    # the optimized names pin the canonical stream.
    if spec.out == "e":
        sddmm_req = {"auto": "auto", "pallas": "pallas",
                     "push": "gather", "segment": "gather"
                     }.get(strategy, "canonical")
        return gsddmm(g, op_name, u=u, v=v, e=e, strategy=sddmm_req)

    if spec.reduce == "none":
        raise ValueError(f"{op_name}: copy-reduce to nodes needs a reducer")

    runner = None
    if planner.get_mode() == "autotune" and strategy == "auto":
        def runner(s):
            return gspmm(g, op_name, u=u, v=v, e=e, strategy=s,
                         ell=ell, tiles=tiles, cache=cache)

    plan = planner.plan_gspmm(g, spec, lhs_data, rhs_data,
                              requested=strategy, cache=cache,
                              ell=ell, tiles=tiles, runner=runner)
    with jax.named_scope(f"gspmm.{spec.name}"):
        out = _execute(g, spec, lhs_data, rhs_data, plan)
    # node outputs keep the feature operand's dtype: a bf16 feature
    # against fp32 edge norms silently promotes the message stream to
    # fp32 under JAX's type rules, which would upcast every layer of a
    # half-precision model after its first aggregation
    if (jnp.issubdtype(lhs_data.dtype, jnp.floating)
            and jnp.issubdtype(out.dtype, jnp.floating)
            and out.dtype != lhs_data.dtype):
        out = out.astype(lhs_data.dtype)
    return out


# --------------------------------------------------------------------- #
# gSDDMM: planned edge-output computation (DESIGN.md §9)
# --------------------------------------------------------------------- #
def gsddmm(g: Graph, op_name: str, *,
           u: Optional[jnp.ndarray] = None,
           v: Optional[jnp.ndarray] = None,
           e: Optional[jnp.ndarray] = None,
           strategy: str = "auto") -> jnp.ndarray:
    """Generalized SDDMM: per-edge ⊗ of node/edge operands (the second
    core primitive of the DGL architecture — attention logits, softmax
    shift/divide, bilinear edge scores).

    Operand conventions match :func:`gspmm`; the op's ``out`` target
    must be ``e``. Returns (n_edges, d) in the caller's original edge
    order (1-D operands widen to d=1, like the node-output path).

    ``strategy``: 'auto' (planner, logged ``sddmm:<op>``), 'canonical'
    (gather in dst-sorted order, ⊗ on the sorted stream, one un-permute
    out), 'gather' (operands gathered straight into caller order — the
    DGL-style baseline), or 'pallas' (tiled kernel over the canonical
    stream, ``repro.kernels.sddmm``).

    Floating operands run under a scatter-free custom VJP: ∂u rides the
    graph's free src-sorted view (``perm_src`` + one SORTED segment
    reduce), ∂v the canonical dst-sorted stream, ∂e stays per-edge —
    mirroring the reverse-block backward, no scatter anywhere.
    """
    spec = parse_op(op_name)
    if spec.out != "e":
        raise ValueError(f"{op_name}: gsddmm computes edge outputs "
                         f"(got out={spec.out!r}); use gspmm")
    data = {"u": u, "v": v, "e": e}
    if data[spec.lhs] is None:
        raise ValueError(f"{op_name}: operand {spec.lhs!r} missing")
    if spec.rhs is not None and data[spec.rhs] is None:
        raise ValueError(f"{op_name}: operand {spec.rhs!r} missing")

    lhs_data = _as2d(data[spec.lhs])
    rhs_data = _as2d(data[spec.rhs]) if spec.rhs is not None else None

    if spec.op == "dot":
        d = 1
    elif rhs_data is None:
        d = int(math.prod(lhs_data.shape[1:]))
    else:
        d = int(max(math.prod(lhs_data.shape[1:]),
                    math.prod(rhs_data.shape[1:])))

    runner = None
    if (planner.get_mode() == "autotune" and strategy == "auto"
            and not planner.graph_is_traced(g)
            and not planner._is_traced(lhs_data)
            and (rhs_data is None
                 or not planner._is_traced(rhs_data))):
        def runner(s):
            return _sddmm_execute(g, spec, lhs_data, rhs_data, s)

    chosen = planner.plan_sddmm((g.n_src, g.n_dst, g.n_edges), spec, d,
                                requested=strategy, lhs_data=lhs_data,
                                rhs_data=rhs_data, runner=runner)

    floating = (jnp.issubdtype(lhs_data.dtype, jnp.floating)
                and (rhs_data is None
                     or jnp.issubdtype(rhs_data.dtype, jnp.floating)))
    with jax.named_scope(f"gsddmm.{spec.name}"):
        if floating:
            return _sddmm_exec_rev(spec, chosen, g, lhs_data, rhs_data)
        return _sddmm_execute(g, spec, lhs_data, rhs_data, chosen)


def _sddmm_execute(g: Graph, spec: BRSpec, lhs_data, rhs_data,
                   chosen: str) -> jnp.ndarray:
    """Run one edge-output BR with an already-resolved strategy."""
    if chosen == "gather":
        # caller-order view of the endpoints: one double-indirect
        # gather per operand, no output permute
        src_c = jnp.take(g.src, g.eid_inv)
        dst_c = jnp.take(g.dst, g.eid_inv)

        def fetch(target, data):
            if target == "u":
                return jnp.take(data, src_c, axis=0)
            if target == "v":
                return jnp.take(data, dst_c, axis=0)
            return data                       # e: identity in caller order

        lhs_val = fetch(spec.lhs, lhs_data)
        rhs_val = (fetch(spec.rhs, rhs_data)
                   if spec.rhs is not None else None)
        return BINARY_OPS[spec.op](lhs_val, rhs_val)

    # canonical / pallas: dst-sorted operand streams, one un-permute out
    lhs_val = _edge_val(g, spec.lhs, lhs_data)
    rhs_val = (_edge_val(g, spec.rhs, rhs_data)
               if spec.rhs is not None else None)
    if chosen == "pallas":
        from repro.kernels.sddmm.ops import sddmm as sddmm_pallas

        msg = sddmm_pallas(lhs_val, rhs_val, spec.op)
    else:
        msg = BINARY_OPS[spec.op](lhs_val, rhs_val)
    return jnp.take(msg, g.eid_inv, axis=0)


def _sddmm_grads(g: Graph, spec: BRSpec, lhs_data, rhs_data, ct):
    """Scatter-free adjoints of one edge-output BR.

    ∂(u-operand): per-edge cotangent products pulled through the graph's
    src-sorted view (``perm_src``) + one SORTED segment reduce.
    ∂(v-operand): same products on the canonical dst-sorted stream.
    ∂(e-operand): per-edge, directly in caller order. Mirrors the
    reverse-block VJP — no scatter anywhere.
    """
    perm = g.perm_src
    src_sorted = jnp.take(g.src, perm)
    orders = {
        "srcsort": (src_sorted, jnp.take(g.dst, perm),
                    jnp.take(g.eid, perm)),
        "canon": (g.src, g.dst, g.eid),
        "caller": (jnp.take(g.src, g.eid_inv), jnp.take(g.dst, g.eid_inv),
                   None),      # eid in caller order is the identity
    }

    def fetch(target, data, order):
        s, dd, eid = orders[order]
        if target == "u":
            return jnp.take(data, s, axis=0)
        if target == "v":
            return jnp.take(data, dd, axis=0)
        return data if eid is None else jnp.take(data, eid, axis=0)

    def ct_in(order):
        # ct arrives in caller edge order; eid maps any other order's
        # positions back to caller ids
        eid = orders[order][2]
        return ct if eid is None else jnp.take(ct, eid, axis=0)

    def grad_for(side):
        target = spec.lhs if side == "l" else spec.rhs
        data = lhs_data if side == "l" else rhs_data
        other = rhs_data if side == "l" else lhs_data
        other_t = spec.rhs if side == "l" else spec.lhs
        order = {"u": "srcsort", "v": "canon", "e": "caller"}[target]
        lhs_val = rhs_val = None
        if spec.op in _NEEDS_OTHER:
            val = fetch(other_t, other, order)
            lhs_val, rhs_val = ((None, val) if side == "l" else (val, None))
            if spec.op == "div" and side == "r":
                rhs_val = fetch(target, data, order)  # d/dr needs both
        gmsg = _dmsg(spec.op, side, lhs_val, rhs_val, ct_in(order))
        gmsg = _unbroadcast(gmsg, tuple(data.shape[1:]))
        if target == "u":
            out = jax.ops.segment_sum(gmsg, src_sorted,
                                      num_segments=g.n_src,
                                      indices_are_sorted=True)
        elif target == "v":
            out = jax.ops.segment_sum(gmsg, g.dst,
                                      num_segments=g.n_dst,
                                      indices_are_sorted=True)
        else:
            out = gmsg
        return out.astype(data.dtype)

    dlhs = grad_for("l")
    drhs = grad_for("r") if spec.rhs is not None else None
    return dlhs, drhs


@partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _sddmm_exec_rev(spec: BRSpec, chosen: str, g: Graph,
                    lhs_data, rhs_data):
    """``_sddmm_execute`` with the scatter-free backward."""
    return _sddmm_execute(g, spec, lhs_data, rhs_data, chosen)


def _sddmm_exec_rev_fwd(spec, chosen, g, lhs_data, rhs_data):
    out = _sddmm_execute(g, spec, lhs_data, rhs_data, chosen)
    return out, (g, lhs_data, rhs_data)


def _sddmm_exec_rev_bwd(spec, chosen, res, ct):
    g, lhs_data, rhs_data = res
    dlhs, drhs = _sddmm_grads(g, spec, lhs_data, rhs_data, ct)
    return None, dlhs, drhs


_sddmm_exec_rev.defvjp(_sddmm_exec_rev_fwd, _sddmm_exec_rev_bwd)


def _execute(g: Graph, spec: BRSpec, lhs_data, rhs_data,
             plan: planner.Plan) -> jnp.ndarray:
    """Run one node-output BR with a resolved plan."""
    if plan.strategy == "ell":
        return _gspmm_ell(g, spec, plan.ell, lhs_data, rhs_data)
    if plan.strategy == "onehot":
        return _gspmm_onehot(g, spec, plan.tiles, lhs_data, rhs_data)
    if plan.strategy == "pallas":
        return _gspmm_pallas_diff(g, spec, plan.tiles, lhs_data, rhs_data)
    if plan.strategy == "ring":
        return _gspmm_ring(g, spec, plan.partition, lhs_data, rhs_data)

    # ---- generic path: per-edge messages then reduce
    lhs_val = _edge_val(g, spec.lhs, lhs_data)
    rhs_val = (_edge_val(g, spec.rhs, rhs_data)
               if spec.rhs is not None else None)
    msg = BINARY_OPS[spec.op](lhs_val, rhs_val)

    if spec.out == "v":
        tgt, n_tgt, deg = g.dst, g.n_dst, g.in_degrees
    else:  # 'u'
        msg = jnp.take(msg, g.perm_src, axis=0)
        tgt = jnp.take(g.src, g.perm_src)
        n_tgt, deg = g.n_src, g.out_degrees

    if plan.strategy == "push":
        return S.push_scatter(msg, tgt, n_tgt, spec.reduce, deg)
    # default: segment (Alg. 2)
    return S.pull_segment(msg, tgt, n_tgt, spec.reduce, deg)


def _gspmm_ring(g: Graph, spec: BRSpec, pg, lhs_data, rhs_data
                ) -> jnp.ndarray:
    """Partitioned (multi-device ring) execution of a weighted CR.

    The planner only routes here under an active :func:`planner.use_ring`
    context (``supports``/``pack_available`` gate it), so the mesh is
    live. Mean folds 1/deg into the per-edge weights — the ring itself
    is a pure weighted CR-sum (core/partition.py). Layout conversions
    happen per call; partitioned *training* keeps features in the
    padded sharded layout end-to-end instead (models/gnn/train.py).
    """
    from .partition import ring_gspmm

    ctx = planner.active_ring()
    # weights stay at ≥fp32: degree norms truncated to bf16 before the
    # multiply lose precision the fp32 accumulators can't win back
    wdt = (jnp.promote_types(lhs_data.dtype, jnp.float32)
           if jnp.issubdtype(lhs_data.dtype, jnp.floating)
           else lhs_data.dtype)
    if spec.op == "mul":
        w = rhs_data[:, 0]
    else:                       # copy
        w = jnp.ones((g.n_edges,), wdt)
    if spec.reduce == "mean":
        deg = jnp.maximum(g.in_degrees, 1).astype(wdt)
        dst_caller = jnp.take(g.dst, g.eid_inv)
        w = w / jnp.take(deg, dst_caller)
    out = ring_gspmm(pg, pg.scatter_nodes(lhs_data), pg.scatter_edges(w),
                     mesh=ctx.mesh if ctx is not None else None,
                     axis=ctx.axis if ctx is not None else "data")
    return pg.gather_nodes(out, g.n_dst)


def _gspmm_pallas_diff(g: Graph, spec: BRSpec, tiles, lhs_data, rhs_data
                       ) -> jnp.ndarray:
    """Pallas forward with a segment-path adjoint.

    ``pallas_call`` has no transpose rule (and interpret mode never will),
    but the kernel computes the same operator as the segment strategy —
    so the segment path's VJP IS the pallas path's VJP. This keeps the
    planner free to choose pallas inside differentiated train steps.
    """
    from repro.kernels.dispatch import gspmm_pallas

    seg_plan = planner.Plan(strategy="segment", requested="segment",
                            reason="pallas-adjoint")

    def seg(l, r):
        return _execute(g, spec, l, r, seg_plan)

    if rhs_data is None:
        @jax.custom_vjp
        def f(l):
            return gspmm_pallas(g, spec, l, None, tiles=tiles)

        f.defvjp(lambda l: (f(l), (l,)),
                 lambda res, ct: jax.vjp(lambda l: seg(l, None),
                                         *res)[1](ct))
        return f(lhs_data)

    @jax.custom_vjp
    def f2(l, r):
        return gspmm_pallas(g, spec, l, r, tiles=tiles)

    f2.defvjp(lambda l, r: (f2(l, r), (l, r)),
              lambda res, ct: jax.vjp(seg, *res)[1](ct))
    return f2(lhs_data, rhs_data)


def _gspmm_ell(g: Graph, spec: BRSpec, pack: ELLPack,
               lhs_data, rhs_data) -> jnp.ndarray:
    """Blocked pull with the ⊗ fused into the per-class chunk gather."""
    def chunk_fetch(cls, target: str, data):
        if target == "u":
            return jnp.take(data, cls.chunk_cols, axis=0)      # (C, W, d)
        if target == "e":
            return jnp.take(data, cls.chunk_eids, axis=0)
        if target == "v":
            val = jnp.take(data, cls.chunk_row, axis=0)        # (C, d)
            return val[:, None, :]                             # broadcast W
        raise ValueError(target)

    def msg_fn(cls):
        lhs_val = chunk_fetch(cls, spec.lhs, lhs_data)
        rhs_val = (chunk_fetch(cls, spec.rhs, rhs_data)
                   if spec.rhs is not None else None)
        return BINARY_OPS[spec.op](lhs_val, rhs_val)

    return S.pull_ell_reduce(pack, msg_fn, spec.reduce, deg=g.in_degrees)


def _gspmm_onehot(g: Graph, spec: BRSpec, tiles: TilePack,
                  lhs_data, rhs_data) -> jnp.ndarray:
    """MXU one-hot SpMM path. Supports u_copy_{add,mean}_v and
    u_mul_e_{add,mean}_v with scalar edge weights (the planner's
    ``supports()`` predicate gates dispatch onto this path)."""
    if spec.lhs != "u":
        raise ValueError("onehot strategy needs lhs on source nodes")
    w = None
    if spec.op == "mul" and spec.rhs == "e":
        ew = rhs_data
        if ew.shape[-1] != 1:
            raise ValueError("onehot edge weights must be scalar per edge")
        w = jnp.take(ew[:, 0], tiles.eids, axis=0)  # (T, eb)
    elif spec.op != "copy":
        raise ValueError(f"onehot strategy does not support ⊗={spec.op}")
    return S.onehot_spmm(tiles, lhs_data, spec.reduce, edge_weight=w,
                         deg=g.in_degrees)


# --------------------------------------------------------------------- #
# sugar
# --------------------------------------------------------------------- #
def copy_reduce(g: Graph, x: jnp.ndarray, reduce: str = "sum",
                strategy: str = "auto", **kw) -> jnp.ndarray:
    """CR: ``u_copy_<reduce>_v`` (paper Eq. 3/4)."""
    red = {"sum": "add", "prod": "mul"}.get(reduce, reduce)
    return gspmm(g, f"u_copy_{red}_v", u=x, strategy=strategy, **kw)


def binary_reduce(g: Graph, op_name: str, lhs: jnp.ndarray,
                  rhs: Optional[jnp.ndarray] = None,
                  strategy: str = "auto", **kw) -> jnp.ndarray:
    """Positional-operand flavour: operands assigned per the op name."""
    spec = parse_op(op_name)
    ops: Dict[str, jnp.ndarray] = {spec.lhs: lhs}
    if spec.rhs is not None:
        if rhs is None:
            raise ValueError(f"{op_name} needs two operands")
        if spec.rhs == spec.lhs:
            raise ValueError(f"{op_name}: operands share a target; use gspmm")
        ops[spec.rhs] = rhs
    return gspmm(g, op_name, strategy=strategy,
                 **{k: v for k, v in ops.items()}, **kw)
