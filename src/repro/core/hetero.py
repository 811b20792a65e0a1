"""Relation-fused heterogeneous execution (DESIGN.md §8).

RGCN (103 relations on BGS), GCMC (one subgraph per rating level),
MoNet (one aggregation per mixture kernel) and LGNN (node graph + line
graph) all compute the same shape of operator:

    out[v] = Σ_r Σ_{(u→v) ∈ E_r}  msg_r(u, e)

The pre-refactor implementation ran a Python loop of R sequential
``gspmm`` calls over per-relation ``Graph``s — exactly the per-type
kernel-launch overhead the DGL heterograph design (Wang et al.,
1909.01315) eliminates by stacking relations. :class:`RelGraph` is that
stacking: all relations' edge sets concatenated into ONE fused graph
(canonically (dst, src)-sorted, so the whole ``Graph``/``PlanCache``
machinery applies wholesale) with a relation id per edge, per-relation
degree norms (RGCN's 1/c_{v,r}), a relation-sorted permutation (the
per-relation-loop view), and a (src, rel)-sorted reverse table (the
gather backward's lookup structure — see §8.4).

:func:`hetero_gspmm` is the fused Σ_r CR:

* gather ``u`` at the fused sources (or the relation-transformed
  features at ``(rel, src)``),
* index ``W`` (or the basis-composed ``W_r``) by edge relation id,
* ONE sorted segment reduce into destinations,

with a custom VJP that mirrors the PR-4 reverse-block backward: the
per-``(src, rel)`` cotangent aggregate is one SORTED segment reduce
over the reverse table — no scatter — and ∂W/∂u follow by two dense
einsums. ``strategy="auto"`` routes through the planner
(:func:`repro.core.planner.plan_hetero`, logged as ``hetero:<op>``):
``fused`` vs the per-relation ``loop`` baseline vs ``ell`` (fused
messages reduced by the fused graph's blocked pull) from
relation-count/size-skew statistics, memoized per signature and
measurable under autotune mode. When relation sizes are materially
skewed, the ``ell`` route splits into per-size-class packs
(ell-per-relation-class, :func:`_build_skew_classes`) so one giant
relation doesn't set the ELL pad width for every tiny one.
"""
from __future__ import annotations

import dataclasses
import weakref
from functools import partial
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import planner
from ..compat import trace_state_clean
from . import strategies as S
from .binary_reduce import parse_op, _execute
from .graph import Graph, from_coo

__all__ = ["RelGraph", "from_typed", "from_rels", "hetero_gspmm",
           "hetero_block_gspmm", "caller_coo"]


# --------------------------------------------------------------------- #
# the fused relational structure
# --------------------------------------------------------------------- #
@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True, eq=False)
class RelGraph:
    """All relations' edges stacked into one relation-tagged graph.

    ``g`` is the fused :class:`Graph` in the repo's canonical
    (dst, src)-sorted edge order — its CSR, packs and
    :class:`~repro.core.planner.PlanCache` serve the fused strategies
    unchanged. The remaining arrays are views of the SAME edge set:

    * ``rel``        (E,) relation id per edge, canonical order;
    * ``mean_norm``  (E,) 1/deg_r(dst) per edge, canonical order — the
      per-relation mean weight (RGCN's 1/c_{v,r});
    * ``perm_rel``   (E,) relation-sorted position → canonical slot
      (stable, so each relation's slice stays dst-sorted) — the
      per-relation-loop view; slice boundaries are the static
      ``rel_ptr``;
    * ``rev_perm``/``rev_src``/``rev_dst``/``rev_rel`` — the edges
      sorted by (src, rel): ``rev_src * n_rel + rev_rel`` is
      non-decreasing, so the backward's per-(src, rel) cotangent
      aggregate is ONE sorted segment reduce (no scatter).

    Caller edge order (the order ``e`` operands are indexed in) is the
    relation-concatenated order the constructor received; ``g.eid``
    maps canonical slots back to it, exactly as for plain graphs.
    """
    g: Graph
    rel: jnp.ndarray          # (E,) int32, canonical order
    mean_norm: jnp.ndarray    # (E,) float32, canonical order
    perm_rel: jnp.ndarray     # (E,) int32 rel-sorted pos -> canonical slot
    rev_perm: jnp.ndarray     # (E,) int32 (src,rel)-sorted pos -> canonical
    rev_src: jnp.ndarray      # (E,) int32, non-decreasing
    rev_dst: jnp.ndarray      # (E,) int32
    rev_rel: jnp.ndarray      # (E,) int32
    cache: planner.PlanCache  # the fused graph's plan cache
    n_rel: int = dataclasses.field(metadata={"static": True})
    rel_sizes: Tuple[int, ...] = dataclasses.field(
        metadata={"static": True})

    def tree_flatten(self):
        return ((self.g, self.rel, self.mean_norm, self.perm_rel,
                 self.rev_perm, self.rev_src, self.rev_dst, self.rev_rel,
                 self.cache), (self.n_rel, self.rel_sizes))

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, n_rel=aux[0], rel_sizes=aux[1])

    # -- static views ----------------------------------------------------
    @property
    def n_src(self) -> int:
        return self.g.n_src

    @property
    def n_dst(self) -> int:
        return self.g.n_dst

    @property
    def n_edges(self) -> int:
        return self.g.n_edges

    @property
    def rel_ptr(self) -> Tuple[int, ...]:
        """Static per-relation offsets into the relation-sorted view."""
        ptr = [0]
        for s in self.rel_sizes:
            ptr.append(ptr[-1] + s)
        return tuple(ptr)

    @property
    def signature(self) -> Tuple[int, int, int, int]:
        """Static planner key: (n_src, n_dst, n_edges, n_rel)."""
        return (self.n_src, self.n_dst, self.n_edges, self.n_rel)

    def __repr__(self):
        return (f"RelGraph(n_src={self.n_src}, n_dst={self.n_dst}, "
                f"n_edges={self.n_edges}, n_rel={self.n_rel})")


def from_typed(src, dst, rel, *, n_src: int, n_dst: int,
               n_rel: Optional[int] = None) -> RelGraph:
    """Build a :class:`RelGraph` from one typed COO edge list.

    ``rel[i]`` is the relation id of caller edge ``i``; caller order is
    preserved for ``e`` operands. Host-side (numpy), like ``from_coo``.
    """
    src = np.asarray(src, np.int64)
    dst = np.asarray(dst, np.int64)
    rel = np.asarray(rel, np.int64)
    if not (src.shape == dst.shape == rel.shape) or src.ndim != 1:
        raise ValueError("src/dst/rel must be equal-length 1-D")
    n_rel = int(n_rel if n_rel is not None
                else (rel.max() + 1 if rel.size else 0))
    if rel.size and (rel.min() < 0 or rel.max() >= n_rel):
        raise ValueError("relation ids out of range")

    g = from_coo(src, dst, n_src=n_src, n_dst=n_dst)
    eid = np.asarray(g.eid)
    rel_canon = rel[eid]
    src_canon = np.asarray(g.src)
    dst_canon = np.asarray(g.dst)

    # per-(relation, dst) in-degree -> the per-relation mean weight
    key = rel_canon * n_dst + dst_canon
    cnt = np.bincount(key, minlength=n_rel * max(n_dst, 1)) if rel.size \
        else np.zeros(0, np.int64)
    mean_norm = (1.0 / np.maximum(cnt[key], 1)).astype(np.float32) \
        if rel.size else np.zeros(0, np.float32)

    perm_rel = np.argsort(rel_canon, kind="stable").astype(np.int32)
    rel_sizes = tuple(int(x) for x in
                      np.bincount(rel, minlength=n_rel))

    rev_perm = np.lexsort((rel_canon, src_canon)).astype(np.int32)
    return RelGraph(
        g=g,
        rel=jnp.asarray(rel_canon, jnp.int32),
        mean_norm=jnp.asarray(mean_norm),
        perm_rel=jnp.asarray(perm_rel),
        rev_perm=jnp.asarray(rev_perm),
        rev_src=jnp.asarray(src_canon[rev_perm], jnp.int32),
        rev_dst=jnp.asarray(dst_canon[rev_perm], jnp.int32),
        rev_rel=jnp.asarray(rel_canon[rev_perm], jnp.int32),
        cache=planner.get_plan_cache(g),
        n_rel=n_rel, rel_sizes=rel_sizes)


def from_rels(rels: Sequence[Tuple[np.ndarray, np.ndarray]], *,
              n_src: int, n_dst: int) -> RelGraph:
    """Build a :class:`RelGraph` from per-relation ``(src, dst)`` pairs.

    Caller edge order is the concatenation order: relation 0's edges
    (in their given order), then relation 1's, … — so per-relation edge
    features concatenate the same way.
    """
    srcs = [np.asarray(s, np.int64) for s, _ in rels]
    dsts = [np.asarray(d, np.int64) for _, d in rels]
    rel = np.concatenate(
        [np.full(len(s), r, np.int64) for r, s in enumerate(srcs)]
        or [np.zeros(0, np.int64)])
    src = np.concatenate(srcs or [np.zeros(0, np.int64)])
    dst = np.concatenate(dsts or [np.zeros(0, np.int64)])
    return from_typed(src, dst, rel, n_src=n_src, n_dst=n_dst,
                      n_rel=len(rels))


def caller_coo(g: Graph) -> Tuple[np.ndarray, np.ndarray]:
    """Host-side (src, dst) of a concrete graph in CALLER edge order."""
    eid_inv = np.asarray(g.eid_inv)
    return np.asarray(g.src)[eid_inv], np.asarray(g.dst)[eid_inv]


# --------------------------------------------------------------------- #
# message computation (relation-indexed)
# --------------------------------------------------------------------- #
# Per-edge W indexing materializes an (E, d_in, d_out) operand stream;
# beyond this many elements the relation-batched pre-transform
# (H = u @ W for all relations, then one (rel, src) gather) is used
# instead — same math, R·n·d_out memory.
_EDGE_MODE_ELEMS = 2_000_000


def _scale(rg: RelGraph, e, reduce: str) -> Optional[jnp.ndarray]:
    """Combined per-edge scalar weight in canonical order (or None)."""
    s = None
    if e is not None:
        ec = jnp.take(e[:, 0] if e.ndim == 2 else e, rg.g.eid, axis=0)
        s = ec
    if reduce == "mean":
        s = rg.mean_norm if s is None else s * rg.mean_norm
    return s


def _messages(rg: RelGraph, u, w, basis, coeff, s) -> jnp.ndarray:
    """Per-edge relation-indexed messages, canonical order.

    ``u`` 2-D + ``w``: messages are ``u[src] @ w[rel]`` — computed by
    per-edge W indexing when the (E, d_in, d_out) stream is small, and
    by the relation-batched pre-transform + one fused (rel, src) gather
    otherwise. ``u`` 2-D + ``basis``/``coeff``: W stays FACTORED — one
    dense basis transform of all nodes (n·B·d·o flops, below the
    loop's E·d·o once B < avg relation degree), then the
    relation-indexed einsum against ``coeff[rel]`` per edge. ``u`` 3-D
    (n_src, n_rel, d): the caller pre-transformed per relation
    (MoNet's per-kernel features); the gather indexes ``(src, rel)``
    directly.
    """
    g = rg.g
    if u.ndim == 3:
        if w is not None or basis is not None:
            raise ValueError("3-D u is already per-relation; w/basis "
                             "must be None")
        flat = u.reshape(u.shape[0] * rg.n_rel, u.shape[2])
        msg = jnp.take(flat, g.src * rg.n_rel + rg.rel, axis=0)
    elif basis is not None:
        # basis decomposition as a relation-indexed einsum INSIDE the
        # fused op: hb = u @ basis once for all nodes, coeff[rel]
        # contracts the basis axis per edge
        hb = jnp.einsum("nd,bdo->nbo", u, basis)
        msg = jnp.einsum("ebo,eb->eo", jnp.take(hb, g.src, axis=0),
                         jnp.take(coeff, rg.rel, axis=0))
    elif w is None:
        msg = jnp.take(u, g.src, axis=0)
    else:
        d_in, d_out = u.shape[1], w.shape[2]
        if g.n_edges * d_in * d_out <= _EDGE_MODE_ELEMS:
            # the literal fused form: gather h at fused-src, index W by
            # edge relation id, one einsum
            msg = jnp.einsum("ed,edo->eo", jnp.take(u, g.src, axis=0),
                             jnp.take(w, rg.rel, axis=0))
        else:
            # relation-batched pre-transform: R dense matmuls (BLAS),
            # then ONE relation-indexed gather — the sparse side stays
            # a single fused stream
            H = jnp.einsum("nd,rdo->rno", u, w)
            flat = H.reshape(rg.n_rel * u.shape[0], d_out)
            msg = jnp.take(flat, rg.rel * u.shape[0] + g.src, axis=0)
    if s is not None:
        msg = msg * s[:, None]
    return msg


def _reduce_fused(rg: RelGraph, msg, reduce: str,
                  strategy: str) -> jnp.ndarray:
    """One reduction over the fused (dst-sorted) edge stream."""
    g = rg.g
    base = "sum" if reduce in ("sum", "mean") else reduce
    if strategy == "ell":
        spec = parse_op(f"e_copy_{'add' if base == 'sum' else base}_v")
        if base == "sum":
            classes = _skew_classes(rg)
            if classes is not None:
                # size-skew-aware per-relation-class pull: each size
                # class reduces over its OWN sub-graph's ELL pack, so
                # one giant relation's degrees no longer set the pad
                # width for everyone; the class partials sum exactly
                out = None
                for cg, slots in classes:
                    pack = planner.get_plan_cache(cg).peek("ell")
                    plan = planner.Plan(strategy="ell", requested="ell",
                                        reason="hetero-skew", ell=pack)
                    if pack is None:    # never happens: built eagerly
                        plan = planner.Plan(strategy="segment",
                                            requested="ell",
                                            reason="hetero-skew")
                    part = _execute(cg, spec,
                                    jnp.take(msg, slots, axis=0), None,
                                    plan)
                    out = part if out is None else out + part
                return out
        elif base in ("max", "min"):
            classes = _skew_classes(rg)
            if classes is not None:
                # extrema version of the skew-class pull: per-class RAW
                # reductions (±inf kept on per-class-empty rows so a
                # zero fill can't clobber another class's negative
                # extremum), combined with the extremum, finalized once
                comb = jnp.maximum if base == "max" else jnp.minimum
                seg = (jax.ops.segment_max if base == "max"
                       else jax.ops.segment_min)
                out = None
                for cg, slots in classes:
                    sub = jnp.take(msg, slots, axis=0)  # class caller
                    pack = planner.get_plan_cache(cg).peek("ell")
                    if pack is not None:
                        part = S.pull_ell_reduce(
                            pack,
                            lambda cls, sub=sub: jnp.take(
                                sub, cls.chunk_eids, axis=0),
                            base, raw=True)
                    else:               # in-trace, pack never built
                        part = seg(jnp.take(sub, cg.eid, axis=0),
                                   cg.dst, num_segments=cg.n_dst,
                                   indices_are_sorted=True)
                    out = part if out is None else comb(out, part)
                out = jnp.where(jnp.isfinite(out), out,
                                jnp.zeros((), out.dtype))
                return S.finalize_empty_rows(out, g.in_degrees, base)
        # peek only: hetero_gspmm guarantees the pack was built (on an
        # eager call) before routing here — building now could run
        # inside a trace and leak
        plan = planner.Plan(strategy="ell", requested="ell",
                            reason="hetero", ell=rg.cache.peek("ell"))
        if plan.ell is None:        # in-trace, pack never built
            plan = planner.Plan(strategy="segment", requested="ell",
                                reason="hetero-ell-unavailable")
        # _execute's e-target gather indexes caller order
        return _execute(g, spec, jnp.take(msg, g.eid_inv, axis=0), None,
                        plan)
    return S.pull_segment(msg, g.dst, g.n_dst, base, deg=g.in_degrees)


# --------------------------------------------------------------------- #
# size-skew-aware relation classes (ell-per-relation-class)
# --------------------------------------------------------------------- #
# One uniform ELL pack over the fused graph pads every destination row
# to the GLOBAL max degree — and relation sizes in real heterographs are
# wildly skewed (BGS: one relation holds half the edges), so the giant
# relation's hubs set the pad width paid by every tiny relation's rows.
# When the skew is material we bucket relations into log2 size classes,
# split the fused edge set per class, and give each class its own
# sub-graph + ELL pack: same Σ math (the class partials sum), narrow
# pads per class. Class structures build eagerly (host-side) and are
# memoized per fused graph; inside a trace a never-built entry simply
# falls back to the global pack.
_SKEW_RATIO = 8.0       # max relation size / median — below this, skip
_SKEW_MIN_RELS = 3      # fewer relations: bucketing can't help
_MISSING = object()
_SKEW_CLASSES: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()


def _build_skew_classes(rg: RelGraph):
    """Host-side build: relations bucketed by ⌊log2(edge count)⌋.

    Returns ``((class_graph, canonical_slots), ...)`` — slots index the
    fused graph's CANONICAL edge order and double as the class graph's
    caller edge order — or None when the size distribution doesn't
    warrant splitting (skew below ratio, too few relations, or all
    relations land in one size class)."""
    sizes = np.asarray(rg.rel_sizes, np.int64)
    nz = sizes[sizes > 0]
    if nz.size < _SKEW_MIN_RELS:
        return None
    med = max(float(np.median(nz)), 1.0)
    if float(nz.max()) / med < _SKEW_RATIO:
        return None
    band = np.where(sizes > 0,
                    np.floor(np.log2(np.maximum(sizes, 1))), -1.0)
    band = band.astype(np.int64)
    distinct = sorted({int(b) for b in band if b >= 0})
    if len(distinct) < 2:
        return None
    g = rg.g
    src = np.asarray(g.src)
    dst = np.asarray(g.dst)
    perm = np.asarray(rg.perm_rel)
    ptr = rg.rel_ptr
    classes = []
    for b in distinct:
        slots = np.concatenate([perm[ptr[r]:ptr[r + 1]]
                                for r in range(rg.n_rel)
                                if band[r] == b])
        cg = from_coo(src[slots], dst[slots],
                      n_src=g.n_src, n_dst=g.n_dst)
        planner.get_plan_cache(cg).ell()    # the class's own pad width
        classes.append((cg, jnp.asarray(slots, jnp.int32)))
    return tuple(classes)


def _skew_classes(rg: RelGraph):
    """Memoized class structures for ``rg.g`` (None = use global pack).

    Builds only when no trace is active — in-trace the memo is read-only
    and a miss means the caller stays on the fused graph's single pack."""
    got = _SKEW_CLASSES.get(rg.g, _MISSING)
    if got is not _MISSING:
        return got
    if not trace_state_clean() or planner.graph_is_traced(rg.g):
        return None                 # don't build (or memoize) in-trace
    classes = _build_skew_classes(rg)
    _SKEW_CLASSES[rg.g] = classes   # memoize None too: not-skewed is final
    return classes


def _exec_hetero(rg: RelGraph, u, w, basis, coeff, s, reduce: str,
                 strategy: str) -> jnp.ndarray:
    if strategy == "loop" or strategy == "push":
        if basis is not None:       # the pre-refactor form materializes W
            w = jnp.einsum("rb,bdo->rdo", coeff, basis)
        return _exec_loop(rg, u, w, s, reduce,
                          inner="push" if strategy == "push"
                          else "segment")
    return _reduce_fused(rg, _messages(rg, u, w, basis, coeff, s),
                         reduce, strategy)


def _exec_loop(rg: RelGraph, u, w, s, reduce: str,
               inner: str = "segment") -> jnp.ndarray:
    """The pre-refactor baseline: one aggregation call per relation.

    R sequential gathers + reduces over the relation-sorted slices —
    the per-type launch overhead the fused path exists to remove. Kept
    (a) as the planner's small-R candidate and (b) as the measured
    baseline in ``benchmarks/fig_hetero.py``; ``inner='push'`` swaps
    the per-relation reduce for the scatter baseline (fig2's 'push').
    """
    g = rg.g
    base = "sum" if reduce in ("sum", "mean") else reduce
    ptr = rg.rel_ptr
    out = None
    for r in range(rg.n_rel):
        lo, hi = ptr[r], ptr[r + 1]
        if hi == lo:
            continue            # empty relation: no call at all
        slots = jax.lax.slice_in_dim(rg.perm_rel, lo, hi)
        src_r = jnp.take(g.src, slots)
        dst_r = jnp.take(g.dst, slots)
        if u.ndim == 3:
            msg = jnp.take(u[:, r, :], src_r, axis=0)
        else:
            msg = jnp.take(u, src_r, axis=0)
            if w is not None:
                msg = msg @ w[r]
        if s is not None:
            msg = msg * jnp.take(s, slots)[:, None]
        if inner == "push":
            # identity fill preserved (no deg): cross-relation combine
            # below stays correct for negative extrema
            part = S.push_scatter(msg, dst_r, g.n_dst, base)
        elif base == "sum":
            part = jax.ops.segment_sum(msg, dst_r, num_segments=g.n_dst,
                                       indices_are_sorted=True)
        else:
            # raw segment extrema keep ±inf on per-relation-empty rows —
            # pull_segment's zero fill would clobber another relation's
            # negative extremum in the combine
            seg = (jax.ops.segment_max if base == "max"
                   else jax.ops.segment_min)
            part = seg(msg, dst_r, num_segments=g.n_dst,
                       indices_are_sorted=True)
        if out is None:
            out = part
        elif base == "sum":
            out = out + part
        elif base == "max":
            out = jnp.maximum(out, part)
        elif base == "min":
            out = jnp.minimum(out, part)
        else:
            raise ValueError(f"unsupported hetero reducer {reduce!r}")
    d_out = (u.shape[-1] if w is None else w.shape[-1])
    if out is None:
        return jnp.zeros((g.n_dst, d_out), u.dtype)
    if base in ("max", "min"):
        out = jnp.where(jnp.isfinite(out), out, jnp.zeros((), out.dtype))
        out = S.finalize_empty_rows(out, g.in_degrees, base)
    return out


# --------------------------------------------------------------------- #
# the gather backward (custom VJP — DESIGN.md §8.4)
# --------------------------------------------------------------------- #
def _hetero_grads(rg: RelGraph, u, w, basis, coeff, s, ct):
    """Gather-based adjoints of the fused relational CR.

    Every cotangent derives from ONE sorted segment reduce over the
    (src, rel)-sorted reverse table: C[s, r] = Σ_{e∈E_r: src=s} s_e ·
    ct[dst_e]. Then ∂u = Σ_r C[·,r] Wᵣᵀ and ∂Wᵣ = uᵀ C[·,r] — or, with
    the basis kept factored, the same contractions against Cb =
    C·coeff — are dense einsums: no scatter anywhere, mirroring the
    reverse-block VJP.
    """
    g = rg.g
    ct_rev = jnp.take(ct, rg.rev_dst, axis=0)
    if s is not None:
        ct_rev = ct_rev * jnp.take(s, rg.rev_perm)[:, None]
    if u.ndim == 3:
        key = rg.rev_src * rg.n_rel + rg.rev_rel
        C = jax.ops.segment_sum(ct_rev, key,
                                num_segments=g.n_src * rg.n_rel,
                                indices_are_sorted=True)
        du = C.reshape(u.shape).astype(u.dtype)
        return du, None, None, None
    if w is None and basis is None:
        du = jax.ops.segment_sum(ct_rev, rg.rev_src,
                                 num_segments=g.n_src,
                                 indices_are_sorted=True)
        return du.astype(u.dtype), None, None, None
    key = rg.rev_src * rg.n_rel + rg.rev_rel
    C = jax.ops.segment_sum(ct_rev, key,
                            num_segments=g.n_src * rg.n_rel,
                            indices_are_sorted=True)
    C = C.reshape(g.n_src, rg.n_rel, ct.shape[-1])
    if basis is not None:
        Cb = jnp.einsum("nro,rb->nbo", C, coeff)
        du = jnp.einsum("nbo,bdo->nd", Cb, basis).astype(u.dtype)
        dbasis = jnp.einsum("nbo,nd->bdo", Cb, u).astype(basis.dtype)
        hb = jnp.einsum("nd,bdo->nbo", u, basis)
        dcoeff = jnp.einsum("nro,nbo->rb", C, hb).astype(coeff.dtype)
        return du, None, dbasis, dcoeff
    du = jnp.einsum("nro,rdo->nd", C, w).astype(u.dtype)
    dw = jnp.einsum("nro,nd->rdo", C, u).astype(w.dtype)
    return du, dw, None, None


def _hetero_de(rg: RelGraph, u, w, basis, coeff, norm, ct):
    """∂(e-operand): per-edge <unscaled message, ct[dst]>, caller order."""
    g = rg.g
    base = _messages(rg, u, w, basis, coeff, norm)  # mean folded, e NOT
    ds = jnp.sum(base * jnp.take(ct, g.dst, axis=0), axis=-1)
    return jnp.take(ds, g.eid_inv, axis=0)


@partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _hetero_fused_rev(reduce: str, strategy: str, rg: RelGraph,
                      u, w, basis, coeff, e):
    s = _scale(rg, e, reduce)
    return _exec_hetero(rg, u, w, basis, coeff, s, reduce, strategy)


def _hetero_fused_rev_fwd(reduce, strategy, rg, u, w, basis, coeff, e):
    out = _hetero_fused_rev(reduce, strategy, rg, u, w, basis, coeff, e)
    return out, (rg, u, w, basis, coeff, e)


def _hetero_fused_rev_bwd(reduce, strategy, res, ct):
    rg, u, w, basis, coeff, e = res
    s = _scale(rg, e, reduce)
    du, dw, dbasis, dcoeff = _hetero_grads(rg, u, w, basis, coeff, s, ct)
    de = None
    if e is not None:
        norm = rg.mean_norm if reduce == "mean" else None
        de = _hetero_de(rg, u, w, basis, coeff, norm, ct).astype(e.dtype)
        if e.ndim == 2:
            de = de[:, None]
    return None, du, dw, dbasis, dcoeff, de


_hetero_fused_rev.defvjp(_hetero_fused_rev_fwd, _hetero_fused_rev_bwd)


# --------------------------------------------------------------------- #
# main entry
# --------------------------------------------------------------------- #
def hetero_gspmm(rg: RelGraph, u: jnp.ndarray, *,
                 w: Optional[jnp.ndarray] = None,
                 basis: Optional[jnp.ndarray] = None,
                 coeff: Optional[jnp.ndarray] = None,
                 e: Optional[jnp.ndarray] = None,
                 reduce: str = "sum",
                 strategy: str = "auto") -> jnp.ndarray:
    """Fused heterogeneous aggregation: ``out[v] = ⊕_r Σ_{E_r} msg``.

    Operands:
      * ``u``: (n_src, d) node features, or (n_src, n_rel, d) when the
        caller already holds per-relation features (MoNet's kernels);
      * ``w``: (n_rel, d_in, d_out) per-relation projection — messages
        become ``u[src] @ w[rel]`` (relation-indexed inside the op);
      * ``basis``/``coeff``: RGCN basis decomposition, kept FACTORED
        inside the op — one dense basis transform of all nodes, then a
        relation-indexed ``coeff[rel]`` einsum per edge (cheaper than
        materializing any W once B < the average relation degree); the
        custom VJP emits ∂basis/∂coeff directly;
      * ``e``: (n_edges,) or (n_edges, 1) per-edge scalar weight in
        caller order (MoNet's kernel weights, GCN-style norms).

    ``reduce``: 'sum' | 'mean' (per-RELATION mean, RGCN's 1/c_{v,r}) |
    'max' | 'min' (extrema over the fused edge set). Linear reducers
    run under the gather custom VJP; max/min stay on autodiff.

    ``strategy``: 'auto' (planner, logged ``hetero:<op>``), 'fused',
    'loop' (per-relation baseline), 'ell' (fused messages + the fused
    graph's blocked pull; under material relation-size skew the
    sum/max/min forms split into per-size-class packs), or any plain
    gspmm strategy name — which
    pins the per-relation loop with that inner reduce ('push' is the
    fig2 baseline; the rest run the loop's segment form).
    """
    if reduce not in ("sum", "mean", "max", "min"):
        raise ValueError(f"unknown hetero reducer {reduce!r}")
    if basis is not None or coeff is not None:
        if basis is None or coeff is None:
            raise ValueError("basis and coeff must be given together")
        if w is not None:
            raise ValueError("pass either w or basis/coeff, not both")
    if u.ndim == 3 and u.shape[1] != rg.n_rel:
        raise ValueError(f"3-D u must be (n_src, n_rel={rg.n_rel}, d), "
                         f"got {u.shape}")

    projected = w is not None or basis is not None
    op_name = "u{}{}_{}_v".format("_w" if projected else "",
                                  "_e" if e is not None else "", reduce)
    d_out = int(w.shape[-1] if w is not None
                else basis.shape[-1] if basis is not None
                else u.shape[-1])

    # packs may only be BUILT on fully-eager calls: a concrete graph
    # closed over by a jitted function would otherwise build its pack
    # inside the trace and leak trace-bound constants into the cache
    # "eager" must mean NO trace is active at all — not merely concrete
    # operands: a jitted function that closes over everything still
    # traces, and np→jnp conversions inside it (a pack build, autotune
    # measurement) would leak trace-bound values into the cache
    eager = (trace_state_clean()
             and not any(planner._is_traced(x)
                         for x in (rg.g.src, u, w, basis, coeff, e)
                         if x is not None))
    runner = None
    if planner.get_mode() == "autotune" and strategy == "auto" and eager:
        def runner(st):
            if st == "ell":
                rg.cache.ell()
            return _exec_hetero(rg, u, w, basis, coeff,
                                _scale(rg, e, reduce), reduce, st)

    ell_ok = rg.cache.peek("ell") is not None or eager
    chosen = planner.plan_hetero(rg.signature, op_name, d_out,
                                 requested=strategy,
                                 stats=rg.cache.stats, ell_ok=ell_ok,
                                 runner=runner)
    if chosen == "ell":
        pack = rg.cache.ell() if eager else rg.cache.peek("ell")
        if pack is None:
            chosen = "fused"    # in-trace without a prebuilt pack
    if reduce in ("sum", "mean") and chosen in ("fused", "ell"):
        return _hetero_fused_rev(reduce, chosen, rg, u, w, basis, coeff, e)
    return _exec_hetero(rg, u, w, basis, coeff, _scale(rg, e, reduce),
                        reduce, chosen)


# --------------------------------------------------------------------- #
# relational blocks (sampled RGCN — DESIGN.md §8.5)
# --------------------------------------------------------------------- #
def hetero_block_gspmm(bg, rel: jnp.ndarray, u: jnp.ndarray,
                       w: jnp.ndarray, *,
                       norm: Optional[jnp.ndarray] = None,
                       strategy: str = "auto",
                       bwd_strategy: str = "auto") -> jnp.ndarray:
    """Fused relational aggregation over one sampled block.

    ``bg`` is a reverse-table-carrying
    :class:`~repro.core.blocks.BlockGraph`; ``rel`` (n_edges_pad,) the
    relation id per edge and ``norm`` the per-(dst, relation) mean
    weight, both in caller edge order (the relational sampler emits
    them; pad edges carry norm 0 and point at the dummy destination
    row, so they vanish either way). Messages are ``u[src] @ w[rel]``
    — per-edge W indexing; blocks are small by construction — and the
    reduce stage rides the shape-keyed block planner
    (:func:`~repro.core.planner.plan_block_gspmm`, as an ``e``-operand
    sum). ``bwd_strategy='gather'`` (or 'auto' on large blocks) pulls
    ∂u over the block's reverse table exactly like
    :func:`~repro.core.blocks.block_gspmm`'s custom VJP.
    """
    from .blocks import _block_execute      # local: blocks↔hetero

    spec = parse_op("e_copy_add_v")
    d_out = int(w.shape[-1])
    chosen = planner.plan_block_gspmm(bg.signature, spec, d_out,
                                      requested=strategy,
                                      dtype=str(u.dtype))
    bwd = planner.plan_block_vjp(bg.signature, spec, d_out,
                                 requested=bwd_strategy,
                                 gather_available=bg.has_reverse,
                                 dtype=str(u.dtype))
    if bwd == "gather":
        return _hetero_block_rev(chosen, bg, rel, u, w, norm)
    msg = _block_messages(bg, rel, u, w, norm)
    return _block_execute(bg, spec, msg, None, chosen)


def _block_messages(bg, rel, u, w, norm) -> jnp.ndarray:
    """Per-edge relation-projected messages in CALLER edge order."""
    g = bg.g
    src_caller = jnp.take(g.src, g.eid_inv)
    msg = jnp.einsum("ed,edo->eo", jnp.take(u, src_caller, axis=0),
                     jnp.take(w, rel, axis=0))
    if norm is not None:
        msg = msg * norm[:, None]
    return msg


@partial(jax.custom_vjp, nondiff_argnums=(0,))
def _hetero_block_rev(fwd_strategy: str, bg, rel, u, w, norm):
    from .blocks import _block_execute

    msg = _block_messages(bg, rel, u, w, norm)
    return _block_execute(bg, parse_op("e_copy_add_v"), msg, None,
                          fwd_strategy)


def _hetero_block_rev_fwd(fwd_strategy, bg, rel, u, w, norm):
    out = _hetero_block_rev(fwd_strategy, bg, rel, u, w, norm)
    return out, (bg, rel, u, w, norm)


def _hetero_block_rev_bwd(fwd_strategy, res, ct):
    bg, rel, u, w, norm = res
    g = bg.g
    # zero dummy-destination row: pad edges pull exactly zero
    ct_pad = jnp.concatenate(
        [ct, jnp.zeros((1,) + ct.shape[1:], ct.dtype)], axis=0)
    rel_rev = jnp.take(rel, bg.rev_eid)
    ct_rev = jnp.take(ct_pad, bg.rev_dst, axis=0)
    if norm is not None:
        ct_rev = ct_rev * jnp.take(norm, bg.rev_eid)[:, None]
    # ∂u: pull over the src-sorted reverse table — no scatter
    du = jax.ops.segment_sum(
        jnp.einsum("eo,edo->ed", ct_rev, jnp.take(w, rel_rev, axis=0)),
        bg.rev_src, num_segments=g.n_src,
        indices_are_sorted=True).astype(u.dtype)
    # ∂w: per-relation outer products (R segments; blocks are small)
    src_caller = jnp.take(g.src, g.eid_inv)
    dst_caller = jnp.take(g.dst, g.eid_inv)
    ct_e = jnp.take(ct_pad, dst_caller, axis=0)
    if norm is not None:
        ct_e = ct_e * norm[:, None]
    outer = jnp.einsum("ed,eo->edo", jnp.take(u, src_caller, axis=0),
                       ct_e)
    dw = jax.ops.segment_sum(outer, rel,
                             num_segments=w.shape[0]).astype(w.dtype)
    return None, None, du, dw, None


_hetero_block_rev.defvjp(_hetero_block_rev_fwd, _hetero_block_rev_bwd)
