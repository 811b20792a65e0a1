"""Graph containers for aggregation primitives.

The paper's Alg. 3 sorts source indices inside each K-block with a radix
sort so that DRAM accesses stream in ascending order. On TPU (and in a
functional framework) the idiomatic place for that work is a one-time
format conversion: `Graph` canonically sorts the edge list by
``(dst, src)`` at construction, exposing

  * COO views ``(src, dst, eid)`` sorted by destination (pull order),
  * CSR-by-destination ``indptr_dst`` (pull model, paper Alg. 2/3),
  * CSC-by-source ``indptr_src`` + permutation (push model, paper Alg. 1),

``eid`` maps a sorted edge slot back to the caller's original edge-feature
row so edge features never need reordering on the user side.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["Graph", "from_coo", "from_dst_sorted", "reverse",
           "add_self_loops"]


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True, eq=False)  # identity hash; jnp fields
class Graph:
    """Directed graph with dual CSR/CSC index structure.

    All index arrays are ``int32`` device arrays; sizes are static Python
    ints so the structure can cross ``jit`` boundaries as a pytree.
    """

    # --- COO, sorted by (dst, src): the canonical pull order -------------
    src: jnp.ndarray  # (nnz,) source node id per edge
    dst: jnp.ndarray  # (nnz,) destination node id per edge (non-decreasing)
    eid: jnp.ndarray  # (nnz,) original edge id for edge-feature lookup

    # --- CSR by destination (pull) ---------------------------------------
    indptr_dst: jnp.ndarray  # (n_dst + 1,)

    # --- CSC by source (push) --------------------------------------------
    indptr_src: jnp.ndarray  # (n_src + 1,)
    perm_src: jnp.ndarray    # (nnz,) permutation: sorted-by-src -> canonical slot

    # --- edge-id inverse: original edge id -> canonical slot ---------------
    eid_inv: jnp.ndarray     # (nnz,)

    # --- static metadata ---------------------------------------------------
    n_src: int = dataclasses.field(metadata={"static": True})
    n_dst: int = dataclasses.field(metadata={"static": True})
    n_edges: int = dataclasses.field(metadata={"static": True})

    # ------------------------------------------------------------------ #
    # pytree protocol
    # ------------------------------------------------------------------ #
    def tree_flatten(self):
        children = (self.src, self.dst, self.eid, self.indptr_dst,
                    self.indptr_src, self.perm_src, self.eid_inv)
        aux = (self.n_src, self.n_dst, self.n_edges)
        return children, aux

    @classmethod
    def tree_unflatten(cls, aux, children):
        src, dst, eid, indptr_dst, indptr_src, perm_src, eid_inv = children
        n_src, n_dst, n_edges = aux
        return cls(src=src, dst=dst, eid=eid, indptr_dst=indptr_dst,
                   indptr_src=indptr_src, perm_src=perm_src, eid_inv=eid_inv,
                   n_src=n_src, n_dst=n_dst, n_edges=n_edges)

    # ------------------------------------------------------------------ #
    # convenience
    # ------------------------------------------------------------------ #
    @property
    def in_degrees(self) -> jnp.ndarray:
        """(n_dst,) number of incoming edges per destination node."""
        return self.indptr_dst[1:] - self.indptr_dst[:-1]

    @property
    def out_degrees(self) -> jnp.ndarray:
        """(n_src,) number of outgoing edges per source node."""
        return self.indptr_src[1:] - self.indptr_src[:-1]

    def numpy_coo(self) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        return (np.asarray(self.src), np.asarray(self.dst),
                np.asarray(self.eid))

    def __repr__(self):  # keep reprs short in test logs
        return (f"Graph(n_src={self.n_src}, n_dst={self.n_dst}, "
                f"n_edges={self.n_edges})")


def _indptr(ids: np.ndarray, n: int) -> np.ndarray:
    """CSR pointers (int32, length ``n + 1``) of the ids ``0 <= ids < n``:
    a count per id and its running sum. The sum runs only up to the
    largest id; every pointer past it is the total."""
    counts = np.bincount(ids)
    indptr = np.empty(n + 1, dtype=np.int32)
    indptr[0] = 0
    indptr[1:len(counts) + 1] = np.cumsum(counts)
    indptr[len(counts) + 1:] = len(ids)
    return indptr


def from_coo(src, dst, *, n_src: Optional[int] = None,
             n_dst: Optional[int] = None) -> Graph:
    """Build a :class:`Graph` from COO edge arrays (host-side, numpy).

    Edge ids are assigned in the caller's order: edge features passed to the
    aggregation primitives are always indexed in the order of ``src``/``dst``
    given here.
    """
    src = np.asarray(src, dtype=np.int64)
    dst = np.asarray(dst, dtype=np.int64)
    if src.shape != dst.shape or src.ndim != 1:
        raise ValueError(f"src/dst must be equal-length 1-D, got "
                         f"{src.shape} vs {dst.shape}")
    nnz = src.shape[0]
    n_src = int(n_src if n_src is not None else (src.max() + 1 if nnz else 0))
    n_dst = int(n_dst if n_dst is not None else (dst.max() + 1 if nnz else 0))
    if nnz and (src.min() < 0 or src.max() >= n_src):
        raise ValueError("src ids out of range")
    if nnz and (dst.min() < 0 or dst.max() >= n_dst):
        raise ValueError("dst ids out of range")

    # canonical sort by (dst, src) — the paper's radix sort, done once.
    order = np.lexsort((src, dst))
    s_src, s_dst = src[order], dst[order]
    eid = order.astype(np.int32)  # canonical slot -> original edge id

    indptr_dst = _indptr(s_dst, n_dst)

    # push-side (CSC by src): permutation from sorted-by-(src,dst) to slot
    order_src = np.lexsort((s_dst, s_src))
    indptr_src = _indptr(s_src, n_src)

    eid_inv = np.empty_like(eid)
    eid_inv[eid] = np.arange(nnz, dtype=np.int32)

    return Graph(
        src=jnp.asarray(s_src, dtype=jnp.int32),
        dst=jnp.asarray(s_dst, dtype=jnp.int32),
        eid=jnp.asarray(eid, dtype=jnp.int32),
        indptr_dst=jnp.asarray(indptr_dst),
        indptr_src=jnp.asarray(indptr_src),
        perm_src=jnp.asarray(order_src.astype(np.int32)),
        eid_inv=jnp.asarray(eid_inv),
        n_src=n_src, n_dst=n_dst, n_edges=int(nnz),
    )


def from_dst_sorted(src, dst, *, n_src: int, n_dst: int, n_edges: int
                    ) -> Tuple[Graph, Tuple[np.ndarray, np.ndarray,
                                            np.ndarray]]:
    """Build a padded :class:`Graph` and its reverse table from edges
    already grouped by destination (host-side, numpy).

    ``src``/``dst`` are the real edges in caller order with ``dst``
    non-decreasing, as a neighbor sampler's row-major draw emits them.
    Caller slots ``len(src)`` to ``n_edges - 1`` are pad edges from the
    last source slot ``n_src - 1`` into the last destination row
    ``n_dst - 1``. When there are pads no real edge may touch either, so
    the pads hold the largest ``(dst, src)`` key and the largest source
    slot: they sit last in every order, and only the real edges are
    sorted.

    Returns ``(g, (rev_src, rev_dst, rev_eid))``. ``g`` equals
    ``from_coo`` over the padded edge list with the same ``n_src`` and
    ``n_dst``, array for array, dtypes and static sizes included. The
    reverse table is that edge list stably sorted by source slot (int32
    host arrays, ``rev_eid`` in caller order), as
    ``blocks.build_reverse_table(g)`` gives it.
    """
    src = np.asarray(src, dtype=np.int64)
    dst = np.asarray(dst, dtype=np.int64)
    n_real = src.shape[0]
    pad = n_edges - n_real
    if src.shape != dst.shape or src.ndim != 1 or pad < 0:
        raise ValueError(f"src/dst must be equal-length 1-D with at most "
                         f"n_edges={n_edges} edges, got {src.shape} vs "
                         f"{dst.shape}")
    if pad and (n_src < 1 or n_dst < 1):
        raise ValueError("pad edges need a source slot and a destination "
                         "row")
    # real ids stay below the pads' when there are pads
    if n_real and (src.min() < 0 or src.max() >= n_src - (pad > 0)):
        raise ValueError("src ids out of range")
    if n_real and (dst.min() < 0 or dst.max() >= n_dst - (pad > 0)):
        raise ValueError("dst ids out of range")
    if (np.diff(dst) < 0).any():
        raise ValueError("dst must be non-decreasing")

    # canonical (dst, src) order of the real edges; stable, so duplicate
    # edges keep caller order as from_coo's lexsort does. dst is already
    # sorted, so it is its own canonical view.
    order = np.argsort(dst * n_src + src, kind="stable")
    s_src = src[order]
    # push order: the canonical slots stably by source, which is
    # lexsort((s_dst, s_src)) because the canonical order is dst-sorted.
    # Slot-tagged keys are unique, so any sort is stable; int32 ids keep
    # n_src * n_real under 2**62.
    order_src = np.argsort(s_src * n_real + np.arange(n_real))
    pads = np.arange(n_real, n_edges, dtype=np.int32)

    def padded(real, fill):
        out = np.empty(n_edges, dtype=np.int32)
        out[:n_real] = real
        out[n_real:] = fill
        return out

    eid = padded(order, pads)
    eid_inv = padded(0, pads)
    eid_inv[order] = np.arange(n_real, dtype=np.int32)
    indptr_dst = _indptr(dst, n_dst)
    indptr_dst[-1] += pad
    indptr_src = _indptr(s_src, n_src)
    indptr_src[-1] += pad
    # reverse table: caller edges stably by source slot, i.e. the push
    # order mapped through eid (within one source, caller order and
    # canonical order are both dst-major)
    rev = (padded(s_src[order_src], n_src - 1),
           padded(dst[order_src], n_dst - 1),
           padded(order[order_src], pads))
    g = Graph(
        src=jnp.asarray(padded(s_src, n_src - 1)),
        dst=jnp.asarray(padded(dst, n_dst - 1)),
        eid=jnp.asarray(eid),
        indptr_dst=jnp.asarray(indptr_dst),
        indptr_src=jnp.asarray(indptr_src),
        perm_src=jnp.asarray(padded(order_src, pads)),
        eid_inv=jnp.asarray(eid_inv),
        n_src=int(n_src), n_dst=int(n_dst), n_edges=int(n_edges),
    )
    return g, rev


def reverse(g: Graph) -> Graph:
    """Reverse every edge (used by backward passes: grad of pull = push)."""
    src, dst, eid = g.numpy_coo()
    # keep the same original edge ids so edge features still line up
    rg = from_coo(dst, src, n_src=g.n_dst, n_dst=g.n_src)
    # from_coo assigned fresh eids by position; remap through g.eid
    remapped = np.asarray(g.eid)[np.asarray(rg.eid)]
    inv = np.empty_like(remapped)
    inv[remapped] = np.arange(len(remapped), dtype=remapped.dtype)
    return dataclasses.replace(rg, eid=jnp.asarray(remapped, jnp.int32),
                               eid_inv=jnp.asarray(inv, jnp.int32))


def add_self_loops(src, dst, n: int):
    """Append one self-loop per node to host COO arrays (GCN-style)."""
    src = np.concatenate([np.asarray(src, np.int64), np.arange(n)])
    dst = np.concatenate([np.asarray(dst, np.int64), np.arange(n)])
    return src, dst
