"""Partitioned-graph execution: the paper's K-blocking lifted to shards.

The paper's Alg. 2 argument — owner-computes pull aggregation over
bounded K-block working sets — reappears one level up when a graph is
vertex-partitioned across devices (DistGNN, Vasimuddin et al., 2021,
makes exactly this lift for the same Intel DGL kernels). This module is
that level as a first-class subsystem:

* :class:`PartitionedGraph` — a host-planned vertex partition of a
  :class:`Graph`: each of ``n_shards`` shards owns a padded block of
  ``rows`` destination rows, and every edge lives in exactly one
  ``(dst_shard, src_shard)`` bucket (padded to ``eb`` slots). Buckets
  are the cluster-granularity K-blocks: at ring stage ``s`` a device
  holds one remote source block and consumes exactly one bucket.
  Registered as a pytree so it flows through ``jit`` like
  :class:`~repro.models.gnn.common.GraphBundle`.
* :func:`ring_gspmm` — differentiable sharded weighted Copy-Reduce.
  Forward: source blocks rotate around a ``lax.ppermute`` ring while
  each owner reduces its resident bucket (compute overlaps the next
  transfer). Backward (``custom_vjp``): the *transposed ring* — the
  permute direction reverses and the src/dst bucket roles swap, which
  is the cluster-level form of the PR-2 observation that the adjoint of
  Copy-Reduce is Copy-Reduce on the reverse graph.
* :func:`ring_edge_values` / :func:`bucket_softmax` — per-edge operand
  assembly and destination softmax over the bucketed edge layout; with
  :func:`ring_gspmm` they cover GAT-style attention on shards.
* :func:`ring_gspmm_delayed` — DistGNN-style delayed halo: remote
  partial aggregates are refreshed every k-th step and otherwise reused
  stale (gradients flow through the owner-local part only), trading
  exactness for a ring-free step.

Every ring function takes ``mesh=None`` to run an *emulated*
single-device path: the same bucket math and the same custom-VJP
structure with the device loop unrolled in Python. The emulated path is
the differential-test oracle (it joins the cross-strategy equivalence
harness) and makes the partitioned model forwards runnable anywhere.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from .graph import Graph
from ..compat import pvary, shard_map
from ..obs import metrics as _metrics
from ..optim.compression import compress_payload, wire_bytes

__all__ = ["PartitionStats", "PartitionedGraph", "build_partition",
           "ring_gspmm", "ring_edge_values", "bucket_softmax",
           "local_gspmm", "ring_gspmm_delayed", "ring_reference",
           "require_auto_mesh", "PARTITION_MODES", "COMM_MODES"]

PARTITION_MODES = ("contiguous", "hash", "uniform")
COMM_MODES = ("none", "int8")


def _acc_dtype(dtype):
    """Reduce accumulators never drop below fp32: bf16 features sum in
    fp32 and only the final output is cast back (DESIGN.md §12)."""
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.promote_types(dtype, jnp.float32)
    return dtype


def _count_exchange(pg: "PartitionedGraph", x, comm: str) -> None:
    """Account one full ring exchange in the obs metrics registry.

    A ring pass moves every source block through ``ragged_stages`` hops
    (at most S-1; trailing all-empty bucket diagonals are never
    rotated): S·stages block-sends of ``rows × feat`` elements.
    ``raw_bytes`` is what the uncompressed payload would weigh at
    ``x.dtype``; ``wire_bytes`` is what actually travels under ``comm``
    (int8 + per-block fp32 scales). Both counters bump together, so
    their ratio is the measured compression factor regardless of call
    count. ``pad_slots`` tracks the bucket slots the ragged schedule
    touches beyond the real edges — the residual padding tax.
    """
    if not _metrics.enabled() or pg.n_shards < 2:
        return
    st = pg.stats
    elems = pg.rows * int(np.prod(x.shape[1:], dtype=np.int64))
    raw, wire = wire_bytes(elems, jnp.dtype(x.dtype).itemsize, comm)
    stages = st.ragged_stages if st.ragged_stages >= 0 else pg.n_shards - 1
    hops = pg.n_shards * stages
    _metrics.counter("comm.ring.raw_bytes").inc(hops * raw)
    _metrics.counter("comm.ring.wire_bytes").inc(hops * wire)
    slots = st.ragged_slots if st.ragged_slots > 0 else (
        pg.n_shards * pg.n_shards * pg.eb)
    _metrics.counter("comm.ring.pad_slots").inc(
        max(slots - pg.n_edges, 0))


# --------------------------------------------------------------------- #
# the partition plan
# --------------------------------------------------------------------- #
@dataclasses.dataclass(frozen=True)
class PartitionStats:
    """Static, hashable features of a partition — the planner's view."""
    n_shards: int
    rows_per_shard: int
    eb: int                 # padded edge slots per (dst, src) bucket
    n_edges: int
    cut_fraction: float     # edges whose endpoints live on different shards
    pad_ratio: float        # S*S*eb / n_edges — bucket padding waste
    balance: float          # max / mean edges owned per dst shard
    # ragged bucket accounting (defaults keep hand-built stats valid):
    # slots the per-diagonal-max schedule touches (S · Σ_s w_s, diagonal
    # included), the last non-empty bucket diagonal (= ring transfers
    # per device; -1 means "unknown, assume dense S-1"), and the ragged
    # slots / n_edges waste ratio.
    ragged_slots: int = 0
    ragged_stages: int = -1
    ragged_pad_ratio: float = 1.0


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True, eq=False)
class PartitionedGraph:
    """Host-planned vertex partition + per-(dst,src)-shard edge buckets.

    Vertices are mapped to padded slots ``shard * rows + local``
    (``to_pad`` / ``from_pad``); each edge occupies one slot of bucket
    ``(shard(dst), shard(src))`` with its endpoints stored as *local*
    offsets and its caller-order edge id in ``eid`` (so per-edge
    weights are bucketed with one gather). All bucket arrays are padded
    to the common width ``eb``; pad slots are masked and index 0.
    """
    to_pad: jnp.ndarray      # (n,) vertex id -> padded slot
    from_pad: jnp.ndarray    # (n_pad,) padded slot -> vertex id or -1
    src_local: jnp.ndarray   # (S, S, eb) int32 source offset in its shard
    dst_local: jnp.ndarray   # (S, S, eb) int32 destination offset
    eid: jnp.ndarray         # (S, S, eb) int32 caller-order edge id
    mask: jnp.ndarray        # (S, S, eb) bool

    n_shards: int = dataclasses.field(metadata={"static": True})
    rows: int = dataclasses.field(metadata={"static": True})
    eb: int = dataclasses.field(metadata={"static": True})
    n: int = dataclasses.field(metadata={"static": True})
    n_edges: int = dataclasses.field(metadata={"static": True})
    mode: str = dataclasses.field(metadata={"static": True})
    stats: PartitionStats = dataclasses.field(metadata={"static": True})
    # real (unpadded) slot count of bucket (i, j); the bucket fill is
    # contiguous from slot 0, so a static [:eb_ij[i][j]] slice captures
    # exactly the real edges. Default () means "unknown — dense eb".
    eb_ij: Tuple[Tuple[int, ...], ...] = dataclasses.field(
        default=(), metadata={"static": True})

    # -- pytree protocol -------------------------------------------------
    def tree_flatten(self):
        return ((self.to_pad, self.from_pad, self.src_local,
                 self.dst_local, self.eid, self.mask),
                (self.n_shards, self.rows, self.eb, self.n, self.n_edges,
                 self.mode, self.stats, self.eb_ij))

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, *aux)

    @property
    def n_pad(self) -> int:
        return self.n_shards * self.rows

    def bucket_width(self, i: int, j: int) -> int:
        """Real slot count of bucket (i, j) — ``eb`` when unknown."""
        if not self.eb_ij:
            return self.eb
        return self.eb_ij[i][j]

    # -- layout converters ----------------------------------------------
    def scatter_nodes(self, x: jnp.ndarray) -> jnp.ndarray:
        """(n_rows, *feat) vertex-ordered -> (n_pad, *feat) padded."""
        out = jnp.zeros((self.n_pad,) + x.shape[1:], x.dtype)
        return out.at[self.to_pad[: x.shape[0]]].set(x)

    def gather_nodes(self, xp: jnp.ndarray,
                     n_rows: Optional[int] = None) -> jnp.ndarray:
        """(n_pad, *feat) padded -> (n_rows, *feat) vertex-ordered."""
        n_rows = self.n if n_rows is None else n_rows
        return jnp.take(xp, self.to_pad[:n_rows], axis=0)

    def scatter_edges(self, w: jnp.ndarray) -> jnp.ndarray:
        """(n_edges, ...) caller-order edge values -> bucketed
        (S, S, eb, ...) with zeros on pad slots."""
        vals = jnp.take(w, self.eid, axis=0)
        mask = self.mask.reshape(self.mask.shape
                                 + (1,) * (vals.ndim - self.mask.ndim))
        return jnp.where(mask, vals, jnp.zeros((), vals.dtype))

    def gather_edges(self, wb: jnp.ndarray) -> jnp.ndarray:
        """Bucketed (S, S, eb, ...) -> (n_edges, ...) caller order."""
        flat = wb.reshape((-1,) + wb.shape[3:])
        eid = self.eid.reshape(-1)
        mk = self.mask.reshape(-1)
        out = jnp.zeros((self.n_edges,) + wb.shape[3:], wb.dtype)
        sel = jnp.where(mk, eid, self.n_edges)   # drop pads out of range
        return out.at[sel].set(flat, mode="drop")

    def __repr__(self):
        return (f"PartitionedGraph(S={self.n_shards}, rows={self.rows}, "
                f"eb={self.eb}, n={self.n}, mode={self.mode!r})")


def _shard_assignment(g: Graph, n_shards: int, mode: str
                      ) -> Tuple[np.ndarray, np.ndarray, int]:
    """vertex id -> (shard, local offset); returns (shard, local, rows)."""
    n = max(g.n_src, g.n_dst)
    ids = np.arange(n, dtype=np.int64)
    if mode == "hash":
        shard = ids % n_shards
        local = ids // n_shards
    elif mode == "uniform":
        rows = -(-n // n_shards)
        shard = ids // rows
        local = ids % rows
        return shard, local, rows
    elif mode == "contiguous":
        # degree-balanced contiguous ranges: split the cumulative edge
        # mass (in + out degree) into n_shards nearly-equal chunks
        deg = np.zeros(n, np.int64)
        deg[: g.n_dst] += np.asarray(g.in_degrees, np.int64)
        deg[: g.n_src] += np.asarray(g.out_degrees, np.int64)
        cum = np.cumsum(deg + 1)            # +1 keeps empty rows spread
        targets = cum[-1] * (np.arange(1, n_shards) / n_shards)
        bounds = np.searchsorted(cum, targets, side="left")
        shard = np.searchsorted(bounds, ids, side="right")
        starts = np.concatenate([[0], bounds])
        local = ids - starts[shard]
    else:
        raise ValueError(f"unknown partition mode {mode!r}; expected one "
                         f"of {PARTITION_MODES}")
    rows = int(np.bincount(shard, minlength=n_shards).max()) if n else 1
    return shard, local, max(rows, 1)


def build_partition(g: Graph, n_shards: int,
                    mode: str = "contiguous") -> PartitionedGraph:
    """Host-side partition planning — fully vectorized (no per-edge
    Python loop; the bucket fill is one stable sort + one scatter)."""
    if n_shards < 1:
        raise ValueError("n_shards must be >= 1")
    shard, local, rows = _shard_assignment(g, n_shards, mode)
    n = max(g.n_src, g.n_dst)

    src = np.asarray(g.src, np.int64)
    dst = np.asarray(g.dst, np.int64)
    eid = np.asarray(g.eid, np.int64)       # canonical slot -> caller id
    E = src.shape[0]

    i = shard[dst] if E else np.zeros(0, np.int64)   # dst (owner) shard
    j = shard[src] if E else np.zeros(0, np.int64)   # src shard
    key = i * n_shards + j
    order = np.argsort(key, kind="stable")
    counts = np.bincount(key, minlength=n_shards * n_shards)
    eb = max(1, int(counts.max())) if E else 1
    offs = np.concatenate([[0], np.cumsum(counts)])
    pos = np.arange(E) - offs[key[order]]            # slot within bucket

    SL = np.zeros((n_shards * n_shards, eb), np.int32)
    DL = np.zeros((n_shards * n_shards, eb), np.int32)
    EID = np.zeros((n_shards * n_shards, eb), np.int32)
    MK = np.zeros((n_shards * n_shards, eb), bool)
    SL[key[order], pos] = local[src[order]]
    DL[key[order], pos] = local[dst[order]]
    EID[key[order], pos] = eid[order]
    MK[key[order], pos] = True

    to_pad = (shard * rows + local).astype(np.int32)
    from_pad = np.full(n_shards * rows, -1, np.int32)
    from_pad[to_pad] = np.arange(n, dtype=np.int32)

    owned = np.bincount(i, minlength=n_shards) if E else np.zeros(n_shards)
    cut = int((i != j).sum()) if E else 0
    counts2 = counts.reshape(n_shards, n_shards)
    eb_ij = tuple(tuple(int(c) for c in rowc) for rowc in counts2)
    ws = [max(int(counts2[(jj + s) % n_shards, jj])
              for jj in range(n_shards)) for s in range(n_shards)]
    nz = [s for s in range(n_shards) if ws[s] > 0]
    ragged_slots = int(n_shards * sum(ws))
    ragged_stages = nz[-1] if nz else 0
    stats = PartitionStats(
        n_shards=n_shards, rows_per_shard=rows, eb=eb, n_edges=E,
        cut_fraction=float(cut / max(E, 1)),
        pad_ratio=float(n_shards * n_shards * eb / max(E, 1)),
        balance=float(owned.max() / max(owned.mean(), 1e-9)),
        ragged_slots=ragged_slots, ragged_stages=ragged_stages,
        ragged_pad_ratio=float(ragged_slots / max(E, 1)))
    return PartitionedGraph(
        to_pad=jnp.asarray(to_pad), from_pad=jnp.asarray(from_pad),
        src_local=jnp.asarray(SL.reshape(n_shards, n_shards, eb)),
        dst_local=jnp.asarray(DL.reshape(n_shards, n_shards, eb)),
        eid=jnp.asarray(EID.reshape(n_shards, n_shards, eb)),
        mask=jnp.asarray(MK.reshape(n_shards, n_shards, eb)),
        n_shards=n_shards, rows=rows, eb=eb, n=n, n_edges=E, mode=mode,
        stats=stats, eb_ij=eb_ij)


# --------------------------------------------------------------------- #
# the shared per-stage kernel (one K-block)
# --------------------------------------------------------------------- #
def _stage_reduce(block, gather_idx, scatter_idx, mk, wb, out):
    """Consume one bucket: gather from the resident block, weight, mask,
    scatter-add into the accumulator. Forward uses (gather=src,
    scatter=dst); the transposed ring swaps the two index roles."""
    vals = jnp.take(block, gather_idx, axis=0)           # (eb, *feat)
    if wb is not None:
        # the weight (degree norm / attention) stays at ITS dtype —
        # fp32 norms must not be truncated to bf16 before the multiply
        wv = wb.reshape(wb.shape + (1,) * (vals.ndim - wb.ndim))
        vals = vals * wv
    vals = vals.astype(out.dtype)
    mask = mk.reshape(mk.shape + (1,) * (vals.ndim - 1))
    vals = jnp.where(mask, vals, jnp.zeros((), vals.dtype))
    return out.at[scatter_idx].add(vals)


def _edge_dot(xg, cg, mk, head_rank):
    """Per-slot <x, ct> reduced over the trailing feature axes that the
    weight does NOT carry: (eb,) for scalar weights, (eb, H) for
    per-head weights on (H, F) features."""
    acc = _acc_dtype(jnp.promote_types(xg.dtype, cg.dtype))
    prod = xg.astype(acc) * cg.astype(acc)                # (eb, *feat)
    axes = tuple(range(1 + head_rank, prod.ndim))
    dw = prod.sum(axis=axes) if axes else prod
    mask = mk.reshape(mk.shape + (1,) * (dw.ndim - 1))
    return jnp.where(mask, dw, jnp.zeros((), dw.dtype))


def _fwd_perm(S):
    return [(k, (k + 1) % S) for k in range(S)]


def _bwd_perm(S):
    return [(k, (k - 1) % S) for k in range(S)]


def _diag_widths(pg: PartitionedGraph) -> Tuple[int, ...]:
    """Max real bucket width along each ring diagonal.

    At stage ``s`` every device consumes the bucket whose (dst - src)
    shard distance is ``s`` (mod S); under SPMD the stage's slice width
    must be the max over that diagonal. ``ws[0]`` is the owner-local
    diagonal; trailing zero entries are stages the ring can skip
    entirely."""
    S = pg.n_shards
    if not pg.eb_ij:
        return (pg.eb,) * S
    return tuple(max(pg.eb_ij[(j + s) % S][j] for j in range(S))
                 for s in range(S))


def _last_stage(ws: Tuple[int, ...]) -> int:
    """Index of the last non-empty diagonal (0 if all empty)."""
    nz = [s for s in range(len(ws)) if ws[s] > 0]
    return nz[-1] if nz else 0


# --------------------------------------------------------------------- #
# ring_gspmm: differentiable sharded weighted Copy-Reduce
# --------------------------------------------------------------------- #
def _ring_fwd_emu(pg: PartitionedGraph, x, w):
    S, rows = pg.n_shards, pg.rows
    feat = x.shape[1:]
    xs = x.reshape((S, rows) + feat)
    outs = []
    for i in range(S):
        out = jnp.zeros((rows,) + feat, _acc_dtype(x.dtype))
        for j in range(S):
            wij = pg.bucket_width(i, j)      # real slots: exact slice,
            if not wij:                      # empty bucket: no work
                continue
            out = _stage_reduce(xs[j], pg.src_local[i, j][:wij],
                                pg.dst_local[i, j][:wij],
                                pg.mask[i, j][:wij],
                                w[i, j][:wij], out)
        outs.append(out)
    return jnp.stack(outs).reshape((S * rows,) + feat).astype(x.dtype)


def _ring_bwd_emu(pg: PartitionedGraph, x, w, ct):
    S, rows, eb = pg.n_shards, pg.rows, pg.eb
    feat = x.shape[1:]
    head_rank = w.ndim - 3
    acc_t = _acc_dtype(jnp.promote_types(x.dtype, ct.dtype))
    xs = x.reshape((S, rows) + feat)
    cts = ct.reshape((S, rows) + feat)
    dxs, dws = [], []
    for j in range(S):           # transposed: iterate SOURCE shards
        dx = jnp.zeros((rows,) + feat, _acc_dtype(x.dtype))
        for i in range(S):       # gather at dst, scatter at src (swap)
            wij = pg.bucket_width(i, j)
            if not wij:
                continue
            dx = _stage_reduce(cts[i], pg.dst_local[i, j][:wij],
                               pg.src_local[i, j][:wij],
                               pg.mask[i, j][:wij],
                               w[i, j][:wij], dx)
        dxs.append(dx)
    for i in range(S):
        dwrow = []
        for j in range(S):
            wij = pg.bucket_width(i, j)
            if wij:
                xg = jnp.take(xs[j], pg.src_local[i, j][:wij], axis=0)
                cg = jnp.take(cts[i], pg.dst_local[i, j][:wij], axis=0)
                d = _edge_dot(xg, cg, pg.mask[i, j][:wij], head_rank)
                d = jnp.pad(d, ((0, eb - wij),)
                            + ((0, 0),) * (d.ndim - 1))
            else:
                d = jnp.zeros(w.shape[2:], acc_t)
            dwrow.append(d)
        dws.append(jnp.stack(dwrow))
    dx = jnp.stack(dxs).reshape((S * rows,) + feat).astype(x.dtype)
    return dx, jnp.stack(dws).astype(w.dtype)


def require_auto_mesh(mesh: Mesh) -> None:
    """Reject a mesh with Explicit (or Manual) axes. On Explicit axes the
    ring's forward runs, but its custom VJP asks for a mesh context and
    plain indexing of its outputs cannot resolve their sharding;
    ``jax.make_mesh`` builds Explicit axes by default, so say how to get
    Auto ones."""
    types = tuple(mesh.axis_types)
    if any(t != jax.sharding.AxisType.Auto for t in types):
        raise ValueError(
            f"ring execution needs a mesh with Auto axes, got axis types "
            f"{types}; build it with repro.launch.mesh.make_mesh or pass "
            f"axis_types=(jax.sharding.AxisType.Auto,) * {len(types)} to "
            f"jax.make_mesh")


def _node_spec(axis, ndim):
    return P(axis, *([None] * (ndim - 1)))


def _ring_fwd_mesh(pg: PartitionedGraph, mesh, axis, x, w):
    S, rows, eb = pg.n_shards, pg.rows, pg.eb
    feat = x.shape[1:]
    xs = x.reshape((S, rows) + feat)

    ws = _diag_widths(pg)
    s_max = _last_stage(ws)

    def local_fn(xb, sl, dl, mk, wb):
        me = jax.lax.axis_index(axis)
        block = xb[0]
        sl, dl, mk, wb = sl[0], dl[0], mk[0], wb[0]
        out = pvary(jnp.zeros((rows,) + feat, _acc_dtype(x.dtype)), axis)

        # static unroll (S is small): each stage slices its bucket to
        # the diagonal's max real width, and the ring stops after the
        # last non-empty diagonal — trailing stages move no bytes.
        for s in range(s_max + 1):
            shard = (me - s) % S
            # kick off the NEXT block transfer (overlaps the reduce)
            nxt = (jax.lax.ppermute(block, axis, _fwd_perm(S))
                   if s < s_max else block)
            if ws[s]:
                out = _stage_reduce(block,
                                    jnp.take(sl, shard, axis=0)[:ws[s]],
                                    jnp.take(dl, shard, axis=0)[:ws[s]],
                                    jnp.take(mk, shard, axis=0)[:ws[s]],
                                    jnp.take(wb, shard, axis=0)[:ws[s]],
                                    out)
            block = nxt
        return out.astype(x.dtype)[None]

    bucket = P(axis, None, None)
    f = shard_map(local_fn, mesh=mesh,
                  in_specs=(_node_spec(axis, xs.ndim), bucket, bucket,
                            bucket, _node_spec(axis, w.ndim)),
                  out_specs=_node_spec(axis, xs.ndim))
    out = f(xs, pg.src_local, pg.dst_local, pg.mask, w)
    return out.reshape((S * rows,) + feat)


def _ring_bwd_mesh(pg: PartitionedGraph, mesh, axis, x, w, ct):
    """The transposed ring, one pass: cotangent blocks (with their
    weight-bucket rows) rotate BACKWARD for ∂x while source blocks
    rotate forward for ∂w; src/dst bucket roles are swapped for ∂x."""
    S, rows, eb = pg.n_shards, pg.rows, pg.eb
    feat = x.shape[1:]
    head_rank = w.ndim - 3
    xs = x.reshape((S, rows) + feat)
    cts = ct.reshape((S, rows) + feat)
    slT = jnp.swapaxes(pg.src_local, 0, 1)
    dlT = jnp.swapaxes(pg.dst_local, 0, 1)
    mkT = jnp.swapaxes(pg.mask, 0, 1)

    ws = _diag_widths(pg)
    s_max = _last_stage(ws)

    def local_fn(xb, ctb, wb, sl, dl, mk, slt, dlt, mkt):
        me = jax.lax.axis_index(axis)
        xblock = xb[0]
        ct_local = ctb[0]
        wrow = wb[0]                       # (S, eb[, H]) — my dst row
        sl, dl, mk = sl[0], dl[0], mk[0]   # buckets (me, :)
        slt, dlt, mkt = slt[0], dlt[0], mkt[0]   # buckets (:, me)
        dx = pvary(jnp.zeros((rows,) + feat, _acc_dtype(x.dtype)), axis)
        dw = pvary(jnp.zeros(wrow.shape, w.dtype), axis)
        ctblock, wblock = ct_local, wrow

        # static unroll mirroring the forward: at stage s both the ∂x
        # bucket (i_ct, me) and the ∂w bucket (me, j_x) sit on the same
        # (dst - src) ≡ s diagonal, so one width ws[s] serves both;
        # trailing empty diagonals skip transfers entirely.
        for s in range(s_max + 1):
            i_ct = (me + s) % S      # dst shard resident via reverse ring
            j_x = (me - s) % S       # src shard resident via forward ring
            if s < s_max:
                x_nxt = jax.lax.ppermute(xblock, axis, _fwd_perm(S))
                ct_nxt = jax.lax.ppermute(ctblock, axis, _bwd_perm(S))
                w_nxt = jax.lax.ppermute(wblock, axis, _bwd_perm(S))
            else:
                x_nxt, ct_nxt, w_nxt = xblock, ctblock, wblock
            if ws[s]:
                # ∂x for MY src shard from bucket (i_ct, me): gather at
                # dst, scatter at src — the swapped-role stage kernel
                dx = _stage_reduce(ctblock,
                                   jnp.take(dlt, i_ct, axis=0)[:ws[s]],
                                   jnp.take(slt, i_ct, axis=0)[:ws[s]],
                                   jnp.take(mkt, i_ct, axis=0)[:ws[s]],
                                   jnp.take(wblock, me, axis=0)[:ws[s]],
                                   dx)
                # ∂w for MY dst bucket (me, j_x): per-edge <x, ct> dot
                xg = jnp.take(xblock,
                              jnp.take(sl, j_x, axis=0)[:ws[s]], axis=0)
                cg = jnp.take(ct_local,
                              jnp.take(dl, j_x, axis=0)[:ws[s]], axis=0)
                de = _edge_dot(xg, cg,
                               jnp.take(mk, j_x, axis=0)[:ws[s]],
                               head_rank).astype(w.dtype)
                de = jnp.pad(de, ((0, eb - ws[s]),)
                             + ((0, 0),) * (de.ndim - 1))
                dw = dw.at[j_x].set(de)
            xblock, ctblock, wblock = x_nxt, ct_nxt, w_nxt
        return dx[None], dw[None]

    bucket = P(axis, None, None)
    nspec = _node_spec(axis, xs.ndim)
    wspec = _node_spec(axis, w.ndim)
    f = shard_map(local_fn, mesh=mesh,
                  in_specs=(nspec, nspec, wspec, bucket, bucket, bucket,
                            bucket, bucket, bucket),
                  out_specs=(nspec, wspec))
    dx, dw = f(xs, cts, w, pg.src_local, pg.dst_local, pg.mask,
               slT, dlT, mkT)
    return dx.reshape((S * rows,) + feat).astype(x.dtype), dw


def _ring_call(pg: PartitionedGraph, x, w, mesh, axis):
    """The raw differentiable ring (custom transposed-ring VJP)."""
    if mesh is None:
        @jax.custom_vjp
        def f(x, w):
            return _ring_fwd_emu(pg, x, w)

        f.defvjp(lambda x, w: (_ring_fwd_emu(pg, x, w), (x, w)),
                 lambda res, ct: _ring_bwd_emu(pg, *res, ct))
        return f(x, w)

    require_auto_mesh(mesh)

    @jax.custom_vjp
    def f(x, w):
        return _ring_fwd_mesh(pg, mesh, axis, x, w)

    f.defvjp(lambda x, w: (_ring_fwd_mesh(pg, mesh, axis, x, w), (x, w)),
             lambda res, ct: _ring_bwd_mesh(pg, mesh, axis, *res, ct))
    return f(x, w)


def ring_gspmm(pg: PartitionedGraph, x: jnp.ndarray, w: jnp.ndarray, *,
               mesh: Optional[Mesh] = None, axis: str = "data",
               comm: str = "none", residual: Optional[jnp.ndarray] = None):
    """Sharded weighted CR-sum: ``out[v] = Σ_{e=(u→v)} w_e · x[u]``.

    ``x``: (n_pad, *feat) in padded layout (see
    :meth:`PartitionedGraph.scatter_nodes`); ``w``: bucketed weights
    (S, S, eb) scalar or (S, S, eb, H) per-head against (H, F) features
    (see :meth:`~PartitionedGraph.scatter_edges`; pass bucketed ones for
    plain CR-sum; fold 1/deg into ``w`` for mean). Returns (n_pad,
    *feat) destination sums. Differentiable w.r.t. both ``x`` and ``w``
    via the transposed ring; with ``mesh=None`` the same math (and the
    same custom VJP) runs emulated on one device.

    ``comm="int8"`` puts the cross-shard payload on the compressed wire
    (DESIGN.md §12): each source block is quantized ONCE at its owner —
    blockwise int8 + per-256-value fp32 scale, with the error-feedback
    ``residual`` (an (n_pad, *feat) fp32 array, required) folded in so
    compression stays unbiased across steps — and the quantized block
    is what circulates the ring. Owner-local (diagonal-bucket) edges
    read the RAW features; only remote consumers see the dequantized
    values. The straight-through estimator makes the wire transparent
    to autodiff. Returns ``(out, new_residual)``.
    """
    if comm not in COMM_MODES:
        raise ValueError(f"comm must be one of {COMM_MODES}: {comm!r}")
    if comm == "none":
        _count_exchange(pg, x, "none")
        return _ring_call(pg, x, w, mesh, axis)
    if residual is None:
        raise ValueError('comm="int8" needs the error-feedback residual '
                         "(init with jnp.zeros((n_pad, *feat), float32))")
    y, new_residual = compress_payload(x, residual)
    _count_exchange(pg, x, "int8")
    out = (local_gspmm(pg, x, w)
           + _ring_call(pg, y, offdiag_weights(pg, w), mesh, axis))
    return out, new_residual


def ring_reference(pg: PartitionedGraph, x: jnp.ndarray,
                   w: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Single-device oracle (same padded layout, plain loop, no VJP)."""
    if w is None:
        # fp32 weights even for bf16 x: the norm/one weights must not be
        # truncated to the feature dtype (the reduce casts at the end)
        w = jnp.where(pg.mask, 1.0, 0.0)
    return _ring_fwd_emu(pg, x, w)


# --------------------------------------------------------------------- #
# per-edge operand assembly + destination softmax (GAT support)
# --------------------------------------------------------------------- #
def _rev_fwd_emu(pg, el, er):
    S, rows, eb = pg.n_shards, pg.rows, pg.eb
    feat = el.shape[1:]
    res_t = jnp.result_type(el, er)
    els = el.reshape((S, rows) + feat)
    ers = er.reshape((S, rows) + feat)
    out = []
    for i in range(S):
        row = []
        for j in range(S):
            wij = pg.bucket_width(i, j)
            if not wij:
                row.append(jnp.zeros((eb,) + feat, res_t))
                continue
            vals = (jnp.take(els[j], pg.src_local[i, j][:wij], axis=0)
                    + jnp.take(ers[i], pg.dst_local[i, j][:wij], axis=0))
            mk = pg.mask[i, j][:wij].reshape((wij,) + (1,) * len(feat))
            vals = jnp.where(mk, vals, jnp.zeros((), vals.dtype))
            row.append(jnp.pad(vals, ((0, eb - wij),)
                               + ((0, 0),) * len(feat)))
        out.append(jnp.stack(row))
    return jnp.stack(out)


def _rev_bwd_emu(pg, ct):
    S, rows, eb = pg.n_shards, pg.rows, pg.eb
    dtype = _acc_dtype(ct.dtype)
    feat = ct.shape[3:]
    dels, ders = [], []
    for j in range(S):
        dl_ = jnp.zeros((rows,) + feat, dtype)
        for i in range(S):
            wij = pg.bucket_width(i, j)
            if not wij:
                continue
            dl_ = _stage_reduce(ct[i, j][:wij], jnp.arange(wij),
                                pg.src_local[i, j][:wij],
                                pg.mask[i, j][:wij], None, dl_)
        dels.append(dl_)
    for i in range(S):
        dr = jnp.zeros((rows,) + feat, dtype)
        for j in range(S):
            wij = pg.bucket_width(i, j)
            if not wij:
                continue
            dr = _stage_reduce(ct[i, j][:wij], jnp.arange(wij),
                               pg.dst_local[i, j][:wij],
                               pg.mask[i, j][:wij], None, dr)
        ders.append(dr)
    d_el = jnp.stack(dels).reshape((S * rows,) + feat).astype(ct.dtype)
    d_er = jnp.stack(ders).reshape((S * rows,) + feat).astype(ct.dtype)
    return d_el, d_er


def _rev_fwd_mesh(pg, mesh, axis, el, er):
    S, rows, eb = pg.n_shards, pg.rows, pg.eb
    feat = el.shape[1:]
    els = el.reshape((S, rows) + feat)
    ers = er.reshape((S, rows) + feat)

    def local_fn(elb, erb, sl, dl, mk):
        me = jax.lax.axis_index(axis)
        block = elb[0]
        erloc = erb[0]
        sl, dl, mk = sl[0], dl[0], mk[0]
        acc = pvary(jnp.zeros((S, eb) + feat, el.dtype), axis)

        def stage(s, carry):
            acc, block = carry
            shard = (me - s) % S
            nxt = jax.lax.ppermute(block, axis, _fwd_perm(S))
            sls = jnp.take(sl, shard, axis=0)
            dls = jnp.take(dl, shard, axis=0)
            mks = jnp.take(mk, shard, axis=0)
            vals = (jnp.take(block, sls, axis=0)
                    + jnp.take(erloc, dls, axis=0))
            mkr = mks.reshape((eb,) + (1,) * len(feat))
            acc = acc.at[shard].set(
                jnp.where(mkr, vals, jnp.zeros((), vals.dtype)))
            return acc, nxt

        acc, _ = jax.lax.fori_loop(0, S, stage, (acc, block))
        return acc[None]

    bucket = P(axis, None, None)
    nspec = _node_spec(axis, els.ndim)
    f = shard_map(local_fn, mesh=mesh,
                  in_specs=(nspec, nspec, bucket, bucket, bucket),
                  out_specs=P(axis, *([None] * (2 + len(feat)))))
    return f(els, ers, pg.src_local, pg.dst_local, pg.mask)


def _rev_bwd_mesh(pg, mesh, axis, ct):
    S, rows, eb = pg.n_shards, pg.rows, pg.eb
    dtype = _acc_dtype(ct.dtype)
    feat = ct.shape[3:]
    slT = jnp.swapaxes(pg.src_local, 0, 1)
    mkT = jnp.swapaxes(pg.mask, 0, 1)

    def local_fn(ctb, dl, mk, slt, mkt):
        me = jax.lax.axis_index(axis)
        ct_row = ctb[0]                     # (S, eb) + feat — my dst row
        dl, mk = dl[0], mk[0]
        slt, mkt = slt[0], mkt[0]
        # ∂er: fully local — every bucket of my dst row scatters home
        d_er = jnp.zeros((rows,) + feat, dtype)
        for j in range(S):      # static unroll: S is small
            d_er = _stage_reduce(ct_row[j], jnp.arange(eb), dl[j],
                                 mk[j], None, d_er)
        # ∂el: transposed ring — dst rows rotate backward, each device
        # scatters the bucket whose SOURCES it owns
        d_el = pvary(jnp.zeros((rows,) + feat, dtype), axis)

        def stage(s, carry):
            d_el, block = carry
            i_ct = (me + s) % S
            nxt = jax.lax.ppermute(block, axis, _bwd_perm(S))
            d_el = _stage_reduce(jnp.take(block, me, axis=0),
                                 jnp.arange(eb),
                                 jnp.take(slt, i_ct, axis=0),
                                 jnp.take(mkt, i_ct, axis=0), None, d_el)
            return d_el, nxt

        d_el, _ = jax.lax.fori_loop(0, S, stage, (d_el, ct_row))
        return d_el.astype(ct.dtype)[None], d_er.astype(ct.dtype)[None]

    bucket = P(axis, None, None)
    cspec = P(axis, *([None] * (2 + len(feat))))
    nspec = P(axis, *([None] * (1 + len(feat))))
    f = shard_map(local_fn, mesh=mesh,
                  in_specs=(cspec, bucket, bucket, bucket, bucket),
                  out_specs=(nspec, nspec))
    d_el, d_er = f(ct, pg.dst_local, pg.mask, slT, mkT)
    return (d_el.reshape((S * rows,) + feat),
            d_er.reshape((S * rows,) + feat))


def ring_edge_values(pg: PartitionedGraph, el: jnp.ndarray,
                     er: jnp.ndarray, *, mesh: Optional[Mesh] = None,
                     axis: str = "data") -> jnp.ndarray:
    """Bucketed per-edge sums ``el[src_e] + er[dst_e]`` — GAT's
    ``u_add_v_copy_e`` on shards.

    ``el``/``er``: (n_pad, *feat) padded node values. Returns
    (S, S, eb, *feat) bucketed edge values, 0 on pad slots. The VJP is
    local for ``er`` (every dst bucket lives with its owner) and a
    transposed ring for ``el``.
    """
    if mesh is None:
        @jax.custom_vjp
        def f(el, er):
            return _rev_fwd_emu(pg, el, er)

        f.defvjp(lambda el, er: (_rev_fwd_emu(pg, el, er), None),
                 lambda res, ct: _rev_bwd_emu(pg, ct))
        return f(el, er)

    require_auto_mesh(mesh)

    @jax.custom_vjp
    def f(el, er):
        return _rev_fwd_mesh(pg, mesh, axis, el, er)

    f.defvjp(lambda el, er: (_rev_fwd_mesh(pg, mesh, axis, el, er), None),
             lambda res, ct: _rev_bwd_mesh(pg, mesh, axis, ct))
    return f(el, er)


def bucket_softmax(pg: PartitionedGraph, logits: jnp.ndarray
                   ) -> jnp.ndarray:
    """Destination softmax over bucketed edge logits (S, S, eb, *feat).

    Every bucket of dst-shard row ``i`` is owner-resident, so the
    softmax needs no communication of its own: under ``jit`` the global
    scatter/gather below stays shard-local (rows of ``gdst`` in block
    ``i`` index only shard ``i``'s padded rows). Pad slots come back 0.
    """
    S, rows, eb = pg.n_shards, pg.rows, pg.eb
    feat = logits.shape[3:]
    gdst = (jnp.arange(S, dtype=jnp.int32)[:, None, None] * rows
            + pg.dst_local)                              # (S, S, eb)
    gf = gdst.reshape(-1)
    flat = logits.reshape((S * S * eb,) + feat)
    mkf = pg.mask.reshape(-1)
    mkr = mkf.reshape((-1,) + (1,) * len(feat))
    neg = jnp.asarray(-jnp.inf, flat.dtype)
    masked = jnp.where(mkr, flat, neg)
    m = jnp.full((pg.n_pad,) + feat, neg, flat.dtype).at[gf].max(masked)
    m = jnp.where(jnp.isfinite(m), m, jnp.zeros((), flat.dtype))
    ex = jnp.exp(flat - jnp.take(m, gf, axis=0))
    ex = jnp.where(mkr, ex, jnp.zeros((), flat.dtype))
    z = jnp.zeros((pg.n_pad,) + feat, flat.dtype).at[gf].add(ex)
    alpha = ex / jnp.maximum(jnp.take(z, gf, axis=0), 1e-20)
    return alpha.reshape((S, S, eb) + feat)


# --------------------------------------------------------------------- #
# delayed halo (DistGNN-style staleness knob)
# --------------------------------------------------------------------- #
def local_gspmm(pg: PartitionedGraph, x: jnp.ndarray,
                w: jnp.ndarray) -> jnp.ndarray:
    """Owner-local part only: the diagonal (d, d) buckets — edges whose
    both endpoints live on one shard. No communication."""
    S, rows = pg.n_shards, pg.rows
    feat0 = x.shape[1:]
    w0 = _diag_widths(pg)[0]                 # max real diagonal width
    if not w0:
        return jnp.zeros((pg.n_pad,) + feat0, x.dtype)
    diag = jnp.arange(S)
    sl = pg.src_local[diag, diag][:, :w0]    # (S, w0)
    dl = pg.dst_local[diag, diag][:, :w0]
    mk = pg.mask[diag, diag][:, :w0]
    wd = w[diag, diag][:, :w0]               # (S, w0[, H])
    base = (jnp.arange(S, dtype=jnp.int32) * rows)[:, None]
    gsrc = (base + sl).reshape(-1)
    gdst = (base + dl).reshape(-1)
    feat = x.shape[1:]
    vals = jnp.take(x, gsrc, axis=0)         # (S*eb, *feat)
    wv = wd.reshape((-1,) + wd.shape[2:])
    wv = wv.reshape(wv.shape + (1,) * (vals.ndim - wv.ndim))
    mkr = mk.reshape((-1,) + (1,) * len(feat))
    vals = (vals * wv).astype(_acc_dtype(x.dtype))
    vals = jnp.where(mkr, vals, jnp.zeros((), vals.dtype))
    acc = jnp.zeros((pg.n_pad,) + feat, _acc_dtype(x.dtype))
    return acc.at[gdst].add(vals).astype(x.dtype)


def offdiag_weights(pg: PartitionedGraph, w: jnp.ndarray) -> jnp.ndarray:
    """Zero the diagonal buckets — the remote-only weight view."""
    S = pg.n_shards
    off = 1.0 - jnp.eye(S, dtype=w.dtype)
    return w * off.reshape((S, S) + (1,) * (w.ndim - 2))


def ring_gspmm_delayed(pg: PartitionedGraph, x: jnp.ndarray,
                       w: jnp.ndarray, stale: jnp.ndarray, refresh: bool,
                       *, mesh: Optional[Mesh] = None, axis: str = "data",
                       comm: str = "none",
                       residual: Optional[jnp.ndarray] = None):
    """Weighted CR with a delayed halo: ``out = local + remote`` where
    the remote partial (all cross-shard buckets) is recomputed only when
    ``refresh`` (a static Python bool) and otherwise reused from
    ``stale``. Gradients always flow through the local part; through
    the remote part only on refresh steps. Returns ``(out, remote)``
    with the returned remote detached — carry it as the next step's
    ``stale``. A refresh step is numerically exact.

    ``comm="int8"`` compresses the refresh exchange exactly like
    :func:`ring_gspmm` (requires ``residual``; the local part still
    reads raw features). Skipped-refresh steps move no bytes, so the
    residual passes through untouched. Returns
    ``(out, remote, new_residual)``.
    """
    if comm not in COMM_MODES:
        raise ValueError(f"comm must be one of {COMM_MODES}: {comm!r}")
    loc = local_gspmm(pg, x, w)
    if comm == "int8":
        if residual is None:
            raise ValueError('comm="int8" needs the error-feedback '
                             "residual")
        if refresh:
            y, residual = compress_payload(x, residual)
            _count_exchange(pg, x, "int8")
            remote = _ring_call(pg, y, offdiag_weights(pg, w), mesh, axis)
        else:
            remote = jax.lax.stop_gradient(stale)
        return loc + remote, jax.lax.stop_gradient(remote), residual
    if refresh:
        _count_exchange(pg, x, "none")
        remote = _ring_call(pg, x, offdiag_weights(pg, w), mesh, axis)
    else:
        remote = jax.lax.stop_gradient(stale)
    return loc + remote, jax.lax.stop_gradient(remote)
