"""Unified execution-plan layer for the BR/CR lattice.

The paper's speedups come from *transparently* swapping aggregation
implementations (push → segment → blocked pull → fused kernels) under
one API — DGL users never pick a kernel, the framework does. This module
is that selection layer for the reproduction:

* :class:`GraphStats` — host-side statistics of a :class:`Graph`
  (edge count, degree moments, skew, ELL padding estimate) computed once
  per graph. They are plain Python numbers, so they travel through
  ``jit`` as *static* pytree aux data.
* :class:`PlanCache` — per-graph memoized packs (``ELLPack`` /
  ``TilePack`` / uniform ELL) plus the stats and any autotuned
  decisions. Keyed on the ``Graph`` object in a process-wide weak
  registry (:func:`get_plan_cache`), so each pack is built at most once
  per process per graph. Registered as a pytree: the pack arrays are
  children (traceable through ``jit``), the stats are static aux.
* :func:`plan_gspmm` — the planner proper: given a graph, a parsed
  ``BRSpec`` and operand shapes, it picks an execution strategy via an
  explicit cost model (see :func:`estimate_cost` and DESIGN.md §4), or
  measures candidates once and caches the winner when autotune mode is
  on. Pinned strategies that do not support a spec *fall back* down the
  chain ``pallas → onehot → ell → segment`` (with a one-time warning)
  instead of raising.

Every decision is recorded in a process-wide plan log
(:func:`plan_log`) so benchmarks can report which plan served each op.
"""
from __future__ import annotations

import contextlib
import dataclasses
import os
import time
import warnings
import weakref
from collections import Counter
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..compat import trace_state_clean
from .graph import Graph
from .tiling import (ELLClass, ELLPack, TilePack, build_ell,
                     build_ell_ragged, build_ell_uniform, build_tiles)
from ..obs import events as _obs_events
from ..obs import metrics as _obs_metrics
from ..obs.events import plan_events  # noqa: F401 (re-export)
from ..optim.compression import wire_bytes as _wire_bytes

__all__ = ["GraphStats", "PlanCache", "Plan", "get_plan_cache",
           "compute_stats", "estimate_cost", "ell_rowcomplete_padding",
           "plan_gspmm", "supports",
           "plan_log", "clear_plan_log", "last_plan", "pack_build_totals",
           "set_mode", "get_mode", "STRATEGIES", "FALLBACK_CHAIN",
           "block_stats", "plan_block_gspmm", "clear_block_plans",
           "plan_block_vjp", "block_bwd_supports",
           "BLOCK_BWD_STRATEGIES",
           "HETERO_STRATEGIES", "plan_hetero", "clear_hetero_plans",
           "SDDMM_STRATEGIES", "sddmm_supports", "plan_sddmm",
           "clear_sddmm_plans", "ATTN_STRATEGIES", "plan_attention",
           "SERVE_MODES", "plan_serve", "clear_serve_plans",
           "use_ring", "active_ring", "RingContext",
           "plan_events"]

STRATEGIES = ("push", "segment", "ell", "onehot", "pallas", "ring")

# Soft-fallback order for unsupported specs: most specialized first.
FALLBACK_CHAIN = ("pallas", "onehot", "ell", "segment")

# A pinned ring without a mesh degrades to its single-device analogue:
# each ring stage is one K-block, so blocked pull is the natural stand-in.
_RING_FALLBACK = ("ell", "segment")

# Strategies the auto mode considers (push is the pinned baseline only;
# ring only qualifies inside an active use_ring() context).
_AUTO_CANDIDATES = ("ring", "pallas", "onehot", "ell", "segment")

_DEFAULT_ELL_CAP = 64
_DEFAULT_TILE_GEOM = (128, 128, 256)  # (bm, bk, eb) — build_tiles defaults


def _is_traced(x: Any) -> bool:
    return isinstance(x, jax.core.Tracer)


def graph_is_traced(g: Graph) -> bool:
    """True when ``g``'s index arrays are jit tracers (inside a trace)."""
    return _is_traced(g.src)


# --------------------------------------------------------------------- #
# graph statistics
# --------------------------------------------------------------------- #
@dataclasses.dataclass(frozen=True)
class GraphStats:
    """Static, hashable summary of a graph — the planner's features."""
    n_src: int
    n_dst: int
    n_edges: int
    avg_in_deg: float
    max_in_deg: int
    skew: float               # max_in_deg / avg_in_deg
    ell_padded_slots: int     # total (row, slot) cells of the bucketed ELL
    ell_n_classes: int        # number of distinct power-of-two widths
    pad_ratio: float          # ell_padded_slots / n_edges
    # row-complete RAGGED ELL (no row splitting; the fused-attention
    # megakernel's pack — build_ell_ragged). Defaults keep hand-built
    # stats (tests, block_stats) valid without the ragged histogram.
    ragged_padded_slots: int = 0
    ragged_n_classes: int = 0
    ragged_pad_ratio: float = 1.0
    # edge slots of the (M, K)-tile bucket pack at the default geometry
    # (build_tiles): buckets hold one tile pair each, so a sparse graph
    # pads most of every bucket. 0 = not counted (block_stats, hand-built
    # stats): onehot/pallas cannot be priced without it.
    tile_slots: int = 0


def _ell_padding(deg: np.ndarray, cap: int) -> Tuple[int, int]:
    """Padded-slot count + class count of the degree-bucketed ELL,
    estimated from the in-degree histogram without building the pack."""
    deg = deg[deg > 0]
    if deg.size == 0:
        return 0, 0
    full, rem = np.divmod(deg, cap)
    padded = int(full.sum()) * cap
    widths = set()
    rem = rem[rem > 0]
    if rem.size:
        w = np.where(rem > 1,
                     (2 ** np.ceil(np.log2(rem))).astype(np.int64),
                     np.int64(1))
        padded += int(w.sum())
        widths.update(int(x) for x in np.unique(w))
    if full.any():
        widths.add(cap)
    return padded, len(widths)


def tile_pack_slots(g: Graph, bm: int = _DEFAULT_TILE_GEOM[0],
                    bk: int = _DEFAULT_TILE_GEOM[1],
                    eb: int = _DEFAULT_TILE_GEOM[2]) -> int:
    """Edge slots :func:`~repro.core.tiling.build_tiles` allocates: one
    ``eb``-slot bucket per started run of ``eb`` edges of each occupied
    (dst-tile, src-tile) pair. Counted without building the pack."""
    if g.n_edges == 0:
        return eb
    n_tiles_k = max(1, -(-g.n_src // bk))
    key = (np.asarray(g.dst, np.int64) // bm) * n_tiles_k \
        + np.asarray(g.src, np.int64) // bk
    _, counts = np.unique(key, return_counts=True)
    return int((-(-counts // eb)).sum()) * eb


def ell_rowcomplete_padding(deg) -> Tuple[int, int]:
    """Padded-slot + class count of the ROW-COMPLETE ragged ELL
    (``build_ell_ragged``): every nonzero row padded to the next power
    of two of its own in-degree, no splitting. Estimated from the
    degree histogram without building the pack — the ONE formula shared
    by ``fused_attention``'s pallas gate and the planner's ragged
    attention cost row (a gate priced at ``max_degree × n_rows`` would
    veto the megakernel on exactly the power-law tails it now wins)."""
    deg = np.asarray(deg, dtype=np.int64)
    deg = deg[deg > 0]
    if deg.size == 0:
        return 0, 0
    w = np.where(deg > 1,
                 (2 ** np.ceil(np.log2(deg))).astype(np.int64),
                 np.int64(1))
    return int(w.sum()), int(np.unique(w).size)


def compute_stats(g: Graph, ell_cap: int = _DEFAULT_ELL_CAP) -> GraphStats:
    """Host-side stats; requires a concrete (non-traced) graph."""
    deg = np.asarray(g.in_degrees, dtype=np.int64)
    n_edges = int(g.n_edges)
    avg = n_edges / max(g.n_dst, 1)
    mx = int(deg.max()) if deg.size else 0
    padded, n_cls = _ell_padding(deg, ell_cap)
    rslots, rcls = ell_rowcomplete_padding(deg)
    return GraphStats(
        n_src=int(g.n_src), n_dst=int(g.n_dst), n_edges=n_edges,
        avg_in_deg=float(avg), max_in_deg=mx,
        skew=float(mx / max(avg, 1e-9)),
        ell_padded_slots=int(padded), ell_n_classes=int(n_cls),
        pad_ratio=float(padded / max(n_edges, 1)),
        ragged_padded_slots=int(rslots), ragged_n_classes=int(rcls),
        ragged_pad_ratio=float(rslots / max(n_edges, 1)),
        tile_slots=tile_pack_slots(g))


# --------------------------------------------------------------------- #
# per-graph pack cache
# --------------------------------------------------------------------- #
_PACK_BUILDS: Counter = Counter()   # process-wide build counters (tests)


def pack_build_totals() -> Dict[str, int]:
    """How many packs of each kind were *built* (not reused) so far."""
    return dict(_PACK_BUILDS)


def _note_pack_build(kind: str) -> None:
    _PACK_BUILDS[kind] += 1
    _obs_metrics.counter(f"planner.pack_builds.{kind}").inc()


def _ell_pack_slots(pack: ELLPack) -> int:
    """Total padded (chunk, slot) cells of a built ELL pack."""
    return sum(int(c.chunk_mask.shape[0]) * int(c.width)
               for c in pack.classes)


def _note_pad_ratio(kind: str, slots: int, n_edges: int) -> None:
    """``planner.pad_ratio.<kind>`` gauge: padded slots per real edge of
    the most recently built pack of this kind — the pad-tax trajectory
    every BENCH_*.json embeds via its metrics snapshot."""
    _obs_metrics.gauge(f"planner.pad_ratio.{kind}").set(
        slots / max(int(n_edges), 1))


@jax.tree_util.register_pytree_node_class
class PlanCache:
    """Lazily-built, memoized packs + stats for one :class:`Graph`.

    Pack arrays are pytree children so a cache carried by a model bundle
    flows through ``jit``; the stats are static aux, which lets the
    planner run its full cost model inside a trace. Building only
    happens on the concrete (host) side — inside a trace, a pack that
    was never built is simply unavailable and the planner plans around
    it.
    """

    def __init__(self, ell: Optional[ELLPack] = None,
                 tiles: Optional[TilePack] = None,
                 stats: Optional[GraphStats] = None,
                 graph: Optional[Graph] = None,
                 ell_cap: int = _DEFAULT_ELL_CAP,
                 krel: Optional[Any] = None):
        self._ell = ell
        self._tiles = tiles
        self._krel = krel       # K-relation RelGraph (hetero, DESIGN §8)
        self.stats = stats
        self.ell_cap = ell_cap
        self._gref = weakref.ref(graph) if graph is not None else None
        # host-side keyed memos (not part of the pytree)
        self._ell_by_cap: Dict[int, ELLPack] = {}
        self._tiles_by_geom: Dict[Tuple[int, int, int], TilePack] = {}
        self._uniform: Dict[int, ELLClass] = {}
        self._ragged: Optional[ELLPack] = None
        self._autotuned: Dict[Tuple, str] = {}
        self._partitions: Dict[Tuple[int, str], Any] = {}

    # -- pytree protocol ------------------------------------------------
    def tree_flatten(self):
        return ((self._ell, self._tiles, self._krel),
                (self.stats, self.ell_cap))

    @classmethod
    def tree_unflatten(cls, aux, children):
        ell, tiles, krel = children
        return cls(ell=ell, tiles=tiles, krel=krel, stats=aux[0],
                   ell_cap=aux[1])

    # -- pack access -----------------------------------------------------
    def _graph(self) -> Optional[Graph]:
        g = self._gref() if self._gref is not None else None
        if g is None or graph_is_traced(g):
            return None
        return g

    def peek(self, kind: str):
        """Return an already-built pack or None (never builds)."""
        return {"ell": self._ell, "tiles": self._tiles,
                "ell_ragged": self._ragged}[kind]

    def set_ell_cap(self, cap: int) -> None:
        """Change the default ELL width cap. Re-slots any pack built at
        the old cap into the keyed memo (never hands out a pack with
        the wrong blocking) and recomputes the padding stats so the
        cost model describes the cap actually in use."""
        if cap == self.ell_cap:
            return
        if self._ell is not None:
            self._ell_by_cap[self.ell_cap] = self._ell
            self._ell = self._ell_by_cap.pop(cap, None)
        self.ell_cap = cap
        g = self._graph()
        if g is not None:
            self.stats = compute_stats(g, cap)

    def ell(self, width_cap: Optional[int] = None) -> Optional[ELLPack]:
        cap = self.ell_cap if width_cap is None else width_cap
        if cap == self.ell_cap:
            if self._ell is None:
                g = self._graph()
                if g is None:
                    return None
                self._ell = build_ell(g, cap)
                _note_pack_build("ell")
                _note_pad_ratio("ell", _ell_pack_slots(self._ell),
                                g.n_edges)
            return self._ell
        if cap not in self._ell_by_cap:
            g = self._graph()
            if g is None:
                return None
            self._ell_by_cap[cap] = build_ell(g, cap)
            _note_pack_build("ell")
            _note_pad_ratio("ell", _ell_pack_slots(self._ell_by_cap[cap]),
                            g.n_edges)
        return self._ell_by_cap[cap]

    def tiles(self, bm: int = 128, bk: int = 128, eb: int = 256
              ) -> Optional[TilePack]:
        geom = (bm, bk, eb)
        if geom == _DEFAULT_TILE_GEOM:
            if self._tiles is None:
                g = self._graph()
                if g is None:
                    return None
                self._tiles = build_tiles(g, bm, bk, eb)
                _note_pack_build("tiles")
            return self._tiles
        if geom not in self._tiles_by_geom:
            g = self._graph()
            if g is None:
                return None
            self._tiles_by_geom[geom] = build_tiles(g, bm, bk, eb)
            _note_pack_build("tiles")
        return self._tiles_by_geom[geom]

    def ell_uniform(self, width: int) -> Optional[ELLClass]:
        if width not in self._uniform:
            g = self._graph()
            if g is None:
                return None
            self._uniform[width] = build_ell_uniform(g, width)
            _note_pack_build("ell_uniform")
            cls = self._uniform[width]
            _note_pad_ratio("ell_uniform",
                            int(cls.chunk_mask.shape[0]) * int(cls.width),
                            g.n_edges)
        return self._uniform[width]

    def ell_ragged(self) -> Optional[ELLPack]:
        """Row-complete RAGGED ELL (``build_ell_ragged``): whole rows,
        per-power-of-two class widths — the fused-attention megakernel's
        power-law pack. Host-side memo like :meth:`ell_uniform` (never
        builds inside a trace)."""
        if self._ragged is None:
            g = self._graph()
            if g is None:
                return None
            self._ragged = build_ell_ragged(g)
            _note_pack_build("ell_ragged")
            _note_pad_ratio("ell_ragged", _ell_pack_slots(self._ragged),
                            g.n_edges)
        return self._ragged

    def partition(self, n_shards: int, mode: str = "contiguous"):
        """Memoized :class:`~repro.core.partition.PartitionedGraph` for
        ``(n_shards, mode)`` — the ring strategy's pack. Host-side only
        (a traced graph can't be partitioned); one build per process
        per configuration, shared by direct gspmm calls, partitioned
        model bundles and the benchmarks.

        Like the keyed ``_ell_by_cap``/``_tiles_by_geom`` memos (and
        unlike the default-geometry ell/tiles slots), partitions are
        NOT pytree children — the dict's structure varies per build, so
        a cache that crosses a jit boundary arrives without them and
        ring never qualifies inside a trace. Partitioned *training*
        does not route through gspmm's planner at all: it carries the
        ``PartitionedGraph`` itself through jit (models/gnn/train.py).
        """
        key = (int(n_shards), mode)
        if key not in self._partitions:
            g = self._graph()
            if g is None:
                return None
            from .partition import build_partition  # local: avoids cycle
            pg = build_partition(g, n_shards, mode)
            self._partitions[key] = pg
            _note_pack_build("partition")
            st = pg.stats
            _note_pad_ratio("partition",
                            st.n_shards * st.n_shards * st.eb, st.n_edges)
            _note_pad_ratio("partition_ragged", st.ragged_slots,
                            st.n_edges)
        return self._partitions[key]

    def peek_partition(self, n_shards: int, mode: str = "contiguous"):
        return self._partitions.get((int(n_shards), mode))

    def krel(self, n_rel: int):
        """Memoized K-relation :class:`~repro.core.hetero.RelGraph` of
        this graph: the edge set replicated once per relation (MoNet's
        per-kernel aggregation — DESIGN.md §8). A pytree child, so a
        bundle-carried cache serves the fused path inside jitted train
        steps; host-side build only, like every other pack."""
        if self._krel is not None and self._krel.n_rel == int(n_rel):
            return self._krel
        g = self._graph()
        if g is None or not trace_state_clean():
            # never build under an active trace — np→jnp conversions
            # there leak trace-bound arrays into the process-wide cache
            return None
        from .hetero import caller_coo, from_rels  # local: avoids cycle
        src, dst = caller_coo(g)
        self._krel = from_rels([(src, dst)] * int(n_rel),
                               n_src=g.n_src, n_dst=g.n_dst)
        _note_pack_build("krel")
        return self._krel

    # -- planning helpers -------------------------------------------------
    def prefers_ell(self, d: int) -> bool:
        """True when the cost model ranks blocked pull above segment."""
        if self.stats is None:
            return False
        return (estimate_cost("ell", self.stats, d)
                < estimate_cost("segment", self.stats, d))


_CACHES: "weakref.WeakKeyDictionary[Graph, PlanCache]" = \
    weakref.WeakKeyDictionary()


def get_plan_cache(g: Graph) -> PlanCache:
    """Process-wide cache registry: one :class:`PlanCache` per graph."""
    if graph_is_traced(g):
        raise ValueError("get_plan_cache needs a concrete Graph; inside "
                         "jit, pass the cache in explicitly")
    cache = _CACHES.get(g)
    if cache is None:
        cache = PlanCache(stats=compute_stats(g), graph=g)
        _CACHES[g] = cache
    return cache


# --------------------------------------------------------------------- #
# cost model (explicit — see DESIGN.md §4)
# --------------------------------------------------------------------- #
# Relative cost per effective element-op (lower = faster). The numbers
# encode the paper's qualitative ordering, not absolute hardware rates:
# scatter (push) serializes, segment reduce is the vendor baseline,
# blocked pull streams densely, and the MXU formulations only pay off on
# a real TPU (on CPU the Pallas kernels run in interpret mode).
_THROUGHPUT = {
    "cpu": {"push": 6.0, "segment": 1.0, "ell": 0.35,
            "onehot": 64.0, "pallas": 512.0, "ring": 0.5},
    "tpu": {"push": 8.0, "segment": 1.5, "ell": 0.8,
            "onehot": 0.5, "pallas": 0.25, "ring": 0.6},
    # Half precision shifts the table unevenly: the streaming forms
    # (blocked pull, ring stages, segment reduce) are memory-bound, so
    # halving the element footprint buys them more than the
    # scatter/dispatch-bound paths — the ell/segment break-even moves
    # from pad_ratio ≈ 2.9 to ≈ 3.9 at bf16 (DESIGN.md §12).
    "cpu:bf16": {"push": 5.5, "segment": 0.85, "ell": 0.22,
                 "onehot": 64.0, "pallas": 512.0, "ring": 0.35},
    "tpu:bf16": {"push": 7.0, "segment": 1.1, "ell": 0.5,
                 "onehot": 0.35, "pallas": 0.15, "ring": 0.4},
}
# Fixed per-call overhead (dispatch + padding setup), in element-ops.
_FIXED = {"push": 0.0, "segment": 0.0, "ell": 2e4,
          "onehot": 5e4, "pallas": 5e4, "ring": 1e5}
_ELL_CLASS_OVERHEAD = 1.5e3     # per degree class: one segment combine
_RING_COMM = 0.3   # per fp32-equivalent element moved per ring stage
_RING_DEFAULT_SHARDS = 8        # nominal S when no ring context is live


def _throughput_row(backend: Optional[str], dtype) -> Dict[str, float]:
    """Backend throughput row, the ``"<backend>:bf16"`` row for bf16
    operands. A backend without a row is an error: pricing it with
    another device's numbers would pick plans for the wrong machine."""
    backend = backend or jax.default_backend()
    key = backend
    if dtype is not None and jnp.dtype(dtype) == jnp.dtype(jnp.bfloat16):
        key = f"{backend}:bf16"
    row = _THROUGHPUT.get(key)
    if row is None:
        raise ValueError(f"the planner has no cost row for backend "
                         f"{key!r}; known rows: {sorted(_THROUGHPUT)}")
    return row


def estimate_cost(strategy: str, stats: GraphStats, d: int,
                  backend: Optional[str] = None,
                  ring_stats=None, dtype=None,
                  comm: Optional[str] = None) -> float:
    """Estimated execution cost of one gspmm call, in element-ops.

    ``ring_stats`` (a :class:`~repro.core.partition.PartitionStats`)
    refines the ``ring`` estimate with the real bucket padding; without
    it the estimate assumes ideal balance over the active (or nominal)
    shard count. ``dtype`` (operand element type, default fp32) selects
    the per-precision throughput row and sizes the ring's communication
    term in bytes; ``comm`` ("none"/"int8", default the active ring
    context's wire mode) prices that term at the compressed payload —
    so auto can flip toward ``ring`` exactly when compression makes the
    exchange cheap enough.
    """
    tp = _throughput_row(backend, dtype)[strategy]
    dd = max(int(d), 1)
    if strategy in ("push", "segment"):
        work = stats.n_edges * dd
    elif strategy == "ell":
        work = stats.ell_padded_slots * dd
    elif strategy == "ring":
        # per-device slot work + per-stage ppermute traffic: the ring
        # wins when the parallel split beats the communication tax —
        # i.e. on big graphs with enough shards (graph size × S).
        ctx = active_ring()
        if ring_stats is not None:
            S = ring_stats.n_shards
            rows = ring_stats.rows_per_shard
            # per-device slot work: ragged per-bucket widths when the
            # partition carries them (slots = S · Σ_s w_s, the per-stage
            # diagonal maxima), else the dense S²·eb envelope — the two
            # coincide exactly when every bucket fills to eb
            slots = ring_stats.ragged_slots
            if slots <= 0:
                slots = S * S * ring_stats.eb
            work = (slots / S) * dd
            stages = ring_stats.ragged_stages
            if stages < 0:
                stages = S - 1
        else:
            S = ctx.n_shards if ctx is not None else _RING_DEFAULT_SHARDS
            rows = -(-max(stats.n_dst, 1) // S)
            work = (stats.n_edges / S) * dd          # ideal balance
            stages = S - 1
        if comm is None:
            comm = ctx.comm if ctx is not None else "none"
        itemsize = jnp.dtype(dtype or jnp.float32).itemsize
        _, wire = _wire_bytes(rows * dd, itemsize, comm)
        # _RING_COMM is calibrated per fp32 element — normalize the
        # wire payload back to fp32-equivalent elements. Ragged buckets
        # also truncate the ring: stages whose whole diagonal is empty
        # are never exchanged, so the comm term scales with the real
        # stage count, not S-1.
        comm_cost = _RING_COMM * stages * (wire / 4.0)
        return tp * work + comm_cost + _FIXED[strategy]
    else:  # onehot / pallas: the tile pack's padded bucket slots
        slots = stats.tile_slots
        if slots <= 0:
            raise ValueError(f"pricing {strategy!r} needs the tile pack's "
                             f"slot count (GraphStats.tile_slots, set by "
                             f"compute_stats)")
        if strategy == "pallas":
            dd = -(-dd // 128) * 128      # the kernel's 128-lane tiles
        work = slots * dd
    cost = tp * work + _FIXED[strategy]
    if strategy == "ell":
        cost += _ELL_CLASS_OVERHEAD * stats.ell_n_classes
    return cost


# --------------------------------------------------------------------- #
# ring (partitioned) execution context
# --------------------------------------------------------------------- #
@dataclasses.dataclass(frozen=True)
class RingContext:
    """An installed device mesh makes ``ring`` a planner candidate.

    ``comm`` declares the cross-shard wire mode ("none"/"int8") so the
    cost model prices the exchange at the payload that actually moves.
    """
    mesh: Any               # jax.sharding.Mesh
    axis: str = "data"
    mode: str = "contiguous"
    comm: str = "none"

    @property
    def n_shards(self) -> int:
        return int(self.mesh.shape[self.axis])


_RING_CTX: Optional[RingContext] = None


def active_ring() -> Optional[RingContext]:
    return _RING_CTX


@contextlib.contextmanager
def use_ring(mesh, axis: str = "data", mode: str = "contiguous",
             comm: str = "none"):
    """Enable partitioned (ring) execution for ``gspmm`` while active.

    Without an active context — or when the mesh is gone — ``ring``
    never qualifies: ``strategy="auto"`` plans single-device and a
    pinned ``"ring"`` falls back down the established chain.
    """
    from .partition import require_auto_mesh  # local: avoids cycle
    require_auto_mesh(mesh)
    global _RING_CTX
    prev = _RING_CTX
    _RING_CTX = RingContext(mesh=mesh, axis=axis, mode=mode, comm=comm)
    try:
        yield _RING_CTX
    finally:
        _RING_CTX = prev


# --------------------------------------------------------------------- #
# spec support predicates
# --------------------------------------------------------------------- #
# Binary ops the fused Pallas BR kernel implements (kernels/binary_reduce).
_PALLAS_BINOPS = ("add", "sub", "mul", "div")


def supports(strategy: str, spec, lhs_data, rhs_data) -> bool:
    """Can ``strategy`` execute this node-output spec at all?

    ``spec`` is a parsed ``BRSpec`` (duck-typed to avoid a circular
    import). Edge-output specs are planned separately — gspmm delegates
    them to ``gsddmm``, whose strategies live in :func:`plan_sddmm`.
    """
    red = spec.reduce
    if strategy in ("push", "segment"):
        return spec.out in ("u", "v") and red != "none"
    if spec.out != "v" or red == "none":
        return False
    if strategy == "ell":
        return True     # any ⊗, any operand targets, all reducers
    if strategy == "ring":
        # sharded weighted CR: source-node lhs, sum/mean, rank-2, plain
        # copy or a scalar edge weight (mean folds 1/deg into it)
        if red not in ("sum", "mean") or spec.lhs != "u":
            return False
        if lhs_data.ndim != 2:
            return False
        if spec.op == "copy":
            return True
        return (spec.op == "mul" and spec.rhs == "e"
                and rhs_data.ndim == 2 and rhs_data.shape[-1] == 1)
    # MXU formulations: rank-2 operands only, sum/mean only
    rank_ok = (lhs_data.ndim == 2
               and (rhs_data is None or rhs_data.ndim == 2))
    if not rank_ok or red not in ("sum", "mean"):
        return False
    if strategy == "onehot":
        if spec.lhs != "u":
            return False
        if spec.op == "copy":
            return True
        return (spec.op == "mul" and spec.rhs == "e"
                and rhs_data.shape[-1] == 1)
    if strategy == "pallas":
        if spec.op == "copy" and spec.lhs in ("u", "e"):
            return True
        if (spec.lhs == "u" and spec.rhs == "e"
                and spec.op in _PALLAS_BINOPS):
            return True
        return (spec.lhs == "e" and spec.rhs == "u"
                and spec.op in ("add", "mul"))
    raise ValueError(f"unknown strategy {strategy!r}")


# --------------------------------------------------------------------- #
# plan log + fallback warnings
# --------------------------------------------------------------------- #
_PLAN_LOG: Dict[Tuple[str, str], Counter] = {}
_LAST_PLAN: Dict[Tuple[str, str], str] = {}
_WARNED: set = set()


def _record(spec_name: str, requested: str, chosen: str,
            predicted: Optional[float] = None,
            dtype: Optional[str] = None) -> None:
    key = (spec_name, requested)
    _PLAN_LOG.setdefault(key, Counter())[chosen] += 1
    _LAST_PLAN[key] = chosen
    _obs_events.plan_event(spec_name, requested, chosen,
                           predicted_cost=predicted, dtype=dtype)


def plan_log() -> Dict[Tuple[str, str], Dict[str, int]]:
    """(op name, requested strategy) -> {chosen strategy: count}."""
    return {k: dict(v) for k, v in _PLAN_LOG.items()}


def clear_plan_log() -> None:
    _PLAN_LOG.clear()
    _LAST_PLAN.clear()


def last_plan(spec_name: str, requested: str = "auto") -> Optional[str]:
    """Most-recently chosen strategy for (op, requested), or None."""
    return _LAST_PLAN.get((spec_name, requested))


def _warn_fallback(spec_name: str, requested: str, chosen: str) -> None:
    key = (spec_name, requested)
    if key in _WARNED:
        return
    _WARNED.add(key)
    warnings.warn(f"strategy {requested!r} does not support {spec_name!r}"
                  f"; falling back to {chosen!r}", stacklevel=3)


# --------------------------------------------------------------------- #
# planner mode (cost model vs measure-and-cache autotune)
# --------------------------------------------------------------------- #
_MODE = os.environ.get("REPRO_PLANNER_MODE", "cost")


def set_mode(mode: str) -> None:
    """'cost' (default) or 'autotune' (measure candidates once, cache)."""
    global _MODE
    if mode not in ("cost", "autotune"):
        raise ValueError(f"unknown planner mode {mode!r}")
    _MODE = mode


def get_mode() -> str:
    return _MODE


def _measure(runner: Callable[[str], Any], strategy: str) -> float:
    jax.block_until_ready(runner(strategy))     # warmup/compile
    t0 = time.perf_counter()
    jax.block_until_ready(runner(strategy))
    return time.perf_counter() - t0


# --------------------------------------------------------------------- #
# the planner
# --------------------------------------------------------------------- #
@dataclasses.dataclass
class Plan:
    """Resolved execution plan for one gspmm call."""
    strategy: str
    requested: str
    reason: str                     # 'pinned' | 'cost' | 'autotune' | ...
    ell: Optional[ELLPack] = None
    tiles: Optional[TilePack] = None
    partition: Optional[Any] = None   # PartitionedGraph for 'ring'


def plan_gspmm(g: Graph, spec, lhs_data, rhs_data, *,
               requested: str = "auto",
               cache: Optional[PlanCache] = None,
               ell: Optional[ELLPack] = None,
               tiles: Optional[TilePack] = None,
               runner: Optional[Callable[[str], Any]] = None) -> Plan:
    """Pick the execution strategy (and packs) for one node-output BR.

    ``requested='auto'`` consults the cost model (or the autotune cache);
    an explicitly pinned strategy is honored when it supports the spec
    and falls back down :data:`FALLBACK_CHAIN` otherwise. ``runner``
    (optional) executes the call with a pinned strategy — used by
    autotune mode to measure candidates.
    """
    concrete = not graph_is_traced(g)
    if cache is None and concrete:
        cache = get_plan_cache(g)
    stats = cache.stats if cache is not None else None

    def pack_available(strategy: str) -> bool:
        if strategy in ("push", "segment"):
            return True
        if strategy == "ring":
            # needs a live mesh, one shared vertex space, and a
            # host-buildable partition (ring packs never build in-trace)
            ctx = active_ring()
            if ctx is None or stats is None:
                return False
            if stats.n_src != stats.n_dst:
                return False
            if cache is not None and cache.peek_partition(
                    ctx.n_shards, ctx.mode) is not None:
                return True
            return concrete and cache is not None
        kind = "ell" if strategy == "ell" else "tiles"
        explicit = ell if kind == "ell" else tiles
        if explicit is not None:
            return True
        if cache is not None and cache.peek(kind) is not None:
            return True
        # buildable on the host side only
        return concrete and cache is not None

    def ok(strategy: str) -> bool:
        return (supports(strategy, spec, lhs_data, rhs_data)
                and pack_available(strategy))

    if requested == "auto":
        chosen, reason = _plan_auto(spec, lhs_data, rhs_data, stats, ok,
                                    cache, runner, concrete)
    else:
        if requested not in STRATEGIES:
            raise ValueError(f"unknown strategy {requested!r}; expected "
                             f"one of {STRATEGIES + ('auto',)}")
        if ok(requested):
            chosen, reason = requested, "pinned"
        else:
            if requested == "ring":
                chain = _RING_FALLBACK
            elif requested in FALLBACK_CHAIN:
                chain = FALLBACK_CHAIN[FALLBACK_CHAIN.index(requested) + 1:]
            else:
                chain = ("segment",)
            chosen = next((s for s in chain if ok(s)), "segment")
            reason = f"fallback({requested})"
            _warn_fallback(spec.name, requested, chosen)

    plan = Plan(strategy=chosen, requested=requested, reason=reason)
    if chosen == "ell":
        plan.ell = ell if ell is not None else cache.ell()
    elif chosen in ("onehot", "pallas"):
        plan.tiles = tiles if tiles is not None else cache.tiles()
    elif chosen == "ring":
        ctx = active_ring()
        plan.partition = cache.partition(ctx.n_shards, ctx.mode)
    predicted = None
    if _obs_events.enabled() and stats is not None:
        d = int(np.prod(lhs_data.shape[1:])) if lhs_data.ndim > 1 else 1
        if chosen == "ring":
            ctx = active_ring()
            pgp = (cache.peek_partition(ctx.n_shards, ctx.mode)
                   if ctx is not None and cache is not None else None)
            predicted = estimate_cost(chosen, stats, d,
                                      ring_stats=None if pgp is None
                                      else pgp.stats,
                                      dtype=lhs_data.dtype)
        else:
            predicted = estimate_cost(chosen, stats, d,
                                      dtype=lhs_data.dtype)
    _record(spec.name, requested, chosen, predicted,
            dtype=str(lhs_data.dtype))
    return plan


def _plan_auto(spec, lhs_data, rhs_data, stats, ok, cache, runner,
               concrete) -> Tuple[str, str]:
    if stats is None:
        # traced graph with no cache: only static sizes are known, and
        # no pack can be built — segment is always valid and collision-free
        return "segment", "no-stats(traced)"
    d = int(np.prod(lhs_data.shape[1:])) if lhs_data.ndim > 1 else 1
    candidates = [s for s in _AUTO_CANDIDATES if ok(s)]
    if not candidates:           # out == 'u' etc. → segment path
        return "segment", "only-generic"
    operands_concrete = (not _is_traced(lhs_data)
                         and not _is_traced(rhs_data)
                         if rhs_data is not None else
                         not _is_traced(lhs_data))
    if (_MODE == "autotune" and concrete and operands_concrete
            and runner is not None and cache is not None):
        ring_ctx = active_ring()
        # the ring context is part of the key: a winner measured inside
        # use_ring() must not be replayed once the mesh is gone
        key = (spec.name, d, str(lhs_data.dtype),
               None if rhs_data is None else rhs_data.shape[-1],
               None if ring_ctx is None
               else (ring_ctx.n_shards, ring_ctx.axis, ring_ctx.mode))
        winner = cache._autotuned.get(key)
        if winner is None or winner not in candidates:
            times = {s: _measure(runner, s) for s in candidates}
            winner = min(times, key=times.get)
            cache._autotuned[key] = winner
        return winner, "autotune"
    ctx = active_ring()

    def cost(s):
        if s == "ring" and ctx is not None and cache is not None:
            pgp = cache.peek_partition(ctx.n_shards, ctx.mode)
            return estimate_cost(s, stats, d,
                                 ring_stats=None if pgp is None
                                 else pgp.stats, dtype=lhs_data.dtype)
        return estimate_cost(s, stats, d, dtype=lhs_data.dtype)

    chosen = min(candidates, key=cost)
    return chosen, "cost"


# --------------------------------------------------------------------- #
# block (sampled-minibatch) planning — shape-keyed, trace-safe
# --------------------------------------------------------------------- #
# Sampled blocks are padded to static shapes, so their planner features
# depend only on the shape signature (n_src_pad, n_dst_real, n_edges_pad,
# fanout) — not on the particular batch. Decisions are memoized on that
# signature (plus op/width/backend), which makes planning deterministic
# across batches and safe inside a jitted train step: the same compiled
# step serves every minibatch of a sampler configuration.
_BLOCK_PLANS: Dict[Tuple, str] = {}

# Candidates for auto mode on blocks. The uniform pull reuses the 'ell'
# cost entry (it IS a single-class ELL); onehot/pallas need host-built
# tile packs that cannot be rebuilt per batch, so they never qualify.
_BLOCK_AUTO_CANDIDATES = ("ell", "segment")
_BLOCK_FALLBACK = ("ell", "segment")


def block_stats(n_src: int, n_dst_real: int, n_edges: int,
                fanout: int) -> GraphStats:
    """Nominal :class:`GraphStats` of a padded block.

    Every real destination row holds at most ``fanout`` sampled in-edges
    and the neighbor table pads all rows TO ``fanout`` — so the block is
    a uniform single-class ELL by construction: max degree == avg degree
    == fanout, one width class, ``n_dst_real * fanout`` padded slots.
    """
    slots = n_dst_real * fanout
    return GraphStats(
        n_src=int(n_src), n_dst=int(n_dst_real), n_edges=int(n_edges),
        avg_in_deg=float(fanout), max_in_deg=int(fanout), skew=1.0,
        ell_padded_slots=int(slots), ell_n_classes=1,
        pad_ratio=float(slots / max(n_edges, 1)))


def clear_block_plans() -> None:
    _BLOCK_PLANS.clear()
    _BLOCK_BWD_PLANS.clear()


def plan_block_gspmm(signature: Tuple[int, int, int, int], spec, d: int,
                     requested: str = "auto",
                     runner: Optional[Callable[[str], Any]] = None,
                     dtype: Optional[str] = None) -> str:
    """Pick the execution strategy for one block aggregation.

    ``signature`` is :attr:`BlockGraph.signature` — static padded shapes
    only, so this function never touches traced values. The chosen
    strategy is memoized per (signature, op, width, requested, backend)
    and recorded in the plan log under ``block:<op>``.

    In autotune mode (``REPRO_PLANNER_MODE=autotune`` / ``set_mode``),
    ``runner`` — supplied by :func:`~repro.core.blocks.block_gspmm`
    only on *eager* calls with concrete operands — measures the
    candidates once per signature and the winner serves every later
    batch of that configuration, including calls inside the jitted
    train step (same key, already memoized; a traced call with no
    cached decision falls back to the cost model — measuring inside a
    trace is impossible).
    """
    from .blocks import block_supports  # local: blocks imports planner

    backend = jax.default_backend()
    key = (signature, spec.name, int(d), requested, backend, dtype)
    log_name = f"block:{spec.name}"
    chosen = _BLOCK_PLANS.get(key)
    if chosen is None:
        memoize = True
        if requested == "auto":
            candidates = [s for s in _BLOCK_AUTO_CANDIDATES
                          if block_supports(s, spec)]
            if not candidates:
                chosen = "segment"
            elif _MODE == "autotune" and runner is not None:
                times = {s: _measure(runner, s) for s in candidates}
                chosen = min(times, key=times.get)
            else:
                stats = block_stats(*signature)
                chosen = min(candidates,
                             key=lambda s: estimate_cost(s, stats, d,
                                                         backend=backend,
                                                         dtype=dtype))
                # in autotune mode a traced call (no runner) can't
                # measure — don't pin its cost-model stand-in, so a
                # later EAGER call of the same signature still gets to
                # autotune (the cost model is deterministic, so the
                # un-memoized answer is stable across traces)
                memoize = _MODE != "autotune"
        elif requested not in STRATEGIES:
            raise ValueError(f"unknown strategy {requested!r}; expected "
                             f"one of {STRATEGIES + ('auto',)}")
        elif block_supports(requested, spec):
            chosen = requested
        else:
            chosen = next((s for s in _BLOCK_FALLBACK
                           if block_supports(s, spec)), "segment")
            _warn_fallback(log_name, requested, chosen)
        if memoize:
            _BLOCK_PLANS[key] = chosen
    predicted = None
    if _obs_events.enabled() and chosen in ("push", "segment", "ell"):
        predicted = estimate_cost(chosen, block_stats(*signature), d,
                                  backend=backend, dtype=dtype)
    _record(log_name, requested, chosen, predicted, dtype=dtype)
    return chosen


# --------------------------------------------------------------------- #
# block BACKWARD planning — the reverse-table VJP vs autodiff scatter
# --------------------------------------------------------------------- #
# Autodiff of any forward block strategy computes ∂x with a scatter-add
# (the push pathology, paper §4). 'gather' is the reverse-block custom
# VJP (core/blocks.py): cotangents pulled over the sampler's src-sorted
# reverse table + one sorted segment reduce. For max/min the forward
# records an arg-extrema table on the neighbor grid and the pull masks
# cotangents to the winning slot — same reverse table, one extra
# comparison. 'scatter' is plain autodiff — the baseline, and the only
# option for prod (no scatter transpose at all). Decisions are memoized
# per shape signature
# exactly like the forward block plans and logged as ``block_bwd:<op>``,
# so forward and backward strategies are chosen independently.
BLOCK_BWD_STRATEGIES = ("gather", "scatter")

_BLOCK_BWD_PLANS: Dict[Tuple, str] = {}

# Collision/row-density term of the backward cost rows (ROADMAP PR-4
# follow-up). A scatter-add only serializes where updates collide; on
# small blocks the ∂x working set sits in cache and gather/scatter
# measure near parity (slightly pro-scatter), so the push-rate penalty
# is scaled by the block's row density AND by how much of the
# full-serialization edge-slot scale it reaches — below ~100k edge
# slots the tax vanishes and scatter's lack of reorder work wins. The
# gather path pays its reorder tax (reverse-table gather + permuted
# cotangent reads) unconditionally. Autotune mode still measures the
# truth per signature.
_BWD_COLLISION_SLOTS = 1_000_000   # full-serialization edge-slot scale
_BWD_GATHER_REORDER = 0.45         # gather's extra work vs one segment pass


def _block_bwd_cost(strategy: str, signature: Tuple[int, int, int, int],
                    d: int, backend: str) -> float:
    """Estimated cost of differentiating one block op (element-ops)."""
    n_src, _, slots, _ = signature
    tp = _throughput_row(backend, None)
    dd = max(int(d), 1)
    if strategy == "gather":
        return tp["segment"] * (1.0 + _BWD_GATHER_REORDER) * slots * dd
    rho = min(1.0, slots / max(n_src, 1))
    size = min(1.0, slots / _BWD_COLLISION_SLOTS)
    scatter_tp = tp["segment"] + (tp["push"] - tp["segment"]) * rho * size
    return scatter_tp * slots * dd


def block_bwd_supports(strategy: str, spec) -> bool:
    """Can ``strategy`` differentiate this block spec?

    'scatter' (autodiff) always can. 'gather' needs a node output and a
    sum/mean/max/min reducer: the reverse-table pull is the exact
    adjoint of the linear reducers, and the extrema reducers ride the
    same pull with cotangents masked to the recorded arg-extremum slot.
    Only prod stays on autodiff.
    """
    if strategy == "scatter":
        return True
    if strategy == "gather":
        return spec.out == "v" and spec.reduce in ("sum", "mean",
                                                   "max", "min")
    raise ValueError(f"unknown block backward strategy {strategy!r}")


def plan_block_vjp(signature: Tuple[int, int, int, int], spec, d: int,
                   requested: str = "auto", gather_available: bool = True,
                   runner: Optional[Callable[[str], Any]] = None,
                   dtype: Optional[str] = None) -> str:
    """Pick the backward (differentiation) strategy for one block op.

    Shape-keyed and memoized exactly like :func:`plan_block_gspmm`
    (``gather_available`` — whether the block carries a reverse table —
    is part of the key). The cost comparison pits the reverse pull (a
    sorted segment reduce over the same edge count) against the
    autodiff scatter-add; in autotune mode ``runner`` measures the two
    differentiated calls once per signature.
    """
    backend = jax.default_backend()
    key = (signature, spec.name, int(d), requested,
           bool(gather_available), backend)
    log_name = f"block_bwd:{spec.name}"
    chosen = _BLOCK_BWD_PLANS.get(key)
    if chosen is None:
        memoize = True

        def ok(s):
            return (block_bwd_supports(s, spec)
                    and (s != "gather" or gather_available))

        if requested == "auto":
            if not ok("gather"):
                chosen = "scatter"
            elif _MODE == "autotune" and runner is not None:
                times = {s: _measure(runner, s)
                         for s in BLOCK_BWD_STRATEGIES}
                chosen = min(times, key=times.get)
            else:
                chosen = min(BLOCK_BWD_STRATEGIES,
                             key=lambda s: _block_bwd_cost(
                                 s, signature, d, backend))
                # same rule as the forward block plans: a cost-model
                # stand-in computed in autotune mode is not pinned, so a
                # later eager call still gets to measure
                memoize = _MODE != "autotune"
        elif requested not in BLOCK_BWD_STRATEGIES:
            raise ValueError(
                f"unknown block backward strategy {requested!r}; expected "
                f"one of {BLOCK_BWD_STRATEGIES + ('auto',)}")
        elif ok(requested):
            chosen = requested
        else:
            chosen = "scatter"
            _warn_fallback(log_name, requested, chosen)
        if memoize:
            _BLOCK_BWD_PLANS[key] = chosen
    predicted = None
    if _obs_events.enabled():
        predicted = _block_bwd_cost(chosen, signature, d, backend)
    _record(log_name, requested, chosen, predicted, dtype=dtype)
    return chosen


# --------------------------------------------------------------------- #
# heterogeneous (relation-fused) planning — DESIGN.md §8
# --------------------------------------------------------------------- #
# A relational aggregation Σ_r CR(g_r) can run as R sequential calls
# ('loop' — the pre-refactor baseline, one gather + one reduce per
# relation), as ONE fused stream over the relation-stacked graph
# ('fused' — a single sorted segment reduce), or as the fused messages
# pushed through the fused graph's blocked pull ('ell'). The trade is
# per-relation dispatch overhead (loop pays R of them) against the
# fused paths' relation-indexing traffic and, for ell, the padding tax
# of the fused degree histogram. Decisions are memoized per static
# RelGraph signature × op × width × backend — trace-safe, like block
# plans — and logged as ``hetero:<op>``.
HETERO_STRATEGIES = ("fused", "loop", "ell")

_HETERO_PLANS: Dict[Tuple, str] = {}

_HETERO_REL_OVERHEAD = 2e4   # per-relation dispatch + reduce setup (elops)
_HETERO_FUSED_TAX = 0.1      # relation-id/W-indexing traffic multiplier
_HETERO_FIXED = 2e4          # one-time fused-stream setup

_HETERO_FALLBACK = ("fused", "loop")


def _hetero_cost(strategy: str, signature: Tuple[int, int, int, int],
                 d: int, backend: str,
                 stats: Optional[GraphStats] = None) -> Optional[float]:
    """Estimated cost of one relational aggregation (element-ops);
    None when the strategy has no model (ell without fused stats)."""
    _, _, n_edges, n_rel = signature
    tp = _throughput_row(backend, None)
    dd = max(int(d), 1)
    if strategy == "loop":
        return (tp["segment"] * n_edges * dd
                + n_rel * _HETERO_REL_OVERHEAD)
    if strategy == "fused":
        return (tp["segment"] * (1 + _HETERO_FUSED_TAX) * n_edges * dd
                + _HETERO_FIXED)
    if strategy == "ell" and stats is not None:
        return ((1 + _HETERO_FUSED_TAX)
                * estimate_cost("ell", stats, dd, backend=backend))
    if strategy == "push":
        return tp["push"] * n_edges * dd + n_rel * _HETERO_REL_OVERHEAD
    return None


def clear_hetero_plans() -> None:
    _HETERO_PLANS.clear()


def plan_hetero(signature: Tuple[int, int, int, int], op_name: str,
                d: int, requested: str = "auto",
                stats: Optional[GraphStats] = None, ell_ok: bool = True,
                runner: Optional[Callable[[str], Any]] = None) -> str:
    """Pick the execution strategy for one relational aggregation.

    ``signature`` is :attr:`RelGraph.signature` — static sizes only
    (n_src, n_dst, n_edges, n_rel). ``stats`` are the FUSED graph's
    :class:`GraphStats` (static aux on the RelGraph's PlanCache, so
    they survive ``jit``); they feed the ell row's padding estimate —
    without them (or with ``ell_ok=False``, e.g. in-trace with no
    prebuilt pack) ell never qualifies. Plain gspmm strategy names pin
    the per-relation loop with that inner reduce (``'push'`` is the
    fig2 baseline). In autotune mode an eager ``runner`` measures the
    candidates once per signature, exactly like block planning.
    """
    backend = jax.default_backend()
    key = (signature, op_name, int(d), requested, backend)
    log_name = f"hetero:{op_name}"
    chosen = _HETERO_PLANS.get(key)
    if chosen is None:
        memoize = True

        def candidates():
            cand = ["fused", "loop"]
            if ell_ok and stats is not None:
                cand.insert(1, "ell")
            return cand

        if requested == "auto":
            cand = candidates()
            if _MODE == "autotune" and runner is not None:
                times = {s: _measure(runner, s) for s in cand}
                chosen = min(times, key=times.get)
            else:
                chosen = min(cand, key=lambda s: _hetero_cost(
                    s, signature, d, backend, stats))
                memoize = _MODE != "autotune"
        elif requested in HETERO_STRATEGIES:
            if requested == "ell" and not ell_ok:
                chosen = "fused"
                _warn_fallback(log_name, requested, chosen)
            else:
                chosen = requested
        elif requested in STRATEGIES:
            # plain gspmm pin: the per-relation loop with that inner
            # reduce — 'push' is the scatter baseline, everything else
            # runs the loop's segment form
            chosen = "push" if requested == "push" else "loop"
        else:
            raise ValueError(
                f"unknown hetero strategy {requested!r}; expected one "
                f"of {HETERO_STRATEGIES + STRATEGIES + ('auto',)}")
        if memoize:
            _HETERO_PLANS[key] = chosen
    predicted = None
    if _obs_events.enabled():
        predicted = _hetero_cost(chosen, signature, d, backend, stats)
    _record(log_name, requested, chosen, predicted)
    return chosen


# --------------------------------------------------------------------- #
# gSDDMM (edge-output) planning — DESIGN.md §9
# --------------------------------------------------------------------- #
# Edge-output BRs (attention logits, the softmax chain's shift/divide,
# GCMC's bilinear decode) used to be strategy-free gathers. They are now
# planned like every other hot path, logged as ``sddmm:<op>``:
#
#   'gather'    — operands gathered straight into CALLER edge order
#                 (one eid_inv-indirected gather PER operand; the
#                 DGL-style baseline),
#   'canonical' — operands gathered in canonical (dst-sorted) order, ⊗
#                 on the sorted stream, ONE un-permute of the result —
#                 the dst-side reads stream instead of hopping,
#   'pallas'    — the canonical stream's ⊗ computed by the tiled Pallas
#                 kernel (kernels/sddmm) — the TPU form.
#
# Decisions are memoized per static (sizes, op, width, requested,
# backend, pallas-support) key — trace-safe like block plans — and
# autotune mode measures the candidates once per key on eager calls.
SDDMM_STRATEGIES = ("canonical", "gather", "pallas")

_SDDMM_PLANS: Dict[Tuple, str] = {}

# Relative per-element tax between the two universal forms. On
# accelerators the canonical stream wins (dst-side reads stream; the
# single output permute is cheap next to per-operand random gathers),
# so gather pays the tax. On CPU the measured ordering flips — XLA's
# random operand gathers are cheap and the full-width output un-permute
# dominates (benchmarks/fig_sddmm.py: canonical 1.5–4× slower) — so
# canonical pays it there. Autotune mode re-measures either way.
_SDDMM_GATHER_TAX = 1.25

_SDDMM_FALLBACK = ("canonical", "gather")


def clear_sddmm_plans() -> None:
    _SDDMM_PLANS.clear()
    _ATTN_PLANS.clear()


def sddmm_supports(strategy: str, spec, lhs_data, rhs_data) -> bool:
    """Can ``strategy`` execute this EDGE-output spec?

    canonical/gather are universal. The tiled Pallas kernel handles
    rank-2 floating operand streams whose widths match (or broadcast
    from 1) — the shapes the attention/decode ops actually produce.
    """
    if spec.out != "e":
        return False
    if strategy in ("canonical", "gather"):
        return True
    if strategy == "pallas":
        if not jnp.issubdtype(lhs_data.dtype, jnp.floating):
            return False
        if lhs_data.ndim != 2:
            return False
        if rhs_data is not None:
            if rhs_data.ndim != 2:
                return False
            if not jnp.issubdtype(rhs_data.dtype, jnp.floating):
                return False
            dl, dr = lhs_data.shape[-1], rhs_data.shape[-1]
            if dl != dr and 1 not in (dl, dr):
                return False
        return True
    raise ValueError(f"unknown sddmm strategy {strategy!r}")


def _sddmm_cost(strategy: str, n_edges: int, d: int, backend: str) -> float:
    tp = _throughput_row(backend, None)
    work = n_edges * max(int(d), 1)
    if strategy == "canonical":
        tax = _SDDMM_GATHER_TAX if backend == "cpu" else 1.0
        return tp["segment"] * tax * work
    if strategy == "gather":
        tax = 1.0 if backend == "cpu" else _SDDMM_GATHER_TAX
        return tp["segment"] * tax * work
    return tp["pallas"] * work + _FIXED["pallas"]


def plan_sddmm(signature: Tuple[int, int, int], spec, d: int,
               requested: str = "auto",
               lhs_data=None, rhs_data=None,
               runner: Optional[Callable[[str], Any]] = None) -> str:
    """Pick the execution strategy for one edge-output BR (gSDDMM).

    ``signature`` is ``(n_src, n_dst, n_edges)`` — static sizes only,
    so planning is trace-safe. Operand arrays (optional: their absence
    just disqualifies pallas) feed the support predicate; ``runner``
    measures candidates in autotune mode, exactly like block planning.
    Logged as ``sddmm:<op>``.
    """
    backend = jax.default_backend()
    pallas_ok = (lhs_data is not None
                 and sddmm_supports("pallas", spec, lhs_data, rhs_data))
    key = (tuple(signature), spec.name, int(d), requested, backend,
           pallas_ok)
    log_name = f"sddmm:{spec.name}"
    chosen = _SDDMM_PLANS.get(key)
    if chosen is None:
        n_edges = signature[2]
        memoize = True
        if requested == "auto":
            cand = [s for s in SDDMM_STRATEGIES
                    if s != "pallas" or pallas_ok]
            if _MODE == "autotune" and runner is not None:
                times = {s: _measure(runner, s) for s in cand}
                chosen = min(times, key=times.get)
            else:
                chosen = min(cand, key=lambda s: _sddmm_cost(
                    s, n_edges, d, backend))
                # cost stand-ins computed in autotune mode are not
                # pinned — a later eager call still gets to measure
                memoize = _MODE != "autotune"
        elif requested not in SDDMM_STRATEGIES:
            raise ValueError(
                f"unknown sddmm strategy {requested!r}; expected one of "
                f"{SDDMM_STRATEGIES + ('auto',)}")
        elif requested != "pallas" or pallas_ok:
            chosen = requested
        else:
            chosen = next(s for s in _SDDMM_FALLBACK
                          if s != "pallas" or pallas_ok)
            _warn_fallback(log_name, requested, chosen)
        if memoize:
            _SDDMM_PLANS[key] = chosen
    predicted = None
    if _obs_events.enabled():
        predicted = _sddmm_cost(chosen, signature[2], d, backend)
    _record(log_name, requested, chosen, predicted,
            dtype=None if lhs_data is None else str(lhs_data.dtype))
    return chosen


# --------------------------------------------------------------------- #
# fused-attention planning — logits+softmax+aggregate as ONE pass
# --------------------------------------------------------------------- #
# 'fused'  — the canonical single-pass jnp form (segment max/sum over
#            the dst-sorted stream, α never leaves registers→HBM as a
#            separate caller-order tensor);
# 'pallas' — the row-complete ELL megakernel (kernels/edge_softmax):
#            whole destination rows resident in VMEM, softmax AND the
#            weighted reduce in one kernel launch;
# 'ring'   — the partitioned composition (ring_edge_values →
#            bucket_softmax → ring_gspmm), pinned by the partitioned
#            model path.
# Logged under ONE name, ``attn:fused``, so plan logs show the
# attention pipeline as a single planned op rather than its pieces.
ATTN_STRATEGIES = ("fused", "pallas", "ring")

_ATTN_PLANS: Dict[Tuple, str] = {}

# The megakernel runs over a ROW-COMPLETE pack: whole destination rows
# resident per stripe. With the ragged per-class pack its padded-slot
# count is the degree histogram's pow2 row sum (ell_rowcomplete_padding)
# instead of n_rows × max_degree — the change that makes pallas a live
# candidate on power-law degree tails.
_ATTN_PALLAS_FIXED = 5e4


def _attn_cost(strategy: str, n_edges: int, hf: int, backend: str,
               padded_slots: Optional[int] = None) -> Optional[float]:
    """Estimated cost of one fused-attention pass (element-ops); None
    for ring (the partitioned composition has no single-device model)."""
    tp = _throughput_row(backend, None)
    if strategy == "fused":
        return tp["segment"] * n_edges * hf
    if strategy == "pallas":
        slots = n_edges if padded_slots is None else padded_slots
        # On CPU the megakernel lowers through interpret mode to the
        # same dense blocked pull the ell strategy runs — price its
        # slots at the ell rate; the true pallas rate is a TPU number.
        rate = tp["ell"] if backend == "cpu" else tp["pallas"]
        return rate * slots * hf + _ATTN_PALLAS_FIXED
    return None


def plan_attention(signature: Tuple[int, int, int], heads: int, feat: int,
                   requested: str = "auto", pallas_ok: bool = False,
                   padded_slots: Optional[int] = None,
                   dtype: Optional[str] = None) -> str:
    """Pick the fused-attention execution form; logged ``attn:fused``.

    ``signature`` = (n_src, n_dst, n_edges); ``pallas_ok`` — whether
    the row-complete uniform pack is available (host-side build, or
    prebuilt in the graph's cache); ``padded_slots`` refines the
    megakernel's padded work estimate (n_dst_nonzero * max_deg slots).
    """
    backend = jax.default_backend()
    key = (tuple(signature), int(heads), int(feat), requested, backend,
           bool(pallas_ok), padded_slots)
    chosen = _ATTN_PLANS.get(key)
    if chosen is None:
        n_edges = signature[2]
        hf = max(int(heads), 1) * max(int(feat), 1)
        if requested == "auto":
            cand = ["fused"] + (["pallas"] if pallas_ok else [])
            chosen = min(cand, key=lambda s: _attn_cost(
                s, n_edges, hf, backend, padded_slots))
        elif requested not in ATTN_STRATEGIES:
            raise ValueError(
                f"unknown attention strategy {requested!r}; expected one "
                f"of {ATTN_STRATEGIES + ('auto',)}")
        elif requested == "pallas" and not pallas_ok:
            chosen = "fused"
            _warn_fallback("attn:fused", requested, chosen)
        else:
            chosen = requested
        _ATTN_PLANS[key] = chosen
    predicted = None
    if _obs_events.enabled():
        hf = max(int(heads), 1) * max(int(feat), 1)
        predicted = _attn_cost(chosen, signature[2], hf, backend,
                               padded_slots)
    _record("attn:fused", requested, chosen, predicted, dtype=dtype)
    return chosen


# --------------------------------------------------------------------- #
# serving planning — how a micro-batched inference request executes
# --------------------------------------------------------------------- #
# 'layerwise' — each layer computed once for ALL nodes per refresh
#              (the full-graph training forward), requests answered by
#              cached row lookups: per-batch cost is the refresh edge
#              work amortized over the refresh period plus a gather;
# 'fanout'   — per-request full-neighbor L-hop block expansion through
#              forward_blocks: per-batch cost is the (shared-neighbor-
#              re-expanding) padded block edge work, but results are
#              never stale.
# Logged per op as ``serve:<op>`` so plan logs show serving decisions
# alongside kernel-strategy rows.
SERVE_MODES = ("layerwise", "fanout")

_SERVE_PLANS: Dict[Tuple, str] = {}

# Host-side gather + cache bookkeeping per served row, in the same
# edge-work currency as _THROUGHPUT (relative units, CPU-calibrated).
_SERVE_LOOKUP_COST = 8.0


def _serve_cost(mode: str, signature: Tuple[int, int, int, int],
                expansion_edges: int, refresh_batches: int) -> float:
    """Estimated per-batch cost of one serve mode (element-ops)."""
    n_edges, cls, layers = signature[1], signature[2], signature[3]
    if mode == "layerwise":
        per = max(int(refresh_batches), 1)
        return ((n_edges * max(layers, 1)) / per
                + _SERVE_LOOKUP_COST * cls)
    return float(expansion_edges)


def plan_serve(signature: Tuple[int, int, int, int], op_name: str = "infer",
               requested: str = "auto", *, expansion_edges: int,
               refresh_batches: int = 1024) -> str:
    """Pick the serve-time execution mode; logged ``serve:<op_name>``.

    ``signature`` = (n_nodes, n_edges, batch_class, n_layers);
    ``expansion_edges`` is the static padded edge-slot count of ONE
    fan-out batch of this class (sum over its block signatures);
    ``refresh_batches`` amortizes the layer-wise full-graph recompute
    over the expected batches between refreshes.
    """
    backend = jax.default_backend()
    key = (tuple(signature), op_name, requested, backend,
           int(expansion_edges), int(refresh_batches))
    log_name = f"serve:{op_name}"
    chosen = _SERVE_PLANS.get(key)
    if chosen is None:
        if requested == "auto":
            chosen = min(SERVE_MODES, key=lambda m: _serve_cost(
                m, signature, expansion_edges, refresh_batches))
        elif requested not in SERVE_MODES:
            raise ValueError(
                f"unknown serve mode {requested!r}; expected one of "
                f"{SERVE_MODES + ('auto',)}")
        else:
            chosen = requested
        _SERVE_PLANS[key] = chosen
    predicted = None
    if _obs_events.enabled():
        predicted = _serve_cost(chosen, signature, expansion_edges,
                                refresh_batches)
    _record(log_name, requested, chosen, predicted)
    return chosen


def clear_serve_plans() -> None:
    _SERVE_PLANS.clear()
