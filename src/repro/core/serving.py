"""GNN inference serving tier — DESIGN.md §10.

Training optimizes epoch time; serving optimizes request latency under
concurrency. Three pieces turn the training-side machinery into a
low-latency inference service:

* :class:`MicroBatcher` — incoming node-id requests are coalesced into
  batches padded onto a small fixed set of *signature classes* (padded
  batch sizes). Every batch of a class has the same static shapes, so
  the block planner's shape-keyed decisions
  (:func:`~repro.core.planner.plan_block_gspmm`) and the jit cache are
  warm after one batch per class: steady state runs ZERO recompiles
  (enforced by :class:`~repro.data.SignatureTracker`).
* **Layer-wise full-neighbor inference** — at serve time there is no
  variance-reduction reason to sample, and per-request L-hop fan-out
  re-expansion recomputes every shared neighbor once per request
  (2210.03900's dominant inference cost). The layer-wise plan computes
  each layer once for ALL nodes per refresh and answers requests with
  row lookups; the fan-out path is kept as the planned alternative (and
  the benchmark baseline), exact because full-neighbor expansion keeps
  every in-edge (``fanout ≥ max in-degree``). Both modes are planner
  rows (:func:`~repro.core.planner.plan_serve`, logged ``serve:<op>``).
* :class:`FeatureCache` — a hot-node feature/embedding cache tier:
  a degree-ordered *pinned* set (never evicted) over an LRU overflow,
  with exact hit/miss/eviction accounting surfaced as a
  :class:`CacheStats` pytree. The layer-wise plan serves output
  embeddings through it; the fan-out plan pulls input features for the
  expanded frontier through it.

:class:`GNNServer` wires the three together for GCN / GraphSAGE / GAT
(homogeneous) and R-GCN (relational), reusing the training-path
forwards unchanged — every serve path is differentially pinned to the
full-graph forward it must reproduce (tests/launch/test_serve_gnn.py).
"""
from __future__ import annotations

import dataclasses
import time
from collections import OrderedDict
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import planner
from ..obs import metrics as _obs_metrics
from ..obs.spans import span as _span

__all__ = ["CacheStats", "FeatureCache", "MicroBatch", "MicroBatcher",
           "GNNServer", "hot_node_ids", "SERVE_APPS"]


# --------------------------------------------------------------------- #
# hot-node feature/embedding cache tier
# --------------------------------------------------------------------- #
@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class CacheStats:
    """Exact cache accounting — a pytree, so stats stack/aggregate with
    ``jax.tree_util`` like every other metrics bundle in the repo."""
    hits: int = 0
    misses: int = 0
    evictions: int = 0
    pinned_hits: int = 0
    size: int = 0          # resident LRU rows (excludes the pinned set)
    pinned: int = 0
    capacity: int = 0

    @property
    def lookups(self) -> int:
        return self.hits + self.misses

    @property
    def hit_ratio(self) -> float:
        n = self.lookups
        return float(self.hits) / n if n else 0.0

    def tree_flatten(self):
        return ((self.hits, self.misses, self.evictions, self.pinned_hits,
                 self.size, self.pinned, self.capacity), None)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


def hot_node_ids(degrees, k: int) -> np.ndarray:
    """The ``k`` highest-degree node ids, degree-ordered (descending,
    ties broken by id for determinism) — the pinned hot set. Power-law
    graphs concentrate traffic on exactly these rows."""
    deg = np.asarray(degrees)
    k = min(int(k), deg.shape[0])
    if k <= 0:
        return np.empty(0, np.int64)
    order = np.lexsort((np.arange(deg.shape[0]), -deg))
    return order[:k].astype(np.int64)


class FeatureCache:
    """Hot-row cache over a host-side backing row store.

    ``store`` is the authoritative (n, d) array (features or computed
    embeddings). ``pinned`` rows are resident forever — the
    degree-ordered hot set — and do not count against ``capacity``;
    everything else goes through an LRU of at most ``capacity`` rows.
    Duplicate ids inside one lookup hit on the second occurrence,
    exactly like an oracle dict replay (tests/core/test_serving_cache).

    :meth:`update` writes the backing store AND refreshes any resident
    copy in place, so the cache never serves a stale row (the
    invalidation contract the property tests pin down).
    """

    def __init__(self, store: np.ndarray, capacity: int,
                 pinned: Optional[np.ndarray] = None,
                 name: Optional[str] = None):
        self.store = np.asarray(store)
        if self.store.ndim < 1:
            raise ValueError("store must be at least 1-D (rows)")
        self.capacity = int(capacity)
        if self.capacity < 0:
            raise ValueError("capacity must be ≥ 0")
        # a named cache mirrors its counters into the metrics registry
        # (serve.cache.<name>.*), so snapshots carry CacheStats
        self.name = name
        self._pinned: Dict[int, np.ndarray] = {}
        if pinned is not None:
            for i in np.asarray(pinned).reshape(-1):
                self._pinned[int(i)] = self.store[int(i)].copy()
        self._lru: "OrderedDict[int, np.ndarray]" = OrderedDict()
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        self.pinned_hits = 0

    @property
    def pinned_ids(self) -> Tuple[int, ...]:
        return tuple(self._pinned)

    def resident(self, i: int) -> bool:
        """Is row ``i`` currently served without touching the store?"""
        return int(i) in self._pinned or int(i) in self._lru

    def lookup(self, ids) -> np.ndarray:
        """Rows for ``ids`` (any order, duplicates fine), with exact
        hit/miss/eviction accounting. Misses read the backing store and
        become LRU-resident (evicting the least recently used row when
        over capacity); hits refresh recency."""
        ids = np.asarray(ids).reshape(-1)
        h0, m0, e0 = self.hits, self.misses, self.evictions
        out = np.empty((ids.shape[0],) + self.store.shape[1:],
                       self.store.dtype)
        for j, raw in enumerate(ids):
            i = int(raw)
            row = self._pinned.get(i)
            if row is not None:
                self.hits += 1
                self.pinned_hits += 1
                out[j] = row
                continue
            row = self._lru.get(i)
            if row is not None:
                self.hits += 1
                self._lru.move_to_end(i)
                out[j] = row
                continue
            self.misses += 1
            row = self.store[i].copy()
            out[j] = row
            if self.capacity > 0:
                self._lru[i] = row
                if len(self._lru) > self.capacity:
                    self._lru.popitem(last=False)
                    self.evictions += 1
        if self.name is not None:
            pre = f"serve.cache.{self.name}"
            _obs_metrics.counter(f"{pre}.hits").inc(self.hits - h0)
            _obs_metrics.counter(f"{pre}.misses").inc(self.misses - m0)
            _obs_metrics.counter(
                f"{pre}.evictions").inc(self.evictions - e0)
        return out

    def update(self, ids, rows) -> None:
        """Write ``rows`` into the backing store and refresh resident
        copies in place — a later lookup NEVER sees the old value."""
        ids = np.asarray(ids).reshape(-1)
        rows = np.asarray(rows, self.store.dtype)
        rows = rows.reshape((ids.shape[0],) + self.store.shape[1:])
        for j, raw in enumerate(ids):
            i = int(raw)
            self.store[i] = rows[j]
            if i in self._pinned:
                self._pinned[i] = rows[j].copy()
            if i in self._lru:      # refresh, keep recency unchanged
                self._lru[i] = rows[j].copy()

    def invalidate(self, ids=None) -> None:
        """Drop LRU residency (all rows when ``ids`` is None); pinned
        rows re-read the store instead of dropping out."""
        if ids is None:
            self._lru.clear()
            for i in self._pinned:
                self._pinned[i] = self.store[i].copy()
            return
        for raw in np.asarray(ids).reshape(-1):
            i = int(raw)
            self._lru.pop(i, None)
            if i in self._pinned:
                self._pinned[i] = self.store[i].copy()

    def replace_store(self, store: np.ndarray) -> None:
        """Swap the backing store (a layer-wise refresh writing new
        embeddings) and refresh every resident row — counters survive,
        staleness does not."""
        store = np.asarray(store)
        if store.shape != self.store.shape:
            raise ValueError(f"replacement store shape {store.shape} != "
                             f"{self.store.shape}")
        self.store = store
        for i in self._pinned:
            self._pinned[i] = store[i].copy()
        for i in self._lru:
            self._lru[i] = store[i].copy()

    def stats(self) -> CacheStats:
        return CacheStats(hits=self.hits, misses=self.misses,
                          evictions=self.evictions,
                          pinned_hits=self.pinned_hits,
                          size=len(self._lru), pinned=len(self._pinned),
                          capacity=self.capacity)


# --------------------------------------------------------------------- #
# request micro-batching onto signature classes
# --------------------------------------------------------------------- #
@dataclasses.dataclass(frozen=True)
class MicroBatch:
    """One padded batch: ``ids[:n_real]`` are request node ids (caller
    order), the tail is pad (-1). ``spans`` maps each member request to
    its ``[start, stop)`` row range — responses are sliced from real
    rows only, so pad rows can never leak into a response."""
    ids: np.ndarray                      # (cls,) int64, -1 past n_real
    n_real: int
    cls: int                             # the padded signature class
    spans: Tuple[Tuple[int, int, int], ...]   # (rid, start, stop)


class MicroBatcher:
    """Coalesce request streams into signature-class batches.

    ``classes`` is the ascending set of padded batch sizes the serving
    tier compiles for — the batch-side analogue of the sampler's static
    shape signatures. Assignment is deterministic: a batch of ``n``
    real rows pads to the smallest class ≥ n; coalescing packs requests
    in arrival order and flushes when the next request would overflow
    the largest class. Requests larger than the largest class split
    into largest-class chunks (each chunk its own span row range).
    """

    def __init__(self, classes: Sequence[int] = (8, 32, 128)):
        cls = sorted(int(c) for c in classes)
        if not cls or cls[0] < 1:
            raise ValueError("classes must be ≥ 1")
        if len(set(cls)) != len(cls):
            raise ValueError("classes must be unique")
        self.classes = tuple(cls)

    def assign_class(self, n: int) -> int:
        """Smallest class that fits ``n`` real rows (the largest class
        for anything bigger — the caller chunks)."""
        if n < 1:
            raise ValueError("empty batch has no class")
        for c in self.classes:
            if n <= c:
                return c
        return self.classes[-1]

    def _emit(self, members: List[Tuple[int, np.ndarray]]) -> MicroBatch:
        n_real = sum(len(ids) for _, ids in members)
        cls = self.assign_class(n_real)
        ids = np.full(cls, -1, np.int64)
        spans = []
        at = 0
        for rid, req_ids in members:
            ids[at:at + len(req_ids)] = req_ids
            spans.append((rid, at, at + len(req_ids)))
            at += len(req_ids)
        return MicroBatch(ids=ids, n_real=n_real, cls=cls,
                          spans=tuple(spans))

    def coalesce(self, requests: Sequence[Tuple[int, Sequence[int]]]
                 ) -> List[MicroBatch]:
        """Pack ``(rid, node_ids)`` requests into padded class batches,
        preserving arrival order within and across batches."""
        cap = self.classes[-1]
        batches: List[MicroBatch] = []
        members: List[Tuple[int, np.ndarray]] = []
        n = 0
        for rid, req_ids in requests:
            req_ids = np.asarray(req_ids, np.int64).reshape(-1)
            if req_ids.size == 0:
                raise ValueError(f"request {rid}: empty node-id list")
            if (req_ids < 0).any():
                raise ValueError(f"request {rid}: negative node id")
            # oversize request: flush, then emit largest-class chunks
            while req_ids.size > cap:
                if members:
                    batches.append(self._emit(members))
                    members, n = [], 0
                batches.append(self._emit([(int(rid), req_ids[:cap])]))
                req_ids = req_ids[cap:]
            if n + req_ids.size > cap and members:
                batches.append(self._emit(members))
                members, n = [], 0
            members.append((int(rid), req_ids))
            n += req_ids.size
        if members:
            batches.append(self._emit(members))
        return batches

    @staticmethod
    def unpack(batch: MicroBatch, values: np.ndarray
               ) -> Dict[int, np.ndarray]:
        """Slice per-request responses out of a batch result. Only rows
        < ``n_real`` are reachable through the spans — pad rows are
        structurally excluded from every response."""
        if values.shape[0] < batch.n_real:
            raise ValueError(f"batch result has {values.shape[0]} rows "
                             f"< {batch.n_real} real requests")
        return {rid: values[start:stop]
                for rid, start, stop in batch.spans}


# --------------------------------------------------------------------- #
# the server
# --------------------------------------------------------------------- #
SERVE_APPS = ("gcn", "sage", "gat", "rgcn")


class GNNServer:
    """Micro-batched GNN inference over one (typed or plain) graph.

    ``app``: 'gcn' | 'sage' | 'gat' (plain graph ``g`` + ``feats``) or
    'rgcn' (pass ``rels`` — per-relation (src, dst) pairs — instead of
    relying on ``g``'s edges alone). Model ``params`` are the training
    pytrees, used unchanged.

    Each signature class resolves to a serve mode once, via
    :func:`repro.core.planner.plan_serve` (logged ``serve:infer``):

    * ``layerwise`` — every layer computed once for ALL nodes per
      :meth:`refresh`; a request is a row lookup through the hot-node
      cache. Exact by construction (it IS the full-graph forward).
    * ``fanout`` — per-request full-neighbor L-hop expansion through
      the training block path (``forward_blocks``); exact because the
      default ``fanout`` is the max in-degree (every in-edge kept, no
      sampling). The benchmark baseline, and the fallback when the
      output table is stale-intolerant.

    Zero steady-state recompiles are enforced: every served batch's
    static signature feeds a :class:`~repro.data.SignatureTracker`
    bounded by ``len(classes)`` per mode.
    """

    def __init__(self, app: str, params, g, feats, *,
                 rels: Optional[Sequence] = None,
                 mode: str = "auto",
                 classes: Sequence[int] = (8, 32, 128),
                 fanout: Optional[int] = None,
                 cache_rows: int = 4096, pin_hot: int = 256,
                 refresh_batches: int = 1024,
                 seed: int = 0):
        if app not in SERVE_APPS:
            raise ValueError(f"unknown serve app {app!r}; expected one "
                             f"of {SERVE_APPS}")
        if mode not in ("auto",) + planner.SERVE_MODES:
            raise ValueError(f"unknown serve mode {mode!r}; expected "
                             f"'auto' or one of {planner.SERVE_MODES}")
        # apps live above core — import lazily (same pattern as the
        # partition/hetero lazy imports) so core/__init__ stays acyclic
        from ..data.sampler import NeighborSampler
        from ..models.gnn import gat, gcn, rgcn, sage
        from ..models.gnn.common import make_bundle

        self.app = app
        self.params = params
        self.mode = mode
        self.batcher = MicroBatcher(classes)
        self.refresh_batches = int(refresh_batches)
        self.seed = int(seed)
        self._sampler_cls = NeighborSampler
        self._edge_rel = None

        if app == "rgcn":
            if rels is None:
                raise ValueError("app='rgcn' needs rels=[(src, dst), ...]")
            n = int(g.n_src) if g is not None else int(max(
                max(np.max(s), np.max(d)) for s, d in rels)) + 1
            self.g, edge_rel = rgcn.merged_graph(rels, n)
            self._edge_rel = np.asarray(edge_rel)
            self._rg = rgcn.build_relgraph(rels, n)
            mod, self._graph_arg = rgcn, self._rg
        else:
            if g is None:
                raise ValueError("plain-graph apps need g")
            self.g = g
            mod = {"gcn": gcn, "sage": sage, "gat": gat}[app]
            self._graph_arg = make_bundle(g)
        self._full_fn = mod.infer
        self._blocks_fn = mod.infer_blocks

        self.feats = np.asarray(feats, np.float32)
        self.n_layers = len(params["layers"])
        deg = np.asarray(self.g.in_degrees)
        max_deg = int(deg.max()) if deg.size else 0
        # full-neighbor default: keep every in-edge ⇒ serve is exact
        self.fanout = int(fanout) if fanout is not None else max(max_deg, 1)
        self.cache_rows = int(cache_rows)
        self._hot = hot_node_ids(deg, pin_hot)

        from ..obs.signatures import SignatureTracker
        # one signature per (class, mode) is the compile budget;
        # anything beyond that is a recompile leak
        self.tracker = SignatureTracker(
            limit=len(self.batcher.classes) * len(planner.SERVE_MODES),
            name="serve")
        self.compiles = 0
        self.served_batches = 0
        self.served_requests = 0

        self._out_cache: Optional[FeatureCache] = None
        self._feat_cache: Optional[FeatureCache] = None
        self._samplers: Dict[int, object] = {}
        self._infer_jit = jax.jit(
            lambda p, blocks, x: self._blocks_fn(p, blocks, x))
        self._mode_by_class: Dict[int, str] = {}

    # -- planning ------------------------------------------------------- #
    def _expansion_edges(self, cls: int) -> int:
        """Static edge-slot count of one fan-out batch of class ``cls``
        (the per-request re-expansion work the layer-wise plan avoids)."""
        from .blocks import serve_block_signature
        return sum(sig[2] for sig in serve_block_signature(
            cls, self.fanout, self.n_layers))

    def mode_for_class(self, cls: int) -> str:
        chosen = self._mode_by_class.get(cls)
        if chosen is None:
            chosen = planner.plan_serve(
                (self.g.n_src, self.g.n_edges, int(cls), self.n_layers),
                "infer", requested=self.mode,
                expansion_edges=self._expansion_edges(cls),
                refresh_batches=self.refresh_batches)
            self._mode_by_class[cls] = chosen
        return chosen

    # -- layer-wise plan ------------------------------------------------ #
    def refresh(self) -> CacheStats:
        """Recompute the layer-wise output table (each layer once, for
        all nodes — the training-path full forward, unchanged) and push
        it through the hot-node cache without dropping counters."""
        with _span("serve.refresh") as sp:
            logits = self._full_fn(self.params, self._graph_arg,
                                   jnp.asarray(self.feats))
            sp.fence(logits)
        store = np.asarray(jax.block_until_ready(logits))
        if self._out_cache is None:
            self._out_cache = FeatureCache(store, self.cache_rows,
                                           pinned=self._hot, name="out")
        else:
            self._out_cache.replace_store(store)
        return self._out_cache.stats()

    def update_features(self, ids, rows) -> None:
        """Feature update: write the input store (through the fan-out
        path's cache so it never serves stale rows) and recompute the
        layer-wise table — a stale output row is a wrong prediction."""
        ids = np.asarray(ids).reshape(-1)
        if self._feat_cache is not None:
            self._feat_cache.update(ids, rows)
        else:
            self.feats[ids] = np.asarray(rows, np.float32)
        if self._out_cache is not None:
            self.refresh()

    # -- fan-out plan --------------------------------------------------- #
    def _sampler(self, cls: int):
        s = self._samplers.get(cls)
        if s is None:
            s = self._sampler_cls(self.g, [self.fanout] * self.n_layers,
                                  batch_size=cls, seed=self.seed,
                                  edge_rel=self._edge_rel)
            self._samplers[cls] = s
        return s

    def _feature_rows(self, ids: np.ndarray) -> jnp.ndarray:
        """Input features for padded global ids, pulled through the
        hot-node cache (-1 pads read as zero rows)."""
        if self._feat_cache is None:
            self._feat_cache = FeatureCache(self.feats, self.cache_rows,
                                            pinned=self._hot, name="feat")
        ids = np.asarray(ids)
        x = np.zeros((ids.shape[0], self.feats.shape[1]), np.float32)
        real = ids >= 0
        if real.any():
            with _span("serve.cache_lookup", args={"cache": "feat"}):
                x[real] = self._feat_cache.lookup(ids[real])
        return jnp.asarray(x)

    def _serve_fanout(self, batch: MicroBatch) -> np.ndarray:
        sampler = self._sampler(batch.cls)
        with _span("serve.sample", args={"cls": batch.cls}):
            mb = sampler.sample(batch.ids[:batch.n_real],
                                np.zeros(batch.n_real, np.int64))
        x = self._feature_rows(np.asarray(mb.input_ids))
        self._observe(("fanout", batch.cls) + mb.shape_signature())
        with _span("serve.infer", args={"cls": batch.cls}) as sp:
            out = self._infer_jit(self.params, mb.blocks, x)
            sp.fence(out)
        return np.asarray(jax.block_until_ready(out))[:batch.n_real]

    # -- serving -------------------------------------------------------- #
    def _observe(self, signature: Tuple) -> None:
        # the shared train/serve accounting path (repro.obs.signatures)
        if self.tracker.observe_checked(signature):
            self.compiles += 1

    def serve_batch(self, batch: MicroBatch) -> np.ndarray:
        """(n_real, n_out) predictions for one coalesced batch."""
        t0 = time.perf_counter()
        mode = self.mode_for_class(batch.cls)
        if mode == "layerwise":
            if self._out_cache is None:
                self.refresh()
            self._observe(("layerwise", batch.cls))
            with _span("serve.cache_lookup", args={"cache": "out",
                                                   "cls": batch.cls}):
                out = self._out_cache.lookup(batch.ids[:batch.n_real])
        else:
            out = self._serve_fanout(batch)
        self.served_batches += 1
        # the batch latency histogram (out is host-side here — nothing
        # in flight)
        _obs_metrics.histogram("serve.batch_seconds").observe(
            time.perf_counter() - t0)
        return out

    def serve(self, requests: Sequence[Tuple[int, Sequence[int]]]
              ) -> Dict[int, np.ndarray]:
        """Serve ``(rid, node_ids)`` requests; returns rid → (len(ids),
        n_out) predictions, padded rows never included."""
        with _span("serve.batching"):
            batches = self.batcher.coalesce(requests)
        results: Dict[int, List[np.ndarray]] = {}
        for batch in batches:
            vals = self.serve_batch(batch)
            with _span("serve.respond"):
                for rid, rows in self.batcher.unpack(batch,
                                                     vals).items():
                    results.setdefault(rid, []).append(rows)
        self.served_requests += len(results)
        # a request split across largest-class chunks re-assembles here
        return {rid: parts[0] if len(parts) == 1
                else np.concatenate(parts, axis=0)
                for rid, parts in results.items()}

    def serve_requests(self, reqs) -> None:
        """Complete a list of :class:`~repro.data.ServeRequest`s (the
        request-queue protocol): compute, then fulfil each future."""
        try:
            out = self.serve([(r.rid, r.ids) for r in reqs])
        except Exception as e:                     # noqa: BLE001
            for r in reqs:
                r.set_error(e)
            return
        for r in reqs:
            r.set_result(out[r.rid])

    def run(self, request_queue, depth: int = 2) -> None:
        """Drain a :class:`~repro.data.RequestQueue` until it closes,
        with the coalescing window riding the existing
        :class:`~repro.data.Prefetcher` (batch assembly overlaps the
        device step, exactly like sampling overlaps training)."""
        from ..data.pipeline import prefetch
        it = iter(prefetch(request_queue, depth=depth))
        sentinel = object()
        while True:
            # intake (blocking on the coalescing window) and handling
            # are the two top-level spans — together they tile the
            # session wall time, so trace coverage is ~100%
            with _span("serve.intake"):
                reqs = next(it, sentinel)
            if reqs is sentinel:
                break
            with _span("serve.handle"):
                self.serve_requests(reqs)

    def warmup(self) -> None:
        """Trace every signature class once so steady-state request
        latency is a lookup/execute, never a compile."""
        for cls in self.batcher.classes:
            batch = MicroBatch(ids=np.concatenate(
                                   [np.zeros(1, np.int64),
                                    np.full(cls - 1, -1, np.int64)]),
                               n_real=1, cls=cls, spans=((0, 0, 1),))
            self.serve_batch(batch)

    def stats(self) -> Dict:
        """Serving counters + cache stats (a pytree-of-scalars dict)."""
        return {
            "served_batches": self.served_batches,
            "served_requests": self.served_requests,
            "signatures": len(self.tracker.seen),
            "compiles": self.compiles,
            "out_cache": (self._out_cache.stats()
                          if self._out_cache is not None else None),
            "feat_cache": (self._feat_cache.stats()
                           if self._feat_cache is not None else None),
        }
