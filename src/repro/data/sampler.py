"""Neighbor sampling for batched (sampled) GNN training — paper Fig. 3.

Produces fixed-shape (padded) mini-batch blocks so a single jitted train
step serves every batch: per layer l, a bipartite block graph from sampled
frontier nodes to the previous frontier. Two padding devices keep every
array shape static:

* node pads go into a trailing *dummy source slot* whose features are
  zero (``feats_fn`` maps global id -1 to a zero row);
* edge pads go into a trailing *dummy destination row*, so real rows'
  in-degrees — and therefore mean aggregation — are untouched.

Each block also carries the dense uniform neighbor table of
:class:`repro.core.blocks.BlockGraph` (built here for free from the
per-row sample lists), which is what the planner's blocked-pull strategy
consumes, plus per-edge GCN normalization weights gathered from the
FULL graph's degrees (pad edges get weight 0, so they contribute
exactly zero to weighted aggregation).

Alongside the forward table the sampler emits the block's *reverse
table* — the same edge list sorted (stably) by source slot — which is
what the reverse-block VJP pulls over to compute ∂x without a scatter
(core/blocks.py, DESIGN.md §7). Pad edges sort last (dummy source slot)
and keep pointing at the dummy destination row, so the table is
pad-poison safe by construction: a zero cotangent row masks them out.

Sampling is uniform WITHOUT replacement; a node with in-degree ≤ fanout
keeps all its in-edges — so with ``fanout ≥ max in-degree`` the blocks
reproduce the full graph exactly (tests/data/test_sampler.py holds the
sampled forward to the full-graph forward under that condition).

:class:`SampledBlock` and :class:`MiniBatch` are registered pytrees:
a whole minibatch is passed straight into a jitted train step, and its
static aux (padded sizes, fanout) keys the compilation cache — one
compile per sampler configuration.
"""
from __future__ import annotations

import dataclasses
from typing import Iterator, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.blocks import BlockGraph
from ..core.graph import Graph, from_dst_sorted
from ..obs.metrics import counter as _counter
from ..obs.spans import span as _span


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True, eq=False)
class SampledBlock:
    """One bipartite layer of a minibatch (outer hop = larger side).

    ``bg`` holds the padded block graph + uniform neighbor table + the
    src-sorted reverse table (the gather backward's lookup structure);
    ``src_ids`` the global node id per source slot (-1 = pad);
    ``gcn_norm`` per-edge 1/√(deg_out(u)·deg_in(v)) from the FULL
    graph's degrees, caller edge order, 0 on pad edges.

    Relational sampling (``NeighborSampler(..., edge_rel=...)``,
    DESIGN.md §8.5) additionally tags every sampled edge: ``rel`` is
    the relation id (0 on pad edges — harmless, pads point at the
    dummy destination row) and ``rel_norm`` the per-(destination,
    relation) sampled-mean weight 1/|sampled N_r(v)| (0 on pads), both
    in caller edge order — what ``hetero_block_gspmm`` consumes.
    """
    bg: BlockGraph
    src_ids: jnp.ndarray        # (n_src_pad,) int32 global ids, -1 = pad
    gcn_norm: jnp.ndarray       # (n_edges_pad,) float32, 0 on pads
    rel: Optional[jnp.ndarray] = None       # (n_edges_pad,) int32
    rel_norm: Optional[jnp.ndarray] = None  # (n_edges_pad,) float32

    @property
    def graph(self) -> Graph:   # back-compat view
        return self.bg.g

    def tree_flatten(self):
        return ((self.bg, self.src_ids, self.gcn_norm, self.rel,
                 self.rel_norm), ())

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True, eq=False)
class MiniBatch:
    """Blocks (outermost hop first) + seeds. ``label_mask`` is False on
    pad seeds (short final batch padded up to the static batch size) —
    the train step masks their loss rows out."""
    blocks: Tuple[SampledBlock, ...]
    input_ids: jnp.ndarray      # (n_input_pad,) global node ids, -1 = pad
    seed_ids: jnp.ndarray       # (batch,) global seed ids, -1 = pad
    labels: jnp.ndarray         # (batch,) pad rows hold 0
    label_mask: jnp.ndarray     # (batch,) bool

    def tree_flatten(self):
        return ((self.blocks, self.input_ids, self.seed_ids, self.labels,
                 self.label_mask), ())

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    def shape_signature(self) -> Tuple:
        """Static padded-shape signature — identical for every batch of
        one sampler configuration (bounded jit compilations)."""
        return tuple(b.bg.signature for b in self.blocks)


class NeighborSampler:
    """Uniform without-replacement neighbor sampler over incoming edges.

    Sampling is fully vectorized: one batched random-key draw per layer
    (argsorted per row — a uniform without-replacement sample of each
    row's incoming edge slots) instead of a Python loop per destination.
    The stream is deterministic per seed: the same seed replays the
    same batches bit for bit (tests/data/test_sampler.py).
    """

    def __init__(self, g: Graph, fanouts: Sequence[int], batch_size: int,
                 seed: int = 0, edge_rel=None):
        self.indptr = np.asarray(g.indptr_dst, np.int64)
        self.src = np.asarray(g.src, np.int64)
        # relational sampling: per-edge relation ids (caller order) →
        # canonical order, so a sampled edge slot looks its type up
        # directly; blocks then carry rel + per-(dst, rel) mean norms
        if edge_rel is not None:
            edge_rel = np.asarray(edge_rel, np.int64)
            self.rel = edge_rel[np.asarray(g.eid)]
            self.n_rel = int(edge_rel.max()) + 1 if edge_rel.size else 0
        else:
            self.rel = None
            self.n_rel = 0
        self.fanouts = list(fanouts)
        self.batch_size = batch_size
        self.seed = seed
        self.rng = np.random.default_rng(seed)
        self.n = g.n_dst
        self.n_nodes = max(g.n_src, g.n_dst)
        # persistent generation-stamped slot table: sample() maps global
        # node ids to block-local slots in O(touched) per layer instead
        # of allocating/clearing an O(n_nodes) array per call
        self._slot = np.zeros(self.n_nodes, np.int64)
        self._slot_gen = np.zeros(self.n_nodes, np.int64)
        self._gen = 0
        # full-graph degrees for GCN-style symmetric normalization
        self.deg_in = np.maximum(np.asarray(g.in_degrees, np.float64), 1)
        self.deg_out = np.maximum(np.asarray(g.out_degrees, np.float64), 1)
        # label masks depend only on the real-seed count (at most two
        # values per epoch: full batches + one short tail) — cache the
        # device arrays instead of re-building/re-uploading per batch
        self._mask_cache: dict = {}
        # static padded sizes per layer (innermost = batch itself)
        self.layer_sizes = [batch_size]
        for f in reversed(self.fanouts):
            self.layer_sizes.append(self.layer_sizes[-1] * (f + 1))

    def reset(self, seed: Optional[int] = None) -> None:
        """Re-seed the sampling stream (determinism: same seed ⇒ same
        batches, bit for bit)."""
        self.rng = np.random.default_rng(self.seed if seed is None
                                         else seed)

    # ------------------------------------------------------------------ #
    @staticmethod
    def _sample_layer(indptr, rng, frontier: np.ndarray, fanout: int
                      ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Batched uniform without-replacement draw for one layer.

        One random-key matrix per layer: each row gets a key per
        candidate edge slot (∞ past its degree); the argsort's first
        ``min(deg, fanout)`` columns are a uniform without-replacement
        sample of that row's incoming edge slots — all of them when the
        degree fits the fanout. Returns ``(kmask, eslot, take)`` with
        ``kmask``: (n_rows, fanout) valid-sample mask, ``eslot``:
        (n_rows, fanout) global edge slots (garbage where masked).
        """
        n_rows = len(frontier)
        valid = frontier >= 0
        safe = np.where(valid, frontier, 0)
        lo = indptr[safe]
        deg = np.where(valid, indptr[safe + 1] - lo, 0)
        take = np.minimum(deg, fanout)
        # rows whose degree fits keep every in-edge in CSR order — no
        # randomness; only over-fanout rows draw keys, grouped into
        # power-of-two degree classes so the key matrix width tracks
        # each class, not the global max degree (power-law graphs put a
        # handful of huge rows next to thousands of small ones)
        pos = np.broadcast_to(np.arange(fanout, dtype=np.int64),
                              (n_rows, fanout)).copy()
        big = np.nonzero(deg > fanout)[0]
        if len(big):
            cls = np.ceil(np.log2(deg[big])).astype(np.int64)
            for c in np.unique(cls):
                r = big[cls == c]
                K = int(deg[r].max())
                keys = rng.random((len(r), K))
                keys[np.arange(K)[None, :] >= deg[r][:, None]] = np.inf
                pos[r] = np.argpartition(keys, fanout - 1,
                                         axis=1)[:, :fanout]
        kmask = np.arange(fanout)[None, :] < take[:, None]
        return kmask, lo[:, None] + pos, take

    def sample(self, seeds: np.ndarray, labels: np.ndarray,
               rng: Optional[np.random.Generator] = None) -> MiniBatch:
        """Build fully static-shape (node- AND edge-padded) blocks.

        Each block graph has ``n_dst + 1`` destination rows; padded edges
        point at the extra dummy row, so real rows are untouched and a
        single jitted step serves every batch. Consumers slice
        ``[:n_dst]`` (``block_gspmm`` does it internally).

        The call is a ``sampler.sample`` span whose args count the real
        sampled edges and sources against their padded slots over all
        blocks (``edges``, ``edge_slots``, ``src``, ``src_slots``), with
        one ``sampler.draw``, ``sampler.graph`` and ``sampler.upload``
        child per block (and a last ``sampler.upload`` for the seeds'
        arrays); the registry counters ``sampler.edges`` and
        ``sampler.edge_slots`` add up the same counts.
        """
        if rng is None:
            rng = self.rng
        with _span("sampler.sample") as sp:
            mb, counts = self._sample(seeds, labels, rng)
            sp.set(**counts)
        _counter("sampler.edges").inc(counts["edges"])
        _counter("sampler.edge_slots").inc(counts["edge_slots"])
        return mb

    def _sample(self, seeds, labels, rng):
        seeds = np.asarray(seeds, np.int64)
        labels = np.asarray(labels, np.int64)
        n_real_seeds = len(seeds)
        if len(seeds) < self.batch_size:     # short final batch: pad seeds
            pad = self.batch_size - len(seeds)
            seeds = np.concatenate([seeds, np.full(pad, -1, np.int64)])
            labels = np.concatenate([labels, np.zeros(pad, np.int64)])
        label_mask = self._mask_cache.get(n_real_seeds)
        if label_mask is None:
            label_mask = jnp.asarray(
                np.arange(self.batch_size) < n_real_seeds)
            self._mask_cache[n_real_seeds] = label_mask

        blocks: List[SampledBlock] = []
        frontier = seeds
        counts = dict(edges=0, edge_slots=0, src=0, src_slots=0)
        for li, fanout in enumerate(reversed(self.fanouts)):
            n_dst = self.layer_sizes[li]
            n_src_pad = self.layer_sizes[li + 1]
            n_edges_pad = n_dst * fanout
            with _span("sampler.draw"):
                kmask, eslot, take = self._sample_layer(self.indptr, rng,
                                                        frontier, fanout)
                # real sampled edges in row-major (canonical) order
                jj, kk = np.nonzero(kmask)
                nbs = self.src[eslot[jj, kk]]
                # dst-first source numbering: src slot j == dst node j,
                # so a layer can read its destinations' own features as
                # h[:n_dst]. First-occurrence slot table (reversed
                # writes: first wins); a stamp != current generation
                # means "unassigned".
                self._gen += 1
                slot, gen = self._slot, self._slot_gen
                idxs = np.nonzero(frontier >= 0)[0]
                fv = frontier[idxs][::-1]
                slot[fv] = idxs[::-1]
                gen[fv] = self._gen
                # newly discovered neighbors, in first-occurrence order
                new_vals = nbs[gen[nbs] != self._gen]
                uvals, first = np.unique(new_vals, return_index=True)
                new_unique = uvals[np.argsort(first, kind="stable")]
                slot[new_unique] = n_dst + np.arange(len(new_unique))
                gen[new_unique] = self._gen
                n_real_src = n_dst + len(new_unique)
                # pad sources to static size; dummy source = last slot
                src_ids = np.concatenate([
                    frontier, new_unique,
                    np.full(n_src_pad - n_real_src, -1, np.int64)])
                srcs = slot[nbs]
            with _span("sampler.graph"):
                n_real = len(jj)
                nbr = np.full((n_dst, fanout), n_src_pad - 1, np.int32)
                nbr[jj, kk] = srcs
                # pad slots of the neighbor table index SOME valid edge
                # id; they are masked, so the value never reaches a
                # reduction
                nbr_eid = np.full((n_dst, fanout),
                                  min(n_real, n_edges_pad - 1), np.int32)
                nbr_eid[kmask] = np.arange(n_real, dtype=np.int32)
                nbr_mask = kmask
                # pad edges into the dummy destination row n_dst (never
                # any real source slot: a pad edge exists only when some
                # row is under fanout, which leaves the dummy source slot
                # free)
                pad = n_edges_pad - n_real
                norms = np.zeros(n_edges_pad, np.float32)
                norms[:n_real] = 1.0 / np.sqrt(self.deg_out[nbs]
                                               * self.deg_in[frontier[jj]])
                real_deg = take.astype(np.int32)    # == kmask row sums
                # the block graph and its reverse table (the padded edge
                # list stably sorted by source slot — what the gather
                # backward pulls over), built from the row-major layout:
                # only the real edges are sorted. Pad edges (dummy source
                # = last slot) sort last; their dst is the dummy row, so
                # a zero cotangent row masks them exactly.
                g, (rev_src, rev_dst, rev_eid) = from_dst_sorted(
                    srcs, jj, n_src=n_src_pad, n_dst=n_dst + 1,
                    n_edges=n_edges_pad)
                rel_e = rel_w = None
                if self.rel is not None:
                    # relation id per sampled edge + the per-(dst,
                    # relation) sampled-mean weight 1/|sampled N_r(v)|;
                    # pad edges get rel 0 / weight 0 (they point at the
                    # dummy row anyway)
                    rel_e = self.rel[eslot[jj, kk]]
                    key = jj * self.n_rel + rel_e
                    cnt = np.bincount(key,
                                      minlength=n_dst * max(self.n_rel, 1))
                    rel_e = np.concatenate([rel_e, np.zeros(pad, np.int64)])
                    rel_w = np.concatenate(
                        [1.0 / cnt[key], np.zeros(pad)]).astype(np.float32)
            with _span("sampler.upload"):
                bg = BlockGraph(g=g, nbr=jnp.asarray(nbr),
                                nbr_eid=jnp.asarray(nbr_eid),
                                nbr_mask=jnp.asarray(nbr_mask),
                                real_deg=jnp.asarray(real_deg),
                                n_dst_real=n_dst, fanout=fanout,
                                rev_src=jnp.asarray(rev_src),
                                rev_dst=jnp.asarray(rev_dst),
                                rev_eid=jnp.asarray(rev_eid))
                blocks.append(SampledBlock(
                    bg=bg, src_ids=jnp.asarray(src_ids, jnp.int32),
                    gcn_norm=jnp.asarray(norms),
                    rel=(None if rel_e is None
                         else jnp.asarray(rel_e, jnp.int32)),
                    rel_norm=None if rel_w is None else jnp.asarray(rel_w)))
            counts["edges"] += n_real
            counts["edge_slots"] += n_edges_pad
            counts["src"] += len(idxs) + len(new_unique)
            counts["src_slots"] += n_src_pad
            frontier = src_ids
        blocks.reverse()
        with _span("sampler.upload"):
            mb = MiniBatch(blocks=tuple(blocks),
                           input_ids=blocks[0].src_ids,
                           seed_ids=jnp.asarray(seeds, jnp.int32),
                           labels=jnp.asarray(labels, jnp.int32),
                           label_mask=jnp.asarray(label_mask))
        return mb, counts

    def batches(self, node_ids: np.ndarray, labels: np.ndarray,
                drop_last: bool = True) -> Iterator[MiniBatch]:
        """Shuffled minibatches. With ``drop_last=False`` the short final
        batch is padded up to ``batch_size`` (masked via ``label_mask``)
        so even the tail reuses the one compiled step.

        The whole epoch is drawn from a child RNG seeded EAGERLY (one
        draw from the sampler stream per call, before the generator
        runs), so a prefetch thread abandoned mid-epoch can never leave
        the shared stream in a timing-dependent state — epoch k's
        batches depend only on the seed and k, bit for bit.
        """
        node_ids = np.asarray(node_ids)
        labels = np.asarray(labels)
        child = np.random.default_rng(int(self.rng.integers(2 ** 63)))

        def gen() -> Iterator[MiniBatch]:
            order = child.permutation(len(node_ids))
            stop = (len(order) - self.batch_size + 1 if drop_last
                    else len(order))
            for s in range(0, stop, self.batch_size):
                idx = order[s:s + self.batch_size]
                yield self.sample(node_ids[idx], labels[idx], rng=child)

        return gen()
