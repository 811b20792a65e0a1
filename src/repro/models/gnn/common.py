"""Shared GNN plumbing: graph bundles riding on the planner's PlanCache,
the one code path every app's sampled-minibatch forward runs on
(:func:`run_blocks` — see DESIGN.md §5), and the partitioned-execution
bundle every app's sharded forward runs on (DESIGN.md §6)."""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ...core.graph import Graph
from ...core.partition import PartitionedGraph
from ...core.planner import PlanCache, get_plan_cache
from ...core.tiling import ELLPack, TilePack
from ...core.training_ops import TrainingGraph, make_training_graph
from ...substrate.nn import dropout


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True, eq=False)
class GraphBundle:
    """Graph + its PlanCache + precomputed normalization weights.

    ``cache`` is the graph's process-wide :class:`PlanCache`: its packs
    are pytree children (so they cross ``jit`` as traced arrays) and its
    stats are static aux (so the planner can run its cost model inside a
    jitted train step). ``tg`` carries the reverse-graph packs so
    weighted Copy-Reduce runs blocked-pull in the BACKWARD pass too
    (core/training_ops.py). ``mean_norm``: per-edge 1/deg_in(dst) —
    mean aggregation as weighted CR.
    """
    g: Graph
    cache: PlanCache
    gcn_norm: Optional[jnp.ndarray]  # (n_edges,) 1/sqrt(d_u d_v), caller order
    tg: Optional[TrainingGraph]
    mean_norm: Optional[jnp.ndarray]  # (n_edges,) 1/deg_in(dst)

    # back-compat views onto the cache (never build)
    @property
    def ell(self) -> Optional[ELLPack]:
        return self.cache.peek("ell")

    @property
    def tiles(self) -> Optional[TilePack]:
        return self.cache.peek("tiles")

    def use_training_graph(self, strategy: str, d: int) -> bool:
        """Route through the custom-VJP blocked pull (fwd AND bwd)?
        Yes when ell is pinned, or under auto when the cost model
        prefers blocked pull at feature width ``d``."""
        return self.tg is not None and (
            strategy == "ell"
            or (strategy == "auto" and self.cache.prefers_ell(d)))

    def tree_flatten(self):
        return ((self.g, self.cache, self.gcn_norm, self.tg,
                 self.mean_norm), ())

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


def edge_norms(g: Graph) -> Tuple[np.ndarray, np.ndarray]:
    """Per-edge (gcn, mean) normalization weights in CALLER edge order:
    1/sqrt(deg_out(u)·deg_in(v)) and 1/deg_in(v), degrees clamped ≥ 1.
    The one home of this computation — shared by the full-graph bundle
    and the partitioned bundle."""
    deg_in = np.maximum(np.asarray(g.in_degrees, np.float64), 1)
    deg_out = np.maximum(np.asarray(g.out_degrees, np.float64), 1)
    src = np.asarray(g.src)
    dst = np.asarray(g.dst)
    w = 1.0 / np.sqrt(deg_out[src] * deg_in[dst])
    mean_w = 1.0 / deg_in[dst]
    # canonical order -> caller order
    w_caller = np.zeros_like(w)
    w_caller[np.asarray(g.eid)] = w
    m_caller = np.zeros_like(mean_w)
    m_caller[np.asarray(g.eid)] = mean_w
    return w_caller.astype(np.float32), m_caller.astype(np.float32)


def make_bundle(g: Graph, *, ell: bool = True, tiles: bool = False,
                ell_width: int = 64, training: bool = True,
                krel: Optional[int] = None) -> GraphBundle:
    """Assemble a bundle; packs are pulled from (and memoized in) the
    graph's PlanCache, so they are built at most once per process even
    across bundles and direct ``gspmm`` calls. ``krel=K`` prebuilds the
    K-relation RelGraph (MoNet's fused per-kernel aggregation) so it
    crosses jit with the cache."""
    w_caller, m_caller = edge_norms(g)
    cache = get_plan_cache(g)
    cache.set_ell_cap(ell_width)
    if ell or training:
        cache.ell()            # force-build so it crosses jit boundaries
    if tiles:
        cache.tiles()
    if krel is not None:
        cache.krel(krel)
    tg = make_training_graph(g, ell_width) if training else None
    return GraphBundle(
        g=g,
        cache=cache,
        gcn_norm=jnp.asarray(w_caller, jnp.float32),
        tg=tg,
        mean_norm=jnp.asarray(m_caller, jnp.float32),
    )


# --------------------------------------------------------------------- #
# partitioned (multi-device ring) execution bundle
# --------------------------------------------------------------------- #
@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True, eq=False)
class PartitionedBundle:
    """Partition plan + pre-bucketed normalization weights + the mesh.

    ``pg`` is the graph's memoized :class:`PartitionedGraph` (from the
    per-graph PlanCache, so the same partition serves direct ``gspmm``
    calls and the trains); ``gcn_w``/``mean_w`` are ``bundle.gcn_norm``/
    ``mean_norm`` scattered into the (S, S, eb) bucket layout. ``mesh``
    is static aux — ``None`` runs the emulated single-device ring, which
    is how the partitioned forwards stay testable everywhere.
    """
    pg: PartitionedGraph
    gcn_w: jnp.ndarray         # (S, S, eb) 1/sqrt(d_u d_v), 0 on pads
    mean_w: jnp.ndarray        # (S, S, eb) 1/deg_in(dst), 0 on pads
    mesh: Optional[Mesh] = dataclasses.field(
        default=None, metadata={"static": True})
    axis: str = dataclasses.field(default="data",
                                  metadata={"static": True})

    def tree_flatten(self):
        return ((self.pg, self.gcn_w, self.mean_w), (self.mesh, self.axis))

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, *aux)


def make_partitioned_bundle(g: Graph, n_shards: int, *,
                            mesh: Optional[Mesh] = None,
                            axis: str = "data",
                            mode: str = "contiguous") -> PartitionedBundle:
    """Assemble the partitioned bundle. The partition comes from (and is
    memoized in) the graph's PlanCache; the per-edge norms are the same
    quantities :func:`make_bundle` computes, bucketed once host-side."""
    pg = get_plan_cache(g).partition(n_shards, mode)
    w_caller, m_caller = edge_norms(g)
    return PartitionedBundle(
        pg=pg,
        gcn_w=pg.scatter_edges(jnp.asarray(w_caller)),
        mean_w=pg.scatter_edges(jnp.asarray(m_caller)),
        mesh=mesh, axis=axis)


def shard_partitioned(pb: PartitionedBundle, *arrays):
    """``device_put`` the bundle and padded node arrays onto the mesh:
    bucket tensors and (n_pad, ...) node tensors shard along the first
    axis, small index maps replicate. No-op without a mesh."""
    if pb.mesh is None:
        return (pb,) + arrays if arrays else pb
    mesh, axis = pb.mesh, pb.axis
    n_pad = pb.pg.n_pad

    def put(x):
        if hasattr(x, "ndim") and x.ndim >= 1 and (
                x.shape[0] in (pb.pg.n_shards, n_pad)):
            spec = P(axis, *([None] * (x.ndim - 1)))
        else:
            spec = P()
        return jax.device_put(x, NamedSharding(mesh, spec))

    out = jax.tree_util.tree_map(put, (pb,) + arrays)
    return out if arrays else out[0]


# --------------------------------------------------------------------- #
# sampled-minibatch (block) forward — one code path for every app
# --------------------------------------------------------------------- #
def pad_features(feats) -> jnp.ndarray:
    """Append one zero row so global id -1 (pad) gathers zeros."""
    feats = np.asarray(feats, np.float32)
    return jnp.asarray(np.vstack([feats,
                                  np.zeros((1, feats.shape[1]),
                                           np.float32)]))


def block_features(feats_padded: jnp.ndarray, ids) -> jnp.ndarray:
    """Gather input features for padded global ids (-1 → the zero row)."""
    ids = jnp.asarray(ids)
    safe = jnp.where(ids >= 0, ids, feats_padded.shape[0] - 1)
    return jnp.take(feats_padded, safe, axis=0)


def run_blocks(block_layer: Callable, layers: Sequence, blocks: Sequence,
               h: jnp.ndarray, *, strategy: str = "auto",
               bwd_strategy: str = "auto",
               activation: Callable = jax.nn.relu, train: bool = False,
               rng=None, drop: float = 0.0,
               scope: str = "block") -> jnp.ndarray:
    """Drive a per-app layer function over a minibatch's blocks.

    ``block_layer(lyr, blk, h, strategy=..., bwd_strategy=...)`` maps
    the layer-l frontier features ``h`` (n_src_pad, d) to destination
    features (n_dst_real, d'). Thanks to the sampler's dst-first source
    numbering the next block's frontier IS this block's destination set,
    so the loop just chains layers — exactly the full-graph forward with
    the graph swapped per layer. The final block's destinations are the
    seeds: the return value is (batch_size, d_out), no slicing needed.

    ``bwd_strategy`` is the block DIFFERENTIATION strategy (gather /
    scatter / auto — see DESIGN.md §7), threaded to every
    ``block_gspmm`` so the planner's ``block_bwd:<op>`` decisions apply
    inside a differentiated train step.

    Layer ``i`` runs under ``jax.named_scope(f"{scope}.layer{i}")``, so
    its device ops carry the model's layer in their names.
    """
    if len(layers) != len(blocks):
        raise ValueError(f"{len(layers)} layers but {len(blocks)} blocks: "
                         f"sampler fanouts must match model depth")
    for i, (lyr, blk) in enumerate(zip(layers, blocks)):
        with jax.named_scope(f"{scope}.layer{i}"):
            if train and rng is not None and drop > 0.0:
                rng, sub = jax.random.split(rng)
                h = dropout(sub, h, drop, train)
            h = block_layer(lyr, blk, h, strategy=strategy,
                            bwd_strategy=bwd_strategy)
            if i < len(layers) - 1:
                h = activation(h)
    return h
