"""GraphSAGE (mean aggregator) — config: u_copy_add_v (paper Table 2).

Full-graph and sampled (paper Fig. 3) variants. h'_v =
σ(W·[h_v ; mean_{u∈N(v)} h_u]).
"""
from __future__ import annotations

from typing import Dict, List

import jax
import jax.numpy as jnp

from ...core.binary_reduce import gspmm
from ...core.blocks import block_gspmm
from ...core.partition import ring_gspmm, ring_gspmm_delayed
from ...core.training_ops import weighted_copy_reduce
from ...substrate.nn import linear_init, linear_apply, dropout
from .common import GraphBundle, PartitionedBundle, run_blocks


def init(key, d_in: int, d_hidden: int, n_classes: int,
         n_layers: int = 2) -> Dict:
    dims = [d_in] + [d_hidden] * (n_layers - 1) + [n_classes]
    keys = jax.random.split(key, n_layers)
    return {"layers": [linear_init(k, 2 * dims[i], dims[i + 1])
                       for i, k in enumerate(keys)]}


def forward(params: Dict, bundle: GraphBundle, x: jnp.ndarray, *,
            strategy: str = "auto", train: bool = False, rng=None,
            drop: float = 0.5) -> jnp.ndarray:
    h = x
    n_layers = len(params["layers"])
    for i, lyr in enumerate(params["layers"]):
        with jax.named_scope(f"sage.layer{i}"):
            if train and rng is not None:
                rng, sub = jax.random.split(rng)
                h = dropout(sub, h, drop, train)
            if bundle.use_training_graph(strategy, h.shape[-1]):
                # mean = weighted CR with 1/deg(dst); blocked pull both
                # ways
                hn = weighted_copy_reduce(bundle.tg, h,
                                          bundle.mean_norm[:, None])
            else:
                hn = gspmm(bundle.g, "u_copy_mean_v", u=h,
                           strategy=strategy, cache=bundle.cache)
            h = linear_apply(lyr, jnp.concatenate([h, hn], axis=-1))
            if i < n_layers - 1:
                h = jax.nn.relu(h)
    return h


def block_layer(lyr, blk, h: jnp.ndarray, *, strategy: str = "auto",
                bwd_strategy: str = "auto") -> jnp.ndarray:
    """One SAGE layer on a sampled block: mean over sampled in-edges
    (mask-corrected, pad slots contribute zero) concat the destination's
    own features (dst-first numbering: ``h[:n_dst_real]``)."""
    bg = blk.bg
    hn = block_gspmm(bg, "u_copy_mean_v", u=h, strategy=strategy,
                     bwd_strategy=bwd_strategy)
    return linear_apply(lyr, jnp.concatenate(
        [h[: bg.n_dst_real], hn], axis=-1))


def forward_blocks(params: Dict, blocks, x: jnp.ndarray, *,
                   strategy: str = "auto", bwd_strategy: str = "auto",
                   train: bool = False, rng=None,
                   drop: float = 0.5) -> jnp.ndarray:
    """Sampled mini-batch forward (paper Fig. 3) on the shared path."""
    return run_blocks(block_layer, params["layers"], blocks, x,
                      strategy=strategy, bwd_strategy=bwd_strategy,
                      activation=jax.nn.relu,
                      train=train, rng=rng, drop=drop, scope="sage")


def init_halo(params: Dict, pg):
    """Zero remote-partial carry per layer: SAGE aggregates the layer
    INPUT (before the linear), so the halo width is w.shape[0] // 2."""
    return tuple(jnp.zeros((pg.n_pad, lyr["w"].shape[0] // 2), jnp.float32)
                 for lyr in params["layers"])


def init_comm(params: Dict, pg):
    """Zero error-feedback residual for int8-compressed ring exchanges:
    fp32, at the exchange payload's shape — SAGE exchanges the layer
    INPUT (width w.shape[0] // 2). See DESIGN.md §12."""
    return tuple(jnp.zeros((pg.n_pad, lyr["w"].shape[0] // 2), jnp.float32)
                 for lyr in params["layers"])


def forward_partitioned(params: Dict, pb: PartitionedBundle,
                        x: jnp.ndarray, *, halo=None, refresh: bool = True,
                        comm_state=None, train: bool = False, rng=None,
                        drop: float = 0.5):
    """Partitioned full-graph forward: the neighbor mean is a weighted
    ring CR (1/deg folded into ``pb.mean_w``); the self term needs no
    communication. Optional DistGNN-style delayed halo as in GCN, and
    optional int8-compressed exchanges via ``comm_state`` (a tuple from
    :func:`init_comm`) — the return then grows to
    ``(logits_pad, halo_out, comm_out)``."""
    pg = pb.pg
    h = x
    halo_out = []
    comm_out = []
    n_layers = len(params["layers"])
    for i, lyr in enumerate(params["layers"]):
        if train and rng is not None:
            rng, sub = jax.random.split(rng)
            h = dropout(sub, h, drop, train)
        if halo is None:
            if comm_state is None:
                hn = ring_gspmm(pg, h, pb.mean_w, mesh=pb.mesh,
                                axis=pb.axis)
            else:
                hn, res = ring_gspmm(pg, h, pb.mean_w, mesh=pb.mesh,
                                     axis=pb.axis, comm="int8",
                                     residual=comm_state[i])
                comm_out.append(res)
        else:
            if comm_state is None:
                hn, stale = ring_gspmm_delayed(pg, h, pb.mean_w, halo[i],
                                               refresh, mesh=pb.mesh,
                                               axis=pb.axis)
            else:
                hn, stale, res = ring_gspmm_delayed(
                    pg, h, pb.mean_w, halo[i], refresh, mesh=pb.mesh,
                    axis=pb.axis, comm="int8", residual=comm_state[i])
                comm_out.append(res)
            halo_out.append(stale)
        h = linear_apply(lyr, jnp.concatenate([h, hn], axis=-1))
        if i < n_layers - 1:
            h = jax.nn.relu(h)
    halo_ret = tuple(halo_out) if halo is not None else None
    if comm_state is None:
        return h, halo_ret
    return h, halo_ret, tuple(comm_out)


def forward_sampled(params: Dict, blocks, feats_fn, *,
                    strategy: str = "auto", batch_size: int
                    ) -> jnp.ndarray:
    """Back-compat wrapper: gather inputs via ``feats_fn`` then run the
    shared block path. ``feats_fn`` maps padded global ids (-1 = pad) to
    zero-padded features."""
    h = feats_fn(blocks[0].src_ids)
    return forward_blocks(params, blocks, h, strategy=strategy)[:batch_size]


def infer(params: Dict, bundle: GraphBundle, x: jnp.ndarray, *,
          strategy: str = "auto") -> jnp.ndarray:
    """Inference-mode forward — the serving tier's layer-wise refresh
    entry point (dropout off, no rng threading)."""
    return forward(params, bundle, x, strategy=strategy, train=False)


def infer_blocks(params: Dict, blocks, x: jnp.ndarray, *,
                 strategy: str = "auto") -> jnp.ndarray:
    """Inference-mode block forward — the serving tier's fan-out path."""
    return forward_blocks(params, blocks, x, strategy=strategy,
                          train=False)
