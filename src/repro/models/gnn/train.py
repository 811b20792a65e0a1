"""GNN training loops: full-graph (paper Fig. 2), sampled minibatch
(paper Fig. 3), and partitioned multi-device full-graph
(:func:`train_partitioned`, DESIGN.md §6).

One jitted step = forward + CE loss + AdamW update; per-epoch wall time
is the paper's reported metric. ``strategy`` selects the aggregation
implementation — 'auto' (default) lets the planner pick per op: from
graph statistics for full graphs (the bundle's PlanCache carries static
stats through the jitted step), from the shape-keyed block plan cache
for sampled minibatches. Pinning 'push' reproduces the DGL baseline and
'ell'/'segment' the optimized paths.

The sampled loop (:func:`train_sampled`) overlaps host-side neighbor
sampling with the device step via a double-buffered prefetcher, pads the
short final batch up to the static batch size (loss rows masked by
``MiniBatch.label_mask``), and tracks the minibatch shape signatures so
an accidental de-staticization fails loudly instead of recompiling per
batch.
"""
from __future__ import annotations

import time
from functools import partial
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ...data.pipeline import prefetch
from ...data.sampler import NeighborSampler
from ...obs.signatures import SignatureTracker
from ...obs.spans import span as _span
from ...optim import (adamw, apply_updates, cast_logits, cast_tree,
                      clip_by_global_norm, Precision)
from ...substrate.nn import cross_entropy_loss, accuracy
from .common import (block_features, make_partitioned_bundle,
                     pad_features, shard_partitioned)


def _resolve_precision(precision) -> Precision:
    """Accept None (fp32), a name ("fp32"/"bf16"), or a Precision."""
    if precision is None:
        return Precision.fp32()
    if isinstance(precision, str):
        return Precision.parse(precision)
    return precision


def make_train_step(forward_fn: Callable, strategy: str, lr: float = 1e-2,
                    weight_decay: float = 5e-4, clip: float = 5.0,
                    precision=None):
    """Mixed precision (DESIGN.md §12): parameters and optimizer moments
    stay fp32 master copies; the forward runs on ``precision.compute``
    casts, the loss is always taken on fp32 logits, and the cast's VJP
    hands fp32 gradients back to AdamW — SplitSGD-style."""
    precision = _resolve_precision(precision)
    opt_init, opt_update = adamw(lr, weight_decay=weight_decay)

    @partial(jax.jit, static_argnames=())
    def step(params, opt_state, step_i, bundle, x, labels, mask, rng):
        def loss_fn(p):
            pc = cast_tree(p, precision.compute)
            xc = cast_tree(x, precision.compute)
            logits = forward_fn(pc, bundle, xc, strategy=strategy,
                                train=True, rng=rng)
            return cross_entropy_loss(cast_logits(logits), labels, mask)

        loss, grads = jax.value_and_grad(loss_fn)(params)
        with jax.named_scope("optimizer"):
            grads, _ = clip_by_global_norm(grads, clip)
            ups, opt_state = opt_update(grads, opt_state, params, step_i)
            params = apply_updates(params, ups)
        return params, opt_state, loss

    return opt_init, step


def train_full_graph(forward_fn: Callable, params: Dict, bundle, x,
                     labels, train_mask, *, strategy: str = "auto",
                     epochs: int = 10, lr: float = 1e-2, seed: int = 0,
                     val_mask=None, precision=None) -> Tuple[Dict, Dict]:
    """Returns (params, history) with per-epoch times and losses."""
    precision = _resolve_precision(precision)
    opt_init, step = make_train_step(forward_fn, strategy, lr=lr,
                                     precision=precision)
    opt_state = opt_init(params)
    x = jnp.asarray(x)
    labels = jnp.asarray(labels)
    mask = jnp.asarray(train_mask)
    rng = jax.random.PRNGKey(seed)

    history = {"loss": [], "epoch_time": [], "val_acc": []}
    # warmup compile (excluded from timing, like the paper's epoch averages)
    p, o, l = step(params, opt_state, 0, bundle, x, labels, mask, rng)
    jax.block_until_ready(l)

    for e in range(epochs):
        rng, sub = jax.random.split(rng)
        t0 = time.perf_counter()
        params, opt_state, loss = step(params, opt_state, e, bundle, x,
                                       labels, mask, sub)
        jax.block_until_ready(loss)
        history["epoch_time"].append(time.perf_counter() - t0)
        history["loss"].append(float(loss))
        if val_mask is not None:
            logits = forward_fn(params, bundle, x, strategy=strategy)
            history["val_acc"].append(float(accuracy(
                logits, labels, jnp.asarray(val_mask))))
    return params, history


# --------------------------------------------------------------------- #
# partitioned multi-device full-graph training (DESIGN.md §6)
# --------------------------------------------------------------------- #
def make_partitioned_train_step(forward_part_fn: Callable,
                                lr: float = 1e-2,
                                weight_decay: float = 5e-4,
                                clip: float = 5.0, drop: float = 0.0,
                                precision=None):
    """One jitted step over padded sharded node arrays.

    ``forward_part_fn(params, pb, x, halo=..., refresh=..., ...)``
    returns ``(logits_pad, halo_out)``. Features/labels/masks stay in
    the padded layout end-to-end (pad rows are loss-masked); parameters
    are replicated, so with a mesh installed the partitioned loss makes
    GSPMD emit the gradient all-reduce on its own. ``refresh`` is
    static: exact steps and stale-halo steps are two compilations of
    the same function.

    Mixed precision works as in :func:`make_train_step` (fp32 masters,
    compute-dtype casts inside the loss, fp32 logits). When
    ``precision.comm == "int8"`` the step carries the per-layer
    error-feedback residual ``comm`` (from the model's ``init_comm``)
    through the train state: the forward is called with
    ``comm_state=comm`` and its third return becomes next step's
    residual. ``comm=None`` runs uncompressed exchanges; the step
    returns ``(params, opt_state, loss, halo_out, comm_out)`` either
    way (``comm_out`` mirrors ``comm``'s None-ness).
    """
    precision = _resolve_precision(precision)
    opt_init, opt_update = adamw(lr, weight_decay=weight_decay)

    @partial(jax.jit, static_argnames=("refresh",))
    def step(params, opt_state, step_i, pb, xp, yp, mp, halo, comm, rng,
             refresh=True):
        def loss_fn(p):
            pc = cast_tree(p, precision.compute)
            xc = cast_tree(xp, precision.compute)
            if comm is None:
                logits, halo_out = forward_part_fn(
                    pc, pb, xc, halo=halo, refresh=refresh,
                    train=True, rng=rng, drop=drop)
                comm_out = None
            else:
                logits, halo_out, comm_out = forward_part_fn(
                    pc, pb, xc, halo=halo, refresh=refresh,
                    comm_state=comm, train=True, rng=rng, drop=drop)
            return (cross_entropy_loss(cast_logits(logits), yp, mp),
                    (halo_out, comm_out))

        (loss, (halo_out, comm_out)), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params)
        with jax.named_scope("optimizer"):
            grads, _ = clip_by_global_norm(grads, clip)
            ups, opt_state = opt_update(grads, opt_state, params, step_i)
            params = apply_updates(params, ups)
        return params, opt_state, loss, halo_out, comm_out

    return opt_init, step


def train_partitioned(forward_part_fn: Callable, params: Dict, g, x,
                      labels, train_mask, *, n_shards: int, mesh=None,
                      axis: str = "data", mode: str = "contiguous",
                      halo_staleness: int = 0, epochs: int = 10,
                      lr: float = 1e-2, weight_decay: float = 5e-4,
                      drop: float = 0.0, seed: int = 0, val_mask=None,
                      init_halo_fn: Optional[Callable] = None,
                      precision=None,
                      init_comm_fn: Optional[Callable] = None
                      ) -> Tuple[Dict, Dict]:
    """Full-graph training across ``n_shards`` vertex shards.

    Features are scattered once into the padded sharded layout and the
    whole run stays there (labels padded with masked rows); parameters
    are replicated and gradients all-reduced by GSPMD. ``mesh=None``
    trains on the emulated single-device ring (bit-for-bit the same
    math — used by tests and anywhere without emulated devices).

    ``halo_staleness=0`` is exact every step; ``k > 0`` refreshes the
    cross-shard partial aggregates every k-th epoch and reuses them
    stale in between (DistGNN-style; needs ``init_halo_fn``, e.g.
    ``gcn.init_halo``). Returns (params, history) with per-epoch wall
    times, losses, and which epochs refreshed.

    ``precision`` ("fp32"/"bf16" or a :class:`~repro.optim.Precision`)
    selects the compute dtype (masters stay fp32) and, via
    ``precision.comm == "int8"``, per-block-scaled int8 ring exchanges
    with error feedback — which needs ``init_comm_fn`` (e.g.
    ``gcn.init_comm``) to seed the per-layer residual carried in the
    train state (DESIGN.md §12).
    """
    precision = _resolve_precision(precision)
    pb = make_partitioned_bundle(g, n_shards, mesh=mesh, axis=axis,
                                 mode=mode)
    pg = pb.pg
    # the subsystem's execution decision, in the shared plan log (so
    # BENCH_partitioned.json reports it like every planner-routed op)
    from ...core import planner as _planner
    _planner._record(
        "partitioned:train", "auto",
        (f"ring:s{n_shards}:{mode}" if mesh is not None
         else f"ring-emulated:s{n_shards}:{mode}") + f":{precision.tag()}",
        dtype=str(jnp.dtype(precision.compute)))
    x = jnp.asarray(np.asarray(x, np.float32))
    yp = pg.scatter_nodes(jnp.asarray(np.asarray(labels, np.int32)))
    mp = pg.scatter_nodes(jnp.asarray(np.asarray(train_mask, bool)))
    xp = pg.scatter_nodes(x)
    vp = (pg.scatter_nodes(jnp.asarray(np.asarray(val_mask, bool)))
          if val_mask is not None else None)

    delayed = halo_staleness > 0
    if delayed and init_halo_fn is None:
        raise ValueError("halo_staleness > 0 needs init_halo_fn "
                         "(e.g. gcn.init_halo)")
    halo = init_halo_fn(params, pg) if delayed else None
    if precision.comm == "int8" and init_comm_fn is None:
        raise ValueError('precision.comm == "int8" needs init_comm_fn '
                         "(e.g. gcn.init_comm)")
    comm = (init_comm_fn(params, pg)
            if precision.comm == "int8" else None)

    opt_init, step = make_partitioned_train_step(
        forward_part_fn, lr=lr, weight_decay=weight_decay, drop=drop,
        precision=precision)
    opt_state = opt_init(params)
    if mesh is not None:
        pb, xp, yp, mp = shard_partitioned(pb, xp, yp, mp)
        from jax.sharding import NamedSharding, PartitionSpec as P
        rep = NamedSharding(mesh, P())
        params = jax.device_put(params, rep)
        opt_state = jax.device_put(opt_state, rep)
        if delayed:
            halo = shard_partitioned(pb, *halo)[1:]
        if comm is not None:
            comm = shard_partitioned(pb, *comm)[1:]
    rng = jax.random.PRNGKey(seed)

    @jax.jit
    def eval_logits(params, pb, xp):
        pc = cast_tree(params, precision.compute)
        xc = cast_tree(xp, precision.compute)
        return cast_logits(forward_part_fn(pc, pb, xc)[0])

    history = {"loss": [], "epoch_time": [], "val_acc": [],
               "refreshed": []}
    # warmup: compile both refresh variants, discard the updates
    step(params, opt_state, 0, pb, xp, yp, mp, halo, comm, rng,
         refresh=True)
    if delayed:
        step(params, opt_state, 0, pb, xp, yp, mp, halo, comm, rng,
             refresh=False)

    for e in range(epochs):
        refresh = (not delayed) or (e % halo_staleness == 0)
        rng, sub = jax.random.split(rng)
        t0 = time.perf_counter()
        params, opt_state, loss, halo, comm = step(
            params, opt_state, e, pb, xp, yp, mp, halo, comm, sub,
            refresh=refresh)
        jax.block_until_ready(loss)
        history["epoch_time"].append(time.perf_counter() - t0)
        history["loss"].append(float(loss))
        history["refreshed"].append(bool(refresh))
        if vp is not None:
            logits = eval_logits(params, pb, xp)
            history["val_acc"].append(float(accuracy(logits, yp, vp)))
    return params, history


# --------------------------------------------------------------------- #
# sampled minibatch training (paper Fig. 3)
# --------------------------------------------------------------------- #
def make_sampled_train_step(forward_blocks_fn: Callable, strategy: str,
                            bwd_strategy: str = "auto",
                            lr: float = 1e-2, weight_decay: float = 5e-4,
                            clip: float = 5.0, precision=None):
    """One jitted step over a :class:`~repro.data.MiniBatch` pytree.

    The minibatch's static aux (padded sizes + fanouts) keys the jit
    cache, so every batch of one sampler configuration reuses a single
    compilation; block planning inside the trace is shape-keyed and thus
    identical for all of them. Pad seed rows are masked out of the loss.
    ``bwd_strategy`` selects the block differentiation path (DESIGN.md
    §7): 'auto' (default) lets the planner route ∂x through the
    reverse-table gather VJP, 'scatter' pins the autodiff baseline.
    Mixed precision as in :func:`make_train_step` (DESIGN.md §12).
    """
    precision = _resolve_precision(precision)
    opt_init, opt_update = adamw(lr, weight_decay=weight_decay)

    @jax.jit
    def step(params, opt_state, step_i, mb, feats_pad, rng):
        def loss_fn(p):
            pc = cast_tree(p, precision.compute)
            x = cast_tree(block_features(feats_pad, mb.input_ids),
                          precision.compute)
            logits = forward_blocks_fn(pc, mb.blocks, x, strategy=strategy,
                                       bwd_strategy=bwd_strategy,
                                       train=True, rng=rng)
            return cross_entropy_loss(cast_logits(logits), mb.labels,
                                      mb.label_mask)

        loss, grads = jax.value_and_grad(loss_fn)(params)
        with jax.named_scope("optimizer"):
            grads, _ = clip_by_global_norm(grads, clip)
            ups, opt_state = opt_update(grads, opt_state, params, step_i)
            params = apply_updates(params, ups)
        return params, opt_state, loss

    return opt_init, step


def train_sampled(forward_blocks_fn: Callable, params: Dict, g, feats,
                  labels, train_ids, *, fanouts=(10, 10),
                  batch_size: int = 64, strategy: str = "auto",
                  bwd_strategy: str = "auto",
                  epochs: int = 5, lr: float = 1e-2,
                  weight_decay: float = 5e-4, seed: int = 0,
                  prefetch_depth: int = 2, drop_last: bool = False,
                  sampler: Optional[NeighborSampler] = None,
                  max_batches: Optional[int] = None,
                  precision=None) -> Tuple[Dict, Dict]:
    """End-to-end minibatch training: sample (host, prefetched) → one
    jitted step (device) per batch.

    Returns (params, history); history splits per-epoch wall time into
    ``sample_time`` (host time the consumer actually waited on the
    prefetcher) and ``step_time`` (device step incl. transfer) — the
    sampling-vs-aggregation split the Fig. 3 benchmark reports.
    """
    labels = np.asarray(labels)
    train_ids = np.asarray(train_ids)
    opt_init, step = make_sampled_train_step(
        forward_blocks_fn, strategy, bwd_strategy=bwd_strategy,
        lr=lr, weight_decay=weight_decay,
        precision=_resolve_precision(precision))
    opt_state = opt_init(params)
    feats_pad = pad_features(feats)
    if sampler is None:
        sampler = NeighborSampler(g, fanouts, batch_size, seed=seed)
    rng = jax.random.PRNGKey(seed)
    tracker = SignatureTracker()
    history = {"loss": [], "epoch_time": [], "sample_time": [],
               "step_time": [], "n_batches": []}
    step_i = 0
    for _ in range(epochs):
        # one top-level span per epoch; sample/step spans nest
        # under it, so the exported trace tiles the whole run
        with _span("train.epoch"):
            it = prefetch(sampler.batches(train_ids, labels[train_ids],
                                          drop_last=drop_last),
                          depth=prefetch_depth)
            t_epoch = time.perf_counter()
            t_sample = t_step = 0.0
            losses = []
            try:
                while max_batches is None or len(losses) < max_batches:
                    t0 = time.perf_counter()
                    with _span("train.sample"):
                        mb = next(it, None)
                    if mb is None:
                        break
                    t_sample += time.perf_counter() - t0
                    # only a NEW signature (⇒ a fresh compile)
                    # re-checks the bound
                    tracker.observe_checked(mb.shape_signature())
                    rng, sub = jax.random.split(rng)
                    t0 = time.perf_counter()
                    with _span("train.step") as sp:
                        params, opt_state, loss = step(params, opt_state,
                                                       step_i, mb,
                                                       feats_pad, sub)
                        sp.fence(loss)
                        jax.block_until_ready(loss)
                    t_step += time.perf_counter() - t0
                    losses.append(float(loss))
                    step_i += 1
                # stop the clock before close(): the join waits out an
                # abandoned in-flight sample no train step consumed
                t_epoch = time.perf_counter() - t_epoch
            finally:
                it.close()  # never leave the producer thread mid-batch
        history["loss"].append(float(np.mean(losses)) if losses
                               else float("nan"))
        history["epoch_time"].append(t_epoch)
        history["sample_time"].append(t_sample)
        history["step_time"].append(t_step)
        history["n_batches"].append(len(losses))
    return params, history
