"""GraphSAGE with the mean aggregator: the benchmark's weights, the
program's entry points, and the plain float32 reference.

Layer ``l``: ``h' = [h ; mean_{u -> v} h_u] @ W_l + b_l``, ReLU between
layers, dropout on each layer's input while training.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def dims(cfg: dict):
    m = cfg["model"]
    n = int(m["num_layers"])
    return [int(m["in_feats"])] + [int(m["hidden"])] * (n - 1) + \
        [int(m["n_classes"])]


def init(cfg: dict):
    """A jitted function of a key giving the weights in the program's
    layout (``{"layers": [{"w", "b"}, ...]}``), float32, Glorot-uniform
    weights and zero biases, made on the device in one call."""
    ds = dims(cfg)

    @jax.jit
    def make(key):
        keys = jax.random.split(key, len(ds) - 1)
        layers = []
        for k, (a, b) in zip(keys, zip(ds[:-1], ds[1:])):
            lim = jnp.sqrt(6.0 / (2 * a + b))
            layers.append({"w": jax.random.uniform(k, (2 * a, b),
                                                   jnp.float32, -lim, lim),
                           "b": jnp.zeros((b,), jnp.float32)})
        return {"layers": layers}
    return make


def program():
    """The program's model module (``forward``, ``forward_blocks``)."""
    from repro.models.gnn import sage
    return sage


def _dropout(key, h, rate):
    keep = 1.0 - rate
    mask = jax.random.bernoulli(key, keep, h.shape)
    return jnp.where(mask, h / keep, 0.0)


def _dense(h, hn, lyr):
    return jnp.dot(jnp.concatenate([h, hn], axis=-1), lyr["w"],
                   precision=HIGHEST) + lyr["b"]


def logits(params, graph, x, key, *, drop: float, train: bool):
    """Full-graph forward. ``graph = (src, dst, inv_deg)`` with
    ``inv_deg`` (n,) = 1 / in-degree; neighbour means are exact float32
    segment sums."""
    src, dst, inv_deg = graph
    n = x.shape[0]
    h = x
    layers = params["layers"]
    for i, lyr in enumerate(layers):
        if train:
            key, sub = jax.random.split(key)
            h = _dropout(sub, h, drop)
        hn = jax.ops.segment_sum(h[src], dst, num_segments=n,
                                 indices_are_sorted=True) * inv_deg[:, None]
        h = _dense(h, hn, lyr)
        if i < len(layers) - 1:
            h = jax.nn.relu(h)
    return h


def block_logits(params, blocks, x, key, *, drop: float, train: bool):
    """Sampled forward over ``blocks``: per block ``(nbr, mask)``,
    outermost first; ``nbr`` (n_dst, fanout) source slots, ``mask``
    which of them are sampled edges. Destination ``j`` of a block is
    source slot ``j`` (its own features are ``h[:n_dst]``)."""
    h = x
    layers = params["layers"]
    for i, (lyr, (nbr, mask)) in enumerate(zip(layers, blocks)):
        n_dst = nbr.shape[0]
        if train:
            key, sub = jax.random.split(key)
            h = _dropout(sub, h, drop)
        m = mask.astype(h.dtype)[:, :, None]
        cnt = jnp.maximum(jnp.sum(m, axis=1), 1.0)
        hn = jnp.sum(h[nbr] * m, axis=1) / cnt
        h = _dense(h[:n_dst], hn, lyr)
        if i < len(layers) - 1:
            h = jax.nn.relu(h)
    return h


def flops_per_epoch(cfg: dict, n: int, n_edges: int) -> float:
    """Operations one full-graph training step requires, counted from
    shapes: per layer the neighbour mean (a multiply-add per edge and
    input width) and the dense layer (2 x n x 2d_in x d_out); backward
    the weight gradient of every layer, and the input gradient and the
    mean's adjoint of every layer but the first (the input features
    take no gradient). Elementwise work (activations, dropout, bias,
    loss, optimizer) is left out."""
    ds = dims(cfg)
    total = 0.0
    for i, (a, b) in enumerate(zip(ds[:-1], ds[1:])):
        agg = 2.0 * n_edges * a
        dense = 2.0 * n * (2 * a) * b
        total += agg + dense             # forward
        total += dense                   # dW
        if i > 0:
            total += dense + agg         # dX through dense and mean
    return total


def aggregate_bytes_per_epoch(cfg: dict, n: int, n_edges: int) -> float:
    """Bytes the neighbour means of one full-graph training step must
    move, counted from shapes (float32 values, int32 indices): per mean,
    one source row read per edge slot at the layer's input width, one
    source index and one mean weight per edge slot, and one row written
    per destination. Forward every layer; backward the mean's adjoint of
    every layer but the first (the input features take no gradient), a
    mean over the reverse graph at the same width. What an
    implementation chooses to materialise (padded slots, per-slot
    messages) is left out, so this is a lower bound on the traffic."""
    total = 0.0
    for i, a in enumerate(dims(cfg)[:-1]):
        one = 4.0 * (n_edges * a + 2 * n_edges + n * a)
        total += one * (2 if i > 0 else 1)
    return total
