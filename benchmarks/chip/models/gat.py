"""GAT (Velickovic et al., 2018): the benchmark's weights, the
program's entry points, and the plain float32 reference.

Layer ``l`` with ``H`` heads of width ``F``: ``z = h @ W`` as (n, H, F);
per edge ``u -> v`` and head the logit ``leaky_relu(a_l . z_u + a_r .
z_v, 0.2)``, softmax over each destination's in-edges, output the
attention-weighted sum of ``z_u``, heads concatenated; ELU between
layers; the last layer has one head; dropout on each layer's input while
training. No bias, residual or normalisation (the program's ``gat``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def shapes(cfg: dict):
    """Per layer ``(d_in, heads, width)``."""
    m = cfg["model"]
    n, h, f = int(m["num_layers"]), int(m["heads"]), int(m["hidden"])
    out, d = [], int(m["in_feats"])
    for i in range(n):
        last = i == n - 1
        heads, width = (1, int(m["n_classes"])) if last else (h, f)
        out.append((d, heads, width))
        d = heads * width
    return out


def init(cfg: dict):
    """A jitted function of a key giving the weights in the program's
    layout (``{"layers": [{"w", "attn_l", "attn_r"}, ...]}``), float32,
    Glorot-uniform, made on the device in one call."""
    sh = shapes(cfg)

    def glorot(key, shape):
        lim = jnp.sqrt(6.0 / (shape[0] + shape[-1]))
        return jax.random.uniform(key, shape, jnp.float32, -lim, lim)

    @jax.jit
    def make(key):
        layers = []
        for d, heads, width in sh:
            key, k1, k2, k3 = jax.random.split(key, 4)
            layers.append({"w": glorot(k1, (d, heads * width)),
                           "attn_l": glorot(k2, (heads, width)),
                           "attn_r": glorot(k3, (heads, width))})
        return {"layers": layers}
    return make


def program():
    """The program's model module (``forward``)."""
    from repro.models.gnn import gat
    return gat


def _dropout(key, h, rate):
    keep = 1.0 - rate
    mask = jax.random.bernoulli(key, keep, h.shape)
    return jnp.where(mask, h / keep, 0.0)


@jax.checkpoint
def _head(z, el, er, src, dst):
    """One head's attention over all edges: (n, F) out of z (n, F) and
    the per-node logit halves el, er (n,). Checkpointed, so that the
    backward pass recomputes the (E, F) messages instead of keeping
    them."""
    n = z.shape[0]
    e = el[src] + er[dst]
    e = jnp.where(e >= 0, e, 0.2 * e)
    emax = jax.ops.segment_max(e, dst, num_segments=n,
                               indices_are_sorted=True)
    p = jnp.exp(e - emax[dst])
    den = jax.ops.segment_sum(p, dst, num_segments=n,
                              indices_are_sorted=True)
    alpha = p / den[dst]
    return jax.ops.segment_sum(alpha[:, None] * z[src], dst,
                               num_segments=n, indices_are_sorted=True)


def logits(params, graph, x, key, *, drop: float, train: bool):
    """Full-graph forward; ``graph = (src, dst, _)`` sorted by ``dst``.
    Softmax and sums are exact float32, one head at a time."""
    src, dst, _ = graph
    h = x
    layers = params["layers"]
    for i, lyr in enumerate(layers):
        heads, width = lyr["attn_l"].shape
        if train:
            key, sub = jax.random.split(key)
            h = _dropout(sub, h, drop)
        z = jnp.dot(h, lyr["w"], precision=HIGHEST).reshape(-1, heads,
                                                            width)
        el = jnp.sum(z * lyr["attn_l"], axis=-1)
        er = jnp.sum(z * lyr["attn_r"], axis=-1)
        h = jnp.concatenate([_head(z[:, k], el[:, k], er[:, k], src, dst)
                             for k in range(heads)], axis=-1)
        if i < len(layers) - 1:
            h = jax.nn.elu(h)
    return h


def flops_per_epoch(cfg: dict, n: int, n_edges: int) -> float:
    """Operations one full-graph training step requires, counted from
    shapes. Per layer forward: the projection (2 n d_in H F), the two
    logit halves (4 n H F), per edge and head the logit, softmax and
    normalisation (~6 E H) and the weighted sum (2 E H F). Backward
    doubles every part except the first layer's input gradient of the
    projection (the input features take no gradient)."""
    total = 0.0
    for i, (d, heads, width) in enumerate(shapes(cfg)):
        proj = 2.0 * n * d * heads * width
        rest = 4.0 * n * heads * width + 6.0 * n_edges * heads \
            + 2.0 * n_edges * heads * width
        total += 3 * (proj + rest) - (proj if i == 0 else 0.0)
    return total


def aggregate_bytes_per_epoch(cfg: dict, n: int, n_edges: int) -> float:
    """Bytes the attention aggregation of one full-graph training step
    must move, counted from shapes (float32 values, int32 indices), as a
    fused kernel would move them: per layer of ``H`` heads of width
    ``F`` and per direction, one source row of ``z`` and of the logit
    half ``el`` read per edge slot (``H * (F + 1)`` values) and its
    source index; per destination its ``er`` read (``H``) and its output
    row written (``H * F`` forward; backward the output's gradient read
    and, written, ``dz`` and the gradients of both logit halves,
    ``H * F + 2H``). Backward runs on every layer: the first layer's
    projection and attention vectors take gradients through it. The
    per-edge logits and attention weights, which a multi-pass
    implementation writes and reads again, are left out, so this is a
    lower bound on the traffic."""
    total = 0.0
    for _, heads, width in shapes(cfg):
        per_edge = 4.0 * n_edges * (heads * (width + 1) + 1)
        fwd = per_edge + 4.0 * n * (heads + heads * width)
        bwd = per_edge + 4.0 * n * (heads + heads * width
                                    + heads * width + 2 * heads)
        total += fwd + bwd
    return total
