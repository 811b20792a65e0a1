"""What the training cells share: the plain reference's optimizer
steps, the readings taken from the program, and the comparison that
decides ``correct``.

The numbers compared:

- ``loss``: each of the first steps' loss against the reference's, as a
  share of the reference's; the worst step;
- ``grad``: per weight matrix (a leaf of two or more dimensions), the
  norm of the first gradient as the optimizer got it (its first moment
  after one step over ``1 - b1``) against the norm of the reference's
  clipped gradient; the median matrix;
- ``update``: the median leaf's gap of the norm of the weights' change
  over the first steps against the reference's.

A leaf's gap is the difference of the two norms over the larger of the
reference leaf's norm and the median leaf's. ``update`` leaves out
leaves whose reference gradient is under a thousandth of the median
leaf's: Adam moves those by round-off alone.

``grad`` leaves out the vectors (biases, attention vectors) and takes
the median matrix, and ``update`` takes the median leaf, because the
float32 reference is not steady at their worst: a second reference that
only sums each destination's edges in another order reads as far from
the first as the program does (on gat-arxiv up to 6.6e-5 on an
attention vector's gradient and 5.2e-5 on the median leaf's change, on
the same seeds as the program), and a pre-activation within rounding of
an activation's kink sends one hidden unit's gradient one way in the
program and the other in the reference (all of one seed's difference in
one column of a weight matrix; chip runs). The worst leaf of each is
reported beside them.
"""
from __future__ import annotations

from typing import Callable, Dict, List, Sequence

import numpy as np

NOUGHT = 1e-3


def plan_counts() -> dict:
    from repro.core import planner
    return {k: dict(v) for k, v in planner.plan_log().items()}


def plans_since(before: dict) -> Dict[str, Dict[str, int]]:
    """Plans the program's planner logged after ``before``, as
    ``{"op|requested": {strategy: count}}``."""
    out = {}
    for (op, req), counts in plan_counts().items():
        new = {s: n - before.get((op, req), {}).get(s, 0)
               for s, n in counts.items()}
        new = {s: n for s, n in new.items() if n}
        if new:
            out[f"{op}|{req}"] = new
    return out


def _ell_slots(pack) -> int:
    return sum(int(c.chunk_mask.shape[0]) * int(c.width)
               for c in pack.classes)


def pack_slots(bundle, plans: dict) -> Dict[str, int]:
    """Padded edge slots of each ELL pack the step uses: the training
    graph's forward and reverse packs when it took the blocked pull (it
    plans no op then), else the graph's pack when a planned op chose
    ``ell``."""
    if bundle.tg is not None and not plans:
        return {"ell": _ell_slots(bundle.tg.ell),
                "ell_rev": _ell_slots(bundle.tg.ell_rev)}
    chosen = {s for counts in plans.values() for s in counts}
    if "ell" in chosen and bundle.cache.peek("ell") is not None:
        return {"ell": _ell_slots(bundle.cache.peek("ell"))}
    return {}


def leaf_norms(tree) -> List[float]:
    import jax
    return [float(np.linalg.norm(np.asarray(leaf, np.float64)))
            for leaf in jax.tree.leaves(tree)]


def readings(losses, opt_after_first, params0, params_n, *, b1: float):
    """The program's numbers, moved to the host."""
    import jax
    mu = opt_after_first.mu
    grads = jax.tree.map(lambda a: np.asarray(a, np.float64) / (1 - b1), mu)
    delta = jax.tree.map(lambda a, b: np.asarray(a, np.float64)
                         - np.asarray(b, np.float64), params_n, params0)
    return {"loss": [float(v) for v in losses],
            "grad": leaf_norms(grads), "update": leaf_norms(delta)}


def cross_entropy(logits, labels, mask):
    import jax
    import jax.numpy as jnp
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]
    w = mask.astype(nll.dtype)
    return jnp.sum(nll * w) / jnp.maximum(jnp.sum(w), 1.0)


def full_graph_loss(arch, *, drop: float) -> Callable:
    """``loss(params, key, (graph, x, y, mask))`` of the plain reference
    on the whole graph (the arrays are arguments, so the compiled
    reference holds no copy of them)."""
    def loss(params, key, batch):
        graph, x, y, mask = batch
        return cross_entropy(arch.logits(params, graph, x, key, drop=drop,
                                         train=True), y, mask)
    return loss


def reference_steps(loss_fn: Callable, params0, keys: Sequence,
                    opt: dict, batches: Sequence = None) -> dict:
    """Steps of the reference: exact float32 (``highest``) gradients of
    ``loss_fn(params, key, batch)``, clipped by global norm, then AdamW
    with decoupled weight decay."""
    import jax
    import jax.numpy as jnp
    b1, b2, eps = opt["b1"], opt["b2"], opt["eps"]
    lr, wd, clip = opt["lr"], opt["weight_decay"], opt["clip"]
    with jax.default_matmul_precision("highest"):
        vg = jax.jit(jax.value_and_grad(loss_fn))

        @jax.jit
        def adam(p, g, mu, nu, t):
            gn = jnp.sqrt(sum(jnp.sum(jnp.square(a))
                              for a in jax.tree.leaves(g)))
            scale = jnp.minimum(1.0, clip / jnp.maximum(gn, 1e-12))
            g = jax.tree.map(lambda a: a * scale, g)
            mu = jax.tree.map(lambda m, a: b1 * m + (1 - b1) * a, mu, g)
            nu = jax.tree.map(lambda v, a: b2 * v + (1 - b2) * a * a, nu, g)
            c1, c2 = 1 - b1 ** t, 1 - b2 ** t
            p = jax.tree.map(lambda q, m, v: q - lr * (
                (m / c1) / (jnp.sqrt(v / c2) + eps) + wd * q), p, mu, nu)
            return p, g, mu, nu

        p = params0
        mu = jax.tree.map(jnp.zeros_like, params0)
        nu = jax.tree.map(jnp.zeros_like, params0)
        losses, first = [], None
        batches = batches or [None] * len(keys)
        for t, (key, batch) in enumerate(zip(keys, batches), start=1):
            loss, g = vg(p, key, batch)
            p, g, mu, nu = adam(p, g, mu, nu, jnp.float32(t))
            losses.append(float(loss))
            if first is None:
                first = g
    delta = jax.tree.map(lambda a, b: np.asarray(a, np.float64)
                         - np.asarray(b, np.float64), p, params0)
    return {"loss": losses, "grad": leaf_norms(first),
            "update": leaf_norms(delta),
            "ndim": [np.ndim(a) for a in jax.tree.leaves(first)]}


def leaf_gaps(prog: Sequence[float], ref: Sequence[float],
              keep: Sequence[bool]) -> List[float]:
    """Per kept leaf, ``|prog - ref| / max(ref, median ref)`` (NaN and
    infinity read as infinitely far)."""
    med = float(np.median([r for r, k in zip(ref, keep) if k]))
    gaps = [abs(p - r) / max(r, med, 1e-30)
            for p, r, k in zip(prog, ref, keep) if k]
    return [g if np.isfinite(g) else float("inf") for g in gaps]


def compare(prog: dict, ref: dict):
    """The numbers compared, and beside them what is only reported:
    ``grad_worst_leaf`` and ``update_worst``, the worst leaf's gaps."""
    loss = max(abs(p - r) / max(abs(r), 1e-30)
               for p, r in zip(prog["loss"], ref["loss"]))
    if not np.all(np.isfinite(prog["loss"])):
        loss = float("inf")
    med_g = float(np.median(ref["grad"]))
    moved = [r >= NOUGHT * med_g for r in ref["grad"]]
    upd = leaf_gaps(prog["update"], ref["update"], moved)
    grad = leaf_gaps(prog["grad"], ref["grad"], [True] * len(ref["grad"]))
    matrices = [g for g, nd in zip(grad, ref["ndim"]) if nd >= 2]
    compared = {"loss": loss, "grad": float(np.median(matrices)),
                "update": float(np.median(upd))}
    return compared, {"grad_worst_leaf": max(grad),
                      "update_worst": max(upd),
                      "left_out_leaves": len(moved) - sum(moved)}
