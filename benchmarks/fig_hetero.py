"""Relation-fused heterogeneous execution benchmark (DESIGN.md §8).

Two sweeps, fused vs the pre-refactor per-relation loop, forward AND
backward (the acceptance axis of the hetero subsystem):

* **BGS-like**: R-GCN layer shapes on synthetic typed multigraphs with
  50–100 relations (BGS has 103) — the regime where the loop pays R
  sequential gathers + reduces per layer and the fused path pays one.
  Rows time ``hetero_gspmm`` with the basis-decomposed weights exactly
  as the model runs it: ``_fwd`` is the jitted aggregation alone,
  ``_fwdbwd`` the jitted value+grad w.r.t. (features, basis, coeff).
* **GCMC levels**: the encoder's user→item direction swept over rating
  level counts — few relations, large per-relation matmuls, the regime
  where the planner keeps the loop competitive.

An ``auto`` row per config records what the planner picks (plan log →
``BENCH_hetero.json`` via ``benchmarks.run``). ``REPRO_BENCH_QUICK=1``
shrinks every config for CI.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import hetero_gspmm
from repro.data import bipartite_ratings, relational_graph
from repro.models.gnn import gcmc, rgcn

from .common import row, time_fn

QUICK = bool(int(os.environ.get("REPRO_BENCH_QUICK", "0")))

# (n_nodes, n_rel, edges_per_rel) — BGS-like typed multigraphs
BGS_SWEEP = [(4000, 50, 700), (4000, 100, 350)]
# (n_users, n_items, n_ratings, levels) — GCMC level sweep
GCMC_SWEEP = [(2000, 1500, 60_000, 5), (2000, 1500, 60_000, 10)]
D_IN, D_HID, N_BASES = 32, 16, 4

if QUICK:
    BGS_SWEEP = [(300, 50, 40)]
    GCMC_SWEEP = [(200, 150, 2_000, 5)]
    D_IN = 16


def _sweep_strategies(tag: str, agg, grad, args, note: str) -> float:
    """Time loop/fused/auto × fwd/fwd+bwd; print + record the rows.

    ``agg(strategy)``/``grad(strategy)`` return jitted callables over
    ``args``. Returns the forward fused-over-loop speedup.
    """
    t = {}
    for s in ("loop", "fused", "auto"):
        t[s, "fwd"] = time_fn(agg(s), *args, iters=5)
        t[s, "bwd"] = time_fn(grad(s), *args, iters=5)
    for phase in ("fwd", "bwd"):
        sp = t["loop", phase] / max(t["fused", phase], 1e-12)
        name = "_fwdbwd" if phase == "bwd" else "_fwd"
        print(row(f"{tag}{name}_loop", t["loop", phase], note))
        print(row(f"{tag}{name}_fused", t["fused", phase],
                  f"fused_speedup={sp:.2f}x"))
        print(row(f"{tag}{name}_auto", t["auto", phase],
                  f"vs_loop="
                  f"{t['loop', phase] / max(t['auto', phase], 1e-12):.2f}x"))
    return t["loop", "fwd"] / max(t["fused", "fwd"], 1e-12)


def bench_bgs(n: int, n_rel: int, epr: int) -> float:
    rels = relational_graph(n, n_rel, epr, seed=0)
    rg = rgcn.build_relgraph(rels, n)
    rng = np.random.default_rng(0)
    h = jnp.asarray(rng.normal(size=(n, D_IN)).astype(np.float32))
    basis = jnp.asarray(rng.normal(size=(N_BASES, D_IN, D_HID))
                        .astype(np.float32) * 0.3)
    coeff = jnp.asarray(rng.normal(size=(n_rel, N_BASES))
                        .astype(np.float32) * 0.3)
    tag = f"fig_hetero_bgs_n{n}_r{n_rel}"

    def agg(strategy):
        @jax.jit
        def f(h, basis, coeff):
            return hetero_gspmm(rg, h, basis=basis, coeff=coeff,
                                reduce="mean", strategy=strategy)
        return f

    def grad(strategy):
        @jax.jit
        def f(h, basis, coeff):
            def loss(h, basis, coeff):
                out = hetero_gspmm(rg, h, basis=basis, coeff=coeff,
                                   reduce="mean", strategy=strategy)
                return jnp.sum(out * out)
            return jax.value_and_grad(loss, argnums=(0, 1, 2))(
                h, basis, coeff)
        return f

    return _sweep_strategies(tag, agg, grad, (h, basis, coeff),
                             f"edges={n_rel * epr}")


def bench_gcmc_levels(n_users: int, n_items: int, n_ratings: int,
                      levels: int) -> float:
    u, i, r = bipartite_ratings(n_users, n_items, n_ratings, levels,
                                seed=0)
    rg_fwd, _ = gcmc.build_level_relgraphs(u, i, r, n_users, n_items,
                                           levels)
    rng = np.random.default_rng(0)
    d = 64 if not QUICK else 16
    xu = jnp.asarray(rng.normal(size=(n_users, d)).astype(np.float32))
    W = jnp.asarray(rng.normal(size=(levels, d, d)).astype(np.float32)
                    * 0.1)
    tag = f"fig_hetero_gcmc_l{levels}"

    def agg(strategy):
        @jax.jit
        def f(xu, W):
            return hetero_gspmm(rg_fwd, xu, w=W, reduce="mean",
                                strategy=strategy)
        return f

    def grad(strategy):
        @jax.jit
        def f(xu, W):
            def loss(xu, W):
                out = hetero_gspmm(rg_fwd, xu, w=W, reduce="mean",
                                   strategy=strategy)
                return jnp.sum(out * out)
            return jax.value_and_grad(loss, argnums=(0, 1))(xu, W)
        return f

    return _sweep_strategies(tag, agg, grad, (xu, W),
                             f"ratings={n_ratings}")


def main():
    # no --strategy knob: the sweep already times loop/fused/auto
    # explicitly (plain strategy pins map onto the loop baseline)
    for n, n_rel, epr in BGS_SWEEP:
        bench_bgs(n, n_rel, epr)
    for cfg in GCMC_SWEEP:
        bench_gcmc_levels(*cfg)


if __name__ == "__main__":
    main()
