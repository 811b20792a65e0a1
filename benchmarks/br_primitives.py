"""Paper Table 2 / §5: BR-CR primitive microbenchmarks.

Every BR configuration the 7 applications use, timed per strategy:
push (baseline Alg. 1), segment (Alg. 2), ell (Alg. 3 blocked pull),
onehot (MXU formulation). The paper reports BR speedups of 1.72×–34×; the
analogue here is ell/segment-vs-push per config.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import from_coo, gspmm, planner
from repro.data import rmat_graph

from .common import time_fn, row

# the exact configurations from the paper's Table 2
CONFIGS = [
    "u_copy_add_v",        # GCN/SAGE/GCMC/LGNN/RGCN
    "u_mul_e_add_v",       # MoNet, GAT
    "e_copy_add_v",        # GAT
    "e_copy_max_v",        # GAT
    "u_add_v_copy_e",      # GAT
    "e_sub_v_copy_e",      # GAT
    "e_div_v_copy_e",      # GAT
    "v_mul_e_copy_e",      # GAT
    "u_dot_v_add_e",       # GCMC
]

STRATEGIES = ("push", "segment", "ell", "auto")


def main(d: int = 128, strategy: str = None):
    src, dst, n = rmat_graph(15, 200_000, seed=3)
    g = from_coo(src, dst, n_src=n, n_dst=n)
    # packs come from the planner's per-graph cache (built once, shared
    # between the pinned-ell sweep and the auto mode)
    planner.get_plan_cache(g).ell()
    nnz = g.n_edges
    rng = np.random.default_rng(0)
    U = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    V = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    E = jnp.asarray(rng.normal(size=(nnz, d)).astype(np.float32))

    strategies = (STRATEGIES if strategy is None
                  else tuple(dict.fromkeys(("push", strategy))))
    for name in CONFIGS:
        times = {}
        for s in strategies:
            if name.endswith("_e") and s == "ell":
                continue   # edge-output configs have no blocked-pull stage
            fn = jax.jit(lambda u, v, e, s=s, nm=name:
                         gspmm(g, nm, u=u, v=v, e=e, strategy=s))
            times[s] = time_fn(fn, U, V, E, iters=5, warmup=2)
        base = times["push"]
        optimized = [k for k in times if k != "push"]
        best_name = (min(optimized, key=lambda k: times[k])
                     if optimized else None)
        sp = base / times[best_name] if best_name else 1.0
        for s, t in times.items():
            tag = (f"speedup={sp:.2f}x({best_name})"
                   if s == best_name else "")
            if s == "auto":
                chosen = planner.last_plan(name) or "edge-order"
                tag = f"plan={chosen}" + (f";{tag}" if tag else "")
            print(row(f"br_{name}_{s}", t, tag))


if __name__ == "__main__":
    main()
