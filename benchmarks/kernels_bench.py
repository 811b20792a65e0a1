"""Aggregation-kernel strategy sweep (CR = SpMM).

Times the four executable strategies on a power-law graph at several
feature widths. The Pallas kernels run in interpret mode on CPU (their
timings are NOT meaningful hardware numbers — they validate numerics; the
MXU story is the dry-run roofline's job) and are excluded here.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import from_coo, copy_reduce, planner
from repro.data import rmat_graph

from .common import time_fn, row


def main(strategy: str = None):
    src, dst, n = rmat_graph(14, 120_000, seed=5)
    g = from_coo(src, dst, n_src=n, n_dst=n)
    # pre-build through the shared per-graph cache (once per process)
    cache = planner.get_plan_cache(g)
    cache.ell()
    cache.tiles()
    rng = np.random.default_rng(0)
    strategies = (("push", "segment", "ell", "onehot", "auto")
                  if strategy is None else ("push", strategy))
    for d in (32, 128, 512):
        x = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
        for s in strategies:
            fn = jax.jit(lambda x, s=s:
                         copy_reduce(g, x, "sum", strategy=s))
            t = time_fn(fn, x, iters=5, warmup=2)
            gbps = (g.n_edges * d * 4) / t / 1e9
            tag = f"{gbps:.1f}GB/s-gathered"
            if s == "auto":
                tag += f";plan={planner.last_plan('u_copy_add_v')}"
            print(row(f"spmm_d{d}_{s}", t, tag))


if __name__ == "__main__":
    main()
