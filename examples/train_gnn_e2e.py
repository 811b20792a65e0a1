"""End-to-end driver: train GraphSAGE for a few hundred steps with
checkpointing + auto-resume (kill it anywhere; rerun resumes).

    PYTHONPATH=src python examples/train_gnn_e2e.py [--steps 300]
"""
import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import CheckpointManager
from repro.data import make_node_dataset
from repro.launch.compile_cache import setup_compile_cache
from repro.models.gnn import sage, make_bundle
from repro.models.gnn.train import make_train_step
from repro.substrate.nn import accuracy


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--dataset", default="pubmed-like")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_sage_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--strategy", default="auto",
                    help="aggregation strategy; 'auto' lets the planner "
                         "pick per op (pin 'push'/'ell' to reproduce the "
                         "paper's baseline/optimized runs)")
    args = ap.parse_args()
    setup_compile_cache()

    g, feats, labels, tm, vm, nc = make_node_dataset(args.dataset)
    bundle = make_bundle(g)
    params = sage.init(jax.random.PRNGKey(0), feats.shape[1], 64, nc)
    opt_init, step_fn = make_train_step(sage.forward, args.strategy,
                                        lr=5e-3)
    opt_state = opt_init(params)
    state = {"params": params, "opt": opt_state,
             "step": jnp.zeros((), jnp.int32)}

    mgr = CheckpointManager(args.ckpt_dir)
    restored = mgr.restore_latest(state)
    start = 0
    if restored is not None:
        state, start = restored
        print(f"[e2e] resumed from step {start}")

    x = jnp.asarray(feats)
    y = jnp.asarray(labels)
    m = jnp.asarray(tm)
    rng = jax.random.PRNGKey(1)
    t0 = time.perf_counter()
    for step in range(start, args.steps):
        rng, sub = jax.random.split(rng)
        p, o, loss = step_fn(state["params"], state["opt"], step, bundle,
                             x, y, m, sub)
        state = {"params": p, "opt": o,
                 "step": jnp.asarray(step + 1, jnp.int32)}
        if step % 25 == 0:
            logits = sage.forward(p, bundle, x, strategy=args.strategy)
            va = float(accuracy(logits, y, jnp.asarray(vm)))
            print(f"[e2e] step={step} loss={float(loss):.4f} "
                  f"val_acc={va:.3f}")
        if (step + 1) % args.ckpt_every == 0:
            mgr.save(state, step + 1)
    dt = time.perf_counter() - t0
    logits = sage.forward(state["params"], bundle, x,
                          strategy=args.strategy)
    print(f"[e2e] done ({args.steps - start} steps in {dt:.1f}s). "
          f"final val acc "
          f"{float(accuracy(logits, y, jnp.asarray(vm))):.3f}")


if __name__ == "__main__":
    main()
