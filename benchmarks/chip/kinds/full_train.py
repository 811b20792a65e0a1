"""Full-graph training: one jitted step per epoch, back to back.

Set-up builds the configuration's graph, the program's bundle and one
compiled step (``make_train_step(<arch>.forward, strategy)``) with its
state, and drives it through its first ``ref_steps`` steps. The window
then continues that same state for ``--seconds``; ``epoch_s`` is the
window over the steps completed in it. Once the window has closed and
the program's state is freed, the plain reference repeats the first
steps from the same weights and dropout keys, and the losses, the first
gradient (read from the optimizer's first moment) and the weights'
change are compared.

Mix keys: ``strategy`` (the program's aggregation strategy),
``optimizer`` (``lr``, ``weight_decay``, ``clip``, ``b1``, ``b2``,
``eps`` as the program's step uses them), ``ref_steps``.

``FAULTS`` names the faults a cell of this kind can have, each a mode
of ``calibrate.py`` that plants it under the timed path: a step that
returns its state unchanged, and half of the training rows left out of
the loss (one chip: there is no exchange between chips to leave out).
"""
from __future__ import annotations

import gc
import os

import numpy as np

N_KEYS = 8192
FAULTS = ("frozen", "half")


def run(run) -> None:
    import jax
    import jax.numpy as jnp
    import graphs
    import training
    from harness import load_module, seed_key
    from repro.core.graph import from_coo
    from repro.models.gnn.common import make_bundle
    from repro.models.gnn.train import make_train_step

    cfg, mix, hooks = run.cfg, run.mix, run.hooks
    arch = load_module(os.path.join(run.bench_dir, "models",
                                    cfg["model"]["arch"] + ".py"),
                       cfg["model"]["arch"])
    opt_cfg = mix["optimizer"]
    n_ref = int(mix["ref_steps"])

    gd = graphs.build(cfg)
    g = from_coo(gd.src, gd.dst, n_src=gd.n, n_dst=gd.n)
    bundle = make_bundle(g)
    x = jnp.asarray(gd.feats)
    y = jnp.asarray(gd.labels)
    mask = hooks.get("train_mask", lambda m: m)(gd.train_mask)
    m = jnp.asarray(mask)
    k_init, k_drop = jax.random.split(seed_key(run.seed))
    params0 = arch.init(cfg)(k_init)
    step_keys = np.asarray(jax.random.split(k_drop, N_KEYS))

    forward = arch.program().forward
    opt_init, step = make_train_step(
        forward, mix["strategy"], lr=opt_cfg["lr"],
        weight_decay=opt_cfg["weight_decay"], clip=opt_cfg["clip"],
        precision=hooks.get("precision"))
    step = hooks.get("wrap_step", lambda s: s)(step)
    plans_before = training.plan_counts()

    # the first steps, through the window's own call and state
    params, opt = params0, opt_init(params0)
    losses = []
    opt_after_first = None
    for i in range(n_ref):
        params, opt, loss = step(params, opt, i, bundle, x, y, m,
                                 step_keys[i])
        losses.append(loss)
        if i == 0:
            opt_after_first = opt
    jax.block_until_ready((params, opt, losses))
    run.layer["plans"] = training.plans_since(plans_before)
    run.layer["packs"] = training.pack_slots(bundle, run.layer["plans"])
    run.layer["n_edges"] = gd.n_edges
    prog = training.readings(losses, opt_after_first, params0, params,
                             b1=opt_cfg["b1"])

    run.start_window()
    i, n = n_ref, 0
    while True:
        with run.annotate("bench.step"):
            params, opt, loss = step(params, opt, i, bundle, x, y, m,
                                     step_keys[i % N_KEYS])
            loss.block_until_ready()
        i += 1
        n += 1
        if not run.window_open():
            break
    window = run.end_window()
    last = float(loss)
    run.attempted = n
    run.failed = 0 if np.isfinite(last) else n
    run.e2e["epoch_s"] = window / n
    run.layer["steps"] = n
    run.layer["flops_per_step"] = arch.flops_per_epoch(cfg, gd.n,
                                                       gd.n_edges)
    run.layer["aggregate_bytes_per_step"] = arch.aggregate_bytes_per_epoch(
        cfg, gd.n, gd.n_edges)

    # free the program's state before the reference takes the chip
    del params, opt, loss, losses, opt_after_first, bundle, g, step
    gc.collect()

    graph = (jnp.asarray(gd.src), jnp.asarray(gd.dst),
             jnp.asarray(1.0 / np.bincount(gd.dst, minlength=gd.n)
                         .astype(np.float32)))
    loss_fn = training.full_graph_loss(arch,
                                       drop=float(cfg["model"]["dropout"]))
    batch = (graph, x, y, jnp.asarray(gd.train_mask))
    ref = training.reference_steps(loss_fn, params0,
                                   [step_keys[i] for i in range(n_ref)],
                                   opt_cfg, [batch] * n_ref)
    compared, shown = training.compare(prog, ref)
    run.compared.update(compared)
    run.shown.update(shown)
