"""Neighbour-sampled minibatch training with the program's sampler and
prefetcher in the loop.

Set-up builds the configuration's graph, the program's
``NeighborSampler`` (seeded by ``--seed``) behind its ``prefetch``, and
one compiled step (``make_sampled_train_step(<arch>.forward_blocks,
strategy)``) with its state, and drives it through its first
``ref_steps`` minibatches. The window continues the same state and the
same minibatch stream for ``--seconds``, epoch after epoch;
``sampled_seeds_per_s`` is the real (unpadded) seeds stepped in the
window over the window.

After the window the first minibatches are checked against the graph
(every sampled edge is an edge, each row holds ``min(in-degree,
fanout)`` distinct neighbours, the blocks chain, the labels are the
seeds') and the plain reference repeats the first steps on those same
blocks, from the same weights and dropout keys.

Mix keys: ``fanouts`` (outermost hop first, so the last is the seeds'
hop), ``batch_size``, ``prefetch_depth``, ``strategy``,
``bwd_strategy``, ``optimizer``, ``ref_steps``.

``FAULTS`` names the faults a cell of this kind can have (modes of
``calibrate.py``): a step that returns its state unchanged, and half of
the training rows left out of the loss (one chip: no exchange).
"""
from __future__ import annotations

import gc
import os
import time

import numpy as np

N_KEYS = 8192
FAULTS = ("frozen", "half")


def _epochs(sampler, train_ids, labels, batch, depth, prefetch):
    """Endless ``(minibatch, real seeds)`` stream, epoch after epoch,
    through the program's prefetcher."""
    n = len(train_ids)
    while True:
        it = prefetch(sampler.batches(train_ids, labels[train_ids],
                                      drop_last=False), depth=depth)
        try:
            for k, mb in enumerate(it):
                yield mb, min(batch, n - k * batch)
        finally:
            it.close()


def block_errors(mb, keys: np.ndarray, n: int, deg: np.ndarray,
                 labels: np.ndarray, fanouts) -> int:
    """Count what is wrong with one minibatch against the graph: edges
    that are not graph edges, rows with a wrong number of distinct
    neighbours, blocks that do not chain, seeds with wrong labels."""
    bad = 0
    blocks = mb.blocks
    for li, blk in enumerate(blocks):
        bg = blk.bg
        sid = np.asarray(blk.src_ids, np.int64)
        nbr = np.asarray(bg.nbr, np.int64)
        msk = np.asarray(bg.nbr_mask, bool)
        nd = int(bg.n_dst_real)
        gdst = sid[:nd]
        gsrc = np.where(msk, sid[nbr], -1)
        real = msk & (gsrc >= 0)
        bad += int((msk & (gsrc < 0)).sum())
        pairs = (np.broadcast_to(gdst[:, None], gsrc.shape)[real] * n
                 + gsrc[real])
        pos = np.searchsorted(keys, pairs)
        pos = np.minimum(pos, len(keys) - 1)
        bad += int((keys[pos] != pairs).sum())
        want = np.where(gdst >= 0,
                        np.minimum(deg[np.maximum(gdst, 0)], fanouts[li]), 0)
        bad += int((msk.sum(axis=1) != want).sum())
        srt = np.sort(np.where(real, gsrc, -1 - np.arange(gsrc.shape[1])),
                      axis=1)
        bad += int((srt[:, 1:] == srt[:, :-1]).sum())
        nxt = (np.asarray(blocks[li + 1].src_ids, np.int64)
               if li + 1 < len(blocks) else np.asarray(mb.seed_ids, np.int64))
        bad += int((sid[:nd] != nxt[:nd]).sum()) + abs(nd - len(nxt))
    seeds = np.asarray(mb.seed_ids, np.int64)
    lm = np.asarray(mb.label_mask, bool)
    got = np.asarray(mb.labels, np.int64)
    bad += int((lm & (seeds < 0)).sum())
    ok = lm & (seeds >= 0)
    bad += int((got[ok] != labels[seeds[ok]]).sum())
    bad += int((np.asarray(mb.input_ids) != np.asarray(blocks[0].src_ids))
               .sum())
    return bad


def run(run) -> None:
    import jax
    import jax.numpy as jnp
    import graphs
    import training
    from harness import load_module, seed_key
    from repro.core.graph import from_coo
    from repro.data.pipeline import prefetch
    from repro.data.sampler import NeighborSampler
    from repro.models.gnn.common import pad_features
    from repro.models.gnn.train import make_sampled_train_step

    cfg, mix, hooks = run.cfg, run.mix, run.hooks
    arch = load_module(os.path.join(run.bench_dir, "models",
                                    cfg["model"]["arch"] + ".py"),
                       cfg["model"]["arch"])
    opt_cfg = mix["optimizer"]
    n_ref = int(mix["ref_steps"])
    fanouts = [int(f) for f in mix["fanouts"]]
    batch = int(mix["batch_size"])

    gd = graphs.build(cfg)
    g = from_coo(gd.src, gd.dst, n_src=gd.n, n_dst=gd.n)
    mask = hooks.get("train_mask", lambda m: m)(gd.train_mask)
    train_ids = np.nonzero(gd.train_mask)[0]
    labels = gd.labels.astype(np.int64)
    feats_pad = pad_features(gd.feats)
    k_init, k_drop = jax.random.split(seed_key(run.seed))
    params0 = arch.init(cfg)(k_init)
    step_keys = np.asarray(jax.random.split(k_drop, N_KEYS))

    opt_init, step = make_sampled_train_step(
        arch.program().forward_blocks, mix["strategy"],
        bwd_strategy=mix["bwd_strategy"], lr=opt_cfg["lr"],
        weight_decay=opt_cfg["weight_decay"], clip=opt_cfg["clip"],
        precision=hooks.get("precision"))
    step = hooks.get("wrap_step", lambda s: s)(step)
    # the "half" fault leaves out the rows whose seed the mask drops
    keep_rows = jnp.asarray(mask)

    def feed(mb):
        if mask is gd.train_mask:
            return mb
        lm = mb.label_mask & keep_rows[jnp.maximum(mb.seed_ids, 0)]
        return type(mb)(mb.blocks, mb.input_ids, mb.seed_ids, mb.labels, lm)

    sampler = NeighborSampler(g, fanouts, batch, seed=run.seed)
    stream = _epochs(sampler, train_ids, labels, batch,
                     int(mix["prefetch_depth"]), prefetch)

    params, opt = params0, opt_init(params0)
    losses, kept = [], []
    opt_after_first = None
    for i in range(n_ref):
        mb, _ = next(stream)
        params, opt, loss = step(params, opt, i, feed(mb), feats_pad,
                                 step_keys[i])
        losses.append(loss)
        kept.append(mb)
        if i == 0:
            opt_after_first = opt
    jax.block_until_ready((params, opt, losses))
    prog = training.readings(losses, opt_after_first, params0, params,
                             b1=opt_cfg["b1"])

    run.start_window()
    i, n, seeds, wait = n_ref, 0, 0, 0.0
    try:
        while True:
            t0 = time.perf_counter()
            with run.annotate("bench.sample_wait"):
                mb, real = next(stream)
            wait += time.perf_counter() - t0
            with run.annotate("bench.step"):
                params, opt, loss = step(params, opt, i, feed(mb),
                                         feats_pad, step_keys[i % N_KEYS])
                loss.block_until_ready()
            i += 1
            n += 1
            seeds += real
            if not run.window_open():
                break
        window = run.end_window()
    finally:
        stream.close()
    last = float(loss)
    run.attempted = n
    run.failed = 0 if np.isfinite(last) else n
    run.e2e["sampled_seeds_per_s"] = seeds / window
    run.layer["steps"] = n
    run.layer["sample_wait_s"] = wait

    del params, opt, loss, losses, opt_after_first, step, stream
    gc.collect()

    keys = gd.edge_keys()
    deg = np.bincount(gd.dst, minlength=gd.n)
    bad = sum(block_errors(mb, keys, gd.n, deg, labels, fanouts)
              for mb in kept)
    x_all = jnp.asarray(feats_pad)
    y_all = jnp.asarray(gd.labels)
    drop = float(cfg["model"]["dropout"])

    def loss_fn(p, key, b):
        blocks, input_ids, seed_ids, lm, x_all, y_all = b
        x = x_all[jnp.where(input_ids >= 0, input_ids, gd.n)]
        logits = arch.block_logits(p, blocks, x, key, drop=drop, train=True)
        return training.cross_entropy(logits, y_all[jnp.maximum(seed_ids, 0)],
                                      lm)

    batches = [(tuple((jnp.asarray(np.asarray(b.bg.nbr)),
                       jnp.asarray(np.asarray(b.bg.nbr_mask)))
                      for b in mb.blocks),
                jnp.asarray(np.asarray(mb.input_ids)),
                jnp.asarray(np.asarray(mb.seed_ids)),
                jnp.asarray(np.asarray(mb.label_mask)), x_all, y_all)
               for mb in kept]
    del kept
    ref = training.reference_steps(
        loss_fn, params0,
        [step_keys[i] for i in range(n_ref)], opt_cfg, batches)
    compared, shown = training.compare(prog, ref)
    run.compared.update(compared)
    run.shown.update(shown)
    run.compared["invalid_samples"] = float(bad)
