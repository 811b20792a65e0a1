"""Sampler property tests (paper Fig. 3 preconditions).

The properties the minibatch subsystem leans on:

* determinism — same seed ⇒ bit-identical batches;
* padding hygiene — pad slots/edges contribute EXACTLY zero to mean
  aggregation, even when the dummy feature row is poisoned;
* fanout bounds — no destination ever receives more than ``fanout``
  sampled in-edges, and sampling is without replacement;
* exactness — with fanout ≥ max in-degree the blocks contain every
  in-edge, so the sampled forward equals the full-graph forward for
  every app on the shared block path (SAGE, GCN, GAT).
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import block_gspmm, from_coo
from repro.data import NeighborSampler, make_node_dataset
from repro.models.gnn import gat, gcn, sage
from repro.models.gnn.common import (block_features, make_bundle,
                                     pad_features)
from tests.graphgen import random_graph


@pytest.fixture(scope="module")
def tiny():
    return make_node_dataset("tiny")


def _batches(sampler, ids, labels, n=3):
    out = []
    for i, mb in enumerate(sampler.batches(ids, labels)):
        out.append(mb)
        if i + 1 >= n:
            break
    return out


def test_seed_determinism(tiny):
    g, feats, labels, tm, vm, nc = tiny
    ids = np.nonzero(tm)[0]
    a = _batches(NeighborSampler(g, [4, 4], 16, seed=7), ids, labels[ids])
    b = _batches(NeighborSampler(g, [4, 4], 16, seed=7), ids, labels[ids])
    c = _batches(NeighborSampler(g, [4, 4], 16, seed=8), ids, labels[ids])
    for mb_a, mb_b in zip(a, b):
        assert (np.asarray(mb_a.seed_ids) == np.asarray(mb_b.seed_ids)).all()
        for blk_a, blk_b in zip(mb_a.blocks, mb_b.blocks):
            np.testing.assert_array_equal(np.asarray(blk_a.bg.nbr),
                                          np.asarray(blk_b.bg.nbr))
            np.testing.assert_array_equal(np.asarray(blk_a.src_ids),
                                          np.asarray(blk_b.src_ids))
            np.testing.assert_array_equal(np.asarray(blk_a.bg.g.src),
                                          np.asarray(blk_b.bg.g.src))
    assert any((np.asarray(x.seed_ids) != np.asarray(y.seed_ids)).any()
               for x, y in zip(a, c))


def test_vectorized_draw_identical_streams_same_seed():
    """The batched without-replacement draw must be stream-deterministic:
    two samplers with one seed (and one sampler after reset()) emit
    bit-identical minibatches — every array, every block, every batch."""
    rng = np.random.default_rng(11)
    g, src, dst = random_graph(rng, 50, 50, 400)
    ids = np.arange(g.n_dst)
    labels = rng.integers(0, 3, g.n_dst)

    def stream(sampler):
        return _batches(sampler, ids, labels, n=4)

    a = stream(NeighborSampler(g, [3, 5], 8, seed=42))
    b = stream(NeighborSampler(g, [3, 5], 8, seed=42))
    s = NeighborSampler(g, [3, 5], 8, seed=42)
    first = stream(s)
    s.reset()
    replay = stream(s)
    for other in (b, first, replay):
        for mb_a, mb_o in zip(a, other):
            np.testing.assert_array_equal(np.asarray(mb_a.seed_ids),
                                          np.asarray(mb_o.seed_ids))
            np.testing.assert_array_equal(np.asarray(mb_a.labels),
                                          np.asarray(mb_o.labels))
            for blk_a, blk_o in zip(mb_a.blocks, mb_o.blocks):
                for fa, fo in [(blk_a.bg.nbr, blk_o.bg.nbr),
                               (blk_a.bg.nbr_eid, blk_o.bg.nbr_eid),
                               (blk_a.bg.nbr_mask, blk_o.bg.nbr_mask),
                               (blk_a.src_ids, blk_o.src_ids),
                               (blk_a.gcn_norm, blk_o.gcn_norm),
                               (blk_a.bg.g.src, blk_o.bg.g.src),
                               (blk_a.bg.g.dst, blk_o.bg.g.dst)]:
                    np.testing.assert_array_equal(np.asarray(fa),
                                                  np.asarray(fo))


def test_reset_replays_stream(tiny):
    g, feats, labels, tm, vm, nc = tiny
    ids = np.nonzero(tm)[0]
    s = NeighborSampler(g, [3], 8, seed=5)
    first = _batches(s, ids, labels[ids], n=2)
    s.reset()
    again = _batches(s, ids, labels[ids], n=2)
    for mb1, mb2 in zip(first, again):
        np.testing.assert_array_equal(np.asarray(mb1.input_ids),
                                      np.asarray(mb2.input_ids))


@pytest.mark.parametrize("strategy", ["ell", "segment", "push"])
def test_pad_rows_contribute_zero_to_mean(strategy):
    """Poisoning every PAD source slot's features must not change any
    real row of the mean aggregation, for every block strategy."""
    rng = np.random.default_rng(0)
    g, src, dst = random_graph(rng, 40, 40, 160)
    sampler = NeighborSampler(g, fanouts=[3], batch_size=8, seed=1)
    seeds = rng.permutation(g.n_dst)[:8]
    mb = sampler.sample(seeds, np.zeros(8, np.int64))
    blk = mb.blocks[0]
    bg = blk.bg
    feats = rng.normal(size=(g.n_src, 6)).astype(np.float32)
    h = block_features(pad_features(feats), blk.src_ids)
    poison = np.asarray(h).copy()
    poison[np.asarray(blk.src_ids) < 0] = 1e9      # garbage in pad slots
    clean = block_gspmm(bg, "u_copy_mean_v", u=h, strategy=strategy)
    dirty = block_gspmm(bg, "u_copy_mean_v", u=jnp.asarray(poison),
                        strategy=strategy)
    np.testing.assert_array_equal(np.asarray(clean), np.asarray(dirty))
    # and the mean denominator is the REAL degree, not the padded width
    s = block_gspmm(bg, "u_copy_add_v", u=h, strategy=strategy)
    deg = np.maximum(np.asarray(bg.real_deg), 1)[:, None]
    np.testing.assert_allclose(np.asarray(clean), np.asarray(s) / deg,
                               rtol=1e-5, atol=1e-6)


def test_fanout_bounds_and_no_replacement():
    rng = np.random.default_rng(2)
    # unique edges: without-replacement sampling repeats a neighbor only
    # through parallel edges, which a simple graph rules out
    g, src, dst = random_graph(rng, 60, 60, 600, unique=True)
    fanout = 5
    sampler = NeighborSampler(g, fanouts=[fanout], batch_size=16, seed=3)
    indptr = np.asarray(g.indptr_dst)
    gsrc = np.asarray(g.src)
    for mb in _batches(sampler, np.arange(g.n_dst),
                       np.zeros(g.n_dst, np.int64), n=3):
        blk = mb.blocks[0]
        bg = blk.bg
        real_deg = np.asarray(bg.real_deg)
        mask = np.asarray(bg.nbr_mask)
        assert (real_deg <= fanout).all()
        assert (mask.sum(1) == real_deg).all()
        seeds = np.asarray(mb.seed_ids)
        src_ids = np.asarray(blk.src_ids)
        nbr = np.asarray(bg.nbr)
        for j, node in enumerate(seeds):
            if node < 0:
                continue
            in_deg = indptr[node + 1] - indptr[node]
            # never more than min(fanout, degree) samples
            assert real_deg[j] == min(fanout, in_deg)
            neigh = src_ids[nbr[j][mask[j]]]
            # without replacement: sampled globals are distinct, and all
            # are true in-neighbors of the seed
            true_nb = gsrc[indptr[node]:indptr[node + 1]]
            assert len(set(neigh.tolist())) == len(neigh)
            assert set(neigh.tolist()) <= set(true_nb.tolist())


def test_short_final_batch_padded_and_masked(tiny):
    g, feats, labels, tm, vm, nc = tiny
    ids = np.nonzero(tm)[0][:37]        # 37 = 2×16 + 5 tail
    sampler = NeighborSampler(g, [3], 16, seed=0)
    mbs = list(sampler.batches(ids, labels[ids], drop_last=False))
    assert len(mbs) == 3
    for mb in mbs:
        assert mb.seed_ids.shape == (16,)
        assert mb.labels.shape == (16,)
    tail = mbs[-1]
    assert int(tail.label_mask.sum()) == 5
    assert (np.asarray(tail.seed_ids)[np.asarray(~tail.label_mask)]
            == -1).all()
    # padded batch keeps the one static shape signature
    assert tail.shape_signature() == mbs[0].shape_signature()


# ------------------------------------------------------------------ #
# reverse table (the gather backward's lookup structure, DESIGN.md §7)
# ------------------------------------------------------------------ #
def _forward_edge_set(bg):
    """{(src_slot, dst_row, caller_eid)} of the REAL edges, from the
    forward neighbor table."""
    nbr = np.asarray(bg.nbr)
    eid = np.asarray(bg.nbr_eid)
    mask = np.asarray(bg.nbr_mask)
    jj, kk = np.nonzero(mask)
    return set(zip(nbr[jj, kk].tolist(), jj.tolist(),
                   eid[jj, kk].tolist()))


def _reverse_edge_set(bg):
    """Same triple set rebuilt from the reverse table (real edges are
    the ones whose destination is a real row, not the dummy)."""
    rs = np.asarray(bg.rev_src)
    rd = np.asarray(bg.rev_dst)
    re = np.asarray(bg.rev_eid)
    real = rd < bg.n_dst_real
    return set(zip(rs[real].tolist(), rd[real].tolist(),
                   re[real].tolist()))


def test_reverse_table_round_trip():
    """forward table ↦ reverse table ↦ forward: the reverse table is a
    src-sorted permutation of exactly the same edges, every layer."""
    rng = np.random.default_rng(9)
    g, src, dst = random_graph(rng, 40, 40, 200)
    sampler = NeighborSampler(g, fanouts=[3, 4], batch_size=8, seed=2)
    mb = sampler.sample(rng.permutation(g.n_dst)[:8],
                        np.zeros(8, np.int64))
    for blk in mb.blocks:
        bg = blk.bg
        assert bg.has_reverse
        rev_src = np.asarray(bg.rev_src)
        rev_eid = np.asarray(bg.rev_eid)
        # a permutation of ALL edge slots, sorted by source slot
        assert sorted(rev_eid.tolist()) == list(range(bg.g.n_edges))
        assert (np.diff(rev_src) >= 0).all()
        # real-edge triples agree exactly with the forward table
        assert _reverse_edge_set(bg) == _forward_edge_set(bg)
        # pad edges: dummy source slot AND dummy destination row only
        rd = np.asarray(bg.rev_dst)
        pad = rd >= bg.n_dst_real
        assert (rev_src[pad] == bg.g.n_src - 1).all()
        assert (rd[pad] == bg.n_dst_real).all()


def test_reverse_table_deterministic_per_seed():
    rng = np.random.default_rng(13)
    g, src, dst = random_graph(rng, 30, 30, 180)
    ids = np.arange(g.n_dst)
    labels = np.zeros(g.n_dst, np.int64)
    a = _batches(NeighborSampler(g, [4], 8, seed=21), ids, labels)
    b = _batches(NeighborSampler(g, [4], 8, seed=21), ids, labels)
    for mb_a, mb_b in zip(a, b):
        for blk_a, blk_b in zip(mb_a.blocks, mb_b.blocks):
            for fa, fb in [(blk_a.bg.rev_src, blk_b.bg.rev_src),
                           (blk_a.bg.rev_dst, blk_b.bg.rev_dst),
                           (blk_a.bg.rev_eid, blk_b.bg.rev_eid)]:
                np.testing.assert_array_equal(np.asarray(fa),
                                              np.asarray(fb))


def test_reverse_backward_pad_poison_invariance():
    """Poisoning every PAD source slot's features AND every pad edge's
    weight must not change any gradient of the gather backward: pad
    edges pull the dummy destination's zero cotangent row."""
    rng = np.random.default_rng(3)
    g, src, dst = random_graph(rng, 40, 40, 160)
    sampler = NeighborSampler(g, fanouts=[3], batch_size=8, seed=1)
    mb = sampler.sample(rng.permutation(g.n_dst)[:8], np.zeros(8, np.int64))
    blk = mb.blocks[0]
    bg = blk.bg
    n_real = int(np.asarray(bg.real_deg).sum())
    u = jnp.asarray(rng.normal(size=(bg.g.n_src, 6)).astype(np.float32))
    e = jnp.asarray(rng.normal(size=(bg.g.n_edges, 1)).astype(np.float32))
    ct = jnp.asarray(rng.normal(size=(bg.n_dst_real, 6)).astype(np.float32))

    def grads(u, e):
        def f(u, e):
            return jnp.sum(block_gspmm(bg, "u_mul_e_add_v", u=u, e=e,
                                       bwd_strategy="gather") * ct)
        return jax.grad(f, argnums=(0, 1))(u, e)

    pu = np.asarray(u).copy()
    pu[np.asarray(blk.src_ids) < 0] = 1e9          # poison pad src slots
    pe = np.asarray(e).copy()
    pe[n_real:] = -1e9                             # poison pad edges
    du, de = grads(u, e)
    du_p, de_p = grads(jnp.asarray(pu), jnp.asarray(pe))
    np.testing.assert_array_equal(np.asarray(du), np.asarray(du_p))
    # real edges' ∂e unchanged; pad edges' ∂e is exactly zero both ways
    np.testing.assert_array_equal(np.asarray(de)[:n_real],
                                  np.asarray(de_p)[:n_real])
    np.testing.assert_array_equal(np.asarray(de_p)[n_real:], 0.0)


@pytest.mark.parametrize("mod", [sage, gcn, gat],
                         ids=["sage", "gcn", "gat"])
def test_sampled_equals_full_when_fanout_covers_degree(tiny, mod):
    """fanout ≥ max in-degree ⇒ blocks hold every in-edge ⇒ the sampled
    forward must equal the full-graph forward on the seed rows."""
    g, feats, labels, tm, vm, nc = tiny
    maxdeg = int(np.asarray(g.in_degrees).max())
    sampler = NeighborSampler(g, fanouts=[maxdeg, maxdeg], batch_size=16,
                              seed=4)
    ids = np.nonzero(tm)[0][:16]
    mb = sampler.sample(ids, labels[ids])
    bundle = make_bundle(g)
    params = mod.init(jax.random.PRNGKey(0), feats.shape[1], 16, nc)
    full = mod.forward(params, bundle, jnp.asarray(feats))
    x = block_features(pad_features(feats), mb.input_ids)
    sampled = mod.forward_blocks(params, mb.blocks, x)
    ref = np.asarray(full)[np.asarray(mb.seed_ids)]
    np.testing.assert_allclose(np.asarray(sampled), ref,
                               rtol=2e-4, atol=2e-5)


def _regular_graph(n, deg):
    """Every node v has in-edges from v+1 … v+deg (mod n): in-degree
    exactly ``deg`` everywhere, so fanout ``deg`` leaves no pad edge."""
    dst = np.repeat(np.arange(n), deg)
    src = (dst + np.tile(np.arange(1, deg + 1), n)) % n
    return from_coo(src, dst, n_src=n, n_dst=n)


def _equivalence_batches(case, tiny):
    g, feats, labels, tm, vm, nc = tiny
    ids = np.nonzero(tm)[0]
    rng = np.random.default_rng(17)
    if case == "full_batch":
        return _batches(NeighborSampler(g, [4, 3], 16, seed=3), ids,
                        labels[ids], n=2)
    if case == "short_final_batch":
        mbs = list(NeighborSampler(g, [3, 2], 16, seed=5).batches(
            ids[:37], labels[ids[:37]], drop_last=False))
        assert len(mbs) == 3 and int(np.asarray(mbs[-1].label_mask).sum()) == 5
        return mbs[-1:]
    if case == "fanout_covers_degree":
        maxdeg = int(np.asarray(g.in_degrees).max())
        return _batches(NeighborSampler(g, [maxdeg, maxdeg], 16, seed=4),
                        ids, labels[ids], n=1)
    if case == "no_pad_edges":
        # 8 spread-out seeds of a 3-regular graph: both blocks are full,
        # and the seeds' block puts a real edge in its last source slot
        rg = _regular_graph(400, 3)
        mb = NeighborSampler(rg, [3, 3], 8, seed=0).sample(
            np.arange(0, 400, 50), np.zeros(8, np.int64))
        inner = mb.blocks[-1].bg
        assert inner.g.n_edges == int(np.asarray(inner.real_deg).sum())
        assert int(np.asarray(inner.g.src).max()) == inner.g.n_src - 1
        return [mb]
    if case == "duplicate_edges":
        # 20 nodes, 300 random edges: many parallel edges, so rows sample
        # the same source slot more than once
        dg, _, _ = random_graph(rng, 20, 20, 300)
        mbs = _batches(NeighborSampler(dg, [6, 12], 8, seed=1),
                       np.arange(20), np.zeros(20, np.int64), n=2)
        assert any(len(set(r[m].tolist())) < int(m.sum())
                   for mb in mbs for b in mb.blocks
                   for r, m in zip(np.asarray(b.bg.nbr),
                                   np.asarray(b.bg.nbr_mask)))
        return mbs
    assert case == "relational"
    rel = rng.integers(0, 4, g.n_edges)
    return _batches(NeighborSampler(g, [4, 3], 16, seed=6, edge_rel=rel),
                    ids, labels[ids], n=2)


@pytest.mark.parametrize("case", [
    "full_batch", "short_final_batch", "fanout_covers_degree",
    "no_pad_edges", "duplicate_edges", "relational"])
def test_blocks_bit_identical_to_from_coo(tiny, case):
    """Every sampled block's graph equals ``from_coo`` over the same
    padded edge list, array for array and dtype for dtype, and its reverse
    table equals a stable argsort of that list by source slot. The edge
    list is read back from the neighbor table (row-major real edges, then
    pads from the dummy source slot into the dummy row)."""
    for mb in _equivalence_batches(case, tiny):
        for blk in mb.blocks:
            bg = blk.bg
            mask = np.asarray(bg.nbr_mask)
            n_real = int(mask.sum())
            np.testing.assert_array_equal(np.asarray(bg.nbr_eid)[mask],
                                          np.arange(n_real))
            pad = bg.g.n_edges - n_real
            srcs = np.concatenate([np.asarray(bg.nbr)[mask],
                                   np.full(pad, bg.g.n_src - 1)])
            dsts = np.concatenate([np.nonzero(mask)[0],
                                   np.full(pad, bg.n_dst_real)])
            ref = from_coo(srcs, dsts, n_src=bg.g.n_src,
                           n_dst=bg.n_dst_real + 1)
            assert ((bg.g.n_src, bg.g.n_dst, bg.g.n_edges)
                    == (ref.n_src, ref.n_dst, ref.n_edges))
            for f in ("src", "dst", "eid", "indptr_dst", "indptr_src",
                      "perm_src", "eid_inv"):
                got, want = getattr(bg.g, f), getattr(ref, f)
                assert got.dtype == want.dtype, f
                np.testing.assert_array_equal(np.asarray(got),
                                              np.asarray(want), err_msg=f)
            order = np.argsort(srcs, kind="stable")
            for got, want in ((bg.rev_eid, order), (bg.rev_src, srcs[order]),
                              (bg.rev_dst, dsts[order])):
                assert got.dtype == jnp.int32
                np.testing.assert_array_equal(np.asarray(got), want)
