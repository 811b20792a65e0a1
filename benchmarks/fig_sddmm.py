"""Planned gSDDMM + fused-attention benchmark (DESIGN.md §9).

The GAT attention pipeline, multipass (planned gsddmm logits → leaky →
edge softmax → weighted gspmm: four kernel-sized passes with per-edge α
materialized in HBM) vs :func:`repro.core.fused_attention` (ONE pass in
canonical dst-sorted order, α never stored), forward AND forward+
backward — the acceptance axis of the fused-attention subsystem. An
``auto`` row per config records what the attention planner picks.

Configs: the Fig. 2 pubmed-like full-graph shape at the GAT defaults
(hidden=16, heads=4) and a products-like shape (the scale where pass
fusion pays most). A gsddmm strategy sweep (canonical vs the
caller-order gather baseline) rides along on the logits op.
``REPRO_BENCH_QUICK=1`` shrinks every config for CI.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (from_coo, fused_attention, get_plan_cache,
                        gsddmm, gspmm)
from repro.core import planner as _planner
from repro.core.edge_softmax import edge_softmax
from repro.data import make_node_dataset, rmat_graph
from repro.substrate.nn import leaky_relu

from .common import row, time_fn

QUICK = bool(int(os.environ.get("REPRO_BENCH_QUICK", "0")))

HIDDEN, HEADS = 16, 4
# products-like: the dense-ish large shape where the multipass α tensor
# is the biggest intermediate (scaled to CPU bench time)
PRODUCTS_SHAPE = (32_768, 400_000)
# power-law (R-MAT) degree tail: the padding-tax shape — hub rows make
# the row-complete ELL slot count explode, so this is where the ragged
# per-class packs decide whether the Pallas megakernel is viable at all
POWERLAW_SHAPE = (15, 180_000)          # (n_log2, n_edges)
if QUICK:
    PRODUCTS_SHAPE = (2_048, 12_000)
    POWERLAW_SHAPE = (11, 12_000)


def _attention_fns(g, pallas: bool = False):
    """Jitted (fwd, fwd+bwd) callables per pipeline variant."""

    def multipass(el, er, z):
        logits = gsddmm(g, "u_add_v_copy_e", u=el, v=er)
        alpha = edge_softmax(g, leaky_relu(logits))
        return gspmm(g, "u_mul_e_add_v", u=z, e=alpha[:, :, None])

    def fused(el, er, z):
        return fused_attention(g, el, er, z, strategy="fused")

    def auto(el, er, z):
        return fused_attention(g, el, er, z, strategy="auto")

    def pallas_fn(el, er, z):
        return fused_attention(g, el, er, z, strategy="pallas")

    variants = [("multipass", multipass), ("fused", fused),
                ("auto", auto)]
    if pallas:
        variants.append(("pallas", pallas_fn))
    out = {}
    for name, fn in variants:
        fwd = jax.jit(fn)

        def fwdbwd(el, er, z, _fn=fn):
            def loss(el, er, z):
                return jnp.sum(_fn(el, er, z) ** 2)
            return jax.value_and_grad(loss, argnums=(0, 1, 2))(el, er, z)

        out[name] = (fwd, jax.jit(fwdbwd))
    return out


def bench_attention(tag: str, g, note: str, pallas: bool = False) -> float:
    rng = np.random.default_rng(0)
    n_src, n_dst = g.n_src, g.n_dst
    el = jnp.asarray(rng.normal(size=(n_src, HEADS)).astype(np.float32))
    er = jnp.asarray(rng.normal(size=(n_dst, HEADS)).astype(np.float32))
    z = jnp.asarray(rng.normal(size=(n_src, HEADS, HIDDEN))
                    .astype(np.float32))
    fns = _attention_fns(g, pallas=pallas)
    t = {}
    for name, (fwd, fwdbwd) in fns.items():
        t[name, "fwd"] = time_fn(fwd, el, er, z, iters=5)
        t[name, "bwd"] = time_fn(fwdbwd, el, er, z, iters=5)
    for phase in ("fwd", "bwd"):
        sp = t["multipass", phase] / max(t["fused", phase], 1e-12)
        suffix = "_fwdbwd" if phase == "bwd" else "_fwd"
        print(row(f"{tag}{suffix}_multipass", t["multipass", phase],
                  note))
        print(row(f"{tag}{suffix}_fused", t["fused", phase],
                  f"fused_speedup={sp:.2f}x"))
        print(row(f"{tag}{suffix}_auto", t["auto", phase],
                  f"vs_multipass="
                  f"{t['multipass', phase] / max(t['auto', phase], 1e-12):.2f}x"))
        if pallas:
            print(row(f"{tag}{suffix}_pallas", t["pallas", phase],
                      f"vs_fused={t['fused', phase] / max(t['pallas', phase], 1e-12):.2f}x"))
    return t["multipass", "fwd"] / max(t["fused", "fwd"], 1e-12)


def bench_gsddmm_strategies(tag: str, g, note: str) -> None:
    """The logits op alone: canonical stream vs caller-order gather."""
    rng = np.random.default_rng(1)
    el = jnp.asarray(rng.normal(size=(g.n_src, HEADS)).astype(np.float32))
    er = jnp.asarray(rng.normal(size=(g.n_dst, HEADS)).astype(np.float32))
    t = {}
    for s in ("canonical", "gather"):
        fn = jax.jit(lambda el, er, _s=s: gsddmm(
            g, "u_add_v_copy_e", u=el, v=er, strategy=_s))
        t[s] = time_fn(fn, el, er, iters=5)
    sp = t["gather"] / max(t["canonical"], 1e-12)
    print(row(f"{tag}_logits_gather", t["gather"], note))
    print(row(f"{tag}_logits_canonical", t["canonical"],
              f"canonical_speedup={sp:.2f}x"))


def _products_like():
    n, nnz = PRODUCTS_SHAPE
    rng = np.random.default_rng(7)
    src = rng.integers(0, n, nnz)
    dst = rng.integers(0, n, nnz)
    return from_coo(src, dst, n_src=n, n_dst=n)


def _powerlaw():
    n_log2, nnz = POWERLAW_SHAPE
    src, dst, n = rmat_graph(n_log2, nnz, seed=11)
    return from_coo(src, dst, n_src=n, n_dst=n)


def report_pad_slots(tag: str, g) -> None:
    """Pad-slot accounting rows: row-complete ELL vs ragged classes.

    Slot counts land in ``derived`` (they are not timings); the
    pad-ratio trajectory itself is tracked by the
    ``planner.pad_ratio.*`` gauges in the BENCH JSON metrics snapshot.
    """
    deg = np.asarray(g.in_degrees)
    nz = int((deg > 0).sum())
    uniform = nz * int(deg.max()) if nz else 0
    ragged, n_classes = _planner.ell_rowcomplete_padding(deg)
    drop = uniform / max(ragged, 1)
    print(row(f"{tag}_pad_slots_rowcomplete", 0.0,
              f"slots={uniform} edges={g.n_edges} "
              f"ratio={uniform / max(g.n_edges, 1):.2f}"))
    print(row(f"{tag}_pad_slots_ragged", 0.0,
              f"slots={ragged} classes={n_classes} "
              f"ratio={ragged / max(g.n_edges, 1):.2f} drop={drop:.2f}x"))


def main():
    # no --strategy knob: the sweep times multipass/fused/auto explicitly
    g, *_ = make_node_dataset("pubmed-like")
    gp = _products_like()
    gw = _powerlaw()
    for gr in (g, gp, gw):
        # packs build host-side, not in-trace: the recalibrated cost
        # model picks pallas well below power-law scale, so every graph
        # auto touches needs its ragged pack prebuilt or the in-trace
        # path silently demotes to 'fused'
        get_plan_cache(gr).ell()
        get_plan_cache(gr).ell_ragged()
    bench_attention("fig_sddmm_pubmed", g, f"edges={g.n_edges}")
    bench_gsddmm_strategies("fig_sddmm_pubmed", g, f"edges={g.n_edges}")
    bench_attention("fig_sddmm_products", gp, f"edges={gp.n_edges}")
    bench_gsddmm_strategies("fig_sddmm_products", gp,
                            f"edges={gp.n_edges}")
    bench_attention("fig_sddmm_powerlaw", gw, f"edges={gw.n_edges}",
                    pallas=True)
    report_pad_slots("fig_sddmm_powerlaw", gw)


if __name__ == "__main__":
    main()
