"""Edge softmax — GAT's 5-primitive BR chain (paper Table 2, row 8).

DGL executes GAT attention normalization as five separate BR/CR passes:

    m   = e_copy_max_v   (segment max)
    s   = e_sub_v_copy_e (shift)
    x   = exp(s)
    z   = e_copy_add_v   (segment sum)
    out = e_div_v_copy_e (normalize)

``edge_softmax`` composes exactly those primitives (faithful layering);
``edge_softmax_fused`` is the optimized single-pass version that stays in
canonical edge order throughout — one gather in, one gather out, no
intermediate HBM round-trips (beyond-paper fusion; the Pallas kernel in
``repro.kernels.edge_softmax`` is its TPU form).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from . import planner
from ..compat import trace_state_clean
from .binary_reduce import gspmm
from .blocks import BlockGraph, block_gspmm
from .graph import Graph

__all__ = ["edge_softmax", "edge_softmax_fused", "block_edge_softmax",
           "fused_attention", "block_fused_attention",
           "fused_attention_partitioned"]


def edge_softmax(g: Graph, logits: jnp.ndarray,
                 strategy: str = "auto", cache=None) -> jnp.ndarray:
    """Softmax over incoming edges of each destination node.

    ``logits``: (n_edges, H) in the caller's edge order. Returns the same
    shape/order. Composed from the exact BR configs the paper profiles;
    the two node-output reductions route through the planner (pass
    ``cache`` to reuse a per-graph :class:`PlanCache` inside ``jit``).
    """
    with jax.named_scope("edge_softmax"):
        maxv = gspmm(g, "e_copy_max_v", e=logits, strategy=strategy,
                     cache=cache)
        # align with edge_softmax_fused: a zero-in-degree node's max is
        # the reduce identity (-inf) on any strategy that skips the
        # degree finalize — never let it reach the subtract
        maxv = jnp.where(jnp.isfinite(maxv), maxv,
                         jnp.zeros((), maxv.dtype))
        shifted = gspmm(g, "e_sub_v_copy_e", e=logits, v=maxv,
                        strategy=strategy)
        ex = jnp.exp(shifted)
        z = gspmm(g, "e_copy_add_v", e=ex, strategy=strategy, cache=cache)
        return gspmm(g, "e_div_v_copy_e", e=ex, v=z, strategy=strategy)


def block_edge_softmax(bg: BlockGraph, logits: jnp.ndarray,
                       strategy: str = "auto",
                       bwd_strategy: str = "auto") -> jnp.ndarray:
    """Edge softmax over one sampled block's real in-edges.

    Same five-primitive chain as :func:`edge_softmax`, with the two
    node-output reductions routed through the shape-keyed block planner
    (``bwd_strategy`` picks their differentiation path — the max
    reduction always stays on autodiff, see planner.block_bwd_supports).
    Pad edges live in the dummy destination row, so real rows' softmax
    sees exactly their real edges; pad edges' output values are garbage
    but masked out of every downstream block aggregation.
    """
    with jax.named_scope("edge_softmax"):
        x = logits[:, None] if logits.ndim == 1 else logits
        pad = jnp.zeros((1,) + x.shape[1:], x.dtype)
        maxv = block_gspmm(bg, "e_copy_max_v", e=x, strategy=strategy,
                           bwd_strategy=bwd_strategy)
        shifted = gspmm(bg.g, "e_sub_v_copy_e", e=x,
                        v=jnp.concatenate([maxv, pad], axis=0))
        ex = jnp.exp(shifted)
        z = block_gspmm(bg, "e_copy_add_v", e=ex, strategy=strategy,
                        bwd_strategy=bwd_strategy)
        # dummy row gets z=1 so pad edges divide by a finite value;
        # every real edge's destination has ≥ 1 real edge, so z > 0 on
        # real rows
        zp = jnp.concatenate([z, jnp.ones_like(pad)], axis=0)
        out = gspmm(bg.g, "e_div_v_copy_e", e=ex, v=zp)
        return out[:, 0] if logits.ndim == 1 else out


def edge_softmax_fused(g: Graph, logits: jnp.ndarray) -> jnp.ndarray:
    """Single-pass edge softmax in canonical (dst-sorted) order."""
    with jax.named_scope("edge_softmax"):
        x = logits[:, None] if logits.ndim == 1 else logits
        m = jnp.take(x, g.eid, axis=0)                   # canonical order
        kw = dict(num_segments=g.n_dst, indices_are_sorted=True)
        mx = jax.ops.segment_max(m, g.dst, **kw)
        mx = jnp.where(jnp.isfinite(mx), mx, jnp.zeros((), m.dtype))
        ex = jnp.exp(m - jnp.take(mx, g.dst, axis=0))
        z = jax.ops.segment_sum(ex, g.dst, **kw)
        out = ex / jnp.take(z, g.dst, axis=0)
        out = jnp.take(out, g.eid_inv, axis=0)
        return out[:, 0] if logits.ndim == 1 else out


# --------------------------------------------------------------------- #
# fused attention: logits + leaky-relu + softmax + weighted reduce as
# ONE planned pass (DESIGN.md §9)
# --------------------------------------------------------------------- #
def _attention_alpha(g: Graph, el, er, slope):
    """Canonical-order α and the raw logits (for the leaky mask)."""
    m_raw = jnp.take(el, g.src, axis=0) + jnp.take(er, g.dst, axis=0)
    m = jnp.where(m_raw >= 0, m_raw, slope * m_raw)      # (E, H)
    kw = dict(num_segments=g.n_dst, indices_are_sorted=True)
    mx = jax.ops.segment_max(m, g.dst, **kw)
    mx = jnp.where(jnp.isfinite(mx), mx, jnp.zeros((), m.dtype))
    ex = jnp.exp(m - jnp.take(mx, g.dst, axis=0))
    zs = jax.ops.segment_sum(ex, g.dst, **kw)
    alpha = ex / jnp.take(jnp.maximum(zs, 1e-38), g.dst, axis=0)
    return alpha, m_raw


def _attention_execute(g: Graph, pack, el, er, z, slope, chosen):
    if chosen == "pallas":
        from ..kernels.edge_softmax.ops import \
            fused_attention as attention_pallas

        # the ragged pack is resolved OUTSIDE the custom_vjp boundary
        # (g is a tracer in here; plan caches key on concrete graphs)
        return attention_pallas(g, el, er, z, slope=slope, ell=pack)
    alpha, _ = _attention_alpha(g, el, er, slope)
    msg = alpha[..., None] * jnp.take(z, g.src, axis=0)  # (E, H, F)
    return jax.ops.segment_sum(msg, g.dst, num_segments=g.n_dst,
                               indices_are_sorted=True)


def _attention_grads(g: Graph, el, er, z, slope, ct):
    """Scatter-free adjoints of the fused pipeline: recompute α on the
    canonical stream, route source-side grads through the free
    src-sorted view (``perm_src`` + SORTED segment reduce)."""
    alpha, m_raw = _attention_alpha(g, el, er, slope)
    ct_e = jnp.take(ct, g.dst, axis=0)                   # (E, H, F)
    z_src = jnp.take(z, g.src, axis=0)
    g_alpha = jnp.sum(ct_e * z_src, axis=-1)             # (E, H)

    perm = g.perm_src
    src_sorted = jnp.take(g.src, perm)
    skw = dict(num_segments=g.n_src, indices_are_sorted=True)
    dkw = dict(num_segments=g.n_dst, indices_are_sorted=True)

    dz = jax.ops.segment_sum(
        jnp.take(alpha[..., None] * ct_e, perm, axis=0), src_sorted, **skw)

    # softmax adjoint, then the leaky-relu mask (>= matches substrate)
    s_dot = jax.ops.segment_sum(alpha * g_alpha, g.dst, **dkw)
    g_m = alpha * (g_alpha - jnp.take(s_dot, g.dst, axis=0))
    g_m = g_m * jnp.where(m_raw >= 0, jnp.ones((), g_m.dtype),
                          jnp.asarray(slope, g_m.dtype))

    d_el = jax.ops.segment_sum(jnp.take(g_m, perm, axis=0), src_sorted,
                               **skw)
    d_er = jax.ops.segment_sum(g_m, g.dst, **dkw)
    return (d_el.astype(el.dtype), d_er.astype(er.dtype),
            dz.astype(z.dtype))


def _attention_grads_ragged(pack, el, er, z, slope, ct):
    """Adjoints recomputed on the RAGGED ELL stripes: per-class masked
    max/sum over the width axis replaces the whole segment-reduce chain,
    so the backward rides the same pad-tax-free layout as the pallas
    forward. Pad slots carry α = 0 exactly (masked exp), so the src-side
    scatter-adds can index ``chunk_cols`` directly — pads add zeros.
    ∂z and ∂el ride ONE scatter with an (H, F+1) payload: on CPU the
    scatter's per-index overhead dominates its bandwidth, so fusing the
    two source-side adds beats two passes."""
    F = z.shape[-1]
    acc = jnp.zeros(z.shape[:-1] + (F + 1,),
                    jnp.promote_types(z.dtype, ct.dtype))
    d_er = jnp.zeros_like(er)
    one = jnp.ones((), el.dtype)
    sl = jnp.asarray(slope, el.dtype)
    for cls in pack.classes:
        cols, mask, row = cls.chunk_cols, cls.chunk_mask, cls.chunk_row
        el_t = jnp.take(el, cols, axis=0)                  # (C, W, H)
        er_t = jnp.take(er, row, axis=0)[:, None]          # (C, 1, H)
        m_raw = el_t + er_t
        m = jnp.where(m_raw >= 0, m_raw, sl * m_raw)
        mk = mask[..., None]
        mm = jnp.where(mk, m, jnp.asarray(-jnp.inf, m.dtype))
        mx = jnp.max(mm, axis=1, keepdims=True)            # (C, 1, H)
        mx = jnp.where(jnp.isfinite(mx), mx, jnp.zeros((), m.dtype))
        ex = jnp.where(mk, jnp.exp(m - mx), jnp.zeros((), m.dtype))
        zs = jnp.sum(ex, axis=1, keepdims=True)
        alpha = ex / jnp.maximum(zs, 1e-38)                # (C, W, H)

        ct_t = jnp.take(ct, row, axis=0)                   # (C, H, F)
        z_t = jnp.take(z, cols, axis=0)                    # (C, W, H, F)
        g_alpha = jnp.einsum("chf,cwhf->cwh", ct_t, z_t)
        s_dot = jnp.sum(alpha * g_alpha, axis=1, keepdims=True)
        g_m = alpha * (g_alpha - s_dot)
        g_m = g_m * jnp.where(m_raw >= 0, one, sl)

        # rows are disjoint across classes → pure row update; src-side
        # slots repeat → scatter-add (pads contribute exact zeros)
        d_er = d_er.at[row].add(jnp.sum(g_m, axis=1).astype(er.dtype))
        payload = jnp.concatenate(
            [alpha[..., None] * ct_t[:, None], g_m[..., None]], axis=-1)
        acc = acc.at[cols].add(payload.astype(acc.dtype))
    return (acc[..., F].astype(el.dtype), d_er,
            acc[..., :F].astype(z.dtype))


@partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _attention_rev(chosen: str, slope: float, g: Graph, pack, el, er, z):
    """``_attention_execute`` with the scatter-free manual backward."""
    return _attention_execute(g, pack, el, er, z, slope, chosen)


def _attention_rev_fwd(chosen, slope, g, pack, el, er, z):
    out = _attention_execute(g, pack, el, er, z, slope, chosen)
    return out, (g, pack, el, er, z)


def _attention_rev_bwd(chosen, slope, res, ct):
    g, pack, el, er, z = res
    if chosen == "pallas" and pack is not None:
        d_el, d_er, dz = _attention_grads_ragged(pack, el, er, z,
                                                 slope, ct)
    else:
        d_el, d_er, dz = _attention_grads(g, el, er, z, slope, ct)
    return None, None, d_el, d_er, dz


_attention_rev.defvjp(_attention_rev_fwd, _attention_rev_bwd)


def fused_attention(g: Graph, el: jnp.ndarray, er: jnp.ndarray,
                    z: jnp.ndarray, *, negative_slope: float = 0.2,
                    strategy: str = "auto") -> jnp.ndarray:
    """GAT's attention pipeline — ``u_add_v_copy_e`` logits, leaky-relu,
    edge softmax and ``u_mul_e_add_v`` aggregation — as ONE planned pass.

    ``el``: (n_src, H) or (n_src,) source logit terms; ``er``: (n_dst,
    H) destination terms; ``z``: (n_src, H, F) source features ((n_src,
    F) when ``el`` is 1-D). Returns (n_dst, H, F) aggregated features.

    Stays in canonical dst-sorted order throughout — per-edge α is never
    materialized as a caller-order HBM tensor, and the custom VJP routes
    ∂el/∂z through the graph's free src-sorted view with sorted segment
    reduces (no scatter). ``strategy``: 'auto' | 'fused' (canonical jnp)
    | 'pallas' (ragged row-complete ELL megakernel — one stripe grid
    per degree class) | 'ring' is reserved for the partitioned form.
    Logged as ``attn:fused``.
    """
    squeeze = el.ndim == 1
    if squeeze:
        el, er, z = el[:, None], er[:, None], z[:, None, :]
    H = el.shape[-1]
    F = z.shape[-1]

    pallas_ok = False
    padded_slots = None
    if not planner.graph_is_traced(g) and g.n_edges > 0:
        import numpy as np

        # degrees straight off the stored CSR field: g's arrays are
        # concrete here even mid-trace (closed-over constants), but the
        # in_degrees property would compute through traced slices
        indptr = np.asarray(g.indptr_dst)
        deg = indptr[1:] - indptr[:-1]
        max_deg = int(deg.max()) if deg.size else 0
        if max_deg > 0:
            pallas_ok = True
            # per-class slot estimate of the RAGGED row-complete pack —
            # the same formula the cost model's pallas row prices, so
            # the gate can no longer veto the megakernel with the
            # max_degree × n_rows envelope on power-law degree tails
            padded_slots, _ = planner.ell_rowcomplete_padding(deg)

    chosen = planner.plan_attention((g.n_src, g.n_dst, g.n_edges), H, F,
                                    requested=strategy,
                                    pallas_ok=pallas_ok,
                                    padded_slots=padded_slots,
                                    dtype=str(z.dtype))
    if chosen == "ring":
        raise ValueError("strategy='ring' needs a PartitionedGraph — "
                         "use fused_attention_partitioned")

    slope = float(negative_slope)
    pack = None
    if chosen == "pallas":
        # resolve the ragged pack while g is still concrete (inside the
        # custom_vjp g's arrays are tracers and cache lookup is
        # impossible); requesting pallas on a traced graph raises the
        # plan cache's own "pass the cache in explicitly" error. Build
        # only OUTSIDE an active trace — np→jnp conversions inside one
        # would leak trace-bound arrays into the process-wide memo —
        # else peek, demoting to the canonical jnp pipeline when the
        # pack was never prebuilt (same idiom as hetero's skew packs)
        cache = planner.get_plan_cache(g)
        pack = (cache.ell_ragged() if trace_state_clean()
                else cache.peek("ell_ragged"))
        if pack is None:
            chosen = "fused"
    with jax.named_scope("fused_attention"):
        if jnp.issubdtype(z.dtype, jnp.floating):
            out = _attention_rev(chosen, slope, g, pack, el, er, z)
        else:
            out = _attention_execute(g, pack, el, er, z, slope, chosen)
    return out[:, 0, :] if squeeze else out


def block_fused_attention(bg: BlockGraph, el: jnp.ndarray,
                          er: jnp.ndarray, z: jnp.ndarray, *,
                          negative_slope: float = 0.2,
                          strategy: str = "auto") -> jnp.ndarray:
    """Fused attention over one sampled block's real in-edges.

    ``er`` spans the padded destination range (n_dst_real + 1 rows, the
    caller's dummy row last, like every block v-operand). Pad edges all
    point at the dummy destination row, so real rows' softmax sees
    exactly their real edges; the dummy row is consumed internally.
    """
    out = fused_attention(bg.g, el, er, z,
                          negative_slope=negative_slope,
                          strategy=strategy)
    return out[: bg.n_dst_real]


def fused_attention_partitioned(pg, el: jnp.ndarray, er: jnp.ndarray,
                                z: jnp.ndarray, *, mesh=None,
                                axis: str = "data",
                                negative_slope: float = 0.2
                                ) -> jnp.ndarray:
    """Fused attention on a partitioned graph: one ring pass assembles
    bucketed logits, leaky-relu + softmax run owner-local, a second ring
    does the α-weighted reduce. Planned/logged as the 'ring' form of
    ``attn:fused``.
    """
    # lazy import: partition pulls in mesh helpers this module's other
    # entry points never need
    from .partition import bucket_softmax, ring_edge_values, ring_gspmm

    H = el.shape[-1]
    F = z.shape[-1]
    n_edges = pg.n_shards * pg.n_shards * pg.eb
    planner.plan_attention((pg.n_pad, pg.n_pad, n_edges), H, F,
                           requested="ring", dtype=str(z.dtype))
    logits = ring_edge_values(pg, el, er, mesh=mesh, axis=axis)
    logits = jnp.where(logits >= 0, logits, negative_slope * logits)
    alpha = bucket_softmax(pg, logits)
    return ring_gspmm(pg, z, alpha, mesh=mesh, axis=axis)
