"""Jitted public wrappers for the edge-softmax and fused-attention
Pallas kernels."""
from __future__ import annotations

import functools
from typing import Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from ...core.graph import Graph
from ...core.planner import get_plan_cache
from ...core.tiling import ELLClass, ELLPack
from ..common import should_interpret
from ..vmem import (LANES, VMEM_BYTES, edge_softmax_vmem_bytes,
                    fused_attention_vmem_bytes)
from .kernel import edge_softmax_pallas_call, fused_attention_pallas_call


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


# Block geometry from the VMEM model (kernels/vmem.py): a kernel's
# double-buffered blocks, scratch and working values, counted in
# (8, 128) tiles, stay under half of v5e's 16 MiB scoped VMEM. The fused
# attention puts destination rows on the lanes, so a row block is a
# multiple of 128 rows; a stripe wider than ``_WB_MAX`` streams through
# the online softmax in blocks of ``_WB_MAX`` slots, which bounds the
# footprint of hub rows of any degree. ``_BR_MAX`` caps the row block
# so the running (H, F, br) accumulator stays a few dozen vregs.
_VMEM_BUDGET = VMEM_BYTES // 2
_WB_MAX = 32
_BR_MAX = 512


def _attention_blocks(C: int, W: int, H: int, F: int):
    """(br, wb) for one row-complete stripe class of C rows of width W:
    ``wb`` is W or ``_WB_MAX``; ``br`` the largest multiple of 128 rows
    that fits the VMEM budget, no larger than the class needs."""
    wb = W if W <= _WB_MAX else _WB_MAX
    per_block = fused_attention_vmem_bytes(LANES, wb, H, F)
    fit = (_VMEM_BUDGET // per_block) * LANES
    if fit < LANES:
        raise ValueError(
            f"fused attention with H={H}, F={F} needs {per_block} bytes "
            f"of VMEM per 128-row block, over the {_VMEM_BUDGET}-byte "
            f"budget")
    return min(fit, _BR_MAX, _round_up(max(C, 1), LANES)), wb


@functools.partial(jax.jit,
                   static_argnames=("n_edges", "br", "interpret"))
def _edge_softmax_packed(pack: ELLClass, logits: jnp.ndarray,
                         eid_inv: jnp.ndarray, n_edges: int,
                         br: int = 8, interpret: Optional[bool] = None
                         ) -> jnp.ndarray:
    """Softmax over incoming-edge stripes; returns caller edge order."""
    C, W = pack.chunk_cols.shape
    H = logits.shape[-1]
    C_pad = _round_up(C, br)
    need = edge_softmax_vmem_bytes(br, W, H)
    if need > _VMEM_BUDGET:
        raise ValueError(f"edge_softmax block (H={H}, br={br}, W={W}) "
                         f"needs {need} bytes of VMEM, over the "
                         f"{_VMEM_BUDGET}-byte budget: lower br")

    # gather logits (caller order) into head-major padded ELL stripes
    x = jnp.take(logits.T, pack.chunk_eids, axis=1)        # (H, C, W)
    x = jnp.pad(x, ((0, 0), (0, C_pad - C), (0, 0)))
    mask = jnp.pad(pack.chunk_mask.astype(jnp.int32),
                   ((0, C_pad - C), (0, 0)))[None]

    call = edge_softmax_pallas_call(
        C_pad, W, H, br, logits.dtype,
        interpret=should_interpret() if interpret is None else interpret)
    out = call(x, mask)                                    # (H, C_pad, W)

    # back to caller edge order: every real edge occupies exactly one
    # (chunk, w) slot, so this is a pure permutation — find each edge's
    # slot with a 1-D scatter, then gather its row (a scatter of whole
    # H-wide rows compiles for seconds on a TPU)
    flat_vals = out[:, :C].reshape(H, C * W).T
    flat_eids = pack.chunk_eids.reshape(C * W)
    flat_mask = pack.chunk_mask.reshape(C * W)
    safe_ids = jnp.where(flat_mask, flat_eids, n_edges)    # drop pads
    slot = jnp.zeros((n_edges,), jnp.int32).at[safe_ids].set(
        jnp.arange(C * W, dtype=jnp.int32), mode="drop")
    return jnp.take(flat_vals, slot, axis=0)


def edge_softmax(g: Graph, logits: jnp.ndarray,
                 ell: Optional[ELLClass] = None, br: int = 8,
                 interpret: Optional[bool] = None) -> jnp.ndarray:
    """Fused softmax over incoming edges per destination node.

    ``logits``: (n_edges, H) or (n_edges,) in the caller's edge order.
    The pack must be row-complete (one FULL row per chunk): pass
    ``ell=build_ell_uniform(g, max_in_degree)`` or let this wrapper
    build it.
    """
    squeeze = logits.ndim == 1
    x = logits[:, None] if squeeze else logits
    if ell is None:
        max_deg = int(jnp.max(g.in_degrees)) if g.n_dst else 1
        ell = get_plan_cache(g).ell_uniform(max(max_deg, 1))
    elif int(jnp.max(g.in_degrees)) > ell.width:
        raise ValueError("pack splits rows; edge_softmax needs "
                         "width >= max in-degree")
    out = _edge_softmax_packed(ell, x, g.eid_inv, g.n_edges, br=br,
                               interpret=interpret)
    return out[:, 0] if squeeze else out


def _attention_stripes(cls: ELLClass, el: jnp.ndarray, er: jnp.ndarray,
                       z: jnp.ndarray, slope: float,
                       interpret: bool) -> jnp.ndarray:
    """One row-complete stripe class through the megakernel → (C, H, F)
    rows in the class's chunk order, laid out rows-on-lanes."""
    C, W = cls.chunk_cols.shape
    H = el.shape[-1]
    F = z.shape[-1]
    br, wb = _attention_blocks(C, W, H, F)
    C_pad = _round_up(max(C, 1), br)
    W_pad = _round_up(W, wb)
    pad = ((0, W_pad - W), (0, C_pad - C))
    cols = jnp.pad(cls.chunk_cols.T, pad)                  # (W_pad, C_pad)
    mask = jnp.pad(cls.chunk_mask.T.astype(jnp.int32), pad)[None]
    el_t = jnp.take(el.T, cols, axis=1)                    # (H, W_pad, C_pad)
    er_t = jnp.pad(jnp.take(er.T, cls.chunk_row, axis=1),
                   ((0, 0), (0, C_pad - C)))[:, None]      # (H, 1, C_pad)
    z_t = jnp.take(z, cols, axis=0).transpose(2, 0, 3, 1)  # (H, W_pad, F, C_pad)
    call = fused_attention_pallas_call(C_pad, W_pad, H, F, br, wb, z.dtype,
                                       slope=slope, interpret=interpret)
    out = call(el_t, er_t, z_t, mask)                      # (H, F, C_pad)
    return out[:, :, :C].transpose(2, 0, 1)


@functools.partial(jax.jit,
                   static_argnames=("n_dst", "slope", "interpret"))
def _fused_attention_packed(pack: ELLClass, el: jnp.ndarray,
                            er: jnp.ndarray, z: jnp.ndarray, n_dst: int,
                            slope: float, interpret: bool) -> jnp.ndarray:
    """Attention megakernel over row-complete stripes → (n_dst, H, F)."""
    out = _attention_stripes(pack, el, er, z, slope, interpret)
    # row-complete pack: each chunk is one whole destination row, so the
    # scatter-back is a pure permutation; zero-degree rows stay 0 (DGL)
    res = jnp.zeros((n_dst,) + out.shape[1:], out.dtype)
    return res.at[pack.chunk_row].set(out)


@functools.partial(jax.jit,
                   static_argnames=("slope", "interpret"))
def _fused_attention_ragged(pack: ELLPack, el: jnp.ndarray,
                            er: jnp.ndarray, z: jnp.ndarray,
                            slope: float, interpret: bool) -> jnp.ndarray:
    """Attention megakernel over RAGGED per-class stripes.

    One stripe grid per power-of-two degree class, each padded only to
    its own class width — the padded-slot count is the degree
    histogram's pow2 row sum instead of n_rows × max_degree. Classes
    hold disjoint destination rows (build_ell_ragged never splits a
    row), so the per-class scatter-back is a pure permutation and the
    class outputs never overlap.
    """
    res = jnp.zeros((pack.n_dst, el.shape[-1], z.shape[-1]), z.dtype)
    for cls in pack.classes:
        out = _attention_stripes(cls, el, er, z, slope, interpret)
        res = res.at[cls.chunk_row].set(out)
    return res


def fused_attention(g: Graph, el: jnp.ndarray, er: jnp.ndarray,
                    z: jnp.ndarray, slope: float = 0.2,
                    ell: Optional[Union[ELLClass, ELLPack]] = None,
                    interpret: Optional[bool] = None) -> jnp.ndarray:
    """GAT attention pipeline as ONE kernel pass.

    ``el``: (n_src, H) source logit terms; ``er``: (n_dst, H)
    destination terms; ``z``: (n_src, H, F) source features. Computes
    leaky-relu(el[src]+er[dst]) → per-destination softmax → α-weighted
    feature sum without materializing per-edge α in HBM.

    Needs a ROW-COMPLETE pack. By default this is the ragged per-class
    pack (``PlanCache.ell_ragged``), launched as one stripe grid per
    degree class; passing an :class:`ELLClass` (``build_ell_uniform``)
    pins the legacy single-width path instead.
    """
    # degrees straight off the stored CSR field: concrete whenever the
    # graph is (the in_degrees property computes through traced slices
    # inside an active trace); None = graph is traced, skip the checks
    deg = (None if isinstance(g.indptr_dst, jax.core.Tracer)
           else np.diff(np.asarray(g.indptr_dst)))
    if ell is None:
        ell = get_plan_cache(g).ell_ragged()
    if isinstance(ell, ELLPack):
        if deg is not None:     # row-completeness needs concrete degrees
            n_chunks = sum(int(c.chunk_row.shape[0])
                           for c in ell.classes)
            if g.n_edges and n_chunks != int((deg > 0).sum()):
                raise ValueError("pack splits rows; fused_attention "
                                 "needs a row-complete (ragged or "
                                 "uniform) pack")
        return _fused_attention_ragged(
            ell, el, er, z, float(slope),
            should_interpret() if interpret is None else interpret)
    if deg is not None and deg.size and int(deg.max()) > ell.width:
        raise ValueError("pack splits rows; fused_attention needs "
                         "width >= max in-degree")
    return _fused_attention_packed(
        ell, el, er, z, g.n_dst, float(slope),
        should_interpret() if interpret is None else interpret)
