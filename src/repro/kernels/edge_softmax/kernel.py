"""Fused edge-softmax Pallas kernels (GAT's 5-primitive chain in one pass).

The paper's Table 2 shows GAT issuing five BR/CR passes for attention
normalization (max, sub, exp, sum, div) — five HBM round-trips over
edge data. Here each destination row's incoming edges are one dense
stripe of a padded ELL pack, and a kernel computes the whole masked
softmax of a stripe in VMEM: one read, one write.

Layouts are chosen so Mosaic never has to move data between lanes and
sublanes (a shape cast it refuses) and no dot carries two batch
dimensions:

* stripe softmax — heads lead: logits ``(H, rows, W)``, mask
  ``(1, rows, W)``. Grid: row blocks of ``br`` (a multiple of 8)
  destination rows; the softmax reduces along the lanes (``W``).
* fused attention — destination rows on the lanes: logits
  ``(H, W, rows)``, features ``(H, W, F, rows)``, output
  ``(H, F, rows)``. Grid: ``(row blocks of br, width blocks of wb)``
  with an online softmax across width blocks (running max, running
  sum and the rescaled accumulator live in VMEM scratch), so a hub
  row of any degree streams through a fixed VMEM footprint. The
  α-weighted reduce is ``wb`` unrolled multiply-adds of a sublane row
  of α against an ``(H, F, br)`` feature slab — VPU work, no MXU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG = -1e30


def _softmax_kernel(x_ref, mask_ref, out_ref):
    x = x_ref[...].astype(jnp.float32)        # (H, br, W)
    mask = mask_ref[...] != 0                 # (1, br, W)
    x = jnp.where(mask, x, _NEG)
    mx = jnp.max(x, axis=-1, keepdims=True)   # (H, br, 1)
    ex = jnp.exp(x - mx)
    ex = jnp.where(mask, ex, 0.0)
    z = jnp.sum(ex, axis=-1, keepdims=True)
    out = ex / jnp.maximum(z, 1e-38)
    out_ref[...] = out.astype(out_ref.dtype)


def edge_softmax_pallas_call(n_rows_pad: int, W: int, H: int, br: int,
                             dtype, *, interpret: bool):
    """x: (H, n_rows_pad, W) padded ELL logits; mask: (1, n_rows_pad, W)."""
    grid = (n_rows_pad // br,)
    return pl.pallas_call(
        _softmax_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((H, br, W), lambda r: (0, r, 0)),
            pl.BlockSpec((1, br, W), lambda r: (0, r, 0)),
        ],
        out_specs=pl.BlockSpec((H, br, W), lambda r: (0, r, 0)),
        out_shape=jax.ShapeDtypeStruct((H, n_rows_pad, W), dtype),
        interpret=interpret)


def _attention_kernel(slope, wb, el_ref, er_ref, z_ref, mask_ref, out_ref,
                      m_sc, l_sc, acc_sc):
    """GAT attention rows: logits = el[src]+er[dst] through leaky-relu,
    masked online softmax over the width blocks, α-weighted feature
    reduce — one read of the stripes, one (H, F, br) write, α never
    leaves VMEM."""
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_sc[...] = jnp.full(m_sc.shape, _NEG, m_sc.dtype)
        l_sc[...] = jnp.zeros(l_sc.shape, l_sc.dtype)
        acc_sc[...] = jnp.zeros(acc_sc.shape, acc_sc.dtype)

    el = el_ref[...].astype(jnp.float32)          # (H, wb, br)
    er = er_ref[...].astype(jnp.float32)          # (H, 1, br)
    mask = mask_ref[...] != 0                     # (1, wb, br)
    s = el + er
    s = jnp.where(s >= 0, s, slope * s)           # leaky BEFORE the mask
    s = jnp.where(mask, s, _NEG)
    m_prev = m_sc[...]                            # (H, 1, br)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    scale = jnp.exp(m_prev - m_new)
    p = jnp.where(mask, jnp.exp(s - m_new), 0.0)  # (H, wb, br)
    l_sc[...] = scale * l_sc[...] + jnp.sum(p, axis=1, keepdims=True)
    acc = acc_sc[...] * scale                     # (H, F, br)
    for w in range(wb):
        acc = acc + p[:, w:w + 1, :] * z_ref[:, w].astype(jnp.float32)
    acc_sc[...] = acc
    m_sc[...] = m_new

    @pl.when(j == pl.num_programs(1) - 1)
    def _finish():
        out = acc_sc[...] / jnp.maximum(l_sc[...], 1e-38)
        out_ref[...] = out.astype(out_ref.dtype)


def fused_attention_pallas_call(n_rows_pad: int, W: int, H: int, F: int,
                                br: int, wb: int, dtype, *, slope: float,
                                interpret: bool):
    """el: (H, W, n_rows_pad) src terms; er: (H, 1, n_rows_pad) dst
    terms; z: (H, W, F, n_rows_pad) source features; mask:
    (1, W, n_rows_pad). Output (H, F, n_rows_pad). ``br`` is a multiple
    of 128 (or the whole row extent), ``wb`` divides ``W`` and is a
    multiple of 8 or ``W`` itself."""
    grid = (n_rows_pad // br, W // wb)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=0,
        grid=grid,
        in_specs=[
            pl.BlockSpec((H, wb, br), lambda r, j: (0, j, r)),
            pl.BlockSpec((H, 1, br), lambda r, j: (0, 0, r)),
            pl.BlockSpec((H, wb, F, br), lambda r, j: (0, j, 0, r)),
            pl.BlockSpec((1, wb, br), lambda r, j: (0, j, r)),
        ],
        out_specs=pl.BlockSpec((H, F, br), lambda r, j: (0, 0, r)),
        scratch_shapes=[pltpu.VMEM((H, 1, br), jnp.float32),
                        pltpu.VMEM((H, 1, br), jnp.float32),
                        pltpu.VMEM((H, F, br), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_attention_kernel, slope, wb),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((H, F, n_rows_pad), dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret)
