"""VMEM footprint model for the aggregation kernels.

A TPU v5e TensorCore gives a Pallas kernel a 16 MiB scoped-VMEM window
by default (``VMEM_BYTES``). Every block a ``BlockSpec`` streams in or
out is double-buffered by the pipeline, and VMEM holds arrays in
(8, 128) tiles of 32-bit words: the last dimension of a block rounds up
to 128 lanes and the one before it to 8 sublanes. The functions below
count a block the way it is laid out, so a geometry they accept is one
the chip can hold. The kernels keep their working set under half the
window (``budget = VMEM_BYTES // 2``), leaving the rest to the
compiler's own temporaries; ``tests/kernels/test_tpu_compile.py``
checks the geometries these functions choose against the TPU compiler.
"""
from __future__ import annotations

from typing import Dict

VMEM_BYTES = 16 * 1024 * 1024
LANES = 128
SUBLANES = 8


def _ceil(x: int, m: int) -> int:
    return -(-x // m) * m


def tile_bytes(*shape: int, itemsize: int = 4) -> int:
    """VMEM bytes of one array of ``shape`` in 32-bit (8, 128) tiles
    (a narrower dtype packs more rows into a tile: (16, 128) for
    2-byte items)."""
    *lead, sub, lane = (1,) * max(0, 2 - len(shape)) + tuple(shape)
    n = 1
    for x in lead:
        n *= x
    rows = SUBLANES * 4 // itemsize
    return n * _ceil(sub, rows) * _ceil(lane, LANES) * itemsize


def spmm_vmem_bytes(bm: int, bk: int, eb: int, nd: int,
                    dtype_bytes: int = 4) -> int:
    """Live VMEM for one grid step of the SpMM kernel."""
    streamed = (tile_bytes(bk, nd, itemsize=dtype_bytes)    # source K-block
                + tile_bytes(bm, nd)                        # output tile
                + 4 * tile_bytes(1, eb))                    # dst/src/mask/w
    onehots = tile_bytes(bk, eb) + tile_bytes(bm, eb)       # G^T and S
    gathered = tile_bytes(eb, nd)                           # G @ B (f32)
    return 2 * streamed + onehots + gathered


def br_vmem_bytes(bm: int, bk: int, eb: int, nd: int,
                  dtype_bytes: int = 4) -> int:
    """Fused binary-reduce adds the streamed edge-feature block and the
    f32 message it makes."""
    return (spmm_vmem_bytes(bm, bk, eb, nd, dtype_bytes)
            + 2 * tile_bytes(eb, nd, itemsize=dtype_bytes)
            + tile_bytes(eb, nd))


def edge_softmax_vmem_bytes(br_rows: int, width: int, heads: int) -> int:
    """Stripe softmax: (H, br, W) logits in and α out, (1, br, W) mask,
    all double-buffered, plus the f32 working copies."""
    x = tile_bytes(heads, br_rows, width)
    mask = tile_bytes(br_rows, width)
    return 2 * (2 * x + mask) + 2 * x


def fused_attention_vmem_bytes(br: int, wb: int, heads: int,
                               feat: int) -> int:
    """Fused attention with destination rows on the lanes: one grid
    step streams el (H, wb, br), er (H, 1, br), z (H, wb, F, br) and
    the mask (1, wb, br), writes out (H, F, br), and keeps the running
    max/sum (H, 1, br) and accumulator (H, F, br) in scratch."""
    el = tile_bytes(heads, wb, br)
    er = tile_bytes(heads, 1, br)
    z = tile_bytes(heads * wb, feat, br)
    mask = tile_bytes(wb, br)
    out = tile_bytes(heads, feat, br)
    scratch = 2 * er + out
    working = 2 * el + out                     # logits/p and the acc value
    return 2 * (el + er + z + mask + out) + scratch + working


def pick_spmm_geometry(d: int, dtype_bytes: int = 4,
                       budget: int = VMEM_BYTES) -> Dict[str, int]:
    """Largest MXU-aligned K-block that fits the VMEM budget."""
    nd = min(128 * max(1, d // 128), 512)
    best = dict(bm=128, bk=128, eb=256, nd=nd)
    for bk in (1024, 512, 256, 128):
        for eb in (512, 256, 128):
            if spmm_vmem_bytes(128, bk, eb, nd, dtype_bytes) <= budget // 2:
                return dict(bm=128, bk=bk, eb=eb, nd=nd)
    return best
