"""Kernel geometry must fit the v5e VMEM budget, and the autotuned
geometry must stay numerically correct."""
import numpy as np
import jax.numpy as jnp

from repro.core import from_coo, build_tiles
from repro.kernels.spmm.ops import spmm
from repro.kernels.spmm.ref import spmm_ref
from repro.kernels.vmem import (VMEM_BYTES, spmm_vmem_bytes, br_vmem_bytes,
                                edge_softmax_vmem_bytes,
                                pick_spmm_geometry)

from ..conftest import make_graph


def test_default_geometry_fits_vmem():
    # the ops.py defaults: bm=bk=128, eb=256, nd=128
    assert spmm_vmem_bytes(128, 128, 256, 128) < VMEM_BYTES // 2
    assert br_vmem_bytes(128, 128, 256, 128) < VMEM_BYTES // 2
    assert edge_softmax_vmem_bytes(8, 1024, 8) < VMEM_BYTES // 2


def test_autotuner_respects_budget():
    for d in (32, 128, 512, 2048):
        g = pick_spmm_geometry(d)
        assert spmm_vmem_bytes(g["bm"], g["bk"], g["eb"], g["nd"]) \
            <= VMEM_BYTES // 2


def test_autotuned_geometry_correct():
    rng = np.random.default_rng(21)
    g, _, _ = make_graph(rng, 400, 300, 2000)
    B = jnp.asarray(rng.normal(size=(400, 96)).astype(np.float32))
    geo = pick_spmm_geometry(96)
    tiles = build_tiles(g, bm=geo["bm"], bk=geo["bk"], eb=geo["eb"])
    out = spmm(g, B, "sum", tiles=tiles, nd=geo["nd"])
    ref = spmm_ref(g.src, g.dst, B, 300, "sum")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_tile_bytes_counts_layout_padding():
    from repro.kernels.vmem import tile_bytes
    assert tile_bytes(8, 128) == 8 * 128 * 4
    assert tile_bytes(1, 256) == 8 * 256 * 4          # sublanes pad to 8
    assert tile_bytes(4, 16, 16) == 4 * 16 * 128 * 4  # lanes pad to 128
    assert tile_bytes(16, 128, itemsize=2) == 16 * 128 * 2


def test_attention_blocks_fit_budget_at_any_width():
    """Row blocks are whole 128-lane multiples, width blocks divide the
    padded width, and the double-buffered footprint stays in budget —
    including hub classes thousands of slots wide."""
    from repro.kernels.edge_softmax.ops import _attention_blocks
    from repro.kernels.vmem import fused_attention_vmem_bytes
    for C, W, H, F in [(30597, 1, 4, 16), (3992, 32, 4, 16),
                       (1, 8192, 4, 16), (500, 40, 1, 41),
                       (3, 2, 8, 64)]:
        br, wb = _attention_blocks(C, W, H, F)
        assert br % 128 == 0 and br >= 128
        assert wb == W or (wb % 8 == 0 and wb < W)
        assert fused_attention_vmem_bytes(br, wb, H, F) <= VMEM_BYTES // 2
