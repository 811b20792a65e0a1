"""The main-path Pallas kernels compile for a TPU v5e chip.

The interpreter that runs the kernels on the CPU accepts blocks, shape
casts and dots that the TPU compiler refuses, so these tests compile
each kernel at real widths for one chip of a described (not attached)
``v5e:2x2`` topology and check the compiled program holds the kernel
(``tpu_custom_call``). Nothing runs. The topology is described inside a
fixture, never at import: only one process at a time may load the TPU
library, and every test worker imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.tiling import ELLClass, ELLPack, TilePack
from repro.kernels.binary_reduce.ops import _br_packed
from repro.kernels.edge_softmax import ops as es_ops
from repro.kernels.sddmm.ops import _sddmm_padded
from repro.kernels.spmm.ops import _spmm_packed

N = 65536          # reddit-like node count
T = 4096           # tile buckets (eb = 256 slots each)
H, F = 4, 16       # GAT defaults: 4 heads of 16 features


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a described chip's compiles cannot be read back from the
    # persistent cache, so keep them out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    from jax.experimental import topologies
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:                      # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def spec(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])

    def make(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    return make


def _assert_kernel(lowered):
    text = lowered.compile().as_text()
    assert "tpu_custom_call" in text


def _tiles(spec, eb=256, bm=128, bk=128):
    i = jnp.int32
    return TilePack(tile_m=spec((T,), i), tile_k=spec((T,), i),
                    first_of_m=spec((T,), i), dst_local=spec((T, eb), i),
                    src_local=spec((T, eb), i), eids=spec((T, eb), i),
                    mask=spec((T, eb), jnp.bool_), bm=bm, bk=bk, eb=eb,
                    n_dst=N, n_src=N, n_tiles_m=N // bm, n_tiles_k=N // bk)


def _stripes(spec, C, W):
    i = jnp.int32
    return ELLClass(chunk_cols=spec((C, W), i), chunk_eids=spec((C, W), i),
                    chunk_mask=spec((C, W), jnp.bool_),
                    chunk_row=spec((C,), i), width=W)


@pytest.mark.parametrize("d", [256, 602])
@pytest.mark.parametrize("weighted", [False, True])
def test_spmm_compiles(spec, d, weighted):
    w = spec((T, 256)) if weighted else None
    _assert_kernel(_spmm_packed.lower(
        _tiles(spec), spec((N, d)), w, None, reduce_op="sum",
        interpret=False))


@pytest.mark.parametrize("d", [256, 602])
def test_binary_reduce_compiles(spec, d):
    _assert_kernel(_br_packed.lower(
        _tiles(spec), spec((N, d)), spec((T * 256, d)), None,
        binop="mul", reduce_op="sum", interpret=False))


def test_sddmm_dot_compiles(spec):
    E = 600_000
    _assert_kernel(_sddmm_padded.lower(
        spec((E, 64)), spec((E, 64)), op="dot", be=128, interpret=False))


def test_edge_softmax_stripes_compile(spec):
    C, W = 8192, 16
    _assert_kernel(es_ops._edge_softmax_packed.lower(
        _stripes(spec, C, W), spec((C * W, H)), spec((C * W,), jnp.int32),
        n_edges=C * W, br=8, interpret=False))


def test_fused_attention_rowcomplete_compiles(spec):
    _assert_kernel(es_ops._fused_attention_packed.lower(
        _stripes(spec, 8192, 16), spec((N, H)), spec((N, H)),
        spec((N, H, F)), n_dst=N, slope=0.2, interpret=False))


@pytest.mark.parametrize("C,W", [(3992, 32), (16, 2048)])
def test_fused_attention_ragged_class_compiles(spec, C, W):
    """One degree class per launch; the 2048-wide hub class streams
    through the online softmax in 32-slot width blocks."""
    pack = ELLPack(classes=(_stripes(spec, C, W),), n_dst=N)
    _assert_kernel(es_ops._fused_attention_ragged.lower(
        pack, spec((N, H)), spec((N, H)), spec((N, H, F)), slope=0.2,
        interpret=False))
