"""Production mesh construction.

A FUNCTION (not module-level constant) so importing never touches jax
device state. Single pod: (data=16, model=16) = 256 chips (TPU v5e pod
slice); multi-pod: (pod=2, data=16, model=16) = 512 chips with the pod
axis carrying cross-pod data parallelism (DCN-grade collectives only:
gradient all-reduce, optionally int8-compressed).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_shard_mesh(n_shards: int, axis: str = "data") -> Mesh:
    """1-D mesh for partitioned-graph (ring) execution.

    On hardware this is the first ``n_shards`` devices; on a laptop/CI
    host the devices are emulated — set
    ``XLA_FLAGS=--xla_force_host_platform_device_count=<n>`` BEFORE
    importing jax (tests and benchmarks re-exec a subprocess to do
    this; see tests/conftest.run_multidevice). With too few CPU devices,
    :func:`make_mesh`'s error spells out that exact flag.
    """
    return make_mesh((n_shards,), (axis,))


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]) -> Mesh:
    """make_mesh that tolerates more host devices than the mesh needs
    (the dry-run forces 512; the single-pod mesh uses the first 256)."""
    n = int(np.prod(shape))
    devices = jax.devices()
    if len(devices) < n:
        msg = f"mesh {shape} needs {n} devices, have {len(devices)}"
        if devices[0].platform == "cpu":
            # host devices can be emulated; chips cannot
            flag = f"--xla_force_host_platform_device_count={n}"
            msg += (f" — set XLA_FLAGS={flag} BEFORE importing jax "
                    f"(see launch/dryrun.py)")
        raise RuntimeError(msg)
    arr = np.asarray(devices[:n]).reshape(shape)
    return Mesh(arr, axes)
