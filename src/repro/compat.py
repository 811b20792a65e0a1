"""The one place that adapts to the installed JAX API.

``requirements.txt`` pins the JAX this module is written against; code
elsewhere in the package imports these names instead of reaching into
JAX's version-specific modules.
"""
from __future__ import annotations

import jax
from jax._src import core as _core

__all__ = ["trace_state_clean", "shard_map", "pvary"]

shard_map = jax.shard_map


def trace_state_clean() -> bool:
    """True iff no JAX transformation (jit, grad, vmap, ...) is tracing
    the caller — i.e. values are concrete and work runs eagerly."""
    return _core.trace_state_clean()


def pvary(x, axis):
    """Mark ``x`` as varying over mesh axis ``axis`` inside ``shard_map``
    (so a ``fori_loop`` carry keeps its type after a ``ppermute``)."""
    return jax.lax.pcast(x, (axis,), to="varying")
