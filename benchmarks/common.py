"""Benchmark timing helpers (median-of-N, compile excluded) + a
process-wide result collector so ``run.py`` can emit BENCH_*.json.

Every timing loop fences with ``jax.block_until_ready`` — async
dispatch otherwise returns before the work runs and the row measures
dispatch latency, not the kernel.  Rows are mirrored into the
telemetry registry (``bench.<name>`` gauges).
"""
from __future__ import annotations

import time
from typing import Callable, Dict, List

import jax
import numpy as np

from repro.obs import metrics as _obs_metrics

# Every row() call records here; benchmarks.run dumps it as JSON along
# with the planner's per-op chosen-strategy log and a metrics snapshot.
RESULTS: List[Dict] = []


def time_fn(fn: Callable, *args, iters: int = 5, warmup: int = 2,
            **kw) -> float:
    """Median seconds per call; jit warmup excluded."""
    for _ in range(warmup):
        out = fn(*args, **kw)
        jax.block_until_ready(out)
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        out = fn(*args, **kw)
        jax.block_until_ready(out)
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


def row(name: str, seconds: float, derived: str = "") -> str:
    RESULTS.append({"name": name, "us_per_call": round(seconds * 1e6, 1),
                    "derived": derived})
    _obs_metrics.gauge(f"bench.{name}").set(seconds)
    return f"{name},{seconds*1e6:.1f},{derived}"
