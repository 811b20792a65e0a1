"""The compat module is the one place that adapts to the installed JAX."""
import pathlib
import re

import jax
import jax.numpy as jnp

from repro.compat import trace_state_clean

ROOT = pathlib.Path(__file__).resolve().parents[1]

# API spellings of other JAX versions; only repro/compat.py may adapt
_STALE = [re.compile(p) for p in (
    r"jax\.core\.trace_state_clean",
    r"jax\.experimental\.shard_map",
    r"getattr\(\s*(jax\.)?lax\s*,\s*[\"']pvary[\"']",
)]


def test_trace_state_clean_eager_true():
    assert trace_state_clean()


def test_trace_state_clean_false_under_jit_and_grad():
    seen = []

    def f(x):
        seen.append(trace_state_clean())
        return x * 2.0

    jax.jit(f)(jnp.float32(1.0))
    jax.grad(f)(1.0)
    assert seen == [False, False]
    assert trace_state_clean()


def test_no_version_specific_jax_api_outside_compat():
    me = pathlib.Path(__file__).resolve()
    offenders = []
    for top in ("src", "benchmarks", "examples", "tests"):
        for path in (ROOT / top).rglob("*.py"):
            if path.resolve() == me:
                continue
            text = path.read_text()
            for pat in _STALE:
                if pat.search(text):
                    offenders.append(f"{path.relative_to(ROOT)}: "
                                     f"{pat.pattern}")
    assert not offenders, offenders
