"""The compiled training steps name their device ops: each model layer,
each aggregation op (forward and backward) and the optimizer update sit
under a ``jax.named_scope``, which XLA keeps in every instruction's
``op_name`` metadata (and a profiler trace shows beside the op)."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.graph import from_coo
from repro.data.sampler import NeighborSampler
from repro.models.gnn import gat, sage
from repro.models.gnn.common import make_bundle, pad_features
from repro.models.gnn.train import make_sampled_train_step, make_train_step


def _graph(n=40, m=200):
    rng = np.random.default_rng(0)
    g = from_coo(rng.integers(0, n, m), rng.integers(0, n, m),
                 n_src=n, n_dst=n)
    x = rng.standard_normal((n, 8)).astype(np.float32)
    return g, x, rng.integers(0, 3, n)


def _scopes(compiled_text):
    """Every path component of the HLO's op names, with JAX's autodiff
    wrappers (``jvp(...)``, ``transpose(...)``) taken off."""
    out = set()
    for name in re.findall(r'op_name="([^"]*)"', compiled_text):
        for part in name.split("/"):
            out.add(re.sub(r"^(?:\w+\()+|\)+$", "", part))
    return out


@pytest.mark.parametrize("arch,strategy,want", [
    ("sage", "ell", {"sage.layer0", "sage.layer1", "weighted_copy_reduce",
                     "weighted_copy_reduce.bwd", "optimizer"}),
    ("gat", "auto", {"gat.layer0", "gat.layer1", "gsddmm.u_add_v_copy_e",
                     "edge_softmax", "gspmm.u_mul_e_add_v", "optimizer"}),
])
def test_full_graph_step_names_its_ops(arch, strategy, want):
    g, x, y = _graph()
    mod = {"sage": sage, "gat": gat}[arch]
    params = (sage.init(jax.random.PRNGKey(0), 8, 8, 3) if arch == "sage"
              else gat.init(jax.random.PRNGKey(0), 8, 4, 3, n_heads=2))
    opt_init, step = make_train_step(mod.forward, strategy)
    text = step.lower(params, opt_init(params), 0, make_bundle(g),
                      jnp.asarray(x), jnp.asarray(y), jnp.ones(len(y), bool),
                      jax.random.PRNGKey(1)).compile().as_text()
    assert want <= _scopes(text)


def test_sampled_step_names_its_ops():
    g, x, y = _graph()
    params = sage.init(jax.random.PRNGKey(0), 8, 8, 3)
    mb = NeighborSampler(g, (2, 3), 8, seed=0).sample(np.arange(8), y[:8])
    opt_init, step = make_sampled_train_step(sage.forward_blocks, "auto")
    text = step.lower(params, opt_init(params), 0, mb, pad_features(x),
                      jax.random.PRNGKey(1)).compile().as_text()
    assert {"sage.layer0", "sage.layer1", "block_gspmm.u_copy_mean_v",
            "optimizer"} <= _scopes(text)
