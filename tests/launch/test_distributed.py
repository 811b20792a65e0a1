"""Multi-device tests (ring Copy-Reduce, sharded train step).

These re-exec themselves in a subprocess with
XLA_FLAGS=--xla_force_host_platform_device_count=8 so the main pytest
process keeps seeing the single real CPU device. The runner converts
emulation crashes (signal death) into skips — see tests/conftest.py.
"""
import pytest

from tests.conftest import run_multidevice

_RING_PROG = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np, jax, jax.numpy as jnp
from repro.core import from_coo
from repro.core.partition import (build_partition, ring_gspmm,
                                  ring_reference)
from repro.kernels.spmm.ref import spmm_ref
from repro.launch.mesh import make_shard_mesh

mesh = make_shard_mesh(8)        # Auto axes: the ring rejects Explicit
rng = np.random.default_rng(0)
n, nnz, d = 64, 400, 16
src = rng.integers(0, n, nnz); dst = rng.integers(0, n, nnz)
g = from_coo(src, dst, n_src=n, n_dst=n)
# uniform mode: the historical id // rows layout, padded row i == vertex i
plan = build_partition(g, 8, "uniform")
w = jnp.where(plan.mask, 1.0, 0.0).astype(jnp.float32)   # CR-sum weights
x = np.zeros((plan.n_pad, d), np.float32)
x[:n] = rng.normal(size=(n, d))
out = ring_gspmm(plan, jnp.asarray(x), w, mesh=mesh)
ref = ring_reference(plan, jnp.asarray(x))
err = np.abs(np.asarray(out) - np.asarray(ref)).max()
assert err < 1e-4, f"ring vs padded-oracle err={err}"
oracle = spmm_ref(g.src, g.dst, jnp.asarray(x[:n]), n, "sum")
err2 = np.abs(np.asarray(out)[:n] - np.asarray(oracle)).max()
assert err2 < 1e-4, f"ring vs graph-oracle err={err2}"
hlo = jax.jit(lambda x: ring_gspmm(plan, x, w, mesh=mesh)).lower(
    jnp.asarray(x)).compile().as_text()
assert "collective-permute" in hlo, "ring must lower to collective-permute"
print("RING_OK")
"""

_SHARDED_TRAIN_PROG = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np, jax, jax.numpy as jnp
from repro.configs import get_smoke_config
from repro.launch.mesh import make_mesh
from repro.launch import shardings as SR
from repro.launch.steps import TrainState, make_train_step, init_state
from repro.launch.train import synthetic_batch
from repro.pjit_utils import ambient_mesh

cfg = get_smoke_config("qwen2_7b")
mesh = make_mesh((2, 4), ("data", "model"))
state = init_state(jax.random.PRNGKey(0), cfg)
specs = SR.param_specs(state.params, cfg, mesh)
sh = SR.to_named(TrainState(specs, specs, specs,
                            jax.sharding.PartitionSpec()), mesh)
state = jax.device_put(state, sh)
step = jax.jit(make_train_step(cfg), donate_argnums=(0,))
with ambient_mesh(mesh):
    losses = []
    for i in range(3):
        batch = synthetic_batch(cfg, i, 4, 32)
        state, m = step(state, batch)
        losses.append(float(m["loss"]))
assert all(np.isfinite(l) for l in losses), losses
# single-device reference: same math, no mesh
state1 = init_state(jax.random.PRNGKey(0), cfg)
step1 = jax.jit(make_train_step(cfg), donate_argnums=(0,))
l1 = []
for i in range(3):
    batch = synthetic_batch(cfg, i, 4, 32)
    state1, m1 = step1(state1, batch)
    l1.append(float(m1["loss"]))
err = max(abs(a - b) for a, b in zip(losses, l1))
assert err < 5e-2, f"sharded vs single-device loss drift {err}: {losses} {l1}"
print("SHARDED_TRAIN_OK")
"""


def test_ring_copy_reduce_8dev():
    r = run_multidevice(_RING_PROG)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "RING_OK" in r.stdout


def test_sharded_train_matches_single_device():
    r = run_multidevice(_SHARDED_TRAIN_PROG)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "SHARDED_TRAIN_OK" in r.stdout


@pytest.mark.parametrize("platform", ["cpu", "tpu"])
def test_make_mesh_error_prescribes_emulation_only_on_cpu(monkeypatch,
                                                          platform):
    """Too few devices: on the CPU the error names the host-emulation
    flag; on a chip, where devices cannot be emulated, it must not."""
    import types

    import jax

    from repro.launch.mesh import make_shard_mesh

    fake = [types.SimpleNamespace(platform=platform)]
    monkeypatch.setattr(jax, "devices", lambda: fake)
    with pytest.raises(RuntimeError, match="needs 4 devices, have 1") as e:
        make_shard_mesh(4)
    assert ("xla_force_host_platform_device_count" in str(e.value)) == \
        (platform == "cpu")
