"""Multi-device partitioned-graph execution (acceptance tests).

Each test re-execs a subprocess with
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (the main pytest
process keeps the single real CPU device) via
:func:`tests.conftest.run_multidevice`, which converts host-platform
emulation crashes (signal death) into skips.

Covers the ISSUE-3 acceptance criteria: partitioned forward AND
gradients match single-device full-graph execution for GCN, SAGE and
GAT at 2/4/8 emulated shards; ``strategy="auto"`` selects ``ring`` only
when a mesh is active and falls back cleanly otherwise; the partitioned
train loop (exact and delayed-halo) runs on the mesh.
"""
import pytest

from tests.conftest import run_multidevice

_APP_PROG = r"""
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np, jax, jax.numpy as jnp
from jax.flatten_util import ravel_pytree
from repro.core import from_coo
from repro.launch.mesh import make_shard_mesh
from repro.models.gnn import gcn, sage, gat
from repro.models.gnn.common import make_bundle, make_partitioned_bundle
from repro.substrate.nn import cross_entropy_loss

mod = {"gcn": gcn, "sage": sage, "gat": gat}[sys.argv[1]]
rng = np.random.default_rng(0)
n, nnz, d, nc = 64, 400, 8, 3
src = rng.integers(0, n, nnz); dst = rng.integers(0, n, nnz)
g = from_coo(src, dst, n_src=n, n_dst=n)
x = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
labels = jnp.asarray(rng.integers(0, nc, n).astype(np.int32))
mask = jnp.asarray(rng.random(n) < 0.6)
bundle = make_bundle(g)
params = mod.init(jax.random.PRNGKey(0), d, 8, nc)
ref = mod.forward(params, bundle, x)
gref = ravel_pytree(jax.grad(lambda p: cross_entropy_loss(
    mod.forward(p, bundle, x), labels, mask))(params))[0]
for S in (2, 4, 8):
    mesh = make_shard_mesh(S)
    pb = make_partitioned_bundle(g, S, mesh=mesh)
    pg = pb.pg
    xp = pg.scatter_nodes(x)
    out, _ = mod.forward_partitioned(params, pb, xp)
    err = np.abs(np.asarray(pg.gather_nodes(out)) - np.asarray(ref)).max()
    assert err < 2e-4, f"S={S} forward err={err}"
    yp = pg.scatter_nodes(labels); mp = pg.scatter_nodes(mask)
    gp = ravel_pytree(jax.grad(lambda p: cross_entropy_loss(
        mod.forward_partitioned(p, pb, xp)[0], yp, mp))(params))[0]
    gerr = np.abs(np.asarray(gp) - np.asarray(gref)).max()
    assert gerr < 2e-4, f"S={S} grad err={gerr}"
    print(f"S={S} fwd={err:.2e} grad={gerr:.2e}")
print("APP_OK")
"""

_AUTO_RING_PROG = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np, jax, jax.numpy as jnp
from repro.core import from_coo, gspmm, planner, use_ring

rng = np.random.default_rng(0)
n, nnz = 4096, 40000
g = from_coo(rng.integers(0, n, nnz), rng.integers(0, n, nnz),
             n_src=n, n_dst=n)
X = jnp.asarray(rng.normal(size=(n, 64)).astype(np.float32))
ref = gspmm(g, "u_copy_add_v", u=X, strategy="segment")
# Auto axes: the ring rejects jax.make_mesh's default Explicit ones
mesh = jax.make_mesh((8,), ("data",),
                     axis_types=(jax.sharding.AxisType.Auto,))
with use_ring(mesh):
    out = gspmm(g, "u_copy_add_v", u=X)        # auto
    assert planner.last_plan("u_copy_add_v") == "ring", \
        planner.last_plan("u_copy_add_v")
    err = np.abs(np.asarray(out) - np.asarray(ref)).max()
    assert err < 1e-3, err
# outside the context auto must NOT pick ring, and stays correct
out = gspmm(g, "u_copy_add_v", u=X)
assert planner.last_plan("u_copy_add_v") != "ring"
err = np.abs(np.asarray(out) - np.asarray(ref)).max()
assert err < 1e-3, err
print("AUTO_RING_OK")
"""

_TRAIN_PROG = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np, jax, jax.numpy as jnp
from repro.core import from_coo
from repro.launch.mesh import make_shard_mesh
from repro.models.gnn import gcn
from repro.models.gnn.common import make_bundle
from repro.models.gnn.train import train_full_graph, train_partitioned

rng = np.random.default_rng(0)
n, nnz, d, nc = 64, 400, 8, 3
g = from_coo(rng.integers(0, n, nnz), rng.integers(0, n, nnz),
             n_src=n, n_dst=n)
x = rng.normal(size=(n, d)).astype(np.float32)
labels = rng.integers(0, nc, n)
mask = rng.random(n) < 0.6
params = gcn.init(jax.random.PRNGKey(1), d, 8, nc)
mesh = make_shard_mesh(4)
_, hp = train_partitioned(gcn.forward_partitioned, params, g, x, labels,
                          mask, n_shards=4, mesh=mesh, epochs=3,
                          drop=0.0, seed=0)
# single-device reference: same step math (dropout off), no mesh
fw = lambda p, b, xx, **kw: gcn.forward(p, b, xx, drop=0.0, **kw)
_, h1 = train_full_graph(fw, params, make_bundle(g), x, labels, mask,
                         epochs=3, seed=0)
drift = max(abs(a - b) for a, b in zip(hp["loss"], h1["loss"]))
assert drift < 1e-3, f"partitioned vs single-device loss drift {drift}"
# delayed halo: refresh every 2nd epoch, losses stay finite
_, hd = train_partitioned(gcn.forward_partitioned, params, g, x, labels,
                          mask, n_shards=4, mesh=mesh, epochs=4,
                          drop=0.0, halo_staleness=2,
                          init_halo_fn=gcn.init_halo, seed=0)
assert all(np.isfinite(l) for l in hd["loss"]), hd["loss"]
assert hd["refreshed"] == [True, False, True, False]
print("TRAIN_OK")
"""


@pytest.mark.parametrize("app", ["gcn", "sage", "gat"])
def test_partitioned_matches_single_device_2_4_8(app):
    r = run_multidevice(_APP_PROG, app)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "APP_OK" in r.stdout


def test_auto_selects_ring_only_with_mesh():
    r = run_multidevice(_AUTO_RING_PROG)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "AUTO_RING_OK" in r.stdout


def test_train_partitioned_exact_and_delayed():
    r = run_multidevice(_TRAIN_PROG)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "TRAIN_OK" in r.stdout


def test_ring_rejects_explicit_mesh_axes():
    """jax.make_mesh builds Explicit axes by default; the ring needs Auto
    ones and says so at the door instead of failing inside a gather."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import build_partition, from_coo, ring_gspmm, use_ring
    from repro.launch.mesh import make_shard_mesh

    explicit = jax.make_mesh((1,), ("data",))
    assert explicit.axis_types == (jax.sharding.AxisType.Explicit,)
    with pytest.raises(ValueError, match="AxisType.Auto"):
        with use_ring(explicit):
            pass
    rng = np.random.default_rng(0)
    g = from_coo(rng.integers(0, 16, 40), rng.integers(0, 16, 40),
                 n_src=16, n_dst=16)
    pg = build_partition(g, 1)
    x = pg.scatter_nodes(jnp.ones((16, 4), jnp.float32))
    w = jnp.where(pg.mask, 1.0, 0.0)
    with pytest.raises(ValueError, match="AxisType.Auto"):
        ring_gspmm(pg, x, w, mesh=explicit)
    # the repo's own mesh helper builds Auto axes, which the ring runs on
    auto = make_shard_mesh(1)
    with use_ring(auto):
        out = ring_gspmm(pg, x, w, mesh=auto)
    np.testing.assert_allclose(np.asarray(pg.gather_nodes(out, 16)),
                               np.asarray(pg.gather_nodes(
                                   ring_gspmm(pg, x, w), 16)))


def test_partitioned_bench_sweeps_in_process_off_cpu(monkeypatch):
    """On an accelerator the benchmark's parent already holds the chips:
    the shard sweep runs in-process over at most the device count, and
    no emulation child is started."""
    import subprocess

    import jax

    from benchmarks import fig_partitioned as fp

    seen = []
    monkeypatch.setattr(fp, "_baseline", lambda *a: {})
    monkeypatch.setattr(fp, "powerlaw_ring_rows", lambda: None)
    monkeypatch.setattr(fp, "sweep",
                        lambda cfg: seen.append(cfg["shards"]) or iter(()))
    monkeypatch.setattr(subprocess, "run", lambda *a, **k: pytest.fail(
        "started an emulation child"))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "devices", lambda: [object()] * 4)
    fp.main()
    assert seen and seen[0] and max(seen[0]) <= 4
    monkeypatch.setattr(jax, "devices", lambda: [object()])
    with pytest.raises(RuntimeError, match="needs at least"):
        fp.main()
