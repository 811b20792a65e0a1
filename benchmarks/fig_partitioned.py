"""Partitioned full-graph training swept over shard counts (DESIGN.md §6).

For each (dataset, app) the single-device full-graph epoch is the
baseline; the partitioned rows train the same model across 2/4/8
host-emulated shards (ring execution) plus a delayed-halo row for GCN.
On the CPU the shards are emulated devices, which need
``XLA_FLAGS=--xla_force_host_platform_device_count`` set BEFORE jax
imports, so the shard sweep re-execs itself in one child process and
streams rows back; a child killed by a signal (the known host-platform
emulation crash) downgrades to a skip note instead of failing the whole
benchmark run. On an accelerator the parent already holds the chips, so
the sweep runs in-process over shard counts no larger than the device
count.

Reported per row: measured epoch wall time (compile excluded — the
train loops warm up before timing), the speedup over the single-device
baseline, and the partition's cut fraction. The child's plan log is
replayed into the parent so ``BENCH_partitioned.json`` carries the
chosen plans like every other section.

NOTE: on host-EMULATED devices all "shards" share one CPU's cores, so
wall-clock speedups > 1 are not expected at these scales — the sweep
tracks the communication/padding overhead trend across shard counts
(the real-hardware signal), not raw speed.

Run from the repository root (``python -m benchmarks.run --only
partitioned``): the emulation child imports this module.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

import numpy as np

from .common import row, time_fn

QUICK = bool(int(os.environ.get("REPRO_BENCH_QUICK", "0")))

# power-law (R-MAT) ragged-ring leg: hash placement is the scalable
# mode for skewed graphs (DistGNN-style random placement), and also the
# worst case for max-width bucket padding — one hub-heavy bucket sets
# the global eb all S² buckets pad to, so this is where the per-bucket
# eb[i,j] widths decide the pad+wire bill
POWERLAW_SHAPE = (11, 12_000) if QUICK else (13, 60_000)
POWERLAW_SHARDS = 8

if QUICK:
    DATASET = "tiny"
    SHARDS = (2, 4)
    APPS = ("gcn", "sage")
    EPOCHS = 2
    HALO = ()
else:
    DATASET = "pubmed-like"
    SHARDS = (2, 4, 8)
    APPS = ("gcn", "sage", "gat")
    EPOCHS = 3
    HALO = (4,)          # gcn halo-staleness rows

def sweep(cfg: dict):
    """The shard sweep; yields one message dict per result row.

    Runs on whatever devices this process's JAX sees: the emulated host
    devices of the child :func:`main` starts on a CPU, or the chips of
    the process that calls it on an accelerator.
    """
    import jax

    from repro.core import planner
    from repro.data import make_node_dataset
    from repro.launch.mesh import make_shard_mesh
    from repro.models.gnn import gat, gcn, sage
    from repro.models.gnn.train import train_partitioned
    from repro.obs import metrics as _metrics
    from repro.optim import Precision

    mods = {"gcn": gcn, "sage": sage, "gat": gat}
    g, feats, labels, tm, vm, nc = make_node_dataset(cfg["dataset"])
    for app in cfg["apps"]:
        mod = mods[app]
        params = mod.init(jax.random.PRNGKey(0), feats.shape[1], 64, nc)
        for S in cfg["shards"]:
            mesh = make_shard_mesh(S)
            _, hist = train_partitioned(
                mod.forward_partitioned, params, g, feats, labels, tm,
                n_shards=S, mesh=mesh, epochs=cfg["epochs"], drop=0.0,
                seed=1)
            pg = planner.get_plan_cache(g).partition(S, "contiguous")
            yield {"kind": "row", "app": app, "shards": S, "halo": 0,
                   "epoch_time": hist["epoch_time"][-1],
                   "loss": hist["loss"][-1],
                   "cut": pg.stats.cut_fraction, "eb": pg.stats.eb}
    # delayed-halo rows (gcn only): the reported time is a STALE epoch —
    # the ring-free step the staleness knob buys
    for k in cfg["halo"]:
        S = max(cfg["shards"])
        mesh = make_shard_mesh(S)
        params = gcn.init(jax.random.PRNGKey(0), feats.shape[1], 64, nc)
        _, hist = train_partitioned(
            gcn.forward_partitioned, params, g, feats, labels, tm,
            n_shards=S, mesh=mesh, epochs=cfg["epochs"] * 2, drop=0.0,
            halo_staleness=k, init_halo_fn=gcn.init_halo, seed=1)
        # same estimator as every other row: the LAST epoch of the kind
        # the row reports (here: the last stale, i.e. ring-free, epoch)
        stale_epochs = [t for t, r in zip(hist["epoch_time"],
                                          hist["refreshed"]) if not r]
        yield {"kind": "row", "app": "gcn", "shards": S, "halo": k,
               "epoch_time": (stale_epochs[-1] if stale_epochs
                              else hist["epoch_time"][-1]),
               "loss": hist["loss"][-1], "cut": 0.0, "eb": 0}
    # precision x compression sweep (gcn): exchange bytes come from the
    # obs metrics registry (comm.ring.*_bytes), step time and final-loss
    # delta vs the fp32 row ride along (DESIGN.md §12)
    S = 4 if 4 in cfg["shards"] else max(cfg["shards"])
    mesh = make_shard_mesh(S)
    params = gcn.init(jax.random.PRNGKey(0), feats.shape[1], 64, nc)
    base_loss = None
    for pname, comm in (("fp32", "none"), ("bf16", "none"),
                        ("fp32", "int8"), ("bf16", "int8")):
        prec = Precision.parse(pname, comm=comm)
        prev = _metrics.set_enabled(True)
        _metrics.reset_metrics()
        _, hist = train_partitioned(
            gcn.forward_partitioned, params, g, feats, labels, tm,
            n_shards=S, mesh=mesh, epochs=cfg["epochs"], drop=0.0, seed=1,
            precision=prec,
            init_comm_fn=gcn.init_comm if comm == "int8" else None)
        snap = _metrics.snapshot()
        _metrics.set_enabled(prev)
        loss = hist["loss"][-1]
        if base_loss is None:
            base_loss = loss
        yield {"kind": "prec_row", "app": "gcn", "shards": S,
               "precision": prec.tag(),
               "epoch_time": hist["epoch_time"][-1],
               "loss": loss, "loss_delta": loss - base_loss,
               "raw_bytes": snap.get("comm.ring.raw_bytes",
                                     {}).get("value", 0),
               "wire_bytes": snap.get("comm.ring.wire_bytes",
                                      {}).get("value", 0)}


# the emulation child: forces the host device count BEFORE jax imports,
# then streams the sweep's messages back as JSON lines
_CHILD = r"""
import json, os, sys
cfg = json.loads(sys.argv[1])
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=%d" % max(cfg["shards"]))
from benchmarks.fig_partitioned import sweep
from repro.core import planner
for msg in sweep(cfg):
    print(json.dumps(msg), flush=True)
print(json.dumps({"kind": "plans",
                  "plans": {f"{op}|{req}": dict(cnt) for (op, req), cnt
                            in planner.plan_log().items()}}), flush=True)
"""


def _emulated_sweep(cfg: dict):
    """Messages of :func:`sweep` run in a host-emulation child, or None
    when the child died of a signal (the known emulation crash)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", _CHILD, json.dumps(cfg)],
                       env=env, capture_output=True, text=True,
                       timeout=3600)
    if r.returncode < 0:
        print(f"# partitioned sweep skipped: emulation subprocess died "
              f"with signal {-r.returncode}", file=sys.stderr)
        return None
    if r.returncode != 0:
        print(r.stderr[-3000:], file=sys.stderr)
        raise RuntimeError("partitioned benchmark child failed")
    return [json.loads(line) for line in r.stdout.splitlines()
            if line.strip().startswith("{")]


def _baseline(dataset: str, apps, epochs: int) -> dict:
    """Single-device full-graph epoch per app (strategy=auto)."""
    import jax

    from repro.data import make_node_dataset
    from repro.models.gnn import gat, gcn, sage
    from repro.models.gnn.common import make_bundle
    from repro.models.gnn.train import train_full_graph

    mods = {"gcn": gcn, "sage": sage, "gat": gat}
    g, feats, labels, tm, vm, nc = make_node_dataset(dataset)
    base = {}
    for app in apps:
        mod = mods[app]
        params = mod.init(jax.random.PRNGKey(0), feats.shape[1], 64, nc)
        fw = (lambda m: lambda p, b, x, **kw: m.forward(p, b, x, drop=0.0,
                                                        **kw))(mod)
        _, hist = train_full_graph(fw, params, make_bundle(g), feats,
                                   labels, tm, epochs=epochs, seed=1)
        base[app] = hist["epoch_time"][-1]
        print(row(f"figp_{dataset}_{app}_s1_single", base[app],
                  f"loss={hist['loss'][-1]:.3f}"))
    return base


def powerlaw_ring_rows() -> None:
    """Ragged ring buckets on a power-law graph (emulated, 8 shards).

    Reports the dense (max-width ``eb``) vs ragged (per-bucket
    ``eb[i,j]`` diagonal schedule) pad-slot and pad+wire byte bills,
    the emulated ring fwd+bwd wall time, and the gradient gap vs the
    single-device reference. Runs parent-side: the emulated ring shares
    the bucket math and transposed-ring VJP with the mesh path, so no
    device emulation subprocess is needed.
    """
    import jax
    import jax.numpy as jnp

    from repro.core import from_coo, gspmm
    from repro.core.partition import build_partition, ring_gspmm
    from repro.data import rmat_graph

    n_log2, nnz = POWERLAW_SHAPE
    S = POWERLAW_SHARDS
    src, dst, n = rmat_graph(n_log2, nnz, seed=13)
    g = from_coo(src, dst, n_src=n, n_dst=n)
    pg = build_partition(g, S, "hash")
    st = pg.stats
    tag = f"figp_powerlaw_s{S}"

    F = 8
    dense_slots = S * S * st.eb
    stages = st.ragged_stages if st.ragged_stages >= 0 else S - 1
    # wire: S·stages block-sends of rows×F fp32; pad: slots beyond the
    # real edges, each touching an F-wide feature row (same units both
    # sides, so the cut is layout-only)
    wire_d = S * (S - 1) * pg.rows * F * 4
    wire_r = S * stages * pg.rows * F * 4
    pad_d = (dense_slots - g.n_edges) * F * 4
    pad_r = (st.ragged_slots - g.n_edges) * F * 4
    print(row(f"{tag}_pad_dense", 0.0,
              f"slots={dense_slots} edges={g.n_edges} "
              f"padwire_bytes={pad_d + wire_d}"))
    print(row(f"{tag}_pad_ragged", 0.0,
              f"slots={st.ragged_slots} stages={stages} "
              f"padwire_bytes={pad_r + wire_r} "
              f"cut={(pad_d + wire_d) / max(pad_r + wire_r, 1):.2f}x"))

    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=(g.n_src, F)).astype(np.float32))
    w = pg.scatter_edges(jnp.ones((g.n_edges,), jnp.float32))

    def ring_loss(x):
        out = pg.gather_nodes(ring_gspmm(pg, pg.scatter_nodes(x), w))
        return jnp.sum(out ** 2)

    def ref_loss(x):
        return jnp.sum(gspmm(g, "u_copy_add_v", u=x,
                             strategy="segment") ** 2)

    gr = jax.grad(ring_loss)(x)
    gf = jax.grad(ref_loss)(x)
    # hub gradients reach O(1e3), so the honest parity number is the
    # relative gap (absolute diff is pure fp32 reduction-order noise)
    gdiff = float(jnp.max(jnp.abs(gr - gf)))
    grel = gdiff / max(float(jnp.max(jnp.abs(gf))), 1e-12)
    t = time_fn(jax.jit(jax.grad(ring_loss)), x, iters=3)
    print(row(f"{tag}_ring_fwdbwd", t,
              f"edges={g.n_edges} grad_reldiff={grel:.1e}"))


def main() -> None:
    import jax

    base = _baseline(DATASET, APPS, EPOCHS)
    powerlaw_ring_rows()
    cfg = {"dataset": DATASET, "shards": list(SHARDS), "apps": list(APPS),
           "epochs": EPOCHS, "halo": list(HALO)}
    if jax.default_backend() == "cpu":
        msgs = _emulated_sweep(cfg)
        if msgs is None:
            return
    else:
        # this process already holds the chips: sweep them in-process,
        # never in a child that would find them taken
        n_dev = len(jax.devices())
        cfg["shards"] = [S for S in SHARDS if S <= n_dev]
        if not cfg["shards"]:
            raise RuntimeError(f"partitioned sweep needs at least "
                               f"{min(SHARDS)} devices, have {n_dev}")
        msgs = sweep(cfg)
    for msg in msgs:
        if msg["kind"] == "row":
            app, S, k = msg["app"], msg["shards"], msg["halo"]
            name = f"figp_{DATASET}_{app}_s{S}"
            if k:
                name += f"_halo{k}"
            derived = (f"loss={msg['loss']:.3f}"
                       f" speedup={base[app] / max(msg['epoch_time'], 1e-12):.2f}x")
            if not k:
                derived += f" cut={msg['cut']:.0%}"
            else:
                derived += " stale-epoch"
            print(row(name, msg["epoch_time"], derived))
        elif msg["kind"] == "prec_row":
            tag = msg["precision"].replace("+", "_")
            name = f"figp_{DATASET}_{msg['app']}_s{msg['shards']}_{tag}"
            ratio = (msg["raw_bytes"] / msg["wire_bytes"]
                     if msg["wire_bytes"] else float("nan"))
            print(row(name, msg["epoch_time"],
                      f"loss={msg['loss']:.3f}"
                      f" dloss={msg['loss_delta']:+.4f}"
                      f" wire={msg['wire_bytes']}"
                      f" comp={ratio:.2f}x"))
        elif msg["kind"] == "plans":
            # replay the emulation child's decisions into this process's
            # plan log so the BENCH json reports them like every section
            from repro.core import planner
            for key, counts in msg["plans"].items():
                op, req = key.split("|", 1)
                for chosen, cnt in counts.items():
                    for _ in range(int(cnt)):
                        planner._record(op, req, chosen)


if __name__ == "__main__":
    main()
