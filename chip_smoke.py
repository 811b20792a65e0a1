"""Smoke run of GNN training and serving on a TPU, through the entry
points a user calls, on the seeded synthetic ``reddit-like`` graph
(65,536 nodes, ~628k edges with self loops, 602 features, 41 classes;
generated from its seed, nothing downloaded). Weights are random, made
from a seed.

    python chip_smoke.py             # one chip: phases (a)-(c)
    python chip_smoke.py --chips 4   # 4-chip partitioned training only

One chip:
  (a) GraphSAGE (hidden 256, 2 layers): 5 full-graph train steps with
      ``strategy="auto"``, then one forward + grad with the Pallas
      aggregation pinned, against the segment path on the same params.
  (b) GAT (``gat.init`` defaults: 4 heads of 16): 5 train steps with
      ``strategy="auto"``, then one forward + grad with the Pallas
      attention kernel pinned, against the fused jnp attention.
  (c) Serving: a layer-wise GCN server answering 4 clients x 25
      requests; steady state must not recompile, and the served rows
      must match ``gcn.infer`` on the segment path.
``--chips 4``: one GCN forward + grad through the ring over a 4-device
mesh against the single-device segment path, then ring training for 3
epochs with fp32 and with int8 exchanges against single-device training.

Each phase prints its chosen plans, compile seconds, losses and the
largest relative difference from its reference. Those are host-clock
set-up numbers and correctness checks, not device measurements. The run
fails (non-zero exit, no final line) when the backend is not a TPU,
when a kernel would run interpreted, when a pinned strategy falls back,
when a jitted step that planned ``pallas`` holds no Mosaic kernel
(``tpu_custom_call``), when a loss is not finite, or when a difference
exceeds its tolerance. On success the last line is one JSON object
naming the device.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import warnings

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

# fp32 matmuls on a TPU run at the default precision: one bf16 pass per
# operand (8 significant bits, relative rounding 2^-9 ~ 2e-3). The dense
# layers round identically on both sides of a comparison; what differs
# is the aggregation (MXU one-hot matmuls vs exact f32 segment sums, or
# the attention backward's einsum vs elementwise products). Through two
# layers and the backward pass that compounds to a handful of bf16
# roundings (~1e-2 on weight gradients), so 2e-2 of the reference's
# largest magnitude admits it, while a wrong gather, scatter, mask or
# normalisation is off by O(1).
TOL = 2e-2
# Ring-partitioned GCN against single-device GCN on the segment path, on
# the same params: both aggregate in exact f32 (scatter-add per bucket vs
# segment sum), so they differ only in summation order (~1e-7 relative).
# The dense layers round the same values to bf16, except where an
# ordering difference flips one element's rounding by one bf16 ulp
# (2^-8 of that element, a small share of the largest). 1e-3 admits
# that; a dropped or misrouted bucket moves whole rows, O(1e-1).
RING_TOL = 1e-3
# Losses of ring fp32 training against single-device training after
# each of 3 Adam steps, a mean over ~39k train rows. A v5e run of this
# check read 2.7e-7 (fp32 ring) and 1.1e-5 (int8 ring); 1e-4 holds the
# fp32 ring to the order its arithmetic allows. Losses near ln(41) at
# random init say little about routing: RING_TOL's check carries that.
LOSS_TOL = 1e-4

DATASET = "reddit-like"


class SmokeFailure(RuntimeError):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def say(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


def rel_diff(got, want) -> float:
    """Largest |got - want| over the largest |want|, across a pytree."""
    import jax
    import numpy as np
    worst = 0.0
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        a = np.asarray(a, np.float64)
        b = np.asarray(b, np.float64)
        check(a.shape == b.shape, f"shape {a.shape} != {b.shape}")
        check(bool(np.isfinite(a).all()), "non-finite values")
        scale = max(float(np.max(np.abs(b))), 1e-30)
        worst = max(worst, float(np.max(np.abs(a - b))) / scale)
    return worst


def plan_counts():
    from repro.core import planner
    return {k: dict(v) for k, v in planner.plan_log().items()}


def plans_since(before) -> dict:
    """Plans the planner logged after ``before = plan_counts()``."""
    out = {}
    for (op, req), counts in plan_counts().items():
        new = {s: n - before.get((op, req), {}).get(s, 0)
               for s, n in counts.items()}
        new = {s: n for s, n in new.items() if n}
        if new:
            out[f"{op}|{req}"] = new
    return out


class Compiled:
    """A jitted function lowered and compiled once, with the plans its
    trace chose; a plan of ``pallas`` must have left a Mosaic kernel in
    the compiled program."""

    def __init__(self, phase: str, name: str, fn, *args):
        import jax
        before = plan_counts()
        t0 = time.perf_counter()
        self.exe = jax.jit(fn).lower(*args).compile()
        self.compile_s = time.perf_counter() - t0
        self.plans = plans_since(before)
        pallas = any("pallas" in c for c in self.plans.values())
        kernel = "tpu_custom_call" in self.exe.as_text()
        say(phase, f"{name}: compile {self.compile_s:.1f} s, plans "
                   f"{self.plans}, mosaic kernel {kernel}")
        check(kernel or not pallas,
              f"{name} planned pallas but compiled no tpu_custom_call")
        self.pallas = pallas

    def __call__(self, *args):
        return self.exe(*args)


def losses_ok(phase: str, losses) -> None:
    import numpy as np
    say(phase, f"losses {[float(x) for x in losses]}")
    check(bool(np.isfinite(np.asarray(losses)).all()),
          f"{phase}: non-finite loss")


def fwd_grad(forward, params, bundle, x, labels, mask, **kw):
    """(loss, logits) and d loss / d params of one forward."""
    import jax
    from repro.substrate.nn import cross_entropy_loss

    def loss(p):
        logits = forward(p, bundle, x, **kw)
        return cross_entropy_loss(logits, labels, mask), logits
    return jax.value_and_grad(loss, has_aux=True)(params)


def train_steps(phase, forward, params, bundle, x, y, m, steps=5):
    import jax
    from repro.models.gnn.train import make_train_step
    opt_init, step = make_train_step(forward, "auto")
    opt = opt_init(params)
    rng = jax.random.PRNGKey(1)
    exe = Compiled(phase, "train step (auto)", step, params, opt, 0,
                   bundle, x, y, m, rng)
    losses = []
    for i in range(steps):
        rng, sub = jax.random.split(rng)
        params, opt, loss = exe(params, opt, i, bundle, x, y, m, sub)
        losses.append(float(loss))
    losses_ok(phase, losses)
    return exe


def phase_sage(data) -> dict:
    import jax
    import jax.numpy as jnp
    from repro.models.gnn import sage
    from repro.models.gnn.common import make_bundle
    phase = "a:sage"
    g, x, y, m, nc = data
    bundle = make_bundle(g, tiles=True)
    params = sage.init(jax.random.PRNGKey(0), x.shape[1], 256, nc,
                       n_layers=2)
    train_steps(phase, sage.forward, params, bundle, x, y, m)

    def run(strategy):
        fn = (lambda p, b, xx, yy, mm:
              fwd_grad(sage.forward, p, b, xx, yy, mm, strategy=strategy))
        exe = Compiled(phase, f"forward+grad strategy={strategy}", fn,
                       params, bundle, x, y, m)
        return exe, exe(params, bundle, x, y, m)
    pexe, ((lp, logits_p), grads_p) = run("pallas")
    check(pexe.pallas, "pinned pallas was not planned")
    _, ((ls, logits_s), grads_s) = run("segment")
    d_out = rel_diff(logits_p, logits_s)
    d_grad = rel_diff(grads_p, grads_s)
    say(phase, f"pallas vs segment: loss {float(lp)} vs {float(ls)}, "
               f"max rel diff logits {d_out:.3e}, grads {d_grad:.3e} "
               f"(tolerance {TOL})")
    check(d_out <= TOL and d_grad <= TOL, "sage pallas != segment")
    return {"logits": d_out, "grads": d_grad}


def phase_gat(data) -> dict:
    import jax
    from repro.core import planner
    from repro.models.gnn import gat
    from repro.models.gnn.common import make_bundle
    phase = "b:gat"
    g, x, y, m, nc = data
    bundle = make_bundle(g, tiles=True)
    params = gat.init(jax.random.PRNGKey(0), x.shape[1], 16, nc)
    train_steps(phase, gat.forward, params, bundle, x, y, m)

    # the attention kernel plans on a concrete graph with its ragged
    # pack built host-side, so the graph is closed over, not traced
    planner.get_plan_cache(g).ell_ragged()

    def run(attn):
        fn = (lambda p: fwd_grad(gat.forward, p, bundle, x, y, m,
                                 attn=attn))
        exe = Compiled(phase, f"forward+grad attn={attn}", fn, params)
        return exe, exe(params)
    pexe, ((lp, logits_p), grads_p) = run("pallas")
    check(pexe.pallas, "pinned pallas attention was not planned")
    _, ((lf, logits_f), grads_f) = run("fused")
    d_out = rel_diff(logits_p, logits_f)
    d_grad = rel_diff(grads_p, grads_f)
    say(phase, f"pallas vs fused: loss {float(lp)} vs {float(lf)}, "
               f"max rel diff logits {d_out:.3e}, grads {d_grad:.3e} "
               f"(tolerance {TOL})")
    check(d_out <= TOL and d_grad <= TOL, "gat pallas != fused")
    return {"logits": d_out, "grads": d_grad}


def phase_serve() -> dict:
    import numpy as np
    import jax.numpy as jnp
    from repro.launch.serve_gnn import build_server, run_session
    from repro.models.gnn import gcn
    phase = "c:serve"
    # the plans of building, warming and running the server; the
    # layer-wise refresh runs eagerly, so there is no jitted program to
    # inspect, and an eager pallas_call is Mosaic once interpretation
    # is ruled out
    before = plan_counts()
    t0 = time.perf_counter()
    srv = build_server("gcn", DATASET, mode="layerwise")
    say(phase, f"server built in {time.perf_counter() - t0:.1f} s")
    n = srv.g.n_src
    res = run_session(srv, n_clients=4, requests_per_client=25,
                      ids_fn=lambda rng: rng.integers(0, n, 4))
    say(phase, f"plans {plans_since(before)}")
    say(phase, f"{res['n_samples']} requests, recompiles_steady "
               f"{res['recompiles_steady']}")
    check(res["n_samples"] == 100, "not every request was answered")
    check(res["recompiles_steady"] == 0, "steady-state recompiles")

    rng = np.random.default_rng(7)
    reqs = [(i, rng.integers(0, n, 4)) for i in range(16)]
    served = srv.serve(reqs)
    ref = np.asarray(gcn.infer(srv.params, srv._graph_arg,
                               jnp.asarray(srv.feats), strategy="segment"))
    got = np.concatenate([served[i] for i, _ in reqs])
    want = np.concatenate([ref[ids] for _, ids in reqs])
    d = rel_diff(got, want)
    say(phase, f"served rows vs gcn.infer(segment): max rel diff "
               f"{d:.3e} (tolerance {TOL})")
    check(d <= TOL, "served rows differ from gcn.infer")
    return {"served": d}


def phase_partitioned(data, n_chips: int) -> dict:
    import jax
    import numpy as np
    from repro.core.planner import get_plan_cache
    from repro.launch.mesh import make_shard_mesh
    from repro.models.gnn import gcn
    from repro.models.gnn.common import (make_bundle,
                                         make_partitioned_bundle,
                                         shard_partitioned)
    from repro.models.gnn.train import train_full_graph, train_partitioned
    from repro.optim import Precision
    phase = f"d:ring{n_chips}"
    g, x, y, m, nc = data
    check(len(jax.devices()) >= n_chips,
          f"--chips {n_chips} needs {n_chips} devices, have "
          f"{len(jax.devices())}")
    mesh = make_shard_mesh(n_chips)
    params = gcn.init(jax.random.PRNGKey(0), x.shape[1], 256, nc)

    # train_partitioned builds its bundle the same way: the partition is
    # memoized per graph, and shard_partitioned is its placement step
    pb = make_partitioned_bundle(g, n_chips, mesh=mesh)
    check(pb.pg is get_plan_cache(g).partition(n_chips, "contiguous"),
          "partition is not the memoized one train_partitioned uses")
    pg = pb.pg
    pb, xp, yp, mp = shard_partitioned(pb, pg.scatter_nodes(x),
                                       pg.scatter_nodes(y),
                                       pg.scatter_nodes(m))
    for name, arr in (("features", xp), ("buckets", pb.pg.src_local)):
        devs = {s.device for s in arr.addressable_shards}
        say(phase, f"{name} shards on {sorted(str(d) for d in devs)}")
        check(len(devs) == n_chips, f"{name} do not span every chip")

    # one forward + grad of the ring against the single-device segment
    # path on the same params: this is what catches a misrouted bucket
    ring = Compiled(phase, "ring forward+grad", lambda p, b, xx, yy, mm:
                    fwd_grad(lambda q, bb, h: gcn.forward_partitioned(
                        q, bb, h)[0], p, b, xx, yy, mm),
                    params, pb, xp, yp, mp)
    (l_ring, logits_ring), g_ring = ring(params, pb, xp, yp, mp)
    bundle = make_bundle(g)
    ref = Compiled(phase, "single-device forward+grad strategy=segment",
                   lambda p, b, xx, yy, mm: fwd_grad(
                       gcn.forward, p, b, xx, yy, mm, strategy="segment"),
                   params, bundle, x, y, m)
    (l_ref, logits_ref), g_ref = ref(params, bundle, x, y, m)
    d_out = rel_diff(pg.gather_nodes(logits_ring), logits_ref)
    d_grad = rel_diff(g_ring, g_ref)
    say(phase, f"ring vs single device: loss {float(l_ring)} vs "
               f"{float(l_ref)}, max rel diff logits {d_out:.3e}, grads "
               f"{d_grad:.3e} (tolerance {RING_TOL})")
    check(d_out <= RING_TOL and d_grad <= RING_TOL,
          "ring forward/grad != single device")

    def fwd(p, b, xx, **kw):
        return gcn.forward(p, b, xx, drop=0.0, **kw)
    t0 = time.perf_counter()
    _, single = train_full_graph(fwd, params, bundle, x, y, m,
                                 epochs=3, seed=1)
    say(phase, f"single device: {time.perf_counter() - t0:.1f} s "
               f"incl. compile")
    losses_ok(phase + ":single", single["loss"])
    out = {}
    for comm in ("none", "int8"):
        t0 = time.perf_counter()
        p_out, hist = train_partitioned(
            gcn.forward_partitioned, params, g, x, y, m,
            n_shards=n_chips, mesh=mesh, epochs=3, drop=0.0, seed=1,
            precision=Precision.parse("fp32", comm=comm),
            init_comm_fn=gcn.init_comm if comm == "int8" else None)
        say(phase, f"ring comm={comm}: {time.perf_counter() - t0:.1f} s "
                   f"incl. compile")
        losses_ok(f"{phase}:{comm}", hist["loss"])
        leaf = jax.tree.leaves(p_out)[0]
        check(len(leaf.sharding.device_set) == n_chips,
              "trained params are not on every chip")
        d = float(np.max(np.abs(np.asarray(hist["loss"])
                                - np.asarray(single["loss"]))
                         / np.abs(np.asarray(single["loss"]))))
        say(phase, f"ring comm={comm} vs single-device losses: max rel "
                   f"diff {d:.3e}"
                   + (f" (tolerance {LOSS_TOL})" if comm == "none" else
                      " (int8: finite only)"))
        if comm == "none":
            check(d <= LOSS_TOL, "ring fp32 losses differ")
        out[comm] = d
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the partitioned ring training "
                         "across four chips and its single-device "
                         "reference")
    args = ap.parse_args()

    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {platform!r}",
              file=sys.stderr)
        return 2

    import jax.numpy as jnp
    from repro.data import make_node_dataset
    from repro.kernels.common import should_interpret
    from repro.launch.compile_cache import setup_compile_cache

    say("setup", f"compile cache {setup_compile_cache()}")
    check(not should_interpret(), "Pallas kernels would run interpreted")
    # a pinned strategy that cannot run warns and falls back: an error here
    warnings.filterwarnings("error", message=r"strategy .* falling back")

    t0 = time.perf_counter()
    g, feats, labels, train_mask, _, nc = make_node_dataset(DATASET)
    data = (g, jnp.asarray(feats), jnp.asarray(labels),
            jnp.asarray(train_mask), nc)
    say("setup", f"{DATASET}: {g.n_src} nodes, {g.n_edges} edges, "
                 f"{feats.shape[1]} features, {nc} classes, built in "
                 f"{time.perf_counter() - t0:.1f} s")

    if args.chips == 4:
        phase_partitioned(data, 4)
    else:
        phase_sage(data)
        phase_gat(data)
        phase_serve()
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        sys.exit(1)
