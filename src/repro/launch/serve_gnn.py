"""GNN inference serving entrypoint (DESIGN.md §10).

Stands up a :class:`~repro.core.GNNServer` over a synthetic dataset and
drives it with N concurrent requester threads through a
:class:`~repro.data.RequestQueue` — the full production shape: clients
submit node-id requests and block on futures, the serving loop drains
coalescing windows through the prefetcher, batches pad onto signature
classes, and steady state runs zero recompiles.

Usage:
  PYTHONPATH=src python -m repro.launch.serve_gnn --app gcn \
      --dataset tiny --clients 4 --requests 50
"""
from __future__ import annotations

import argparse
import threading
import time
from typing import Callable, Dict, List, Optional

import jax
import numpy as np

from ..core.serving import SERVE_APPS, GNNServer
from ..data import RequestQueue, make_node_dataset, relational_graph
from ..models.gnn import gat, gcn, rgcn, sage
from .compile_cache import setup_compile_cache
from ..obs import export_chrome_trace, percentile_nearest_rank, span_coverage


def build_server(app: str, dataset: str, *, mode: str = "auto",
                 classes=(8, 32, 128), d_hidden: int = 32,
                 fanout: Optional[int] = None, cache_rows: int = 4096,
                 pin_hot: int = 256, seed: int = 0) -> GNNServer:
    """Dataset + randomly-initialized model + server, ready to serve.

    (Serving correctness is parameter-agnostic — the differential tests
    pin served predictions to the full forward under the SAME params,
    so random init exercises exactly the production code path.)
    """
    key = jax.random.PRNGKey(seed)
    if app == "rgcn":
        n, n_rel = (256, 4) if dataset == "tiny" else (4096, 8)
        rels = relational_graph(n, n_rel, max(n // 2, 64), seed=seed)
        rng = np.random.default_rng(seed)
        feats = rng.standard_normal((n, 32)).astype(np.float32)
        params = rgcn.init(key, 32, d_hidden, 8, n_rel)
        return GNNServer("rgcn", params, None, feats, rels=rels, mode=mode,
                         classes=classes, fanout=fanout,
                         cache_rows=cache_rows, pin_hot=pin_hot, seed=seed)
    g, feats, _labels, _tr, _va, n_classes = make_node_dataset(dataset)
    init = {"gcn": gcn.init, "sage": sage.init, "gat": gat.init}[app]
    params = init(key, feats.shape[1], d_hidden, n_classes)
    return GNNServer(app, params, g, feats, mode=mode, classes=classes,
                     fanout=fanout, cache_rows=cache_rows, pin_hot=pin_hot,
                     seed=seed)


def run_session(srv: GNNServer, *, n_clients: int, requests_per_client: int,
                ids_fn: Callable[[np.random.Generator], np.ndarray],
                max_wait: float = 0.002, depth: int = 2,
                timeout: float = 600.0) -> Dict:
    """Drive the server with ``n_clients`` concurrent requester threads.

    Each client submits ``requests_per_client`` node-id requests
    (drawn by ``ids_fn``) and blocks on each future before the next —
    closed-loop load. Returns per-request wall latencies (submit →
    fulfilled, so queueing + batching + compute), the recompile delta
    over the steady-state window, and server stats.
    """
    srv.warmup()                       # compiles happen HERE, not under load
    compiles_before = srv.compiles
    rq = RequestQueue(max_wait=max_wait)
    lat: List[List[float]] = [[] for _ in range(n_clients)]
    errs: List[BaseException] = []

    def client(cid: int) -> None:
        rng = np.random.default_rng(1000 + cid)
        try:
            for _ in range(requests_per_client):
                req = rq.submit(ids_fn(rng))
                req.result(timeout=timeout)
                lat[cid].append(time.perf_counter() - req.t_submit)
        except BaseException as e:      # noqa: BLE001
            errs.append(e)

    threads = [threading.Thread(target=client, args=(c,), daemon=True)
               for c in range(n_clients)]

    def close_when_done() -> None:
        for t in threads:
            t.join()
        rq.close()

    for t in threads:
        t.start()
    threading.Thread(target=close_when_done, daemon=True).start()
    t0 = time.perf_counter()
    srv.run(rq, depth=depth)           # serving loop, main thread
    elapsed = time.perf_counter() - t0
    if errs:
        raise errs[0]

    flat = sorted(x for per in lat for x in per)
    n = len(flat)
    # nearest-rank percentiles over the FULL latency vector (the old
    # floor-index arithmetic under-read both tails: p99 of 100 samples
    # returned the 99th-smallest instead of the 100th)
    return {
        "latencies": flat,
        "n_samples": n,
        "p50_ms": 1e3 * percentile_nearest_rank(flat, 50) if n else
                  float("nan"),
        "p99_ms": 1e3 * percentile_nearest_rank(flat, 99) if n else
                  float("nan"),
        "throughput_rps": n / max(elapsed, 1e-9),
        "elapsed_s": elapsed,
        "recompiles_steady": srv.compiles - compiles_before,
        "stats": srv.stats(),
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--app", choices=SERVE_APPS, default="gcn")
    ap.add_argument("--dataset", default="tiny")
    ap.add_argument("--mode", default="auto",
                    choices=("auto", "layerwise", "fanout"))
    ap.add_argument("--clients", type=int, default=4)
    ap.add_argument("--requests", type=int, default=50,
                    help="requests per client")
    ap.add_argument("--request-ids", type=int, default=4,
                    help="node ids per request")
    ap.add_argument("--classes", type=int, nargs="+", default=[8, 32, 128])
    ap.add_argument("--fanout", type=int, default=None,
                    help="override full-neighbor fanout (inexact if < "
                         "max in-degree)")
    ap.add_argument("--cache-rows", type=int, default=4096)
    ap.add_argument("--pin-hot", type=int, default=256)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--trace", default=None, metavar="OUT.json",
                    help="export the session as Chrome-trace JSON "
                         "(open in Perfetto / chrome://tracing)")
    args = ap.parse_args()
    setup_compile_cache()

    srv = build_server(args.app, args.dataset, mode=args.mode,
                       classes=tuple(args.classes), fanout=args.fanout,
                       cache_rows=args.cache_rows, pin_hot=args.pin_hot,
                       seed=args.seed)
    n_nodes = srv.g.n_src

    def ids_fn(rng: np.random.Generator) -> np.ndarray:
        return rng.integers(0, n_nodes, args.request_ids)

    res = run_session(srv, n_clients=args.clients,
                      requests_per_client=args.requests, ids_fn=ids_fn)
    modes = {c: srv.mode_for_class(c) for c in srv.batcher.classes}
    print(f"[serve_gnn] app={args.app} dataset={args.dataset} "
          f"clients={args.clients} req/client={args.requests} "
          f"ids/req={args.request_ids}")
    print(f"[serve_gnn] class→mode {modes}")
    print(f"[serve_gnn] p50 {res['p50_ms']:.2f} ms  p99 {res['p99_ms']:.2f} "
          f"ms  {res['throughput_rps']:.0f} req/s "
          f"(n={res['n_samples']})")
    print(f"[serve_gnn] steady-state recompiles: "
          f"{res['recompiles_steady']} (must be 0)")
    st = res["stats"]
    for tier in ("out_cache", "feat_cache"):
        cs = st[tier]
        if cs is not None:
            print(f"[serve_gnn] {tier}: hit_ratio {cs.hit_ratio:.3f} "
                  f"({cs.hits}h/{cs.misses}m, {cs.evictions} evictions, "
                  f"{cs.pinned} pinned)")
    if args.trace:
        from ..obs import trace_events
        export_chrome_trace(args.trace)
        print(f"[serve_gnn] trace: {len(trace_events())} events → "
              f"{args.trace} (span coverage {span_coverage():.1%})")
    if res["recompiles_steady"]:
        raise SystemExit("steady-state recompiles detected")


if __name__ == "__main__":
    main()
