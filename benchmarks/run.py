"""Benchmark harness — one section per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows. Sections:
  fig2  — 7 GNN apps full-graph, baseline push vs optimized pull (Fig. 2)
  fig3  — sampled GraphSAGE (Fig. 3)
  br    — BR/CR primitive configs (Table 2)
  prims — BatchNorm1d / Embedding (paper §4)
  spmm  — CR strategy sweep
  partitioned — multi-device ring training swept over shard counts
                (2/4/8 host-emulated shards, GCN/SAGE/GAT + delayed halo)
  hetero — relation-fused aggregation: BGS-like 50–100-relation RGCN
           shapes + GCMC rating-level sweep, fused vs per-relation
           loop, forward and backward
  sddmm — planned gSDDMM + fused GAT attention: the multipass pipeline
          (logits → softmax → aggregate) vs the single-pass
          fused_attention, forward and forward+backward
  serve — inference serving SLO: p50/p99 latency + throughput at N
          concurrent requesters, layer-wise vs fan-out re-expansion,
          per-app serve latency (steady state must log 0 recompiles)

Run all: ``PYTHONPATH=src python -m benchmarks.run``
One section: ``PYTHONPATH=src python -m benchmarks.run --only fig2``
Planner mode: ``--strategy auto`` times push vs the planner's choice and
reports which plan served each op (also recorded in the JSON output).

Every run writes ``BENCH_<section>.json`` (``--json`` overrides the
path) with the timed rows plus the planner's plan log, so the perf
trajectory is tracked across PRs.
"""
import argparse
import inspect
import json
import sys


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    choices=["fig2", "fig3", "br", "prims", "spmm",
                             "partitioned", "hetero", "sddmm", "serve"])
    ap.add_argument("--strategy", default=None,
                    choices=["auto", "push", "segment", "ell", "onehot",
                             "pallas"],
                    help="pin/override the optimized strategy under "
                         "test (sections still time 'push' as baseline)")
    ap.add_argument("--json", default=None,
                    help="output path for the JSON results "
                         "(default BENCH_<section>.json)")
    ap.add_argument("--trace", default=None, metavar="OUT.json",
                    help="also export the run's spans as Chrome-trace "
                         "JSON (open in Perfetto)")
    args = ap.parse_args()

    print("name,us_per_call,derived")
    sections = {
        "fig2": "benchmarks.fig2_full_graph",
        "fig3": "benchmarks.fig3_sampled_sage",
        "br": "benchmarks.br_primitives",
        "prims": "benchmarks.framework_prims",
        "spmm": "benchmarks.kernels_bench",
        "partitioned": "benchmarks.fig_partitioned",
        "hetero": "benchmarks.fig_hetero",
        "sddmm": "benchmarks.fig_sddmm",
        "serve": "benchmarks.fig_serve",
    }
    import importlib

    from repro.core import planner
    from repro import obs
    from repro.launch.compile_cache import setup_compile_cache
    from . import common

    setup_compile_cache()

    # the JSON contract includes the metrics snapshot (pad-ratio gauges,
    # comm counters) — force telemetry on so BENCH_*.json always carries
    # it even under REPRO_TELEMETRY=0 environments
    obs.set_enabled(True)

    for key, modname in sections.items():
        if args.only and key != args.only:
            continue
        print(f"# --- {key} ---", file=sys.stderr)
        mod = importlib.import_module(modname)
        kw = {}
        if (args.strategy is not None
                and "strategy" in inspect.signature(mod.main).parameters):
            kw["strategy"] = args.strategy
        mod.main(**kw)

    out_path = args.json or f"BENCH_{args.only or 'all'}.json"
    plans = {f"{op}|{requested}": chosen
             for (op, requested), chosen in planner.plan_log().items()}
    from repro import obs
    with open(out_path, "w") as f:
        json.dump({"section": args.only or "all",
                   "strategy": args.strategy,
                   "rows": common.RESULTS,
                   "plans": plans,
                   "metrics": obs.snapshot(),
                   "plan_events": obs.plan_events()},
                  f, indent=2, sort_keys=True)
    print(f"# wrote {out_path} ({len(common.RESULTS)} rows)",
          file=sys.stderr)
    if args.trace:
        obs.export_chrome_trace(args.trace)
        print(f"# wrote {args.trace} ({len(obs.trace_events())} span "
              f"events)", file=sys.stderr)


if __name__ == '__main__':
    main()
