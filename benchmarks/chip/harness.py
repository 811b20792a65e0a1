"""The chip benchmark's general harness.

``run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>`` runs
one cell of ``BENCHMARK.json`` on the chip it is started on and prints
one JSON result line. Everything that belongs to one configuration, one
traffic mix or one per-layer metric lives in a file of its own, found
by name:

- ``configs/<config>.json``: the model and graph sizes, with their
  source; its ``model.arch`` names ``models/<arch>.py``, which makes the
  weights, calls the program and holds the plain reference;
- ``mixes/<traffic>.json``: the traffic's parameters; its ``kind`` names
  the general runner ``kinds/<kind>.py``, which holds ``run(run)`` and
  ``FAULTS``, the names of the ``calibrate.py`` modes that plant each
  fault a cell of that kind can have;
- ``limits/<cell>.json``: the limit of each number that decides
  ``correct``, with the readings it was set from;
- ``metrics/<metric>.py``: a reader ``read(run) -> float | None`` of one
  per-layer metric.

A later cell, kind, mix or metric is added by adding such files and
entries, with no edit to this file. Its CPU rehearsals take
``fixtures/<config prefix>-tiny.json`` for the configuration and, where
it exists, ``fixtures/<traffic>-tiny.json`` for the mix
(``test_chipbench_cells.py``).
"""
from __future__ import annotations

import argparse
import dataclasses
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from typing import Any, Dict, List, Optional

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(BENCH_DIR))
CACHE_DIR = os.path.join(ROOT, ".jax_cache")


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def load_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    """Import a Python file of the benchmark by its path (names may hold
    dots, as metric names do)."""
    spec = importlib.util.spec_from_file_location(
        "chipbench_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    """One workload of the spec with everything its name leads to."""
    name: str
    chips: int
    config_name: str
    cfg: dict
    traffic: str
    mix: dict
    limits: dict
    end_to_end: List[dict]
    per_layer: List[dict]


def resolve(spec: dict, workload: str, bench_dir: str = BENCH_DIR) -> Cell:
    """Find a workload's configuration, mix, limits and metrics."""
    wl = {w["name"]: w for w in spec["workloads"]}.get(workload)
    if wl is None:
        raise SystemExit(f"unknown workload {workload!r}; the spec has "
                         f"{sorted(w['name'] for w in spec['workloads'])}")
    conf = {c["name"]: c for c in spec["configs"]}[wl["config"]]
    cfg = load_json(os.path.join(ROOT, conf["file"])
                    if not os.path.isabs(conf["file"]) else conf["file"])
    mix = load_json(os.path.join(bench_dir, "mixes", wl["traffic"] + ".json"))
    limits = load_json(os.path.join(bench_dir, "limits", workload + ".json"))

    def here(m):
        return workload in m["workloads"] if "workloads" in m else None

    e2e = [m for m in spec["end_to_end"] if here(m) is not False]
    names = {m["name"] for m in e2e}
    per = [m for m in spec["per_layer"]
           if here(m) or (here(m) is None and m["moves"] in names)]
    return Cell(name=workload, chips=int(wl["chips"]),
                config_name=wl["config"], cfg=cfg, traffic=wl["traffic"],
                mix=mix, limits=limits, end_to_end=e2e, per_layer=per)


def load_kind(kind: str, bench_dir: str = BENCH_DIR):
    """The runner of a kind of cell, ``kinds/<kind>.py``."""
    return load_module(os.path.join(bench_dir, "kinds", kind + ".py"), kind)


def peaks(device_kind: str, bench_dir: str = BENCH_DIR) -> dict:
    """Published peaks of one chip of this kind; a kind not in the table
    is an error, never a default."""
    table = load_json(os.path.join(bench_dir, "peaks.json"))["devices"]
    if device_kind not in table:
        raise SystemExit(f"device kind {device_kind!r} is not in "
                         f"peaks.json ({sorted(table)})")
    return table[device_kind]


def devices_for(chips: int, require_tpu: bool):
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"needs a TPU, JAX found {devs[0].platform!r}")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return devs[:chips]


def setup_compile_cache() -> str:
    """JAX's persistent compile cache at a fixed path in the checkout
    (or where ``JAX_COMPILATION_CACHE_DIR`` says), holding every program,
    however quick to compile, so that only a cell's first run compiles."""
    import jax
    where = os.environ.get("JAX_COMPILATION_CACHE_DIR") or CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", where)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return where


def seed_key(seed: int):
    """A JAX key from any non-negative whole number (wider than 32 bits
    too): the low 32 bits seed the key, the rest are folded in."""
    import jax
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0x7FFFFFFF)


class Run:
    """State of one run, handed to the cell's runner and to the
    per-layer readers.

    The runner sets ``e2e`` (end-to-end values by name), ``compared``
    (name -> value for each number held to a limit), ``shown`` (readings
    reported beside them, held to none), ``attempted``, ``failed`` and
    ``layer`` (per-layer inputs by name), and calls
    :meth:`start_window` and :meth:`end_window` around its measured
    loop."""

    def __init__(self, cell: Cell, seed: int, seconds: float, trace: bool,
                 devices, t_start: float, hooks: Optional[dict] = None):
        self.bench_dir = BENCH_DIR
        self.cell = cell
        self.cfg = cell.cfg
        self.mix = cell.mix
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.trace = bool(trace)
        self.devices = devices
        self.t_start = t_start
        self.hooks = dict(hooks or {})
        self.e2e: Dict[str, float] = {}
        self.compared: Dict[str, float] = {}
        self.shown: Dict[str, float] = {}
        self.attempted = 0
        self.failed = 0
        self.layer: Dict[str, Any] = {}
        self.setup_s: Optional[float] = None
        self.window_s: Optional[float] = None
        self.trace_data = None
        self.memory_peak_bytes = 0
        self._trace_dir: Optional[str] = None
        self._t_window = None

    # -- the measured window ------------------------------------------
    def start_window(self) -> None:
        """Set-up ends here: start the profiler if this run traces."""
        import jax
        self.setup_s = time.perf_counter() - self.t_start
        if self.trace:
            self._trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-")
            jax.profiler.start_trace(self._trace_dir)
            self._ann = jax.profiler.TraceAnnotation("bench.window")
            self._ann.__enter__()
        self._t_window = time.perf_counter()

    def window_open(self) -> bool:
        return time.perf_counter() - self._t_window < self.seconds

    def end_window(self) -> float:
        """Close the window; returns its length on the host clock."""
        import jax
        self.window_s = time.perf_counter() - self._t_window
        if self.trace:
            self._ann.__exit__(None, None, None)
            jax.profiler.stop_trace()
            from devtrace import load
            try:
                self.trace_data = load(self._trace_dir)
            finally:
                shutil.rmtree(self._trace_dir, ignore_errors=True)
        self.memory_peak_bytes = self._read_peak()
        return self.window_s

    def annotate(self, name: str):
        """A host annotation on the profiler's clock (a no-op context
        when this run does not trace)."""
        import contextlib
        if not self.trace:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    def _read_peak(self) -> int:
        peak = 0
        for d in self.devices:
            st = d.memory_stats() or {}
            peak = max(peak, int(st.get("peak_bytes_in_use", 0)))
        return peak

    @property
    def peaks(self) -> dict:
        return peaks(self.devices[0].device_kind)


def _read_layer_metrics(run: Run, bench_dir: str,
                        sources: Optional[set] = None) -> Dict[str, dict]:
    out = {}
    for m in run.cell.per_layer:
        if sources is not None and m["source"] not in sources:
            continue
        mod = load_module(os.path.join(bench_dir, "metrics",
                                       m["name"] + ".py"), m["name"])
        v = mod.read(run)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             t_start: Optional[float] = None, require_tpu: bool = True,
             hooks: Optional[dict] = None, spec: Optional[dict] = None,
             bench_dir: str = BENCH_DIR) -> dict:
    """Run one cell and return its result object (the line ``run.py``
    prints). ``require_tpu=False`` and ``hooks`` are for the tests: the
    first lets a run proceed on the CPU, where it reports no device
    metric; the second breaks the timed path underneath."""
    t_start = time.perf_counter() if t_start is None else t_start
    if spec is None:
        spec = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = resolve(spec, workload, bench_dir)
    import jax
    # the matmul precision the configuration states (the control runs
    # the program one step below it)
    prec = (hooks or {}).get("matmul_precision",
                             cell.cfg["model"]["matmul_precision"])
    jax.config.update("jax_default_matmul_precision",
                      None if prec == "default" else prec)
    devs = devices_for(cell.chips, require_tpu)
    on_chip = devs[0].platform == "tpu"
    if on_chip:
        setup_compile_cache()
    run = Run(cell, seed, seconds, trace, devs, t_start, hooks)
    run.bench_dir = bench_dir
    load_kind(cell.mix["kind"], bench_dir).run(run)

    checks = {}
    correct = True
    for name, value in run.compared.items():
        limit = float(cell.limits["limits"][name])
        ok = value == value and value <= limit      # NaN fails
        correct = correct and ok
        checks[name] = {"value": value, "limit": limit}
    missing = set(cell.limits["limits"]) - set(run.compared)
    if missing:
        raise RuntimeError(f"no reading for {sorted(missing)}")

    metrics: Dict[str, dict] = {}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": run.memory_peak_bytes}
    result: Dict[str, Any] = {"correct": bool(correct),
                              "attempted": int(run.attempted),
                              "failed": int(run.failed)}
    if not trace:
        run.e2e["setup_s"] = run.setup_s
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": float(run.e2e[m["name"]]),
                                  "unit": m["unit"]}
    elif on_chip:
        from devtrace import busy_s, idle_gaps, top_ops
        td = run.trace_data
        if td is None:
            raise RuntimeError("the traced run left no readable trace")
        device["busy_s"] = busy_s(td)
        device["window_s"] = td.window_s
        metrics = _read_layer_metrics(run, bench_dir)
        result["breakdown"] = {"device_ops": top_ops(td),
                               "idle_gaps": idle_gaps(td)}
    else:
        # off the chip only counts: no time, rate or share of the device
        metrics = _read_layer_metrics(run, bench_dir, {"program_counter"})
    result["metrics"] = metrics
    result["device"] = device
    if run.shown:
        # readings beside the compared numbers, held to no limit
        result["shown"] = run.shown
    result["checks"] = checks
    return result


def cli(argv: List[str], t_start: float) -> int:
    ap = argparse.ArgumentParser(description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        raise SystemExit("--seed must be a non-negative whole number")
    try:
        res = run_cell(args.workload, args.seed, args.seconds,
                       bool(args.trace), t_start=t_start)
    except NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 3
    for name, c in res["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(res), flush=True)
    return 0
