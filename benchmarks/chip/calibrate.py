"""Readings that set the limits of ``correct``, taken on the chip.

    python3 benchmarks/chip/calibrate.py --workload sage-arxiv.full \
        --seeds 101 102 103 --modes sound control half --seconds 1

For each mode and seed this runs the cell through the harness, with a
short window, and prints the numbers compared (one JSON line each, and
a summary line per mode with the largest and smallest reading):

- ``sound``: the program as the configuration states it;
- ``control``: the program with its float32 matmuls one step below
  the configuration's ``highest``: ``high`` (three bfloat16 passes).
  The limits have to fail it;
- ``half``: half of the training rows left out of the loss, the mean
  taken over the rest (a fault the limits have to catch);
- ``frozen``: a step that returns its state unchanged (it reads 1 on
  ``grad`` and ``update`` by their definition).

Which faults a cell can have is its kind's to say: ``FAULTS`` in
``kinds/<kind>.py`` names them, as modes of this file, and ``--modes
faults`` runs them. The benchmark's own runs never run these modes.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(1, os.path.join(os.path.dirname(os.path.dirname(HERE)),
                                "src"))


def _frozen(step):
    def wrapped(params, opt, i, *args):
        return params, opt, step(params, opt, i, *args)[-1]
    return wrapped


def _half(mask):
    import numpy as np
    return mask & (np.arange(mask.shape[0]) % 2 == 0)


MODES = {
    "sound": {},
    "control": {"matmul_precision": "high"},
    "half": {"train_mask": _half},
    "frozen": {"wrap_step": _frozen},
    # XLA:CPU computes float32 dots exactly at every precision setting,
    # so a CPU test stands the next lower precision in for ``high``: the
    # program's bfloat16 training path
    "control_cpu": {"precision": "bf16"},
}


def faults(workload, spec=None, bench_dir=None):
    """The faults a cell can have: ``FAULTS`` of its kind's file, loaded
    as the harness loads it; each has to be a mode here."""
    import harness
    bench_dir = bench_dir or harness.BENCH_DIR
    if spec is None:
        spec = harness.load_json(os.path.join(harness.ROOT,
                                              "BENCHMARK.json"))
    kind = harness.resolve(spec, workload, bench_dir).mix["kind"]
    names = tuple(harness.load_kind(kind, bench_dir).FAULTS)
    unknown = [f for f in names if f not in MODES]
    if unknown:
        raise ValueError(f"kinds/{kind}.py names faults {unknown} that "
                         f"are no mode of calibrate.py ({sorted(MODES)})")
    return names


def calibrate(workload, seeds, modes, seconds, *, require_tpu=True,
              spec=None, out=sys.stdout):
    """Returns ``{mode: {number: [readings...]}}``."""
    import harness
    found = {}
    for mode in modes:
        per = {}
        for seed in seeds:
            t0 = time.perf_counter()
            res = harness.run_cell(workload, seed, seconds, False,
                                   require_tpu=require_tpu,
                                   hooks=MODES[mode], spec=spec)
            line = {"mode": mode, "seed": seed,
                    "s": round(time.perf_counter() - t0, 1),
                    "metrics": {k: v["value"]
                                for k, v in res["metrics"].items()},
                    "checks": {k: v["value"]
                               for k, v in res["checks"].items()},
                    "shown": res.get("shown", {})}
            print(json.dumps(line), file=out, flush=True)
            for k, v in line["checks"].items():
                per.setdefault(k, []).append(v)
        found[mode] = per
        print(json.dumps({"mode": mode, "max": {k: max(v) for k, v in
                                                per.items()},
                          "min": {k: min(v) for k, v in per.items()}}),
              file=out, flush=True)
    return found


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--modes", nargs="+", default=["sound", "control"],
                    choices=sorted(MODES) + ["faults"],
                    help="modes to run; 'faults' stands for the faults "
                    "the cell's kind declares in FAULTS of "
                    "kinds/<kind>.py")
    ap.add_argument("--seconds", type=float, default=1.0)
    args = ap.parse_args(argv)
    modes = []
    for m in args.modes:
        modes += faults(args.workload) if m == "faults" else [m]
    calibrate(args.workload, args.seeds, modes, args.seconds)
    return 0


if __name__ == "__main__":
    sys.exit(main())
