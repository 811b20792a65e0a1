"""The graph aggregation ops' share of their roofline in full-graph
training: the bytes they must move in one step (counted from shapes by
the model's ``aggregate_bytes_per_epoch``: a lower bound, so the share
stays under 100 % unless the time misses ops) over the chip's HBM
bandwidth, as a share of ``aggregate_ms.full``."""
import os


def read(run):
    from harness import load_module
    nbytes = run.layer.get("aggregate_bytes_per_step")
    if not nbytes:
        return None
    ms = load_module(os.path.join(run.bench_dir, "metrics",
                                  "aggregate_ms.full.py"),
                     "aggregate_ms.full").read(run)
    if not ms:
        return None
    peak = run.peaks["hbm_bytes_per_s"] * len(run.devices)
    return 100.0 * (nbytes / peak) / (ms * 1e-3)
