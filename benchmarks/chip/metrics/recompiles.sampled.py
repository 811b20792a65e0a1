"""Programs JAX built inside the measured window of the sampled cell,
read as ``recompiles.full`` reads them (see there). 0 is the expected
reading."""
import os


def read(run):
    from harness import load_module
    return load_module(os.path.join(run.bench_dir, "metrics",
                                    "recompiles.full.py"),
                       "recompiles.full").read(run)
