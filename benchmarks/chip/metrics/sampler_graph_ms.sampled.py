"""Host milliseconds per minibatch of the program's ``sampler.graph``
spans: each block's host arrays: the neighbour table, the padded edge list, its reverse table and the block's `from_coo` (whose own host-to-device copies fall here), summed over the blocks of each ``sampler.sample``
call that ended in the measured window."""
import os


def read(run):
    from harness import load_module
    base = load_module(os.path.join(run.bench_dir, "metrics",
                                    "sampler_ms.sampled.py"),
                       "sampler_ms.sampled")
    return base.child_ms_per_sample(run, "sampler.graph")
