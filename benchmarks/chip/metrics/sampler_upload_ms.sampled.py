"""Host milliseconds per minibatch of the program's ``sampler.upload``
spans: the host-to-device copies of each block's arrays and of the seeds' arrays, summed over the blocks of each ``sampler.sample``
call that ended in the measured window."""
import os


def read(run):
    from harness import load_module
    base = load_module(os.path.join(run.bench_dir, "metrics",
                                    "sampler_ms.sampled.py"),
                       "sampler_ms.sampled")
    return base.child_ms_per_sample(run, "sampler.upload")
