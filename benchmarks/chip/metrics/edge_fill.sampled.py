"""Share of the sampled blocks' edge slots that hold a real sampled
edge: 100 x the ``edges`` over the ``edge_slots`` counted in the args of
the program's ``sampler.sample`` spans that ended in the measured
window (all blocks of each minibatch)."""
import os


def read(run):
    from harness import load_module
    base = load_module(os.path.join(run.bench_dir, "metrics",
                                    "sampler_ms.sampled.py"),
                       "sampler_ms.sampled")
    spans = [e for e in base.window_spans(run, "sampler.sample")
             if "edge_slots" in e["args"]]
    slots = sum(e["args"]["edge_slots"] for e in spans)
    if not slots:
        return None
    return 100.0 * sum(e["args"]["edges"] for e in spans) / slots
