"""Host milliseconds per step that the training loop spent blocked on
the program's prefetcher queue: the program's ``prefetch.wait`` spans
that ended in the measured window, over the steps in it (the inside
twin of ``sample_wait_ms.sampled``, the harness's clock around
``next``)."""
import os


def read(run):
    from harness import load_module
    base = load_module(os.path.join(run.bench_dir, "metrics",
                                    "sampler_ms.sampled.py"),
                       "sampler_ms.sampled")
    steps = run.layer.get("steps")
    spans = base.window_spans(run, "prefetch.wait")
    if not steps or not spans:
        return None
    return sum(e["end_ns"] - e["start_ns"] for e in spans) / 1e6 / steps
