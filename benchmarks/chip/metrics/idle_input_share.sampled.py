"""Share of the first device's idle time in the traced window that the
training loop spent blocked on the program's prefetcher: the
``prefetch.wait`` spans, moved onto the profiler's clock by the offset
between the harness's two marks of the window, less the device's busy
union, over the device's idle time."""
import os


def read(run):
    from devtrace import union_ns
    from harness import load_module
    base = load_module(os.path.join(run.bench_dir, "metrics",
                                    "sampler_ms.sampled.py"),
                       "sampler_ms.sampled")
    td = run.trace_data
    off = base.trace_offset_ns(run)
    waits = base.window_spans(run, "prefetch.wait")
    if off is None or not td.device_ops or not waits:
        return None
    lo, hi = td.window
    ops = [(s, e) for _, s, e in next(iter(td.device_ops.values()))]
    busy = union_ns(ops, lo, hi)
    idle = (hi - lo) - busy
    if idle <= 0:
        return None
    waits = [(e["start_ns"] + off, e["end_ns"] + off) for e in waits]
    # idle inside the waits = union(waits, ops) - union(ops)
    return 100.0 * (union_ns(waits + ops, lo, hi) - busy) / idle
