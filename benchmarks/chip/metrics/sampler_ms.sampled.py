"""Host milliseconds of one ``NeighborSampler.sample`` call: the mean
duration of the program's ``sampler.sample`` spans that ended in the
measured window.

The program's spans are read from its own span buffer
(``repro.obs.trace_events()``), on the host clock of
``time.perf_counter_ns``, the clock the harness's window is taken on:
the window is ``[run._t_window, run._t_window + run.window_s]``. The
helpers here are shared by the other readers of the program's spans,
which load this file. A program without these spans (or with its
telemetry off) gives no reading.
"""


def window_spans(run, name):
    """The program's ``name`` spans that ended in the window (an empty
    list when the program records none)."""
    try:
        from repro.obs import enabled, trace_events
    except ImportError:
        return []
    if not enabled() or run._t_window is None or not run.window_s:
        return []
    lo = run._t_window * 1e9
    hi = lo + run.window_s * 1e9
    return [e for e in trace_events()
            if e["name"] == name and "end_ns" in e
            and lo <= e["end_ns"] <= hi]


def trace_offset_ns(run):
    """Nanoseconds to add to a span's host-clock time to place it on
    the profiler's clock, from the window both clocks marked; None when
    the two windows' lengths differ by more than 1 ms."""
    td = run.trace_data
    if td is None or run._t_window is None or not run.window_s:
        return None
    if abs((td.window[1] - td.window[0]) - run.window_s * 1e9) > 1e6:
        return None
    return td.window[0] - run._t_window * 1e9


def child_ms_per_sample(run, child):
    """Milliseconds of ``child`` spans per ``sampler.sample`` span that
    ended in the window (the children summed over the blocks)."""
    parents = {e["args"]["id"] for e in window_spans(run, "sampler.sample")}
    if not parents:
        return None
    try:
        from repro.obs import trace_events
    except ImportError:
        return None
    ns = sum(e["end_ns"] - e["start_ns"] for e in trace_events()
             if e["name"] == child and "end_ns" in e
             and e["args"].get("parent") in parents)
    return ns / 1e6 / len(parents)


def read(run):
    spans = window_spans(run, "sampler.sample")
    if not spans:
        return None
    return sum(e["end_ns"] - e["start_ns"] for e in spans) / 1e6 / len(spans)
