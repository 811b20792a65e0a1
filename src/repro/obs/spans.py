"""Span tracing on the profiler's clock, with device fencing and
Chrome-trace export.

``with span("compute") as sp: out = f(x); sp.fence(out)`` records a
wall-time interval. At span exit the fence value (if any) is passed to
``jax.block_until_ready`` *before* the stop timestamp is taken, so
asynchronously dispatched device work is attributed to the span that
launched it instead of leaking into whichever span happens to block
next.

Each span also enters a ``jax.profiler.TraceAnnotation`` of its name,
so under an active profiler session the program's spans sit in the
xplane beside the device ops (xprof, TensorBoard, Perfetto); with no
session active that is one cheap call. Each recorded event keeps its
raw ``time.perf_counter_ns()`` start and end (``start_ns``,
``end_ns``), so a reader can place it on the host clock, and the
offset between that clock and the profiler's, measured once against
one annotation, places it among the device ops.

Spans nest per thread and are thread-safe: each event's ``args`` carry
its ``depth``, its span ``id`` and the ``parent`` span's id (0 at the
top of a thread), and a child inherits its parent's correlation key
``batch``, so the spans that built one minibatch on a producer thread
and the span that waited for it on the consumer thread share an
identifier. :func:`export_chrome_trace` writes the buffer as
Chrome-trace JSON (``{"traceEvents": [...]}``, complete-event
``"ph": "X"`` records with microsecond timestamps from a process
epoch) loadable in Perfetto or chrome://tracing.
:func:`span_coverage` reports the fraction of a wall-clock window
covered by top-level spans.

JAX's own compile phases are recorded from inside:
:func:`install_compile_listener` (called once on import of
:mod:`repro.obs`) turns JAX's monitoring durations into spans
``jax.trace``, ``jax.lower``, ``jax.compile`` and ``jax.cache_get``,
each spanning [end - duration, end] on the thread that compiled, and
counts ``jax.compiles`` and ``jax.compile_s``.

Every span also feeds the metrics registry histogram
``span.<name>`` (seconds), so span statistics appear in metrics
snapshots without parsing the trace.
"""
import itertools
import json
import os
import threading
import time

import jax

from . import metrics as _metrics

__all__ = ["Span", "span", "export_chrome_trace", "trace_events",
           "clear_trace", "span_coverage", "install_compile_listener",
           "COMPILE_SPANS"]

# Process epoch for the exported relative timestamps (µs).
_T0_NS = time.perf_counter_ns()

_LOCK = threading.Lock()
_EVENTS = []
# Bounded buffer: long sessions must not grow memory without limit.
# Overflow drops new events and counts them (surfaced in snapshots).
_MAX_EVENTS = 500_000

# Span ids, unique in the process; 0 means "no parent".
_IDS = itertools.count(1)
# The key a child span inherits from its parent on the same thread.
_CORRELATION_KEY = "batch"

_tls = threading.local()


def _stack():
    st = getattr(_tls, "stack", None)
    if st is None:
        st = _tls.stack = []
    return st


def _record(name, cat, t0_ns, t1_ns, args):
    dur_ns = t1_ns - t0_ns
    ev = {
        "name": name,
        "cat": cat,
        "ph": "X",
        "ts": (t0_ns - _T0_NS) / 1e3,
        "dur": dur_ns / 1e3,
        "pid": os.getpid(),
        "tid": threading.get_ident(),
        "start_ns": t0_ns,
        "end_ns": t1_ns,
        "args": args,
    }
    with _LOCK:
        if len(_EVENTS) < _MAX_EVENTS:
            _EVENTS.append(ev)
        else:
            _metrics.counter("trace.dropped_events").inc()
    _metrics.histogram(f"span.{name}").observe(dur_ns / 1e9)


def _child_args(args, stack):
    """A new span's args: the caller's, plus its place in the thread's
    span tree, plus the parent's correlation key unless given."""
    out = dict(args or {})
    parent = stack[-1] if stack else None
    if parent is not None and _CORRELATION_KEY not in out \
            and _CORRELATION_KEY in parent.args:
        out[_CORRELATION_KEY] = parent.args[_CORRELATION_KEY]
    out["depth"] = len(stack)
    out["id"] = next(_IDS)
    out["parent"] = parent.args["id"] if parent is not None else 0
    return out


class Span:
    """One span. ``fence(x)`` registers a value to
    ``block_until_ready`` at exit; ``set(**kv)`` adds counts to the
    event's ``args``; exiting also accepts exceptions (the span is
    recorded either way)."""
    __slots__ = ("name", "cat", "args", "_t0_ns", "_fence", "_ann")

    def __init__(self, name, cat, args):
        self.name = name
        self.cat = cat
        self.args = args
        self._fence = None

    def fence(self, value):
        """Block on ``value`` (any pytree of jax arrays) before the
        span's stop timestamp is taken."""
        self._fence = value
        return value

    def set(self, **kv):
        """Add (or overwrite) entries of the recorded event's args."""
        self.args.update(kv)

    def __enter__(self):
        st = _stack()
        self.args = _child_args(self.args, st)
        st.append(self)
        self._ann = jax.profiler.TraceAnnotation(self.name)
        self._ann.__enter__()
        self._t0_ns = time.perf_counter_ns()
        return self

    def __exit__(self, exc_type, exc, tb):
        if self._fence is not None:
            jax.block_until_ready(self._fence)
        t1_ns = time.perf_counter_ns()
        self._ann.__exit__(None, None, None)
        st = _stack()
        if st and st[-1] is self:
            st.pop()
        elif self in st:            # exited out of order
            st.remove(self)
        _record(self.name, self.cat, self._t0_ns, t1_ns, self.args)
        return False


class _NullSpan:
    __slots__ = ()

    def fence(self, value):
        return value

    def set(self, **kv):
        pass

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        return False


_NULL_SPAN = _NullSpan()


def span(name, cat="repro", args=None):
    """A traced span, to be entered with ``with``. Returns a no-op span
    when telemetry is off, so instrumented code paths cost one
    predicate when disabled."""
    if not _metrics.enabled():
        return _NULL_SPAN
    return Span(name, cat, args)


# ------------------------------------------------------------------ #
# JAX's compile phases
# ------------------------------------------------------------------ #
# JAX monitoring duration event -> span name. JAX 0.9 times a
# persistent-cache load inside the backend compile, so a load is a
# ``jax.compile`` span with a ``jax.cache_get`` span inside it.
COMPILE_SPANS = {
    "/jax/core/compile/jaxpr_trace_duration": "jax.trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jax.lower",
    "/jax/core/compile/backend_compile_duration": "jax.compile",
    "/jax/compilation_cache/cache_retrieval_time_sec": "jax.cache_get",
}

_LISTENING = False


def _on_jax_duration(event, duration, **kwargs):
    name = COMPILE_SPANS.get(event)
    if name is None or not _metrics.enabled():
        return
    t1_ns = time.perf_counter_ns()
    t0_ns = t1_ns - int(duration * 1e9)
    args = _child_args(None, _stack())
    if "fun_name" in kwargs:
        args["fun"] = str(kwargs["fun_name"])
    _record(name, "jax", t0_ns, t1_ns, args)
    if name == "jax.compile":
        _metrics.counter("jax.compiles").inc()
        _metrics.counter("jax.compile_s").inc(duration)


def install_compile_listener():
    """Register the one JAX monitoring listener that records compile
    phases as spans (idempotent)."""
    global _LISTENING
    with _LOCK:
        if _LISTENING:
            return
        _LISTENING = True
    jax.monitoring.register_event_duration_secs_listener(_on_jax_duration)


def trace_events():
    """Copy of the recorded trace events (Chrome-trace dicts)."""
    with _LOCK:
        return list(_EVENTS)


def clear_trace():
    with _LOCK:
        _EVENTS.clear()


def export_chrome_trace(path):
    """Write the span buffer as Chrome-trace JSON; returns ``path``.

    Load in Perfetto (ui.perfetto.dev) or chrome://tracing.
    """
    with _LOCK:
        events = list(_EVENTS)
    doc = {"traceEvents": events, "displayTimeUnit": "ms"}
    with open(path, "w") as f:
        json.dump(doc, f)
    return path


def span_coverage(events=None, tid=None):
    """Fraction of the session window covered by top-level spans.

    The window is [earliest span start, latest span end] over the
    selected events; coverage is the union length of depth-0 spans in
    that window. ``tid`` restricts to one thread (e.g. the serve loop);
    by default all threads' top-level spans contribute to the union.
    Returns 0.0 when there are no events.
    """
    evs = trace_events() if events is None else events
    if tid is not None:
        evs = [e for e in evs if e["tid"] == tid]
    if not evs:
        return 0.0
    t_lo = min(e["ts"] for e in evs)
    t_hi = max(e["ts"] + e["dur"] for e in evs)
    if t_hi <= t_lo:
        return 0.0
    top = sorted((e["ts"], e["ts"] + e["dur"]) for e in evs
                 if e["args"].get("depth", 0) == 0)
    covered = 0.0
    cur_lo = cur_hi = None
    for lo, hi in top:
        if cur_hi is None or lo > cur_hi:
            if cur_hi is not None:
                covered += cur_hi - cur_lo
            cur_lo, cur_hi = lo, hi
        else:
            cur_hi = max(cur_hi, hi)
    if cur_hi is not None:
        covered += cur_hi - cur_lo
    return covered / (t_hi - t_lo)
