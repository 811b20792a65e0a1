"""Reduction of a profiler trace to device metrics.

A traced run marks its measured window on the host with the
annotation ``bench.window`` and each phase of the loop with
``bench.<phase>``. From the trace this module takes:

- the window, from ``bench.window``;
- device busy time: the union of the intervals in which an operation
  ran on a device (the ``XLA Ops`` line of each ``/device:`` plane),
  clipped to the window, averaged over devices;
- the device operations that took most time, by name;
- the longest idle gaps on the device, each named by the innermost
  host annotation that covers most of it;
- each device op's ``tf_op`` scope path (the program's
  ``jax.named_scope`` names under its transforms, e.g.
  ``jit(step)/transpose(jvp(sage.layer1))/weighted_copy_reduce/gather``),
  which sits in the op's event *metadata* and which
  ``jax.profiler.ProfileData`` does not show: it is read from the raw
  ``.xplane.pb``, and the device time under a set of scopes is taken
  from it.

Everything here works on plain ``(name, start_ns, end_ns)`` tuples and
scope strings, so the tests check it on synthetic traces.
"""
from __future__ import annotations

import collections
import dataclasses
import fnmatch
import glob
import importlib.util
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

WINDOW = "bench.window"
Interval = Tuple[str, float, float]


@dataclasses.dataclass
class Trace:
    """Events of one traced window: device ops per device, host
    annotations of the harness (``bench.*``) and other host events of
    the thread that ran the window."""
    device_ops: Dict[str, List[Interval]]
    host: List[Interval]
    window: Tuple[float, float]
    # per device, the ``tf_op`` scope path of each op of ``device_ops``,
    # in the same order ("" for an op without one); None where the
    # trace's event metadata could not be read
    scopes: Optional[Dict[str, List[str]]] = None

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9


def union_ns(intervals: Sequence[Tuple[float, float]],
             lo: float, hi: float) -> float:
    """Length of the union of ``[start, end)`` intervals inside
    ``[lo, hi)``."""
    total = 0.0
    cur_s = cur_e = None
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def busy_s(tr: Trace) -> float:
    """Seconds in the window in which some operation ran, averaged over
    the devices in the trace."""
    if not tr.device_ops:
        return 0.0
    lo, hi = tr.window
    per = [union_ns([(s, e) for _, s, e in ops], lo, hi)
           for ops in tr.device_ops.values()]
    return sum(per) / len(per) * 1e-9


def op_seconds(tr: Trace) -> Dict[str, float]:
    """Device seconds per operation name inside the window, averaged
    over devices (an op's own duration, not the union)."""
    lo, hi = tr.window
    out: Dict[str, float] = collections.defaultdict(float)
    n = max(len(tr.device_ops), 1)
    for ops in tr.device_ops.values():
        for name, s, e in ops:
            d = min(e, hi) - max(s, lo)
            if d > 0:
                out[name] += d * 1e-9 / n
    return dict(out)


def top_ops(tr: Trace, k: int = 10) -> List[List]:
    """The ``k`` operation names that took most device time."""
    secs = op_seconds(tr)
    return [[n, secs[n]] for n in sorted(secs, key=secs.get,
                                         reverse=True)[:k]]


def idle_gaps(tr: Trace, k: int = 10) -> List[List]:
    """The ``k`` longest gaps in the first device's busy union inside
    the window, each named by the host annotation that overlaps it
    most (the innermost, when several overlap equally)."""
    if not tr.device_ops:
        return []
    lo, hi = tr.window
    ops = sorted((max(s, lo), min(e, hi))
                 for _, s, e in next(iter(tr.device_ops.values())))
    gaps = []
    cur = lo
    for s, e in ops:
        if e <= s:
            continue
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if hi > cur:
        gaps.append((cur, hi))
    gaps.sort(key=lambda g: g[1] - g[0], reverse=True)
    out = []
    for gs, ge in gaps[:k]:
        best, best_key = "none", (0.0, 0.0)
        for name, s, e in tr.host:
            if name == WINDOW:
                continue
            # most overlap wins; of equal overlaps the shorter, inner one
            key = (min(e, ge) - max(s, gs), -(e - s))
            if key[0] > 0 and key > best_key:
                best, best_key = name, key
        out.append([best, (ge - gs) * 1e-9])
    return out


_WRAPPED = re.compile(r"[A-Za-z_]\w*\((.*)\)")


def scope_parts(path: str) -> List[str]:
    """The components of a ``tf_op`` path with their transform wrappers
    taken off: ``jit(step)/transpose(jvp(gspmm.u_mul_e_add_v))/mul`` ->
    ``["step", "gspmm.u_mul_e_add_v", "mul"]``. Autodiff keeps a forward
    scope under ``jvp(...)`` and ``transpose(...)``, so a scope names
    its op in both directions."""
    out = []
    for part in path.split("/"):
        m = _WRAPPED.fullmatch(part)
        while m:
            part = m.group(1)
            m = _WRAPPED.fullmatch(part)
        out.append(part)
    return out


def scoped_ns(tr: Trace, patterns: Iterable[str]) -> Optional[float]:
    """Nanoseconds inside the window, averaged over devices, of the
    union of the device ops whose scope path has a component matching
    one of ``patterns`` (``fnmatch``: ``gspmm.*``); None when the trace
    has no scopes."""
    if tr.scopes is None or not tr.device_ops:
        return None
    patterns = tuple(patterns)
    hit: Dict[str, bool] = {}

    def claimed(path: str) -> bool:
        if path not in hit:
            hit[path] = any(fnmatch.fnmatchcase(c, p)
                            for c in scope_parts(path) for p in patterns)
        return hit[path]

    lo, hi = tr.window
    per = [union_ns([(s, e) for (_, s, e), path in zip(ops, tr.scopes[dev])
                     if claimed(path)], lo, hi)
           for dev, ops in tr.device_ops.items()]
    return sum(per) / len(per)


def short_name(hlo: str) -> str:
    """``%fusion.12 = f32[4,8]{1,0:T(8,128)} fusion(...)`` -> ``fusion.12
    f32[4,8]``: a device op's name and result shape, without its
    operands."""
    m = re.match(r"%?([\w.\-]+) = (\(?[a-z]+\d*\[[\d,]*\])", hlo)
    return f"{m.group(1)} {m.group(2)}" if m else hlo[:120]


def _is_device_plane(name: str) -> bool:
    return name.startswith("/device:") and "CPU" not in name


def _xplane_pb2():
    """TensorFlow's generated XSpace classes
    (``tensorflow/tsl/profiler/protobuf/xplane_pb2.py``), loaded from
    their file: they need only ``protobuf``, while importing the
    ``tensorflow`` package takes seconds and loads a second XLA runtime
    beside JAX's. None where TensorFlow is not installed."""
    spec = importlib.util.find_spec("tensorflow")
    if spec is None or not spec.submodule_search_locations:
        return None
    path = os.path.join(spec.submodule_search_locations[0], "tsl",
                        "profiler", "protobuf", "xplane_pb2.py")
    if not os.path.exists(path):
        return None
    mod_spec = importlib.util.spec_from_file_location(
        "chipbench_xplane_pb2", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def read_scopes(path: str, device_ops: Dict[str, List[Interval]]
                ) -> Optional[Dict[str, List[str]]]:
    """The ``tf_op`` stat of each device op's event metadata, in the
    order of ``device_ops`` (as ``load`` read them through
    ``ProfileData``). None where the classes are missing or where the
    raw planes do not hold the same ops in the same order."""
    pb2 = _xplane_pb2()
    if pb2 is None:
        return None
    space = pb2.XSpace()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    out: Dict[str, List[str]] = {}
    for plane in space.planes:
        if plane.name not in device_ops:
            continue
        stat_name = {k: m.name for k, m in plane.stat_metadata.items()}
        meta: Dict[int, Tuple[str, str]] = {}
        for k, md in plane.event_metadata.items():
            scope = ""
            for st in md.stats:
                if stat_name.get(st.metadata_id) == "tf_op":
                    scope = (stat_name.get(st.ref_value, "")
                             if st.WhichOneof("value") == "ref_value"
                             else st.str_value)
            meta[k] = (short_name(md.name), scope)
        names, scopes = [], []
        for ln in plane.lines:
            if ln.name == "XLA Ops":
                for ev in ln.events:
                    name, scope = meta.get(ev.metadata_id, ("", ""))
                    names.append(name)
                    scopes.append(scope)
        if names != [n for n, _, _ in device_ops[plane.name]]:
            return None
        out[plane.name] = scopes
    return out if set(out) == set(device_ops) else None


def load(trace_dir: str) -> Optional[Trace]:
    """Read the ``.xplane.pb`` under ``trace_dir``; None when there is
    none or it holds no ``bench.window`` annotation."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        return None
    pd = ProfileData.from_file(paths[0])
    device_ops: Dict[str, List[Interval]] = {}
    host: List[Interval] = []
    window = None
    for plane in pd.planes:
        if _is_device_plane(plane.name):
            lines = list(plane.lines)
            ops = [ln for ln in lines if ln.name == "XLA Ops"]
            evs = [(short_name(e.name), e.start_ns, e.end_ns)
                   for ln in ops for e in ln.events]
            if evs:
                device_ops[plane.name] = evs
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for e in ln.events:
                    if e.name == WINDOW:
                        window = (e.start_ns, e.end_ns)
                    if e.name.startswith("bench."):
                        host.append((e.name, e.start_ns, e.end_ns))
    if window is None:
        return None
    return Trace(device_ops=device_ops, host=host, window=window,
                 scopes=read_scopes(paths[0], device_ops))
