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
  host annotation that covers most of it.

Everything here works on plain ``(name, start_ns, end_ns)`` tuples, so
the test checks it on a synthetic trace.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

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


def short_name(hlo: str) -> str:
    """``%fusion.12 = f32[4,8]{1,0:T(8,128)} fusion(...)`` -> ``fusion.12
    f32[4,8]``: a device op's name and result shape, without its
    operands."""
    m = re.match(r"%?([\w.\-]+) = (\(?[a-z]+\d*\[[\d,]*\])", hlo)
    return f"{m.group(1)} {m.group(2)}" if m else hlo[:120]


def _is_device_plane(name: str) -> bool:
    return name.startswith("/device:") and "CPU" not in name


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
    return Trace(device_ops=device_ops, host=host, window=window)
