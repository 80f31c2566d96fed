"""From a JAX profiler trace (``*.xplane.pb``) to device busy time, idle
share, device time per program, and idle gaps named by host spans.

The traced window is the host span ``bench.window`` that the harness puts
around it.  Device planes are the ``/device:TPU:<k>`` planes; an operation
is an event on a plane's ``XLA Ops`` line and a program one on its
``XLA Modules`` line.  Busy time is the union of the operations'
intervals inside the window, averaged over the chips that ran any.  Each
idle gap of the first chip is named by the innermost benchmark span that
covers its midpoint on the host (``other`` where none does).
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
from typing import Dict, Iterable, List, Tuple

WINDOW_SPAN = "bench.window"
OPS_LINE = "XLA Ops"
PROGRAMS_LINE = "XLA Modules"
DEVICE_PREFIX = "/device:TPU:"

Interval = Tuple[float, float]


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    busy_s: float                  # averaged over the chips that ran ops
    chips: int
    programs: Dict[str, float]     # program name -> device seconds, summed
    ops: Dict[str, float]          # program/operation -> device seconds
    idle_by_span: Dict[str, float]  # host span -> idle seconds of chip 0

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no *.xplane.pb under {trace_dir}")
    return paths[-1]


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, disjoint intervals covering the same points."""
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def clip(intervals: Iterable[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def gaps(busy: List[Interval], lo: float, hi: float) -> List[Interval]:
    """The complement of disjoint sorted ``busy`` inside [lo, hi]."""
    out, t = [], lo
    for a, b in busy:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if t < hi:
        out.append((t, hi))
    return out


def op_name(hlo: str) -> str:
    """``%fusion.14 = s32[500000]{..} fusion(...), kind=kCustom`` ->
    ``%fusion.14 fusion``: an operation event's name is its HLO text."""
    name, sep, rest = hlo.partition(" = ")
    if not sep:
        return hlo
    if rest.startswith("("):           # a tuple shape: skip to its close
        depth = 0
        for k, ch in enumerate(rest):
            depth += {"(": 1, ")": -1}.get(ch, 0)
            if depth == 0:
                rest = rest[k + 1:]
                break
    else:
        rest = rest.partition(" ")[2]
    kind = rest.strip().partition("(")[0]
    return f"{name} {kind}" if kind else name


def _events(plane, line_name=None):
    for line in plane.lines:
        if line_name is None or line.name == line_name:
            for ev in line.events:
                yield ev


def reduce_profile(profile, span_names: Iterable[str]) -> TraceSummary:
    """Reduce a loaded ``jax.profiler.ProfileData``."""
    span_names = set(span_names)
    window = None
    spans: List[Tuple[float, float, str]] = []
    devices = []
    for plane in profile.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            devices.append(plane)
        elif plane.name.startswith("/host:"):
            for ev in _events(plane):
                if ev.name == WINDOW_SPAN and window is None:
                    window = (ev.start_ns, ev.end_ns)
                elif ev.name in span_names:
                    spans.append((ev.start_ns, ev.end_ns, ev.name))
    if window is None:
        raise ValueError(f"the trace has no host span {WINDOW_SPAN!r}")
    w0, w1 = window
    programs: Dict[str, float] = {}
    ops: Dict[str, float] = {}
    busy_per_chip = []
    first_busy: List[Interval] = []
    for plane in sorted(devices, key=lambda p: p.name):
        runs = sorted((ev.start_ns, ev.end_ns, ev.name)
                      for ev in _events(plane, PROGRAMS_LINE)
                      if ev.end_ns > w0 and ev.start_ns < w1)
        for _, _, name in runs:
            programs[name] = programs.get(name, 0.0)
        for a, b, name in runs:
            programs[name] += (b - a) * 1e-9
        run_starts = [r[0] for r in runs]
        intervals = []
        for ev in _events(plane, OPS_LINE):
            if ev.end_ns > w0 and ev.start_ns < w1:
                intervals.append((ev.start_ns, ev.end_ns))
                # an operation's name is only unique within its program
                k = bisect.bisect_right(run_starts, ev.start_ns) - 1
                prog = runs[k][2].partition("(")[0] if k >= 0 and \
                    runs[k][1] >= ev.start_ns else "?"
                op = f"{prog}/{op_name(ev.name)}"
                ops[op] = ops.get(op, 0.0) + ev.duration_ns * 1e-9
        if not intervals:
            continue
        busy = union(clip(intervals, w0, w1))
        if not busy_per_chip:
            first_busy = busy
        busy_per_chip.append(sum(b - a for a, b in busy) * 1e-9)
    idle: Dict[str, float] = {}
    spans.sort(key=lambda s: (s[0], -s[1]))
    starts = [s[0] for s in spans]
    for a, b in gaps(first_busy, w0, w1):
        mid = 0.5 * (a + b)
        # the innermost covering span is the latest-starting one that
        # covers; benchmark spans barely nest, so a short walk back finds it
        name = "other"
        k = bisect.bisect_right(starts, mid) - 1
        for s0, s1, sname in reversed(spans[max(k - 7, 0):k + 1]):
            if s1 >= mid:
                name = sname
                break
        idle[name] = idle.get(name, 0.0) + (b - a) * 1e-9
    chips = len(busy_per_chip)
    return TraceSummary(
        window_s=(w1 - w0) * 1e-9,
        busy_s=sum(busy_per_chip) / chips if chips else 0.0,
        chips=chips, programs=programs, ops=ops, idle_by_span=idle)


def reduce_dir(trace_dir: str, span_names: Iterable[str]) -> TraceSummary:
    from jax.profiler import ProfileData

    return reduce_profile(ProfileData.from_file(find_xplane(trace_dir)),
                          span_names)


def top(table: Dict[str, float], k: int = 10) -> List[list]:
    """The ``k`` largest entries as ``[name, seconds]``."""
    return [[name, sec] for name, sec in
            sorted(table.items(), key=lambda kv: -kv[1])[:k]]
