"""Device time by program stage, and device idle by the innermost host span.

The program names its device stages with ``jax.named_scope`` (``ddm.sort``,
``ddm.count``, ``ddm.ranks``, ``ddm.search``, ``ddm.gather``) and its host
phases with profiler spans (``ddm.probe`` ⊃ ``ddm.probe.readback``,
``ddm.emit`` ⊃ ``ddm.emit.readback``).  A device operation's event names
its HLO instruction; the trace keeps each program's optimized HLO (the
``Hlo Proto`` stat of the ``/host:metadata`` plane, keyed by the
``<id>`` of the program's ``jit_…(<id>)`` events on the ``XLA Modules``
line), and there each instruction's ``op_name``.
``jax.profiler.ProfileData`` exposes neither, so they are read here from
the ``.xplane.pb`` itself, in protobuf's wire format.

An instruction's stage is the first ``ddm.*`` component of its
``op_name``.  Where its ``op_name`` names none, because jax lowered the
code into a function of its own (``jnp.cumsum`` does: its operations are
named ``reduce_window_sum``, without the caller's scope) or the compiler
made the instruction (copies), the stage is that of its nearest user
that names one, else of its nearest operand, else of the instruction that
calls its computation; ``unscoped`` where none does.  A stage's time is
the union of its operations' intervals inside the window, so a ``while``
and the fusions of its body count once.  Each idle gap of the first chip
is cut at the host spans' edges and each piece named by the innermost
span over it: the benchmark's spans and every ``ddm.*`` span (``other``
where none is).
"""
from __future__ import annotations

import bisect
import dataclasses
from typing import Dict, Iterable, Iterator, List, Tuple

from bench.trace import reduce as trace_reduce

PREFIX = "ddm."
UNSCOPED = "unscoped"

Interval = Tuple[float, float]


@dataclasses.dataclass
class StageSummary:
    window_s: float
    busy_s: float                    # averaged over the chips that ran ops
    chips: int
    stages: Dict[str, float]         # stage -> device seconds (union)
    scoped_s: float                  # union of every scoped operation
    ops: Dict[str, float]            # program/stage/operation -> seconds
    idle_by_span: Dict[str, float]   # innermost host span -> idle seconds


# -- the protobuf wire format, as far as XSpace needs it ------------------

def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf: bytes, lo: int = 0, hi: int = -1
            ) -> Iterator[Tuple[int, object]]:
    """(field number, value) of each field of the message in
    ``buf[lo:hi]``; a length-delimited value is its ``(start, end)``."""
    i, hi = lo, len(buf) if hi < 0 else hi
    while i < hi:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 1:
            value, i = buf[i:i + 8], i + 8
        elif wire == 2:
            n, i = _varint(buf, i)
            value, i = (i, i + n), i + n
        elif wire == 5:
            value, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"wire type {wire} at byte {i}")
        yield key >> 3, value


def _text(buf: bytes, span) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace")


_U64 = (1 << 64) - 1


def _entry(buf: bytes, span) -> Tuple[int, object]:
    """(key, value) of a map entry (key 1, value 2)."""
    key, value = 0, None
    for num, v in _fields(buf, *span):
        if num == 1:
            key = v
        elif num == 2:
            value = v
    return key, value


def _ints(buf: bytes, value) -> List[int]:
    """A repeated integer field's value: packed, or one varint."""
    if not isinstance(value, tuple):
        return [value]
    out, i = [], value[0]
    while i < value[1]:
        v, i = _varint(buf, i)
        out.append(v)
    return out


def _plane_metadata(data: bytes, plane):
    """(name, event metadata entries, stat id -> stat name) of an XPlane
    (name 2, event_metadata 4, stat_metadata 5; XStatMetadata name 2)."""
    name, metas, stat_names = "", [], {}
    for num, value in _fields(data, *plane):
        if num == 2:
            name = _text(data, value)
        elif num == 4:
            metas.append(value)
        elif num == 5:
            sid, smeta = _entry(data, value)
            stat_names[sid] = next((_text(data, v) for n, v in
                                    _fields(data, *smeta) if n == 2), "")
    return name, metas, stat_names


def hlo_stages(data: bytes) -> Dict[Tuple[int, str], str]:
    """``(program id, instruction name) -> stage`` of every program whose
    HLO the trace keeps.  XEventMetadata: stats 5; XStat: metadata_id 1,
    bytes 6."""
    out: Dict[Tuple[int, str], str] = {}
    for num, plane in _fields(data):
        if num != 1:
            continue
        name, metas, stat_names = _plane_metadata(data, plane)
        if name != "/host:metadata":
            continue
        for entry in metas:
            pid, meta = _entry(data, entry)
            for mnum, stat in _fields(data, *meta):
                if mnum != 5:
                    continue
                sid, blob = 0, None
                for xnum, xval in _fields(data, *stat):
                    if xnum == 1:
                        sid = xval
                    elif xnum == 6:
                        blob = xval
                if blob is not None and stat_names.get(sid) == "Hlo Proto":
                    for iname, stage in _module_stages(data, blob).items():
                        out[(pid & _U64, iname)] = stage
    return out


def _module_stages(data: bytes, blob) -> Dict[str, str]:
    """Instruction name -> stage of one HloProto (hlo_module 1;
    HloModuleProto computations 3; HloComputationProto instructions 2,
    id 5, is_fusion_computation 7; HloInstructionProto name 1, metadata 7
    (OpMetadata op_name 2), id 35, operand_ids 36, called_computation_ids
    38).  Fusion computations are skipped: their instructions are no
    events of their own."""
    names: Dict[int, str] = {}
    own: Dict[int, str] = {}
    operands: Dict[int, List[int]] = {}
    called: Dict[int, List[int]] = {}
    members: Dict[int, List[int]] = {}
    for num, module in _fields(data, *blob):
        if num != 1:
            continue
        for mnum, comp in _fields(data, *module):
            if mnum != 3:
                continue
            cid, fusion, insts = 0, False, []
            for cnum, cval in _fields(data, *comp):
                if cnum == 2:
                    insts.append(cval)
                elif cnum == 5:
                    cid = cval
                elif cnum == 7:
                    fusion = bool(cval)
            if fusion:
                continue
            members[cid] = []
            for inst in insts:
                iid, iname, op, ops, calls = 0, "", "", [], []
                for inum, ival in _fields(data, *inst):
                    if inum == 1:
                        iname = _text(data, ival)
                    elif inum == 7:
                        op = next((_text(data, v) for n, v in
                                   _fields(data, *ival) if n == 2), "")
                    elif inum == 35:
                        iid = ival
                    elif inum == 36:
                        ops += _ints(data, ival)
                    elif inum == 38:
                        calls += _ints(data, ival)
                names[iid], operands[iid], called[iid] = iname, ops, calls
                members[cid].append(iid)
                if scope_of(op) != UNSCOPED:
                    own[iid] = scope_of(op)
    users: Dict[int, List[int]] = {}
    for iid, ops in operands.items():
        for o in ops:
            users.setdefault(o, []).append(iid)

    def nearest(start: int, edges: Dict[int, List[int]]):
        seen, frontier = {start}, [start]
        while frontier:
            nxt = []
            for i in frontier:
                for j in edges.get(i, ()):
                    if j in own:
                        return own[j]
                    if j not in seen:
                        seen.add(j)
                        nxt.append(j)
            frontier = nxt
        return None

    stage = dict(own)
    for iid in names:
        if iid not in stage:
            found = nearest(iid, users) or nearest(iid, operands)
            if found:
                stage[iid] = found
    # what is left inherits from the instruction that calls its computation
    caller = {cid: iid for iid, cids in called.items() for cid in cids}
    for cid, ids in members.items():
        up = stage.get(caller.get(cid, -1))
        for iid in ids:
            if iid not in stage and up:
                stage[iid] = up
    return {names[i]: stage.get(i, UNSCOPED) for i in names}


def scope_of(op_name: str) -> str:
    """The first ``ddm.*`` component of an ``op_name``, else ``unscoped``."""
    for part in op_name.split("/"):
        if part.startswith(PREFIX):
            return part.partition(":")[0]
    return UNSCOPED


def program_id(run_name: str) -> int:
    """``jit__sbm_enumerate_jit(123)`` -> 123 (-1 where there is none)."""
    inner = run_name.rpartition("(")[2].rstrip(")")
    try:
        return int(inner) & _U64
    except ValueError:
        return -1


# -- host spans: the innermost one over each stretch of the window --------

def innermost(spans: List[Tuple[float, float, str]], lo: float, hi: float
              ) -> List[Tuple[float, float, str]]:
    """``[lo, hi]`` cut at the spans' edges, each piece named by the
    latest-starting span that covers it (``other`` where none does)."""
    edges = []
    for k, (a, b, _) in enumerate(spans):
        edges.append((a, 1, -b, k))       # a parent opens before its child
        edges.append((b, 0, 0, k))
    edges.sort()
    out, active, t = [], [], lo
    for when, opens, _, k in edges:
        when = min(max(when, lo), hi)
        if when > t:
            out.append((t, when, spans[active[-1]][2] if active else "other"))
            t = when
        if opens:
            active.append(k)
        else:
            active.remove(k)
    if t < hi:
        out.append((t, hi, "other"))
    return out


def idle_by_span(busy: List[Interval], pieces: List[Tuple[float, float, str]],
                 lo: float, hi: float) -> Dict[str, float]:
    """Seconds of each gap of ``busy`` in ``[lo, hi]`` under each piece."""
    out: Dict[str, float] = {}
    starts = [p[0] for p in pieces]
    for a, b in trace_reduce.gaps(busy, lo, hi):
        k = max(bisect.bisect_right(starts, a) - 1, 0)
        while k < len(pieces) and pieces[k][0] < b:
            p0, p1, name = pieces[k]
            cut = min(b, p1) - max(a, p0)
            if cut > 0:
                out[name] = out.get(name, 0.0) + cut * 1e-9
            k += 1
    return out


# -- the reduction ---------------------------------------------------------

def reduce_stages(profile, stages_of: Dict[Tuple[int, str], str],
                  span_names: Iterable[str]) -> StageSummary:
    """Reduce a loaded ``ProfileData`` with the :func:`hlo_stages` of its
    file."""
    span_names = set(span_names)
    window = None
    spans: List[Tuple[float, float, str]] = []
    devices = []
    for plane in profile.planes:
        if plane.name.startswith(trace_reduce.DEVICE_PREFIX):
            devices.append(plane)
        elif plane.name.startswith("/host:"):
            for ev in trace_reduce._events(plane):
                if ev.name == trace_reduce.WINDOW_SPAN and window is None:
                    window = (ev.start_ns, ev.end_ns)
                elif ev.name in span_names or ev.name.startswith(PREFIX):
                    spans.append((ev.start_ns, ev.end_ns, ev.name))
    if window is None:
        raise ValueError(f"the trace has no host span "
                         f"{trace_reduce.WINDOW_SPAN!r}")
    w0, w1 = window
    stages: Dict[str, float] = {}
    ops: Dict[str, float] = {}
    busy_per_chip, scoped_per_chip = [], []
    first_busy: List[Interval] = []
    for plane in sorted(devices, key=lambda p: p.name):
        runs = sorted((ev.start_ns, ev.end_ns, ev.name) for ev in
                      trace_reduce._events(plane, trace_reduce.PROGRAMS_LINE)
                      if ev.end_ns > w0 and ev.start_ns < w1)
        run_starts = [r[0] for r in runs]
        by_stage: Dict[str, List[Interval]] = {}
        for ev in trace_reduce._events(plane, trace_reduce.OPS_LINE):
            if ev.end_ns <= w0 or ev.start_ns >= w1:
                continue
            k = bisect.bisect_right(run_starts, ev.start_ns) - 1
            run = runs[k] if k >= 0 and runs[k][1] >= ev.start_ns else None
            prog = run[2].partition("(")[0] if run else "?"
            pid = program_id(run[2]) if run else -1
            inst = ev.name.partition(" = ")[0].lstrip("%")
            stage = stages_of.get((pid, inst), UNSCOPED)
            by_stage.setdefault(stage, []).append((ev.start_ns, ev.end_ns))
            op = f"{prog}/{stage}/{trace_reduce.op_name(ev.name)}"
            ops[op] = ops.get(op, 0.0) + ev.duration_ns * 1e-9
        if not by_stage:
            continue
        every = [iv for ivs in by_stage.values() for iv in ivs]
        busy = trace_reduce.union(trace_reduce.clip(every, w0, w1))
        if not busy_per_chip:
            first_busy = busy
        busy_per_chip.append(sum(b - a for a, b in busy) * 1e-9)
        scoped = [iv for s, ivs in by_stage.items() if s != UNSCOPED
                  for iv in ivs]
        scoped_per_chip.append(_length(scoped, w0, w1))
        for stage, ivs in by_stage.items():
            stages[stage] = stages.get(stage, 0.0) + _length(ivs, w0, w1)
    chips = len(busy_per_chip)
    pieces = innermost(spans, w0, w1)
    return StageSummary(
        window_s=(w1 - w0) * 1e-9,
        busy_s=sum(busy_per_chip) / chips if chips else 0.0,
        chips=chips,
        stages={s: v / chips for s, v in stages.items()},
        scoped_s=sum(scoped_per_chip) / chips if chips else 0.0,
        ops=ops,
        idle_by_span=idle_by_span(first_busy, pieces, w0, w1))


def _length(intervals: Iterable[Interval], lo: float, hi: float) -> float:
    return sum(b - a for a, b in trace_reduce.union(
        trace_reduce.clip(intervals, lo, hi))) * 1e-9


def reduce_file(path: str, span_names: Iterable[str]) -> StageSummary:
    from jax.profiler import ProfileData

    with open(path, "rb") as f:
        data = f.read()
    return reduce_stages(ProfileData.from_serialized_xspace(data),
                         hlo_stages(data), span_names)


def reduce_dir(trace_dir: str, span_names: Iterable[str]) -> StageSummary:
    return reduce_file(trace_reduce.find_xplane(trace_dir), span_names)


def sync_idle_s(summary: StageSummary) -> float:
    """Idle under a ``ddm.*.readback`` span: the blocking reads."""
    return sum(v for k, v in summary.idle_by_span.items()
               if k.startswith(PREFIX) and k.endswith(".readback"))


def dispatch_idle_s(summary: StageSummary) -> float:
    """Idle under ``ddm.probe`` or ``ddm.emit`` outside their readbacks."""
    return sum(summary.idle_by_span.get(PREFIX + p, 0.0)
               for p in ("probe", "emit"))
