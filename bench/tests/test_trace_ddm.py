"""Device time by program stage and idle by program span
(``bench/trace/scopes.py``), on synthetic data and on a trace of planned
audits recorded on a TPU v5e by ``bench/trace/record_ddm.py``."""
from pathlib import Path

import pytest

from bench.trace import reduce as tr
from bench.trace import scopes

RECORDED = Path(__file__).resolve().parents[1] / "trace" / "testdata" / \
    "recorded_ddm.xplane.pb"
SPANS = ("audit.match", "audit.d2h")
STAGES = {"ddm.sort", "ddm.count", "ddm.ranks", "ddm.search", "ddm.gather"}
PHASE_SPANS = {"ddm.probe", "ddm.probe.readback", "ddm.emit",
               "ddm.emit.readback"}


# -- synthetic ---------------------------------------------------------------

def _varint(v: int) -> bytes:
    out = bytearray()
    while True:
        b, v = v & 0x7F, v >> 7
        out.append(b | (0x80 if v else 0))
        if not v:
            return bytes(out)


def test_wire_fields_and_packed_ints():
    packed = _varint(1) + _varint(150) + _varint(1 << 40)
    msg = (_varint(1 << 3 | 0) + _varint(300)            # 1: varint
           + _varint(2 << 3 | 2) + _varint(2) + b"ab"    # 2: bytes
           + _varint(36 << 3 | 2) + _varint(len(packed)) + packed)
    fields = list(scopes._fields(msg))
    assert [n for n, _ in fields] == [1, 2, 36]
    assert fields[0][1] == 300
    assert scopes._text(msg, fields[1][1]) == "ab"
    assert scopes._ints(msg, fields[2][1]) == [1, 150, 1 << 40]
    assert scopes._ints(msg, 7) == [7]


def test_scope_and_program_id():
    assert scopes.scope_of("jit(f)/ddm.search/jit(searchsorted)/while") \
        == "ddm.search"
    assert scopes.scope_of("jit(f)/ddm.ranks:") == "ddm.ranks"
    assert scopes.scope_of("reduce_window_sum") == scopes.UNSCOPED
    assert scopes.program_id("jit__sbm_enumerate_jit(554417862)") \
        == 554417862
    assert scopes.program_id("jit__f(-1)") == (1 << 64) - 1
    assert scopes.program_id("jit__f") == -1


def test_innermost_span_pieces():
    spans = [(10, 50, "audit.match"), (12, 30, "ddm.probe"),
             (20, 30, "ddm.probe.readback"), (31, 49, "ddm.emit"),
             (60, 70, "audit.d2h")]
    pieces = scopes.innermost(spans, 0, 80)
    assert pieces == [(0, 10, "other"), (10, 12, "audit.match"),
                      (12, 20, "ddm.probe"), (20, 30, "ddm.probe.readback"),
                      (30, 31, "audit.match"), (31, 49, "ddm.emit"),
                      (49, 50, "audit.match"), (50, 60, "other"),
                      (60, 70, "audit.d2h"), (70, 80, "other")]
    # a parent and its child that open together: the child is innermost
    assert scopes.innermost([(0, 10, "p"), (0, 5, "c")], 0, 10) == \
        [(0, 5, "c"), (5, 10, "p")]


def test_idle_is_cut_at_span_edges():
    pieces = [(0, 10, "a"), (10, 20, "b"), (20, 40, "c")]
    busy = [(0, 5), (15, 25)]
    idle = scopes.idle_by_span(busy, pieces, 0, 40)
    assert idle == pytest.approx({"a": 5e-9, "b": 5e-9, "c": 15e-9})


# -- the recorded trace ---------------------------------------------------------

@pytest.fixture(scope="module")
def recorded():
    from jax.profiler import ProfileData

    data = RECORDED.read_bytes()
    profile = ProfileData.from_serialized_xspace(data)
    summary = scopes.reduce_stages(profile, scopes.hlo_stages(data), SPANS)
    return profile, data, summary


def _host_spans(profile):
    return [(ev.start_ns, ev.end_ns, ev.name, dict(ev.stats))
            for p in profile.planes if p.name.startswith("/host:")
            for line in p.lines for ev in line.events
            if ev.name in SPANS or ev.name.startswith(scopes.PREFIX)
            or ev.name == tr.WINDOW_SPAN]


def test_recorded_spans_are_bare_and_nest(recorded):
    profile, _, _ = recorded
    spans = _host_spans(profile)
    ddm = [s for s in spans if s[2].startswith(scopes.PREFIX)]
    assert {s[2] for s in ddm} == PHASE_SPANS
    calls = {}
    for a, b, name, stats in ddm:
        assert stats["engine"] == "sweep"
        calls.setdefault(stats["call"], []).append((a, b, name))
    assert len(calls) == 6                  # two sets, three audits each
    for evs in calls.values():
        by = {name: (a, b) for a, b, name in evs}
        assert set(by) == PHASE_SPANS
        for child, parent in (("ddm.probe.readback", "ddm.probe"),
                              ("ddm.emit.readback", "ddm.emit")):
            assert by[parent][0] <= by[child][0] <= by[child][1] \
                <= by[parent][1]
        assert by["ddm.probe"][1] <= by["ddm.emit"][0]


def test_every_operation_has_its_program_and_stage(recorded):
    profile, data, s = recorded
    stages = scopes.hlo_stages(data)
    plane = next(p for p in profile.planes
                 if p.name.startswith(tr.DEVICE_PREFIX))
    runs = {ev.name for line in plane.lines
            if line.name == tr.PROGRAMS_LINE for ev in line.events}
    emits = {r for r in runs if r.startswith("jit__sbm_enumerate_jit(")}
    assert len(emits) == 2                   # two buffer buckets, two ids
    with_hlo = {pid for pid, _ in stages}
    assert all(scopes.program_id(r) in with_hlo for r in runs)
    assert {r.partition("(")[0] for r in runs} >= {
        "jit__sbm_count_partials", "jit__sbm_enumerate_jit"}
    assert set(s.stages) - {scopes.UNSCOPED} == STAGES
    for op in s.ops:
        program, stage, name = op.split("/", 2)
        assert stage in STAGES | {scopes.UNSCOPED}, op
        if program in ("jit__sbm_count_partials", "jit__sbm_enumerate_jit"):
            assert stage != scopes.UNSCOPED, op


def test_stages_cover_busy_time_once(recorded):
    profile, _, s = recorded
    base = tr.reduce_profile(profile, SPANS)
    assert s.busy_s == pytest.approx(base.busy_s, rel=1e-9)
    assert s.scoped_s >= 0.95 * s.busy_s
    # a stage is the union of its operations: none exceeds busy, and the
    # stages do not overlap by more than rounding
    assert all(v <= s.busy_s * (1 + 1e-9) for v in s.stages.values())
    assert sum(v for k, v in s.stages.items() if k != scopes.UNSCOPED) \
        == pytest.approx(s.scoped_s, rel=1e-3)
    # the search's while loop and its body fusions count once
    search_ops = sum(v for k, v in s.ops.items() if "/ddm.search/" in k)
    assert s.stages["ddm.search"] < search_ops


def test_idle_by_span_sums_to_window_minus_busy(recorded):
    _, _, s = recorded
    assert sum(s.idle_by_span.values()) == pytest.approx(
        s.window_s - s.busy_s, rel=1e-6)
    assert s.idle_by_span.get("ddm.probe.readback", 0) > 0
    assert s.idle_by_span.get("ddm.emit.readback", 0) > 0


def _brute_idle(profile, w0, w1, step_ns=1000.0):
    """Idle of chip 0 under each innermost span, sampled every µs."""
    import numpy as np

    plane = sorted((p for p in profile.planes
                    if p.name.startswith(tr.DEVICE_PREFIX)),
                   key=lambda p: p.name)[0]
    t = np.arange(w0, w1, step_ns)
    busy = np.zeros(t.size, bool)
    for line in plane.lines:
        if line.name == tr.OPS_LINE:
            for ev in line.events:
                busy |= (t >= ev.start_ns) & (t < ev.end_ns)
    name = np.full(t.size, "other", dtype=object)
    start = np.full(t.size, -np.inf)
    for a, b, span, _ in _host_spans(profile):
        if span == tr.WINDOW_SPAN:
            continue
        inside = (t >= a) & (t < b) & (a >= start)
        name[inside], start[inside] = span, a
    out = {}
    for k in set(name[~busy]):
        out[k] = float(((name == k) & ~busy).mean() * (w1 - w0) * 1e-9)
    return out


def test_program_spans_name_the_gaps_they_cover(recorded):
    profile, _, s = recorded
    window = next((a, b) for a, b, name, _ in _host_spans(profile)
                  if name == tr.WINDOW_SPAN)
    brute = _brute_idle(profile, *window)
    assert set(brute) <= set(s.idle_by_span)
    for name, seconds in s.idle_by_span.items():
        assert seconds == pytest.approx(brute.get(name, 0.0),
                                        rel=0.05, abs=5e-6), name
