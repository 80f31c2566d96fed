"""The trace reduction, on synthetic intervals and on a small trace
recorded on a TPU v5e by ``bench/trace/record.py``."""
from pathlib import Path

import pytest

from bench.trace import reduce as tr

RECORDED = Path(__file__).resolve().parents[1] / "trace" / "testdata" / \
    "recorded.xplane.pb"


def test_union_clip_gaps():
    busy = tr.union([(5, 7), (0, 2), (1, 3), (6, 9), (12, 13)])
    assert busy == [(0, 3), (5, 9), (12, 13)]
    assert tr.clip(busy, 2, 12.5) == [(2, 3), (5, 9), (12, 12.5)]
    assert tr.gaps(tr.clip(busy, 2, 12.5), 2, 12.5) == [(3, 5), (9, 12)]
    assert tr.gaps([], 0, 4) == [(0, 4)]


def _profile():
    from jax.profiler import ProfileData

    return ProfileData.from_file(str(RECORDED))


def _brute_busy(profile, w0, w1, step_ns=1000.0):
    """Busy time of chip 0 by sampling the window every microsecond."""
    import numpy as np

    plane = sorted((p for p in profile.planes
                    if p.name.startswith(tr.DEVICE_PREFIX)),
                   key=lambda p: p.name)[0]
    t = np.arange(w0, w1, step_ns)
    hit = np.zeros(t.size, bool)
    for line in plane.lines:
        if line.name == tr.OPS_LINE:
            for ev in line.events:
                hit |= (t >= ev.start_ns) & (t < ev.end_ns)
    return hit.mean() * (w1 - w0) * 1e-9


def test_recorded_trace():
    profile = _profile()
    s = tr.reduce_profile(profile, ("tick.flush", "tick.move"))
    assert s.chips == 1
    assert 0 < s.busy_s < s.window_s
    assert 0 < s.idle_share < 1
    assert s.programs and s.ops
    assert all(op.startswith("jit__lambda/%") for op in s.ops)
    # the host sleeps inside tick.move, with nothing queued on the chip
    assert s.idle_by_span.get("tick.move", 0) >= 5 * 0.002 * 0.9
    assert sum(s.idle_by_span.values()) == pytest.approx(
        s.window_s - s.busy_s, rel=1e-6)
    window = [(ev.start_ns, ev.end_ns) for p in profile.planes
              if p.name.startswith("/host:") for line in p.lines
              for ev in line.events if ev.name == tr.WINDOW_SPAN][0]
    assert s.busy_s == pytest.approx(_brute_busy(profile, *window),
                                     rel=0.02, abs=2e-6)


def test_a_trace_without_the_window_is_refused():
    with pytest.raises(ValueError):
        tr.reduce_profile(_NoWindow(), ())


class _NoWindow:
    planes = ()


def test_op_names_are_short():
    assert tr.op_name("%fusion.14 = s32[500000]{0:T(1024)} fusion(s32[2]"
                      " %a), kind=kCustom") == "%fusion.14 fusion"
    assert tr.op_name("%copy-start = (f32[8]{0}, u32[]{:S(2)}) copy-start("
                      "f32[8]{0} %x.1)") == "%copy-start copy-start"
    assert tr.op_name("plain") == "plain"
