"""The matcher's own instrumentation on the profiler's clock.

``MatchStats`` is the one recorder: ``phase`` times a phase and opens the
host span ``ddm.<phase>``; ``readback`` wraps a blocking device→host read
in ``ddm.<phase>.readback`` and counts it.  Inside the two device
programs of the planned sweep, ``jax.named_scope`` names the stages
``ddm.sort``/``ddm.count`` (probe) and ``ddm.ranks``/``ddm.exchange``/
``ddm.search``/``ddm.gather`` (emission); the names are metadata only.
"""
import contextlib
import glob
import os
import re
import sys
import time

import jax
import pytest

from repro.core import make_uniform_workload, runtime
from repro.core.enumerate import (_emit_sharded, _one_device_mesh,
                                  _slot_map, sbm_enumerate_planned)
from repro.core.sweep import _sbm_count_partials, _sort_count_sharded

jax.config.update("jax_platform_name", "cpu")

PROBE_SCOPES = {"ddm.sort", "ddm.count"}
MESH_EMIT_SCOPES = {"ddm.ranks", "ddm.exchange", "ddm.search", "ddm.gather"}
MESH = _one_device_mesh()


def _workload(seed=0):
    return make_uniform_workload(jax.random.PRNGKey(seed), 300, 400, 2.0)


def _sets(subs, upds):
    return subs, upds


def _stream(subs, upds):
    return _sort_count_sharded(subs, upds, mesh=MESH, axis_name="p")[:1]


# (name, jitted function, its arguments from the sets, static arguments,
# stages) of sbm_count's program and the planned sweep's two programs on
# the one-device mesh a call without a mesh takes (tests/test_core_mesh.py
# runs them on four); with 2·(n+m) = 1400 stream records the emission
# expands 1024 slots and searches for 64
PROGRAMS = [
    ("count", _sbm_count_partials, _sets,
     dict(num_segments=8, scan_impl="two_level"), PROBE_SCOPES),
    ("mesh_count", _sort_count_sharded, _sets,
     dict(mesh=MESH, axis_name="p"), PROBE_SCOPES),
    ("mesh_emit", _emit_sharded, _stream,
     dict(n=300, m=400, max_pairs=1024, mesh=MESH, axis_name="p"),
     MESH_EMIT_SCOPES),
    ("mesh_emit_search", _emit_sharded, _stream,
     dict(n=300, m=400, max_pairs=64, mesh=MESH, axis_name="p"),
     MESH_EMIT_SCOPES),
]
# the loops each program keeps: only the binary search is a while (the
# splitter search of the sort across the mesh needs two or more chips)
LOOPS = {"count": {"sort"}, "mesh_count": {"sort"}, "mesh_emit": set(),
         "mesh_emit_search": {"while"}}


def _instructions(hlo: str):
    """(opcode, op_name or None) of every instruction of an HLO text."""
    out = []
    for line in hlo.splitlines():
        m = re.match(r"\s*(?:ROOT )?%\S+ = (.*)$", line)
        if not m:
            continue
        rest = m.group(1)
        if rest.startswith("("):          # tuple shape: skip to its close
            depth = 0
            for k, ch in enumerate(rest):
                depth += {"(": 1, ")": -1}.get(ch, 0)
                if depth == 0:
                    rest = rest[k + 1:]
                    break
        else:
            rest = rest.partition(" ")[2]
        opcode = rest.strip().partition("(")[0]
        name = re.search(r'op_name="([^"]*)"', line)
        out.append((opcode, name.group(1) if name else None))
    return out


def _scope(op_name):
    for part in (op_name or "").split("/"):
        if part.startswith("ddm."):
            return part
    return None


def _strip_metadata(hlo: str) -> str:
    """The HLO text without op metadata and the source tables it cites."""
    hlo = re.sub(r", metadata=\{[^}]*\}", "", hlo)
    return re.sub(r"\n(FileNames|FunctionNames|FileLocations|StackFrames)"
                  r"\n(.+\n)*", "\n", hlo)


# ---------------------------------------------------------------------------
# The phase construct


def test_phase_accumulates_seconds_as_before():
    stats = runtime.MatchStats(engine="t")
    with stats.phase("probe"):
        time.sleep(0.01)
    with stats.phase("emit"):
        time.sleep(0.005)
    with stats.phase("emit"):
        time.sleep(0.005)
    assert stats.phase_seconds["probe"] >= 0.01
    assert stats.phase_seconds["emit"] >= 0.01
    assert set(stats.phase_seconds) == {"probe", "emit"}
    assert stats.readbacks == 0


def test_phase_body_that_raises_records_no_time():
    stats = runtime.MatchStats()
    with pytest.raises(RuntimeError):
        with stats.phase("emit"):
            raise RuntimeError("boom")
    assert "emit" not in stats.phase_seconds


def test_readback_counts_and_records_no_phase():
    stats = runtime.MatchStats()
    with stats.phase("probe"):
        with stats.readback("probe", 4):
            pass
    with stats.readback("emit"):
        pass
    assert stats.readbacks == 5
    assert set(stats.phase_seconds) == {"probe"}
    assert stats.as_dict()["readbacks"] == 5


def test_phase_without_jax_only_times(monkeypatch):
    """A host-only process never imports jax for a span."""
    monkeypatch.delitem(sys.modules, "jax")
    monkeypatch.setitem(runtime._annotation, "cls", None)
    stats = runtime.MatchStats()
    assert isinstance(stats._span("probe"), contextlib.nullcontext)
    with stats.phase("probe"):
        with stats.readback("probe"):
            pass
    assert "probe" in stats.phase_seconds and stats.readbacks == 1
    assert "jax" not in sys.modules


def test_call_numbers_are_distinct_and_not_compared():
    a, b = runtime.MatchStats(engine="e"), runtime.MatchStats(engine="e")
    assert a.call != b.call
    assert a == b


# ---------------------------------------------------------------------------
# The planned sweep: spans, readbacks, scopes


def test_planned_call_makes_five_readbacks():
    subs, upds = _workload()
    _, count, stats = sbm_enumerate_planned(subs, upds)
    assert stats.retries == 0 and int(count) == stats.count > 0
    assert stats.readbacks == 5      # four count partials, then the count


@pytest.mark.parametrize("alpha,regime", [(0.1, "search"), (2.0, "expand")])
def test_planned_call_names_its_slot_map(alpha, regime):
    """``stats.regime`` is the slot map the rule gives the planned buffer."""
    subs, upds = make_uniform_workload(jax.random.PRNGKey(4), 300, 400, alpha)
    _, _, stats = sbm_enumerate_planned(subs, upds)
    assert stats.regime == _slot_map(stats.capacity, 1400) == regime
    assert stats.as_dict()["regime"] == regime


def test_planned_call_spans_on_the_profiler_clock(tmp_path):
    from jax.profiler import ProfileData

    subs, upds = _workload(seed=1)
    sbm_enumerate_planned(subs, upds)          # compile outside the trace
    with jax.profiler.trace(str(tmp_path)):
        _, _, stats = sbm_enumerate_planned(subs, upds)
    path = sorted(glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                            recursive=True))[-1]
    spans = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("ddm"):
                    spans.setdefault(ev.name, []).append(
                        (ev.start_ns, ev.end_ns, dict(ev.stats)))
    # bare names: the call's metadata travels as stats, not in the name
    assert set(spans) == {"ddm.probe", "ddm.probe.readback", "ddm.emit",
                          "ddm.emit.readback"}
    for name, evs in spans.items():
        assert len(evs) == 1, name
        assert evs[0][2] == {"engine": "sweep", "call": stats.call}

    def inside(child, parent):
        (c0, c1, _), = spans[child]
        (p0, p1, _), = spans[parent]
        return p0 <= c0 and c1 <= p1

    assert inside("ddm.probe.readback", "ddm.probe")
    assert inside("ddm.emit.readback", "ddm.emit")
    (_, probe_end, _), = spans["ddm.probe"]
    (emit_start, _, _), = spans["ddm.emit"]
    assert probe_end <= emit_start


def test_every_stage_is_named_in_the_compiled_programs():
    """Each program's stages appear in its optimized HLO's ``op_name``s,
    and no code of the program lies outside a stage: every instruction
    whose ``op_name`` is rooted at the program (``jit(...)/...``) has a
    ``ddm.*`` component, and so has every sort and while, in the emission's
    search and expansion forms alike.  (A fusion the
    CPU backend makes around a single pad or reduce-window carries no
    ``op_name``; one inside a comparator or fused computation carries a
    name relative to it, like ``or``.)"""
    subs, upds = _workload(seed=2)
    seen = set()
    for name, fn, args, static, stages in PROGRAMS:
        hlo = fn.lower(*args(subs, upds), **static).compile().as_text()
        insts = _instructions(hlo)
        scopes = {_scope(op) for _, op in insts} - {None}
        assert scopes == stages, name
        seen |= scopes
        rooted = [op for _, op in insts
                  if op and op.startswith("jit(") and not _scope(op)]
        assert not rooted, (name, rooted)
        loops = [(opc, op) for opc, op in insts if opc in ("sort", "while")]
        assert {opc for opc, _ in loops} == LOOPS[name], name
        assert all(_scope(op) for _, op in loops), (name, loops)
    assert seen == PROBE_SCOPES | MESH_EMIT_SCOPES


def test_named_scopes_are_metadata_only(monkeypatch):
    """With metadata stripped, the optimized HLO is the same with the
    stage scopes and without them."""
    subs, upds = _workload(seed=3)
    with_scopes = {}
    for name, fn, args, static, _ in PROGRAMS:
        fresh = jax.jit(fn.__wrapped__, static_argnames=tuple(static))
        with_scopes[name] = fresh.lower(*args(subs, upds), **static
                                        ).compile().as_text()
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    jax.clear_caches()                     # trace the programs again
    for name, fn, args, static, _ in PROGRAMS:
        fresh = jax.jit(fn.__wrapped__, static_argnames=tuple(static))
        bare = fresh.lower(*args(subs, upds), **static).compile().as_text()
        assert "ddm." not in bare
        assert "ddm." in with_scopes[name]
        assert _strip_metadata(bare) == _strip_metadata(with_scopes[name]), \
            name


# Optimized HLO, metadata stripped, of the one-chip programs at the static
# cells' shapes (float32 bounds, n = m = 5·10⁵; 8,192 rows and 2²⁶), on
# the CPU backend: sbm_count's program, and the planned sweep's emission
# from the probe's 2·10⁶ sorted records on the one-device mesh.
def _bounds():
    import jax.numpy as jnp

    from repro.core import Extents

    bounds = jax.ShapeDtypeStruct((500_000,), jnp.float32)
    return Extents(bounds, bounds), Extents(bounds, bounds)


def _tags():
    import jax.numpy as jnp

    return (jax.ShapeDtypeStruct((2_000_000,), jnp.int32),)


def _emit_static(max_pairs):
    return dict(n=500_000, m=500_000, max_pairs=max_pairs,
                mesh=_one_device_mesh(), axis_name="p")


FLOAT32_HLO = {
    "count": (_sbm_count_partials, _bounds,
              dict(num_segments=8, scan_impl="two_level"),
              "67374da03d38841f"),
    "emit": (_emit_sharded, _tags, _emit_static(8192), "51e6454ca86003a1"),
    "emit_2e26": (_emit_sharded, _tags, _emit_static(1 << 26),
                  "9112ca80ea7ff295"),
}


@pytest.mark.parametrize("name", sorted(FLOAT32_HLO))
def test_float32_programs_keep_their_hlo(name):
    import hashlib

    fn, args, static, want = FLOAT32_HLO[name]
    with jax.enable_x64(False):
        hlo = fn.lower(*args(), **static).compile().as_text()
    got = hashlib.sha256(_strip_metadata(hlo).encode()).hexdigest()[:16]
    assert got == want
