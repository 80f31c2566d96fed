"""Sweep-based pair enumeration: every engine (XLA sweep, Pallas pass C,
blocked oracle, d-dim composition) returns exactly the brute-force pair set,
including ties, duplicates, zero-length intervals and the overflow contract."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hyp import HAVE_HYPOTHESIS, given, settings, st
from repro.core import (
    Extents,
    brute_force_pairs_numpy,
    enumerate_matches,
    enumerate_matches_ddim,
    make_clustered_workload,
    make_uniform_workload,
    sbm_enumerate,
    sbm_enumerate_planned,
)
from repro.core import enumerate as enumerate_mod
from repro.core.enumerate import (_emit_sharded, _expand_slots_sharded,
                                  _one_device_mesh, _pinned_offsets,
                                  _search_slots,
                                  enumerate_matches_sweep_numpy)
from repro.core.sweep import _sort_count_sharded, sequential_sbm_pairs_numpy
from repro.kernels import sbm_enumerate_kernel

jax.config.update("jax_platform_name", "cpu")


def _mk(lo_s, hi_s, lo_u, hi_u):
    subs = Extents(jnp.asarray(lo_s, jnp.float32), jnp.asarray(hi_s, jnp.float32))
    upds = Extents(jnp.asarray(lo_u, jnp.float32), jnp.asarray(hi_u, jnp.float32))
    return subs, upds


def _pset(pairs):
    a = np.asarray(pairs)
    return {(int(i), int(j)) for i, j in a if i >= 0}


def _check_all_engines(subs, upds):
    """Pair-set agreement across every enumeration engine."""
    want = brute_force_pairs_numpy(subs, upds)
    cap = max(len(want), 1) + 8
    assert sequential_sbm_pairs_numpy(subs, upds) == want
    pairs, count = sbm_enumerate(subs, upds, max_pairs=cap)
    assert int(count) == len(want)
    assert _pset(pairs) == want
    pairs, count, _ = sbm_enumerate_planned(subs, upds)
    assert int(count) == len(want)
    assert _pset(pairs) == want
    pairs, count = sbm_enumerate_kernel(subs, upds, max_pairs=cap,
                                        block_size=32, interpret=True)
    assert int(count) == len(want)
    assert _pset(pairs) == want
    return want


# ---------------------------------------------------------------------------
# hand-made adversarial cases
# ---------------------------------------------------------------------------

def test_paper_figure1_pairs():
    subs, upds = _mk([0, 3, 6], [4, 8, 14], [1, 9], [7, 13])
    want = _check_all_engines(subs, upds)
    assert want == {(0, 0), (1, 0), (2, 0), (2, 1)}


def test_touching_endpoints_closed_semantics():
    _check_all_engines(*_mk([0.0], [5.0], [5.0], [9.0]))
    _check_all_engines(*_mk([5.0], [9.0], [0.0], [5.0]))


def test_zero_length_intervals():
    want = _check_all_engines(*_mk([2.0, 4.0], [2.0, 4.0], [2.0], [2.0]))
    assert want == {(0, 0)}


def test_duplicates_all_pairs():
    n, m = 17, 13
    want = _check_all_engines(*_mk([1.0] * n, [2.0] * n,
                                   [1.5] * m, [3.0] * m))
    assert len(want) == n * m


def test_containment_and_duplicates():
    _check_all_engines(*_mk([0, 0, 1, 1], [10, 10, 2, 2],
                            [1, 0, 5], [2, 100, 5]))


def test_empty_sets():
    for subs, upds in [_mk([], [], [1.0], [2.0]), _mk([1.0], [2.0], [], [])]:
        pairs, count = sbm_enumerate(subs, upds, max_pairs=4)
        assert int(count) == 0 and _pset(pairs) == set()
        pairs, count = sbm_enumerate_kernel(subs, upds, max_pairs=4,
                                            interpret=True)
        assert int(count) == 0 and _pset(pairs) == set()


# ---------------------------------------------------------------------------
# overflow contract: count stays exact, buffer holds valid pairs only
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("engine", ["sweep", "kernel", "blocked"])
def test_overflow_still_counts(engine):
    lo = jnp.zeros((4,), jnp.float32)
    hi = jnp.ones((4,), jnp.float32)
    subs = upds = Extents(lo, hi)
    want = brute_force_pairs_numpy(subs, upds)
    if engine == "sweep":
        pairs, count = sbm_enumerate(subs, upds, max_pairs=5)
    elif engine == "kernel":
        pairs, count = sbm_enumerate_kernel(subs, upds, max_pairs=5,
                                            block_size=8, interpret=True)
    else:
        pairs, count = enumerate_matches(subs, upds, max_pairs=5, block=4)
    assert int(count) == 16          # true K despite the short buffer
    got = _pset(pairs)
    assert len(got) == 5             # buffer completely used...
    assert got <= want               # ...with genuine pairs only


def test_planned_call_without_a_mesh_is_the_one_device_mesh_call():
    """A planned call given no mesh runs the mesh programs on one device:
    the buffer and count are those of a call on a one-device mesh."""
    subs, upds = make_uniform_workload(jax.random.PRNGKey(5), 300, 400,
                                       alpha=2.0, length=1000.0)
    pairs, count, stats = sbm_enumerate_planned(subs, upds)
    mesh_pairs, mesh_count, mesh_stats = sbm_enumerate_planned(
        subs, upds, mesh=jax.make_mesh((1,), ("q",)))
    np.testing.assert_array_equal(pairs, mesh_pairs)
    assert int(count) == int(mesh_count) == stats.count > 0
    assert stats.chips == mesh_stats.chips == 1
    assert stats.regime == mesh_stats.regime


# ---------------------------------------------------------------------------
# randomized agreement (uniform, clustered, integer-grid ties)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,m,alpha", [(100, 140, 2.0), (64, 200, 0.05),
                                       (180, 60, 30.0)])
def test_uniform_workloads_match_oracles(n, m, alpha):
    subs, upds = make_uniform_workload(jax.random.PRNGKey(n + m), n, m,
                                       alpha=alpha, length=1000.0)
    want = _check_all_engines(subs, upds)
    # blocked oracle and host sweep agree too
    pairs, count = enumerate_matches(subs, upds,
                                     max_pairs=max(len(want), 1) + 8, block=64)
    assert int(count) == len(want) and _pset(pairs) == want
    arr = enumerate_matches_sweep_numpy(subs, upds)
    assert {(int(i), int(j)) for i, j in arr} == want


def test_clustered_workload_matches_oracles():
    subs, upds = make_clustered_workload(jax.random.PRNGKey(7), 120, 120,
                                         alpha=20.0)
    _check_all_engines(subs, upds)


@pytest.mark.parametrize("seed", range(8))
def test_random_integer_grids(seed):
    """Integer coordinates → heavy tie-breaking at every endpoint."""
    rng = np.random.RandomState(seed)
    n, m = rng.randint(1, 50, 2)
    ls = rng.randint(0, 25, n).astype(float)
    hs = ls + rng.randint(0, 7, n)
    lu = rng.randint(0, 25, m).astype(float)
    hu = lu + rng.randint(0, 7, m)
    _check_all_engines(*_mk(ls.tolist(), hs.tolist(),
                            lu.tolist(), hu.tolist()))


def test_sweep_matches_blocked_on_larger_instance():
    subs, upds = make_uniform_workload(jax.random.PRNGKey(3), 800, 700,
                                       alpha=10.0, length=1.0e5)
    want = brute_force_pairs_numpy(subs, upds)
    pairs, count = sbm_enumerate(subs, upds, max_pairs=len(want) + 1)
    assert int(count) == len(want)
    assert _pset(pairs) == want


# ---------------------------------------------------------------------------
# d-dimensional composition
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("method", ["sweep", "blocked"])
def test_ddim_enumeration(method):
    key = jax.random.PRNGKey(9)
    k1, k2 = jax.random.split(key)
    d, n, m = 3, 40, 50
    lo_s = jax.random.uniform(k1, (d, n), maxval=80.0)
    hi_s = lo_s + jax.random.uniform(jax.random.fold_in(k1, 1), (d, n), maxval=30.0)
    lo_u = jax.random.uniform(k2, (d, m), maxval=80.0)
    hi_u = lo_u + jax.random.uniform(jax.random.fold_in(k2, 1), (d, m), maxval=30.0)
    subs, upds = Extents(lo_s, hi_s), Extents(lo_u, hi_u)
    want = brute_force_pairs_numpy(subs, upds)
    pairs, count = enumerate_matches_ddim(subs, upds, max_pairs=n * m,
                                          method=method)
    assert _pset(pairs) == want and int(count) == len(want)


# ---------------------------------------------------------------------------
# hypothesis property sweep (bare-env fallback: the seeded tests above)
# ---------------------------------------------------------------------------

if HAVE_HYPOTHESIS:
    finite_floats = st.floats(min_value=-1e4, max_value=1e4, allow_nan=False,
                              width=32, allow_subnormal=False)

    @st.composite
    def interval_sets(draw):
        n = draw(st.integers(1, 30))
        m = draw(st.integers(1, 30))

        def mk(count):
            lows, highs = [], []
            for _ in range(count):
                a = draw(finite_floats)
                b = draw(finite_floats)
                lows.append(min(a, b))
                highs.append(max(a, b))
            return lows, highs

        ls, hs = mk(n)
        lu, hu = mk(m)
        return ls, hs, lu, hu

    @given(interval_sets())
    @settings(max_examples=40, deadline=None)
    def test_property_pair_sets_equal_brute_force(data):
        subs, upds = _mk(*data)
        want = brute_force_pairs_numpy(subs, upds)
        cap = max(len(want), 1) + 4
        pairs, count = sbm_enumerate(subs, upds, max_pairs=cap)
        assert int(count) == len(want)
        assert _pset(pairs) == want


# ---------------------------------------------------------------------------
# slot maps: binary search against expansion by scatter and prefix scan
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("max_pairs",))
def _slot_maps(counts, starts, *, max_pairs):
    """Both slot maps of emitters with these counts and counterpart
    starts, on their pinned offsets: the search's emitter and rank, and
    :func:`_expand_slots_sharded`'s emitter and base inside a one-device
    ``shard_map``."""
    from jax.sharding import PartitionSpec as P

    excl, total = _pinned_offsets(counts, max_pairs)
    e_search, r = _search_slots(jnp.arange(max_pairs, dtype=jnp.int32), excl)
    expand = jax.shard_map(
        lambda x, *v: tuple(_expand_slots_sharded(x, v, max_pairs, "p")),
        mesh=_one_device_mesh(), in_specs=P("p"), out_specs=P("p"),
        check_vma=False)
    e_expand, base = expand(excl, jnp.arange(counts.shape[0]), starts - excl)
    return total, e_search, r, e_expand, base


def _emit_forced(subs, upds, max_pairs, form, monkeypatch):
    """The emission program from the probe's stream with ``form`` as its
    slot map, traced afresh."""
    n, m = subs.size, upds.size
    tags, _ = _sort_count_sharded(subs, upds, mesh=_one_device_mesh(),
                                  axis_name="p")
    with monkeypatch.context() as patch:
        patch.setattr(enumerate_mod, "_slot_map", lambda *_: form)
        return jax.jit(lambda t: _emit_sharded.__wrapped__(
            t, n=n, m=m, max_pairs=max_pairs, mesh=_one_device_mesh(),
            axis_name="p"))(tags)


SLOT_MAP_CASES = {
    # name: (emitter counts, max_pairs) or (workload, max_pairs)
    "zero_runs_lead_inner_trail": ([0, 0, 3, 0, 0, 2, 0,
                                    0, 1, 0, 0, 4, 0, 0], 16),
    "k_zero": ([0, 0, 0, 0, 0], 8),
    "k_equals_max_pairs": ([2, 0, 3, 1, 0, 2, 0, 0], 8),
    "k_beyond_max_pairs": ([3, 4, 0, 2, 4, 0, 1, 3], 6),
    "one_emitter_owns_every_slot": ([0, 0, 5, 0, 0, 0, 0, 0, 0], 5),
    "one_emitter_overflows": ([0, 0, 5, 0, 0, 0, 0, 0, 0], 3),
    "n_is_one": ([3, 0, 1, 0], 4),
    "m_is_one": ([1, 0, 1, 1, 3], 8),
    "ties": (lambda: _mk([0, 2, 2, 4, 4], [2, 4, 4, 6, 6],
                         [2, 2, 4, 0], [2, 4, 4, 6]), 32),
    "duplicates": (lambda: _mk([1.0] * 9, [2.0] * 9, [1.5] * 7, [3.0] * 7),
                   64),
    "duplicates_overflow": (lambda: _mk([1.0] * 9, [2.0] * 9,
                                        [1.5] * 7, [3.0] * 7), 40),
}


@pytest.mark.parametrize("x64", [False, True], ids=["x32", "x64"])
@pytest.mark.parametrize("case", sorted(SLOT_MAP_CASES))
def test_slot_maps_are_bit_identical(case, x64, monkeypatch):
    """The expansion gives every slot the search's emitter and counterpart
    rank on the same pinned offsets; on a workload's stream the emission
    returns the same padded buffer with either slot map forced."""
    spec, max_pairs = SLOT_MAP_CASES[case]
    with jax.enable_x64(x64):
        if callable(spec):
            subs, upds = spec()
            want = brute_force_pairs_numpy(subs, upds)
            search, expand = (_emit_forced(subs, upds, max_pairs, f,
                                           monkeypatch)
                              for f in ("search", "expand"))
            assert search[0].dtype == expand[0].dtype == jnp.int32
            np.testing.assert_array_equal(expand[0], search[0])
            assert int(search[1]) == int(expand[1]) == len(want)
            k = min(len(want), max_pairs)
            got = np.asarray(search[0])
            assert np.all(got[k:] == -1) and np.all(got[:k] >= 0)
            assert _pset(got) <= want and len(_pset(got)) == k
            return
        counts = np.asarray(spec, np.int32)
        starts = np.random.RandomState(len(spec)).randint(
            0, 1000, len(spec)).astype(np.int32)
        total, e_search, r, e_expand, base = (np.asarray(x) for x in (
            _slot_maps(counts, starts, max_pairs=max_pairs)))
        assert int(total) == min(sum(spec), max_pairs)
        np.testing.assert_array_equal(e_expand, e_search)
        np.testing.assert_array_equal(base + np.arange(max_pairs),
                                      starts[e_search] + r)
        # every slot below K lies in its emitter's range
        k = int(total)
        assert np.all(r[:k] < counts[e_search][:k])


@pytest.mark.parametrize("max_pairs,form", [(64, "search"), (1024, "expand")])
def test_enumerate_rows_equal_the_search_rows(monkeypatch, max_pairs, form):
    """The emission takes the rule's slot map for the stream's 2·(n+m)
    records and returns, row for row, what it returns with the search
    forced."""
    subs, upds = make_uniform_workload(jax.random.PRNGKey(11), 300, 400,
                                       alpha=2.0, length=1000.0)
    assert enumerate_mod._slot_map(max_pairs, 1400) == form
    tags, _ = _sort_count_sharded(subs, upds, mesh=_one_device_mesh(),
                                  axis_name="p")
    pairs, count = _emit_sharded(tags, n=300, m=400, max_pairs=max_pairs,
                                 mesh=_one_device_mesh(), axis_name="p")
    want_pairs, want_count = _emit_forced(subs, upds, max_pairs, "search",
                                          monkeypatch)
    assert int(count) == int(want_count) > max_pairs // 2
    np.testing.assert_array_equal(pairs, want_pairs)


@pytest.mark.parametrize("max_pairs,n_emitters,form", [
    (64, 700, "search"), (140, 700, "search"), (141, 700, "expand"),
    (1024, 700, "expand"), (4, 2, "search"), (5, 2, "expand"),
    (8192, 10**6, "search"), (1 << 26, 10**6, "expand")])
def test_slot_map_rule_counts_work(max_pairs, n_emitters, form):
    """Expand where the search's max_pairs·⌈log2(n+m)⌉ probes outnumber
    the expansion's 2·(n+m) scattered marks."""
    assert enumerate_mod._slot_map(max_pairs, n_emitters) == form
