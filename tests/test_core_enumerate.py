"""Sweep-based pair enumeration: every engine (XLA sweep, Pallas pass C,
blocked oracle, d-dim composition) returns exactly the brute-force pair set,
including ties, duplicates, zero-length intervals and the overflow contract."""
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
)
from repro.core import enumerate as enumerate_mod
from repro.core.enumerate import (_emit_pairs, _expand_slots, _offset_cumsum,
                                  _sbm_enumerate_jit, _search_slots,
                                  enumerate_matches_sweep_numpy)
from repro.core.sweep import (_pad_stream, emission_rank_tables,
                              encode_endpoints, resolve_cumsum,
                              sequential_sbm_pairs_numpy)
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
    for scan_impl in ("two_level", "xla"):
        pairs, count = sbm_enumerate(subs, upds, max_pairs=cap,
                                     num_segments=4, scan_impl=scan_impl)
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
    pairs, count = sbm_enumerate(subs, upds, max_pairs=len(want) + 1,
                                 num_segments=16)
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
        pairs, count = sbm_enumerate(subs, upds, max_pairs=cap,
                                     num_segments=4)
        assert int(count) == len(want)
        assert _pset(pairs) == want


# ---------------------------------------------------------------------------
# slot maps: binary search against expansion by scatter and prefix scan
# ---------------------------------------------------------------------------

def _synthetic_tables(a_cnt, b_cnt, seed=0):
    """Offset and rank tables with the given per-emitter counts: each
    emitter's range start drawn so its counterpart ranks stay inside the
    table, the rank→id tables random permutations."""
    n, m = len(a_cnt), len(b_cnt)
    rng = np.random.RandomState(seed)
    a_start = jnp.asarray([rng.randint(0, m - c + 1) for c in a_cnt],
                          jnp.int32)
    b_start = jnp.asarray([rng.randint(0, n - c + 1) for c in b_cnt],
                          jnp.int32)
    counts = jnp.asarray(list(a_cnt) + list(b_cnt), jnp.int32)
    off = _offset_cumsum(counts)
    return (off, counts, off[-1], a_start, b_start,
            jnp.asarray(rng.permutation(n), jnp.int32),
            jnp.asarray(rng.permutation(m), jnp.int32))


def _workload_tables(subs, upds):
    """The program's own offset and rank tables of an input."""
    n, m = subs.lo.shape[0], upds.lo.shape[0]
    ep = _pad_stream(encode_endpoints(subs, upds), 8)
    a_start, a_cnt, b_start, b_cnt, subs_by_lo, upds_by_lo = \
        emission_rank_tables(ep, n, m, resolve_cumsum("two_level", 8))
    counts = jnp.concatenate([a_cnt, b_cnt])
    off = _offset_cumsum(counts)
    return off, counts, off[-1], a_start, b_start, subs_by_lo, upds_by_lo


SLOT_MAP_CASES = {
    # name: (a_cnt, b_cnt, max_pairs) or (workload, max_pairs)
    "zero_runs_lead_inner_trail": ([0, 0, 3, 0, 0, 2, 0],
                                   [0, 1, 0, 0, 4, 0, 0], 16),
    "k_zero": ([0, 0, 0], [0, 0], 8),
    "k_equals_max_pairs": ([2, 0, 3, 1], [0, 2, 0, 0], 8),
    "k_beyond_max_pairs": ([3, 4, 0, 2], [4, 0, 1, 3], 6),
    "one_emitter_owns_every_slot": ([0, 0, 5, 0], [0, 0, 0, 0, 0], 5),
    "one_emitter_overflows": ([0, 0, 5, 0], [0, 0, 0, 0, 0], 3),
    "n_is_one": ([3], [0, 1, 0], 4),
    "m_is_one": ([1, 0, 1, 1], [3], 8),
    "ties": (lambda: _mk([0, 2, 2, 4, 4], [2, 4, 4, 6, 6],
                         [2, 2, 4, 0], [2, 4, 4, 6]), 32),
    "duplicates": (lambda: _mk([1.0] * 9, [2.0] * 9, [1.5] * 7, [3.0] * 7),
                   64),
    "duplicates_overflow": (lambda: _mk([1.0] * 9, [2.0] * 9,
                                        [1.5] * 7, [3.0] * 7), 40),
}


@pytest.mark.parametrize("x64", [False, True], ids=["x32", "x64"])
@pytest.mark.parametrize("case", sorted(SLOT_MAP_CASES))
def test_slot_maps_are_bit_identical(case, x64):
    """The expansion gives every slot the search's emitter and counterpart
    rank, and the same padded buffer, on the same offset tables."""
    spec = SLOT_MAP_CASES[case]
    with jax.enable_x64(x64):
        if callable(spec[0]):
            tables = _workload_tables(*spec[0]())
        else:
            tables = _synthetic_tables(*spec[:2])
        max_pairs = spec[-1]
        off, counts, k_total, a_start, b_start = tables[:5]
        n, m = a_start.shape[0], b_start.shape[0]
        slots = jnp.arange(max_pairs, dtype=jnp.int32)

        e_search, r = _search_slots(slots, off, counts)
        starts = jnp.concatenate([a_start, m + b_start])
        e_expand, base = _expand_slots(off - counts, starts - (off - counts),
                                       max_pairs, 8)
        np.testing.assert_array_equal(e_expand, e_search)
        np.testing.assert_array_equal(base + slots, starts[e_search] + r)

        search, expand = (_emit_pairs(*tables, max_pairs=max_pairs,
                                      num_segments=8, form=f)
                          for f in ("search", "expand"))
        assert search.dtype == expand.dtype == jnp.int32
        np.testing.assert_array_equal(expand, search)
        k = min(int(k_total), max_pairs)
        got = np.asarray(search)
        assert np.all(got[k:] == -1) and np.all(got[:k] >= 0)
        assert np.all(got[:k, 0] < n) and np.all(got[:k, 1] < m)


@pytest.mark.parametrize("max_pairs,form", [(64, "search"), (1024, "expand")])
def test_enumerate_rows_equal_the_search_rows(monkeypatch, max_pairs, form):
    """The program takes the rule's slot map and returns, row for row, what
    it returns with the search forced."""
    subs, upds = make_uniform_workload(jax.random.PRNGKey(11), 300, 400,
                                       alpha=2.0, length=1000.0)
    assert enumerate_mod._slot_map(max_pairs, 700) == form
    static = dict(max_pairs=max_pairs, num_segments=8, scan_impl="two_level")
    pairs, count = _sbm_enumerate_jit(subs, upds, **static)
    monkeypatch.setattr(enumerate_mod, "_slot_map", lambda *_: "search")
    searched = jax.jit(_sbm_enumerate_jit.__wrapped__,
                       static_argnames=tuple(static))
    want_pairs, want_count = searched(subs, upds, **static)
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
