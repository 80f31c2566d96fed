"""Parallel SBM correctness: exact agreement with brute force on adversarial
inputs (ties, duplicates, zero-length, containment), across scan backends and
segment counts."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hyp import HAVE_HYPOTHESIS, given, settings, st
from repro.core import (
    Extents,
    active_sets_at_segment_starts,
    brute_force_count_numpy,
    make_uniform_workload,
    sbm_count,
    sbm_count_exact,
    sequential_sbm_count_numpy,
    sequential_sbm_pairs_numpy,
)

jax.config.update("jax_platform_name", "cpu")


def _mk(lo_s, hi_s, lo_u, hi_u):
    subs = Extents(jnp.asarray(lo_s, jnp.float32), jnp.asarray(hi_s, jnp.float32))
    upds = Extents(jnp.asarray(lo_u, jnp.float32), jnp.asarray(hi_u, jnp.float32))
    return subs, upds


def test_paper_figure1_example():
    # Fig. 1 of the paper (projected to 1-D x-axis, hand-made coordinates):
    # S1=[0,4], S2=[3,8], S3=[6,14], U1=[1,7], U2=[9,13]
    subs, upds = _mk([0, 3, 6], [4, 8, 14], [1, 9], [7, 13])
    # overlaps: (S1,U1), (S2,U1), (S3,U1), (S3,U2) → 4 (paper reports 4 in 2-D)
    assert int(sbm_count(subs, upds)) == 4
    assert sequential_sbm_count_numpy(subs, upds) == 4


@pytest.mark.parametrize("scan_impl", ["two_level", "blelloch", "xla"])
@pytest.mark.parametrize("num_segments", [1, 2, 8, 32])
def test_matches_brute_force_random(scan_impl, num_segments):
    key = jax.random.PRNGKey(0)
    subs, upds = make_uniform_workload(key, 100, 140, alpha=2.0, length=1000.0)
    want = brute_force_count_numpy(subs, upds)
    got = int(sbm_count(subs, upds, num_segments=num_segments, scan_impl=scan_impl))
    assert got == want


@pytest.mark.parametrize("alpha", [0.01, 1.0, 100.0])
def test_alpha_sweep(alpha):
    key = jax.random.PRNGKey(1)
    subs, upds = make_uniform_workload(key, 300, 300, alpha=alpha)
    assert int(sbm_count(subs, upds)) == brute_force_count_numpy(subs, upds)


def test_touching_endpoints_closed_semantics():
    # S ends exactly where U begins → closed intervals intersect.
    subs, upds = _mk([0.0], [5.0], [5.0], [9.0])
    assert int(sbm_count(subs, upds)) == 1
    # and the mirror
    subs, upds = _mk([5.0], [9.0], [0.0], [5.0])
    assert int(sbm_count(subs, upds)) == 1


def test_zero_length_intervals():
    subs, upds = _mk([2.0, 4.0], [2.0, 4.0], [2.0], [2.0])
    # S1=[2,2] matches U=[2,2]; S2=[4,4] does not.
    assert int(sbm_count(subs, upds)) == 1


def test_identical_intervals_all_pairs():
    n = 17
    subs, upds = _mk([1.0] * n, [2.0] * n, [1.5] * 13, [3.0] * 13)
    assert int(sbm_count(subs, upds)) == n * 13


def test_containment_and_duplicates():
    subs, upds = _mk([0, 0, 1, 1], [10, 10, 2, 2], [1, 0, 5], [2, 100, 5])
    assert int(sbm_count(subs, upds)) == brute_force_count_numpy(
        *_mk([0, 0, 1, 1], [10, 10, 2, 2], [1, 0, 5], [2, 100, 5]))


def test_empty_sets():
    subs, upds = _mk([], [], [1.0], [2.0])
    assert int(sbm_count(subs, upds)) == 0
    subs, upds = _mk([1.0], [2.0], [], [])
    assert int(sbm_count(subs, upds)) == 0


def _check_counts_and_pairs(ls, hs, lu, hu):
    from repro.core import brute_force_pairs_numpy
    subs, upds = _mk(ls, hs, lu, hu)
    want = brute_force_count_numpy(subs, upds)
    assert int(sbm_count(subs, upds, num_segments=4)) == want
    assert sequential_sbm_count_numpy(subs, upds) == want
    assert sequential_sbm_pairs_numpy(subs, upds) == \
        brute_force_pairs_numpy(subs, upds)


def _random_interval_sets(rng, max_size=40, integer=False):
    """Adversarial random sets: integer grids produce heavy ties."""
    n = rng.randint(1, max_size + 1)
    m = rng.randint(1, max_size + 1)

    def mk(count):
        if integer:
            lo = rng.randint(-10, 11, count).astype(float)
            hi = lo + rng.randint(0, 6, count)
        else:
            a = rng.uniform(-1e4, 1e4, count)
            b = rng.uniform(-1e4, 1e4, count)
            lo, hi = np.minimum(a, b), np.maximum(a, b)
        return lo.tolist(), hi.tolist()

    return mk(n) + mk(m)


@pytest.mark.parametrize("seed", range(12))
def test_random_examples_agree(seed):
    """Example-based property sweep (runs with or without hypothesis)."""
    rng = np.random.RandomState(seed)
    for _ in range(4):
        ls, hs, lu, hu = _random_interval_sets(rng, integer=(seed % 2 == 0))
        _check_counts_and_pairs(ls, hs, lu, hu)


if HAVE_HYPOTHESIS:
    # allow_subnormal=False: XLA CPU flushes float32 denormals to zero, numpy
    # does not — comparisons at ~1e-42 would differ between oracle and sweep.
    finite_floats = st.floats(min_value=-1e4, max_value=1e4, allow_nan=False,
                              width=32, allow_subnormal=False)

    @st.composite
    def interval_sets(draw):
        n = draw(st.integers(1, 40))
        m = draw(st.integers(1, 40))

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
    @settings(max_examples=60, deadline=None)
    def test_property_count_and_pairs_equal_brute_force(data):
        _check_counts_and_pairs(*data)


# ---------------------------------------------------------------------------
# wide accumulation: K ≥ 2³¹ must not wrap (regression for the silent
# int32 overflow in jnp.sum(emit) / the enumeration offset table)
# ---------------------------------------------------------------------------

def _all_overlapping(n, m):
    """Duplicated extents: K = n·m with a stream of only 2(n+m) endpoints —
    the cheap construction for counts beyond 2³¹."""
    subs = Extents(jnp.zeros(n, jnp.float32), jnp.ones(n, jnp.float32))
    upds = Extents(jnp.full(m, 0.5, jnp.float32), jnp.full(m, 2.0, jnp.float32))
    return subs, upds


def test_count_beyond_int32_is_exact_and_saturates():
    n = m = 1 << 16                      # K = 2³² > 2³¹
    subs, upds = _all_overlapping(n, m)
    assert sbm_count_exact(subs, upds) == n * m
    got = int(sbm_count(subs, upds))
    if jax.config.read("jax_enable_x64"):
        assert got == n * m              # exact int64
    else:
        assert got == 2**31 - 1          # documented sentinel, never a wrap


def test_count_exact_agrees_below_int32():
    for seed in range(3):
        subs, upds = make_uniform_workload(jax.random.PRNGKey(seed), 120, 90,
                                           alpha=5.0, length=500.0)
        want = brute_force_count_numpy(subs, upds)
        assert sbm_count_exact(subs, upds) == want == int(sbm_count(subs, upds))
    assert sbm_count_exact(*_mk([], [], [1.0], [2.0])) == 0


def test_enumerate_offsets_beyond_int32():
    """With K ≥ 2³¹ the offset table must stay monotonic (saturate, not
    wrap): emitted pairs are still genuine and the count pins at the
    sentinel instead of going negative."""
    from repro.core import sbm_enumerate
    n = m = 1 << 16
    subs, upds = _all_overlapping(n, m)
    pairs, count = sbm_enumerate(subs, upds, max_pairs=16)
    got = int(count)
    if jax.config.read("jax_enable_x64"):
        assert got == n * m
    else:
        assert got == 2**31 - 1
    arr = np.asarray(pairs)
    assert np.all(arr >= 0) and np.all(arr[:, 0] < n) and np.all(arr[:, 1] < m)


OFFSET_CASES = {
    # name: (counts, cap)
    "below_cap": ([3, 0, 2, 4], 16),
    "crossing_lands_on_cap": ([3, 5, 2, 4], 8),
    "one_count_past_2e30": ([5, 2**30 + 7, 1, 2**31 - 1, 3], 2**20),
    "k_is_2e32": ([2**16] * 2**16, 2**29),
}


@pytest.mark.parametrize("case", sorted(OFFSET_CASES))
def test_emission_offsets_pin_at_the_buffer(case):
    """The emission's offsets are exactly min(exclusive prefix, cap) and
    its total min(K, cap), from one int32 scan, however far K runs past
    2³¹: monotonic, so every slot below the cap finds its emitter."""
    from repro.core.enumerate import _pinned_offsets

    counts, cap = OFFSET_CASES[case]
    c = np.asarray(counts, np.int64)
    want = np.minimum(np.cumsum(c) - c, cap)
    excl, total = jax.jit(_pinned_offsets, static_argnums=1)(
        jnp.asarray(counts, jnp.int32), cap)
    np.testing.assert_array_equal(np.asarray(excl), want)
    assert int(total) == min(int(c.sum()), cap)


def test_algorithm6_active_sets_match_sequential():
    """SubSet[p]/UpdSet[p] (Alg. 6 lines 18-21) equal the sequential sweep's
    state right after segment T_{p-1} — the paper's correctness condition."""
    key = jax.random.PRNGKey(7)
    subs, upds = make_uniform_workload(key, 48, 40, alpha=8.0, length=100.0)
    num_segments = 8
    ep, sub_active, upd_active = active_sets_at_segment_starts(
        subs, upds, num_segments)
    # Sequential replay over the same (sorted, padded) endpoint stream:
    values = np.asarray(ep.values)
    is_up = np.asarray(ep.is_upper)
    is_sub = np.asarray(ep.is_sub)
    owner = np.asarray(ep.owner)
    total = values.shape[0]
    seg = total // num_segments
    cur_s, cur_u = set(), set()
    for p in range(num_segments):
        got_s = set(np.nonzero(np.asarray(sub_active[p]))[0].tolist())
        got_u = set(np.nonzero(np.asarray(upd_active[p]))[0].tolist())
        assert got_s == cur_s, f"segment {p}: SubSet mismatch"
        assert got_u == cur_u, f"segment {p}: UpdSet mismatch"
        for k in range(p * seg, (p + 1) * seg):
            if owner[k] < 0:
                continue
            tgt = cur_s if is_sub[k] else cur_u
            if is_up[k]:
                tgt.discard(int(owner[k]))
            else:
                tgt.add(int(owner[k]))
