"""Integer region bounds (HLA's dimension ranges) on the one-chip paths.

Every padding sentinel comes from the bounds' dtype
(:func:`repro.core.runtime.inert_bounds`), not from a cast of a float's
±inf, which has no integer value.  The engines must equal the sequential
Algorithm 4 on int32 sets with ties, zero-length extents, values at the
range's ends, and streams that need padding to a segment multiple.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (Extents, enumerate_matches, sbm_count,
                        sbm_count_exact, sbm_enumerate, sbm_enumerate_planned)
from repro.core.runtime import inert_bounds, pad_axis
from repro.core.sweep import sequential_sbm_pairs_numpy
from repro.core.intervals import intersect_1d

jax.config.update("jax_platform_name", "cpu")

TOP = np.iinfo(np.int32).max
BOTTOM = np.iinfo(np.int32).min


def _ext(lo, hi):
    return Extents(jnp.asarray(lo, jnp.int32), jnp.asarray(hi, jnp.int32))


def _random(seed, n, m, span, longest):
    rng = np.random.default_rng(seed)
    lo = rng.integers(0, span, n + m)
    hi = np.minimum(lo + rng.integers(0, longest + 1, n + m), TOP)
    return _ext(lo[:n], hi[:n]), _ext(lo[n:], hi[n:])


CASES = {
    # closed intervals: touching ends match, equal bounds tie
    "ties": lambda: (_ext([0, 2, 2, 4, 4], [2, 4, 4, 6, 6]),
                     _ext([2, 2, 4, 0], [2, 4, 4, 6])),
    "zero_length": lambda: (_ext([3, 3, 7], [3, 3, 7]),
                            _ext([3, 0, 7, 8], [3, 3, 9, 8])),
    "range_ends": lambda: (_ext([0, 0, TOP, TOP - 1, 5], [0, TOP, TOP, TOP,
                                                          TOP - 2]),
                           _ext([0, TOP, TOP - 2, 1], [1, TOP, TOP - 1, 4])),
    # 5 + 2 extents: 14 endpoints, padded to the segment multiple
    "padded_bucket": lambda: (_ext([1, 4, 9, 9, 20], [5, 9, 12, 9, 30]),
                              _ext([5, 10], [8, 25])),
    "random_ties": lambda: _random(7, 300, 260, 400, 12),
    "random_wide": lambda: _random(8, 129, 77, 2**31 - 64, 2**28),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_int32_sets_match_algorithm_4(case):
    subs, upds = CASES[case]()
    want = sequential_sbm_pairs_numpy(subs, upds)
    assert int(sbm_count(subs, upds)) == len(want)
    assert sbm_count_exact(subs, upds) == len(want)
    for cap in (len(want) + 3, 2 * len(want) + 64):
        pairs, count = sbm_enumerate(subs, upds, max_pairs=cap)
        got = {(int(i), int(j)) for i, j in np.asarray(pairs) if i >= 0}
        assert int(count) == len(want) and got == want
    pairs, count, stats = sbm_enumerate_planned(subs, upds)
    got = {(int(i), int(j)) for i, j in np.asarray(pairs) if i >= 0}
    assert int(count) == stats.count == len(want) and got == want
    assert stats.retries == 0 and stats.chips == 1
    assert stats.exchange_bytes == 0


def test_int32_blocked_oracle_pads_with_inert_subscriptions():
    """Updates at both ends of the int32 range against a padded block of
    subscriptions: the padding matches neither."""
    subs = _ext([0, 5, 9], [3, 5, TOP])
    upds = _ext([BOTTOM, 3, TOP], [BOTTOM, 6, TOP])
    want = sequential_sbm_pairs_numpy(subs, upds)
    pairs, count = enumerate_matches(subs, upds, max_pairs=16, block=4)
    got = {(int(i), int(j)) for i, j in np.asarray(pairs) if i >= 0}
    assert int(count) == len(want) and got == want


@pytest.mark.parametrize("dtype", [jnp.int32, jnp.int16, jnp.uint16,
                                   jnp.float32, jnp.bfloat16])
def test_inert_padding_matches_nothing(dtype):
    top, bottom = inert_bounds(dtype)
    if jnp.issubdtype(dtype, jnp.floating):
        assert (top, bottom) == (jnp.inf, -jnp.inf)
        info_lo, info_hi = -1e4, 1e4
    else:
        info = jnp.iinfo(dtype)
        assert (top, bottom) == (info.max, info.min)
        info_lo, info_hi = info.min, info.max
    lo = jnp.asarray([[info_lo, 0, info_hi - 1]], dtype)
    hi = jnp.asarray([[info_lo, info_hi - 1, info_hi - 1]], dtype)
    plo, phi = pad_axis(lo, hi, 4)
    assert plo.shape == (1, 4) and plo.dtype == lo.dtype
    assert not np.any(np.asarray(intersect_1d(plo[0, 3], phi[0, 3],
                                              lo[0], hi[0])))
