"""The reference against brute force at small sizes, and its power to see
a wrong answer."""
import numpy as np
import pytest

from bench import reference


def _sets(seed, n=300, m=250, L=1000.0):
    rng = np.random.default_rng(seed)
    s_lo = rng.uniform(0, L, n).astype(np.float32)
    s_hi = (s_lo + rng.uniform(0, 30, n)).astype(np.float32)
    u_lo = rng.uniform(0, L, m).astype(np.float32)
    u_hi = (u_lo + rng.uniform(0, 30, m)).astype(np.float32)
    # shared endpoints: closed intervals that touch overlap
    u_lo[:20] = s_hi[:20]
    u_hi[:20] = u_lo[:20] + np.float32(5.0)
    return s_lo, s_hi, u_lo, u_hi


def _brute(s_lo, s_hi, u_lo, u_hi):
    hit = (u_lo[None, :] <= s_hi[:, None]) & (s_lo[:, None] <= u_hi[None, :])
    return np.nonzero(hit)


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_summary_matches_brute_force(seed):
    s_lo, s_hi, u_lo, u_hi = _sets(seed)
    w = reference.weights(u_lo.size, seed)
    i, j = _brute(s_lo, s_hi, u_lo, u_hi)
    want = reference.pairs_summary(i, j, s_lo.size, u_lo.size, w)
    got = reference.reference_summary(s_lo, s_hi, u_lo, u_hi, w)
    assert reference.subs_wrong(got, want) == 0 and got.total == want.total
    # one pair repeated, one dropped, one foreign: each is seen
    for bad_i, bad_j in ((np.r_[i, i[:1]], np.r_[j, j[:1]]),
                         (i[1:], j[1:]),
                         (np.r_[i[:-1], i[-1]], np.r_[j[:-1], (j[-1] + 1)
                                                     % u_lo.size])):
        bad = reference.pairs_summary(bad_i, bad_j, s_lo.size, u_lo.size, w)
        assert reference.subs_wrong(bad, got) >= 1
    out = reference.pairs_summary(np.r_[i, -1], np.r_[j, 0], s_lo.size,
                                  u_lo.size, w)
    assert out.bad == 1 and reference.subs_wrong(out, got) >= 1


def test_bf16_moves_bounds():
    x = np.float32([999999.9, 123456.7, 1.0])
    y = reference.to_bf16(x)
    assert y.dtype == np.float32 and y[2] == 1.0 and np.all(y[:2] != x[:2])
