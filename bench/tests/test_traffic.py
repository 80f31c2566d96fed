"""The generators are deterministic in the seed, past 32 bits too."""
import numpy as np

from bench.traffic import paper

BIG = 2**33 + 12345


def _sets(seed):
    lo, hi = paper.uniform_sets(paper.device_key(seed, 0), 2, 1000, 1.0,
                                1.0e6)
    return np.asarray(lo), np.asarray(hi)


def test_sets_repeat_with_the_seed():
    a, b = _sets(BIG), _sets(BIG)
    assert np.array_equal(a[0], b[0]) and np.array_equal(a[1], b[1])
    c = _sets(BIG + 1)
    assert not np.array_equal(a[0], c[0])
    lo, hi = a
    assert lo.dtype == np.float32 and lo.shape == (2, 1000)
    seg = paper.segment_length(1.0, 1.0e6, 1000)
    assert np.all(lo >= 0) and np.all(hi <= 1.0e6)
    assert np.allclose(hi - lo, seg, rtol=1e-3)
