"""The plain reference that decides ``correct``, and its comparisons.

Nothing here imports the system under test or takes anything it made.
Every comparison is exact: both sides compare the same float32 bounds.

Two closed intervals ``[s_lo, s_hi]`` and ``[u_lo, u_hi]`` overlap iff
``u_lo <= s_hi`` and ``s_lo <= u_hi``.  Of the two ways to miss,
``u_lo > s_hi`` and ``u_hi < s_lo`` exclude each other (``lo <= hi``), so
a subscription's matches are the updates with ``u_lo <= s_hi`` less those
with ``u_hi < s_lo``, which two binary searches over sorted update bounds
count.  Prefix sums of a random 32-bit weight per update, read at the same
two ranks, give the weight sum of each subscription's match set.  A match
set is checked as (count, weight sum) per subscription: a missing,
repeated or foreign pair changes one of them, short of a 2**-32 collision.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Tuple

import numpy as np


def to_bf16(x: np.ndarray) -> np.ndarray:
    """``x`` rounded to bfloat16 and widened back: the control's precision."""
    import ml_dtypes

    return np.asarray(x, np.float32).astype(ml_dtypes.bfloat16).astype(
        np.float32)


def weights(m: int, seed: int) -> np.ndarray:
    """One random 32-bit weight per update, as exact float64."""
    rng = np.random.default_rng(
        np.random.SeedSequence([seed & (2**64 - 1), 0x5EED]))
    return rng.integers(0, 2**32, size=m, dtype=np.uint64).astype(np.float64)


@dataclasses.dataclass
class Summary:
    """Per-subscription (count, weight sum) of a match set."""

    count: np.ndarray   # (n,) int64
    wsum: np.ndarray    # (n,) float64, exact: every sum is below 2**53
    total: int          # pairs in all
    bad: int = 0        # pairs whose indices lie outside the sets


def reference_summary(s_lo, s_hi, u_lo, u_hi, w) -> Summary:
    """The exact match set of every subscription, as a :class:`Summary`."""
    s_lo, s_hi = np.asarray(s_lo), np.asarray(s_hi)
    u_lo, u_hi = np.asarray(u_lo), np.asarray(u_hi)
    if np.any(u_lo > u_hi) or np.any(s_lo > s_hi):
        raise ValueError("an extent has lo > hi")
    wi = w.astype(np.uint64)
    by_lo = np.argsort(u_lo, kind="stable")
    c1 = np.searchsorted(u_lo[by_lo], s_hi, side="right")
    cw1 = np.concatenate([[0], np.cumsum(wi[by_lo], dtype=np.uint64)])
    by_hi = np.argsort(u_hi, kind="stable")
    c2 = np.searchsorted(u_hi[by_hi], s_lo, side="left")
    cw2 = np.concatenate([[0], np.cumsum(wi[by_hi], dtype=np.uint64)])
    count = (c1 - c2).astype(np.int64)
    wsum = (cw1[c1] - cw2[c2]).astype(np.float64)
    return Summary(count, wsum, int(count.sum()))


def pairs_summary(i, j, n: int, m: int, w) -> Summary:
    """The :class:`Summary` of a list of (subscription, update) index pairs."""
    i = np.asarray(i, np.int64)
    j = np.asarray(j, np.int64)
    ok = (i >= 0) & (i < n) & (j >= 0) & (j < m)
    bad = int(i.size - np.count_nonzero(ok))
    if bad:
        i, j = i[ok], j[ok]
    count = np.bincount(i, minlength=n).astype(np.int64)
    wsum = np.bincount(i, weights=w[j], minlength=n)
    return Summary(count, wsum, int(i.size) + bad, bad)


def subs_wrong(got: Summary, want: Summary) -> int:
    """Subscriptions whose match set differs, plus pairs out of range."""
    differ = (got.count != want.count) | (got.wsum != want.wsum)
    return int(np.count_nonzero(differ)) + got.bad


def limits_met(checks: Dict[str, Tuple[float, float]]) -> bool:
    """Whether every compared number is within its limit."""
    return all(value <= limit for value, limit in checks.values())
