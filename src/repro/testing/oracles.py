"""The single source of reference pair sets (DESIGN.md §9).

Every conformance check, fuzz seed and test file answers "what SHOULD the
pair set be" through this module — the oracle snippets that used to be
copy-pasted per test file (``_oracle`` in the service tests, the
``sequential_sbm_pairs_numpy_ddim`` reference in the d-dim tests, the
sweep set-diff asserts in the churn smoke) all import from here.

Two independent host references back every answer: the sequential
Algorithm-4 sweep (d-dim form: 1-d sweep + projection filter) and the
vectorized numpy brute force.  :func:`reference_pairs` cross-checks them
against each other, so a bug would have to hit two unrelated host
implementations identically before a device engine could be graded
against a wrong answer.
"""
from __future__ import annotations

from typing import Dict, Set, Tuple

import jax.numpy as jnp
import numpy as np

from repro.core.intervals import Extents, brute_force_pairs_numpy
from repro.core.sweep import (
    sequential_sbm_pairs_numpy,
    sequential_sbm_pairs_numpy_ddim,
)

Pair = Tuple[int, int]
PairSet = Set[Pair]


def pair_set(pairs) -> PairSet:
    """A padded ``(max_pairs, 2)`` buffer → ``{(i, j)}`` (drops ``(-1, -1)``)."""
    arr = np.asarray(pairs)
    if arr.size == 0:
        return set()
    arr = arr[arr[:, 0] >= 0]
    return {(int(i), int(j)) for i, j in arr}


def sequential_pairs(subs: Extents, upds: Extents, sweep_dim: int = 0) -> PairSet:
    """Paper Algorithm 4 on the host (d-dim: sweep ``sweep_dim`` + filter)."""
    return sequential_sbm_pairs_numpy_ddim(subs, upds, sweep_dim)


def brute_force_pairs(subs: Extents, upds: Extents) -> PairSet:
    """Vectorized numpy all-pairs closed-interval test (any d)."""
    return brute_force_pairs_numpy(subs, upds)


def reference_pairs(subs: Extents, upds: Extents,
                    sweep_dim: int = 0) -> PairSet:
    """THE oracle: sequential sweep cross-checked against brute force.

    The two references share no code path (one is a sorted endpoint scan,
    the other a broadcast comparison), so their agreement is itself part
    of the conformance substrate; disagreement raises immediately rather
    than grading engines against a possibly-wrong answer.  ``sweep_dim``
    picks the dimension the sequential sweep runs on (any gives the same
    set; a selective one keeps its candidate set small).
    """
    if subs.size == 0 or upds.size == 0:
        return set()
    want = sequential_sbm_pairs_numpy_ddim(subs, upds, sweep_dim)
    bf = brute_force_pairs_numpy(subs, upds)
    if want != bf:
        raise AssertionError(
            "host references disagree: sequential sweep vs brute force "
            f"differ by {want ^ bf} — the oracle itself is broken")
    return want


# ---------------------------------------------------------------------------
# rid-space oracles over live-region state (stateful engines)
# ---------------------------------------------------------------------------

def live_extents(live: Dict[int, tuple], dims: int):
    """dict rid → (lo, hi) → (sorted rids, Extents) with float32 bounds."""
    ids = sorted(live)
    lo = np.asarray([live[r][0] for r in ids], np.float32).T
    hi = np.asarray([live[r][1] for r in ids], np.float32).T
    if dims == 1:
        lo, hi = lo.reshape(-1), hi.reshape(-1)
    return ids, Extents(jnp.asarray(lo), jnp.asarray(hi))


def live_pairs(live_s: Dict[int, tuple], live_u: Dict[int, tuple],
               dims: int) -> PairSet:
    """Brute-force pair set over live rid → (lo, hi) dicts, in rid space."""
    if not live_s or not live_u:
        return set()
    sids, subs = live_extents(live_s, dims)
    uids, upds = live_extents(live_u, dims)
    return {(sids[i], uids[j])
            for i, j in brute_force_pairs_numpy(subs, upds)}


def sweep_rebuild_pairs(live_s: Dict[int, tuple],
                        live_u: Dict[int, tuple]) -> PairSet:
    """From-scratch device ``sbm_enumerate`` over live regions (1-d), in rid
    space — the churn acceptance-criterion oracle: the delta-composed state
    must equal a stateless sweep rebuild after every batch."""
    from repro.core.enumerate import sbm_enumerate

    if not live_s or not live_u:
        return set()
    sids, subs = live_extents(live_s, 1)
    uids, upds = live_extents(live_u, 1)
    want_k = len(sequential_sbm_pairs_numpy(subs, upds))
    pairs, count = sbm_enumerate(subs, upds, max_pairs=max(want_k, 1) + 8)
    assert int(count) == want_k
    return {(sids[int(i)], uids[int(j)])
            for i, j in np.asarray(pairs) if i >= 0}


def service_pairs(svc) -> PairSet:
    """Reference pair set of a :class:`repro.core.DDMService`, in rid space.

    Reads the live region tables directly (not the delta-maintained cache),
    so comparing ``svc.all_pairs()`` against this is exactly the
    delta-vs-rebuild set-diff assert the churn smoke and service tests run.
    """
    sl = svc._subs.live_ids()
    ul = svc._upds.live_ids()
    if sl.size == 0 or ul.size == 0:
        return set()
    subs = svc._subs.compact(sl)
    upds = svc._upds.compact(ul)
    return {(int(sl[i]), int(ul[j])) for i, j in reference_pairs(subs, upds)}
