"""Rank (searchsorted) matching — the TPU-native analogue of Interval-Tree
Matching (paper §3.3), and the beyond-paper fast counting path.

ITM answers each update query by descending a balanced AVL interval tree in
O(log n).  Pointer-chasing trees do not vectorize on TPU; the equivalent
query over *static* extent sets is two binary searches on sorted endpoint
arrays:

    count(S_i) = |{j : U.lo_j ≤ S.hi_i}| − |{j : U.hi_j < S.lo_i}|

The first term is a rank in U.lo sorted order (every such update *starts*
before S_i ends); the subtracted term counts updates that *ended* strictly
before S_i starts — all of which necessarily started before S_i ends, so the
difference is exactly the number of overlapping updates (closed-interval
semantics).  Cost: O((n+m) log m) after an O(m log m) sort, fully parallel
across queries — the same embarrassingly-parallel query structure the paper
exploits for parallel ITM, minus the serial tree build.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.intervals import Extents


@jax.jit
def per_sub_match_counts(subs: Extents, upds: Extents) -> jax.Array:
    """Number of matching updates for every subscription (exact)."""
    u_lo_sorted = jnp.sort(upds.lo)
    u_hi_sorted = jnp.sort(upds.hi)
    started = jnp.searchsorted(u_lo_sorted, subs.hi, side="right")
    ended_before = jnp.searchsorted(u_hi_sorted, subs.lo, side="left")
    return (started - ended_before).astype(jnp.int32)


@jax.jit
def per_upd_match_counts(subs: Extents, upds: Extents) -> jax.Array:
    """Number of matching subscriptions for every update (exact)."""
    s_lo_sorted = jnp.sort(subs.lo)
    s_hi_sorted = jnp.sort(subs.hi)
    started = jnp.searchsorted(s_lo_sorted, upds.hi, side="right")
    ended_before = jnp.searchsorted(s_hi_sorted, upds.lo, side="left")
    return (started - ended_before).astype(jnp.int32)


@jax.jit
def rank_count(subs: Extents, upds: Extents) -> jax.Array:
    """Total number of matches K (exact; dual of :func:`sbm_count`)."""
    return jnp.sum(per_sub_match_counts(subs, upds))


def rank_count_sharded(subs: Extents, upds: Extents, mesh, axis_name: str):
    """Queries sharded across a mesh axis (parallel-ITM analogue).

    The sorted update arrays are replicated (they play the role of the shared
    interval tree); subscription queries are sharded; a final psum reduces.
    """
    from jax.sharding import PartitionSpec as P

    u_lo_sorted = jnp.sort(upds.lo)
    u_hi_sorted = jnp.sort(upds.hi)

    # Pad queries to a shard multiple with inert [-inf, -inf] queries:
    # started = |{U.lo ≤ -inf}| = 0 and ended = |{U.hi < -inf}| = 0.
    num_shards = mesh.shape[axis_name]
    pad = (-subs.lo.shape[0]) % num_shards
    s_lo = jnp.concatenate([subs.lo, jnp.full((pad,), -jnp.inf, subs.lo.dtype)])
    s_hi = jnp.concatenate([subs.hi, jnp.full((pad,), -jnp.inf, subs.hi.dtype)])

    def body(s_lo, s_hi, u_lo, u_hi):
        started = jnp.searchsorted(u_lo, s_hi, side="right")
        ended = jnp.searchsorted(u_hi, s_lo, side="left")
        return lax.psum(jnp.sum(started - ended), axis_name)

    fn = jax.jit(jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(axis_name), P(axis_name), P(), P()),
        out_specs=P()))
    return fn(s_lo, s_hi, u_lo_sorted, u_hi_sorted)
